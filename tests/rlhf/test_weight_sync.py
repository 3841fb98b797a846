"""Int8 blockwise weight-sync wire: codec units + publisher fan-out."""

import numpy as np
import pytest

from ray_tpu.parallel.quantization import (dequantize_int8_np,
                                           quantize_int8_np)
from ray_tpu.rlhf.weight_sync import (WeightPublisher, _f32_bytes,
                                      pack_weights, packed_wire_bytes,
                                      unpack_weights)

pytestmark = pytest.mark.rlhf


def test_int8_roundtrip_error_bounded_per_block():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((7, 33)).astype(np.float32)
    q, scales = quantize_int8_np(x, block_size=16)
    deq = dequantize_int8_np(q, scales, shape=x.shape,
                             dtype=np.float32)
    # rounding error is at most half an int8 step per block
    assert np.abs(deq - x).max() <= scales.max() / 2 + 1e-7
    # an all-zero block must not divide by zero: scale pins to 1.0
    zq, zscales = quantize_int8_np(np.zeros(32, np.float32),
                                   block_size=16)
    assert (zscales == 1.0).all()
    assert (zq == 0).all()


def test_pack_unpack_tree_round_trip_with_raw_leaves():
    params = {
        "layer": {"w": np.linspace(-1, 1, 40,
                                   dtype=np.float32).reshape(5, 8),
                  "b": np.zeros(5, np.float32)},
        "step": np.array(17, dtype=np.int64),
        "mask": np.array([True, False]),
    }
    packed = pack_weights(params, version=9, block_size=8)
    assert packed["version"] == 9
    out, version = unpack_weights(packed)
    assert version == 9
    assert out["layer"]["w"].shape == (5, 8)
    assert out["layer"]["w"].dtype == np.float32
    assert np.abs(out["layer"]["w"] - params["layer"]["w"]).max() < 0.01
    assert np.array_equal(out["layer"]["b"], params["layer"]["b"])
    # int / bool leaves ship verbatim, not quantized
    assert out["step"] == 17 and out["step"].dtype == np.int64
    assert np.array_equal(out["mask"], params["mask"])


def test_wire_compression_beats_f32_by_2x():
    rng = np.random.default_rng(1)
    params = {"w": rng.standard_normal((64, 64)).astype(np.float32)}
    packed = pack_weights(params, version=1, block_size=64)
    wire = packed_wire_bytes(packed)
    f32 = _f32_bytes(packed)
    assert f32 == 64 * 64 * 4
    assert f32 / wire > 2.0, (wire, f32)


class _StagedEngine:
    """In-process target: receives a dequantized tree."""

    def __init__(self):
        self.staged = []

    def stage_weights(self, params, version):
        self.staged.append((params, version))


class _RemoteEngine:
    """Remote-handle target: receives the packed payload."""

    def __init__(self):
        self.packed = []

    def sync_weights(self, packed):
        self.packed.append(packed)


def test_publisher_fans_out_with_monotone_versions():
    staged, remote = _StagedEngine(), _RemoteEngine()
    pub = WeightPublisher([staged, remote], block_size=8)
    params = {"w": np.ones((4, 4), np.float32)}

    assert pub.publish(params) == 1
    assert pub.publish({"w": np.full((4, 4), 2.0, np.float32)}) == 2
    assert pub.version == 2

    # the in-process engine got a dequantized tree + version, the
    # remote one got the packed wire payload carrying the same version
    assert [v for _, v in staged.staged] == [1, 2]
    assert np.allclose(staged.staged[0][0]["w"], 1.0, atol=0.02)
    assert [p["version"] for p in remote.packed] == [1, 2]
    assert "q" in remote.packed[0]["entries"]["w"]

    s = pub.stats()
    assert s["publishes"] == 2 and s["version"] == 2
    assert s["compression"] is not None and s["compression"] > 2.0
    assert s["wire_bytes_total"] > 0


def test_packed_refresh_into_a_bf16_engine_is_cast_before_the_swap():
    """The wire's float32 tree (``unpack_weights``, numpy leaves) into an
    engine that computes in bf16, by both routes — the publisher's
    in-process ``stage_weights`` and a replica's ``sync_weights``: each
    casts on the caller's thread, the version lands, no step program
    compiles again, and both serve the tokens of an engine built on the
    same dequantised tree."""
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.serve.llm_engine import (EngineConfig, LLMEngine,
                                          LLMServer)
    model = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                 head_dim=8, d_ff=32, max_seq_len=64, rotary_dim=8,
                 block_style="llama", n_kv_heads=1, remat_policy="none")
    ekw = dict(decode_slots=2, kv_block_size=4, max_seq_len=48,
               prefill_chunk=8, max_new_tokens=16)
    cfg = TransformerConfig(**model, dtype=jnp.bfloat16)
    learner = init_params(cfg, jax.random.PRNGKey(3))      # f32 masters
    wire, _ = unpack_weights(pack_weights(learner, 1, block_size=8))
    assert wire["embed"].dtype == np.float32

    def wait(engine, version):
        deadline = time.monotonic() + 30
        while engine.stats()["weight_version"] != version:
            assert time.monotonic() < deadline, "the swap never landed"
            time.sleep(0.005)

    eng = LLMEngine(cfg, EngineConfig(**ekw))
    srv = LLMServer(model=dict(model, dtype="bfloat16"), engine=ekw)
    built = LLMEngine(cfg, EngineConfig(**ekw), params=wire)
    try:
        eng.warmup()
        prompt = [5, 3, 5, 8, 9, 7, 9]
        want = list(built.generate_sync(prompt, max_new_tokens=8))
        for engine, refresh in (
                (eng, lambda: WeightPublisher([eng], block_size=8)
                    .publish(learner)),
                (srv.engine, lambda: srv.sync_weights(
                    pack_weights(learner, 1, block_size=8)))):
            every = engine.stats()["compiled_programs"]
            # (another prompt: a refresh keeps the prefix cache's pages)
            assert list(engine.generate_sync(prompt[::-1], 8))
            assert refresh() == 1
            wait(engine, 1)
            p = engine._params
            assert p["embed"].dtype == p["layers"]["w_down"].dtype \
                == jnp.bfloat16
            assert p["layers"]["attn_norm"].dtype == jnp.float32
            assert list(engine.generate_sync(prompt, 8)) == want
            s = engine.stats()
            assert s["compiled_programs"] == every
            assert s["weight_casts_total"] == s["weight_swaps"] == 1
    finally:
        eng.shutdown()
        srv.engine.shutdown()
        built.shutdown()
