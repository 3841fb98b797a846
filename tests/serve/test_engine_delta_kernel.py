"""``LLMEngine`` with the delta rule's one-token update as the Pallas
kernel (``ops/delta.py`` ``gated_delta_step_slots``, interpreted here):
it serves the tokens the plain form serves, whole prompts, a request
resumed from a snapshot and requests decoding beside one another in
other slots (so that a step holds rows with nothing to do), and
``stats()`` says which form ran and how many decode rows it updated."""
import threading

import pytest

import jax.numpy as jnp

from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

# four heads of 8 x 32: 128 lanes, a shape the kernel tiles on a TPU too
MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8,
                n_kv_heads=4, d_ff=48, max_seq_len=128, rotary_dim=0,
                block_style="llama", dtype=jnp.float32,
                remat_policy="none", norm_eps=1e-6,
                layer_pattern=["delta", "delta", "delta", "full"],
                delta_heads=4, delta_key_dim=8, delta_value_dim=32,
                delta_conv=4, delta_neg_eigval=True,
                output_norm=True, qk_norm_whole=True)
BS, CHUNK, STRIDE = 4, 16, 8
DOC = [(5 * i + 3) % 60 + 2 for i in range(44)]
KERNEL_KEYS = {"delta_step_impl", "delta_kernel_rows_total"}


def _question(i, n=5):
    return [(11 * i + 7 * j) % 60 + 2 for j in range(n)]


def _engine(impl):
    return LLMEngine(
        TransformerConfig(**MODEL_KW, paged_impl=impl),
        EngineConfig(decode_slots=3, kv_block_size=BS, max_seq_len=128,
                     prefill_chunk=CHUNK, max_new_tokens=8,
                     num_kv_blocks=97, enable_prefix_sharing=True,
                     state_snapshot_stride=STRIDE, num_state_snapshots=12))


@pytest.fixture(scope="module")
def engines():
    """impl -> engine, the same seeded weights in each."""
    made = {impl: _engine(impl) for impl in ("reference", "interpret")}
    yield made
    for eng in made.values():
        assert eng.pool_audit() == []
        eng.shutdown()


def _both(engines, prompt, n=8):
    """``prompt`` through both engines: the tokens (equal), and what it
    added to each engine's counters."""
    out, added = {}, {}
    for impl, eng in engines.items():
        s0 = eng.stats()
        out[impl] = list(eng.generate_sync(prompt, n))
        s = eng.stats()
        added[impl] = {k: s[k] - s0[k] for k in s
                       if k.endswith("_total") and isinstance(s[k], int)}
    assert len(out["reference"]) == n
    assert out["interpret"] == out["reference"]
    return added


@pytest.mark.parametrize("prompt", [
    DOC[:9], DOC[:16] + _question(1), DOC + _question(2, 9)],
    ids=["under_a_chunk", "a_chunk_and_five", "four_chunks"])
def test_the_kernel_serves_the_plain_forms_tokens(engines, prompt):
    added = _both(engines, prompt)
    for impl in engines:
        # the first token comes off the last chunk, seven off decode steps
        assert added[impl]["delta_decode_rows_total"] == 7
    assert added["interpret"]["delta_kernel_rows_total"] == 7
    assert added["reference"]["delta_kernel_rows_total"] == 0


def test_resumed_from_a_snapshot_the_kernel_serves_them_too(engines):
    doc = [t % 60 + 2 for t in range(9, 49)]          # ten pages
    first = _both(engines, doc + _question(3))
    second = _both(engines, doc + _question(4))
    for impl in engines:
        assert first[impl]["state_hits_total"] == 0
        assert second[impl]["state_hits_total"] == 1
        assert second[impl]["prefix_hit_blocks_total"] == 10
        assert second[impl]["delta_prefill_tokens_total"] == 5
        assert second[impl]["delta_decode_rows_total"] == 7


def test_rows_with_nothing_to_do_beside_rows_that_decode(engines):
    """Two requests at once in a decode batch of three slots: a step
    holds a row with no sequence throughout, and the long prompt's slot
    is one while its chunks run between the talker's steps."""
    talker = [(9 * j + 1) % 60 + 2 for j in range(9)]
    long_doc = [t % 60 + 2 for t in range(3, 71)] + _question(5)
    want = {"talk": list(engines["reference"].generate_sync(talker, 40)),
            "long": list(engines["reference"].generate_sync(long_doc, 8))}
    eng = engines["interpret"]
    out = {}

    def run(name, prompt, n):
        out[name] = list(eng.generate_sync(prompt, n))
    s0 = eng.stats()
    first = threading.Thread(target=run, args=("talk", talker, 40))
    first.start()
    while eng.stats()["decode_steps"] == s0["decode_steps"]:
        pass                           # the short one is decoding
    second = threading.Thread(target=run, args=("long", long_doc, 8))
    second.start()
    first.join(60), second.join(60)
    s = eng.stats()
    assert out == want
    assert s["decode_slots_skipped_total"] > s0["decode_slots_skipped_total"]
    assert s["delta_kernel_rows_total"] - s0["delta_kernel_rows_total"] \
        == s["delta_decode_rows_total"] - s0["delta_decode_rows_total"] \
        == 39 + 7


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_stats_say_which_form_updated_the_decode_rows(engines, impl):
    _both(engines, DOC[:11])
    s = engines[impl].stats()
    assert KERNEL_KEYS <= set(s)
    assert s["delta_step_impl"] == impl
    assert s["delta_decode_rows_total"] > 0
    assert s["delta_kernel_rows_total"] == (
        s["delta_decode_rows_total"] if impl == "interpret" else 0)
    # the state-space model's keys are not this model's
    assert not {"ssm_step_impl", "ssm_kernel_rows_total"} & set(s)
    assert {"op": "delta_step", "impl": impl, "why": "requested"} \
        in [{k: d[k] for k in ("op", "impl", "why")}
            for d in s["attention_dispatch"]]


def test_auto_off_a_tpu_is_the_plain_form_and_a_dense_model_has_no_such_keys():
    eng = _engine("auto")
    try:
        s = eng.stats()
        assert s["delta_step_impl"] == "reference"
        assert s["delta_kernel_rows_total"] == 0
    finally:
        eng.shutdown()
    dense = {k: v for k, v in MODEL_KW.items()
             if not k.startswith("delta_") and k not in (
                 "layer_pattern", "output_norm", "qk_norm_whole")}
    eng = LLMEngine(TransformerConfig(**dense), EngineConfig(
        decode_slots=2, kv_block_size=BS, max_seq_len=128,
        prefill_chunk=CHUNK, num_kv_blocks=33))
    try:
        assert not KERNEL_KEYS & set(eng.stats())
    finally:
        eng.shutdown()
