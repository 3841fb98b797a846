"""``LLMEngine`` serving a stack with "mamba" layers: a sequence's
recurrent state lives in its decode slot's row of the per-slot arrays
beside the paged pool. What it answers is the plain reference's own
choice (``benchmarks/reference/granite.py``, seeded weights, a small
size); a prompt prefilled in several chunks while other slots decode
between them answers as it does alone; a slot used again answers as a
fresh engine does, whatever the slot was left holding; the counters
count what was driven; what would need a state moved, shared or rolled
back refuses the configuration by name; and a model without such layers
keeps its ``stats()`` keys."""
import threading

import pytest

import jax.numpy as jnp

from benchmarks.reference import granite
from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8,
                n_kv_heads=2, d_ff=48, max_seq_len=128, rotary_dim=0,
                block_style="llama", dtype=jnp.float32,
                remat_policy="none", norm_eps=1e-5,
                layer_pattern=["mamba", "mamba", "full", "mamba"],
                ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_conv=4,
                ssm_chunk=8, attn_scale=0.125, embed_scale=12.0,
                residual_scale=0.22, logit_scale=1 / 16,
                tie_embeddings=True, n_experts=8, experts_per_token=2,
                expert_width=16, shared_expert_width=16)
HP = tuple(sorted(dict(
    num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-5,
    attention_multiplier=0.125, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=16.0, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4,
    num_experts_per_tok=2, expert_first=0, experts_held=8,
    layer_types="mamba,mamba,attention,mamba").items()))
BS, CHUNK = 4, 16
LONG = [(5 * i + 3) % 60 + 2 for i in range(70)]      # five chunks
SHORT = [(7 * i + 1) % 60 + 2 for i in range(9)]
STATE_KEYS = {"state_slots_total", "state_bytes_per_slot",
              "ssm_decode_rows_total", "ssm_prefill_tokens_total",
              "ssm_prefill_calls_total", "ssm_step_impl",
              "ssm_kernel_rows_total"}


def _engine(model=(), **kw):
    ekw = dict(decode_slots=3, kv_block_size=BS, max_seq_len=128,
               prefill_chunk=CHUNK, max_new_tokens=8, num_kv_blocks=97,
               enable_prefix_sharing=False)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**{**MODEL_KW, **dict(model)}),
                     EngineConfig(**ekw))


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.shutdown()


def _gap(eng, prompt, served):
    """How far below the reference's largest logit the served tokens'
    lie, over the largest magnitude (the benchmark's served check)."""
    ids = jnp.asarray(list(prompt) + list(served), jnp.int32)
    want = granite.forward(eng._params, ids[None], HP)[
        0, len(prompt) - 1:len(prompt) - 1 + len(served)]
    picked = jnp.take_along_axis(want, ids[len(prompt):, None], -1)[:, 0]
    return float(jnp.max((jnp.max(want, -1) - picked)
                         / jnp.max(jnp.abs(want))))


def _poison(eng, slots):
    """Overwrite the state rows of ``slots`` (step thread: it owns the
    cache) with numbers no sequence left there."""
    def do():
        for name in ("ssm", "conv"):
            arr = eng._cache[name]
            eng._cache[name] = arr.at[:, jnp.asarray(slots)].set(
                jnp.asarray(1e3, arr.dtype))
    eng._run_on_step_thread(do)


def test_what_it_serves_is_the_references_choice(engine):
    """Alone: five chunks through the slot's state, then decode."""
    s0 = engine.stats()
    served = list(engine.generate_sync(LONG, 8))
    s = engine.stats()
    assert len(served) == 8 and _gap(engine, LONG, served) < 1e-4
    assert STATE_KEYS <= set(s)
    assert s["state_slots_total"] == 3
    # three mamba layers: 4 x 16 x 8 float32 and 3 x (64 + 16) float32
    assert s["state_bytes_per_slot"] == 3 * (4 * 16 * 8 + 3 * 80) * 4
    assert s["ssm_prefill_tokens_total"] \
        - s0["ssm_prefill_tokens_total"] == 70
    assert s["ssm_prefill_calls_total"] - s0["ssm_prefill_calls_total"] == 5
    # the first token comes off the last chunk, the other seven off a
    # decode step each, and the eighth's own step is never run
    assert s["ssm_decode_rows_total"] - s0["ssm_decode_rows_total"] == 7
    # off the chip the update is the plain form, and the engine says so
    assert s["ssm_step_impl"] == "reference"
    assert s["ssm_kernel_rows_total"] == 0
    assert s["active_slots"] == 0 and engine.pool_audit() == []
    assert set(s["compiled_programs"]) == {"prefill", "copy", "decode"}
    assert set(s["compiled_programs"].values()) <= {0, 1}


def test_the_engine_says_the_kernel_updated_its_decode_rows(engine):
    """The same engine with the kernels interpreted: the same tokens,
    and every decode row counted as the kernel's."""
    eng = _engine(model={"paged_impl": "interpret"})
    try:
        s0 = eng.stats()
        assert list(eng.generate_sync(LONG, 8)) \
            == list(engine.generate_sync(LONG, 8))
        s = eng.stats()
    finally:
        eng.shutdown()
    assert s["ssm_step_impl"] == "interpret"
    assert s["ssm_kernel_rows_total"] - s0["ssm_kernel_rows_total"] \
        == s["ssm_decode_rows_total"] - s0["ssm_decode_rows_total"] == 7
    assert {"op": "ssm_step", "impl": "interpret", "why": "requested"} \
        in [{k: d[k] for k in ("op", "impl", "why")}
            for d in s["attention_dispatch"]]


def test_chunks_between_other_slots_decode_steps_change_nothing(engine):
    """A long prompt's chunks run a tick apart with the other slots'
    decode steps between them: its slot is staged with no sequence in
    those steps and must come through them untouched."""
    alone = list(engine.generate_sync(LONG, 8))
    short_alone = list(engine.generate_sync(SHORT, 40))
    out = {}

    def run(name, prompt, n):
        out[name] = list(engine.generate_sync(prompt, n))
    s0 = engine.stats()
    first = threading.Thread(target=run, args=("short", SHORT, 40))
    first.start()
    while engine.stats()["decode_steps"] == s0["decode_steps"]:
        pass                           # the short one is decoding
    second = threading.Thread(target=run, args=("long", LONG, 8))
    second.start()
    first.join(60), second.join(60)
    s = engine.stats()
    assert out["long"] == alone and out["short"] == short_alone
    # they did overlap: decode steps ran while the long prompt prefilled
    assert s["ssm_decode_rows_total"] - s0["ssm_decode_rows_total"] == 39 + 7
    assert s["decode_steps"] - s0["decode_steps"] < 39 + 7
    assert engine.pool_audit() == []


def test_a_slot_used_again_answers_as_a_fresh_engine(engine):
    """One slot, two requests one after the other, and the free slots'
    rows overwritten in between: the second finds nothing of the first
    and reads nothing of the others."""
    one = _engine(decode_slots=1)
    try:
        first = list(one.generate_sync(LONG, 8))
        second = list(one.generate_sync(SHORT, 8))
        assert first == list(engine.generate_sync(LONG, 8))
    finally:
        one.shutdown()
    _poison(engine, [0, 1, 2])
    assert list(engine.generate_sync(SHORT, 8)) == second
    assert _gap(engine, SHORT, second) < 1e-4
    # with a neighbour decoding beside poisoned rows
    _poison(engine, [0, 1, 2])
    out = {}

    def run(name, prompt):
        out[name] = list(engine.generate_sync(prompt, 8))
    threads = [threading.Thread(target=run, args=(n, p))
               for n, p in (("long", LONG), ("short", SHORT))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert out == {"long": first, "short": second}


@pytest.mark.parametrize("kw,name", [
    (dict(enable_prefix_sharing=True), "enable_prefix_sharing"),
    (dict(spec_tokens=2), "spec_tokens > 0")])
def test_what_shares_or_rolls_back_a_prefix_is_refused_at_construction(
        kw, name):
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        _engine(**kw)
    assert name in str(e.value)


@pytest.mark.parametrize("call", [
    lambda e: e.prefill_export(SHORT),
    lambda e: e.submit_adopt({"prompt": SHORT, "block_size": BS}),
    lambda e: e.export_warm_prefixes(),
    lambda e: e.import_warm_prefixes({"block_size": BS, "chains": []})],
    ids=["prefill_export", "submit_adopt", "export_warm_prefixes",
         "import_warm_prefixes"])
def test_what_moves_pages_alone_is_refused_by_name(engine, call, request):
    what = request.node.callspec.id
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        call(engine)
    assert what in str(e.value)


def test_the_pools_are_sized_without_the_state(engine):
    cfg = engine.model_config
    # one paged layer in four: k and v of 2 heads x 8, float32
    assert engine.config.kv_bytes_per_token(cfg) == 2 * 2 * 8 * 4
    assert set(engine._cache) == {"k", "v", "ssm", "conv"}
    assert engine._cache["ssm"].shape[:2] == (3, 3)


def test_a_model_without_such_layers_keeps_its_stats_keys():
    dense = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                 head_dim=8, n_kv_heads=2, d_ff=48, max_seq_len=64,
                 rotary_dim=8, block_style="llama", dtype=jnp.float32,
                 remat_policy="none")
    eng = LLMEngine(TransformerConfig(**dense),
                    EngineConfig(decode_slots=2, kv_block_size=BS,
                                 max_seq_len=64, prefill_chunk=CHUNK))
    try:
        served = list(eng.generate_sync(SHORT, 4))
        s = eng.stats()
        assert len(served) == 4 and not STATE_KEYS & set(s)
        assert set(eng._cache) == {"k", "v"}
        assert set(s["compiled_programs"]) == {
            "prefill", "copy", "decode", "gather", "scatter"}
    finally:
        eng.shutdown()
