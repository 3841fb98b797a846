"""``LLMEngine`` serving the selecting, routing forms: what it answers
is the plain reference's own choice (``benchmarks/reference/keye.py``,
seeded weights, a small size) with a prefix hit, a copy-on-write and an
eviction in the path, and a page's indexer keys travel with its K and V
wherever a page is copied, shipped or adopted."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import keye
from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                head_dim=8, n_kv_heads=2, d_ff=32, max_seq_len=96,
                rotary_dim=8, rope_base=1e4, block_style="llama",
                dtype=jnp.float32, remat_policy="none",
                n_experts=8, experts_per_token=2, expert_width=16,
                qk_norm=True, index_topk=8, index_heads=2, index_dim=8)
HP = tuple(sorted(dict(
    num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-6,
    rope_theta=1e4, indexer_num_heads=2, indexer_head_dim=8,
    indexer_layer_norm_eps=1e-6, topk=8, num_experts_per_tok=2,
    norm_topk_prob=True).items()))
DOC = [(5 * i + 3) % 60 + 2 for i in range(40)]       # ten pages of 4


def _engine(**kw):
    ekw = dict(decode_slots=2, kv_block_size=4, max_seq_len=64,
               prefill_chunk=16, max_new_tokens=8)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**MODEL_KW), EngineConfig(**ekw))


@pytest.fixture(scope="module")
def engine():
    eng = _engine(num_kv_blocks=41)       # 40 pages: two documents' worth
    yield eng
    eng.shutdown()


def _gap(eng, prompt, served):
    """How far below the reference's largest logit the served tokens'
    lie, over the largest magnitude (the benchmark's served check)."""
    ids = jnp.asarray(list(prompt) + list(served), jnp.int32)
    want = keye.forward(eng._params, ids[None], HP)[
        0, len(prompt) - 1:len(prompt) - 1 + len(served)]
    picked = jnp.take_along_axis(want, ids[len(prompt):, None], -1)[:, 0]
    return float(jnp.max((jnp.max(want, -1) - picked)
                         / jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("case", ["cold_hit_cow", "evicted"])
def test_served_tokens_are_the_references(engine, case):
    """Contexts of 41-56 tokens under topk 8. ``cold_hit_cow``: a
    document, the same document under another question (prefix hit),
    and a page-aligned prompt sent twice (all of it matched: the tail
    page is copied on write). ``evicted``: other documents push it out
    of the 40-page pool first."""
    s0 = engine.stats()
    if case == "evicted":
        for i in range(6):
            other = [(7 * i + 11 * j) % 60 + 2 for j in range(37)]
            assert len(list(engine.generate_sync(other, 4))) == 4
        assert engine.stats()["prefix_evictions_total"] \
            > s0["prefix_evictions_total"]
    prompts = [DOC + [9, 8, 7], DOC + [4, 4, 5, 6], DOC, DOC]
    for prompt in prompts:
        served = list(engine.generate_sync(prompt, 8))
        assert len(served) == 8
        assert _gap(engine, prompt, served) < 1e-4
    s = engine.stats()
    assert s["prefix_hit_blocks_total"] - s0["prefix_hit_blocks_total"] >= 20
    assert s["cow_copies_total"] > s0["cow_copies_total"]
    assert engine.pool_audit() == []
    assert set(s["compiled_programs"].values()) <= {0, 1}
    assert s["h2d_transfers_total"] \
        == s["prefill_chunks"] + s["decode_steps"]


def test_counters_follow_positions():
    """One request alone, so the counters can be counted by hand: 20
    prompt tokens in chunks of 16 and 4, then 5 decode steps (6 tokens
    out, the first from the prefill)."""
    eng = _engine()
    try:
        list(eng.generate_sync(DOC[:20], 6))
        s = eng.stats()
        queries = range(20 + 5)                 # positions 0 .. 24
        assert s["keys_visible_total"] == sum(p + 1 for p in queries)
        assert s["keys_attended_total"] == sum(min(p + 1, 8)
                                               for p in queries)
        assert s["indexer_keys_scored_total"] \
            == 2 * s["keys_visible_total"]
        assert s["moe_assignments_total"] == 25 * 2 * 2
        assert eng.config.kv_bytes_per_token(eng.model_config) \
            == 2 * (2 * 2 * 8 + 8) * 4
        # its chunks select their keys in plain XLA: no paged kernel's
        # row block to book
        assert s["prefill_row_blocks"] == {} == s["prefill_row_blocks_live"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_a_shipped_page_carries_its_indexer_keys(wire):
    """Hand-off (prefill here, decode there) and warm-prefix migration:
    the adopting engine answers as the one that prefilled in place
    would, which it cannot unless ``ki`` arrived with k and v (the
    indexer would rank zeros). The int8 wire quantizes k and v and ships
    ``ki`` as it is."""
    ref, pre, dec = _engine(), _engine(kv_wire=wire), _engine(kv_wire=wire)
    try:
        prompt = DOC + [9, 8, 7]
        want = list(ref.generate_sync(prompt, 8))
        payload = pre.prefill_export(prompt)
        assert payload["kv"]["extra"]["ki"].shape[:2] == (2, 11)
        req = dec.submit_adopt(payload, max_new_tokens=8)
        got = []
        while len(got) < 8:
            item = req.out.get(timeout=60)
            if isinstance(item, BaseException):
                raise item
            got.append(item)
        if wire == "bf16":
            assert got == want
        assert _gap(ref, prompt, got) < (1e-4 if wire == "bf16" else 0.05)
        # migration: ref's warm document moves to a fresh engine
        list(ref.generate_sync(DOC + [4, 4], 4))
        moved = ref.export_warm_prefixes(min_hits=1)
        assert moved is not None and "ki" in moved["kv"]["extra"]
        fresh = _engine()
        try:
            assert fresh.import_warm_prefixes(moved) == moved["n_blocks"]
            again = list(fresh.generate_sync(prompt, 8))
            assert fresh.stats()["prefix_hit_blocks_total"] >= 10
            assert again == want
            assert fresh.pool_audit() == []
        finally:
            fresh.shutdown()
        assert pre.pool_audit() == [] and dec.pool_audit() == []
    finally:
        for e in (ref, pre, dec):
            e.shutdown()


@pytest.mark.parametrize("impl,form", [("auto", "reference"),
                                       ("interpret", "interpret")])
def test_stats_say_which_form_the_decode_step_took(impl, form):
    """``sparse_decode_impl`` is what the decode program's selected
    attention was built as (off the TPU ``auto`` is the gather of the
    selected rows; ``interpret`` reads the live pages through the paged
    kernel with the selection as its mask), and
    ``sparse_decode_kernel_steps_total`` the decode steps that ran
    through the kernel: all five, or none; ``sparse_prefill_impl`` is a
    chunk's form, the kernel or XLA's masked pass. Either way the served tokens
    are the reference's own choice. A model that selects nothing has
    neither key."""
    eng = LLMEngine(TransformerConfig(**dict(MODEL_KW, paged_impl=impl)),
                    EngineConfig(decode_slots=2, kv_block_size=4,
                                 max_seq_len=64, prefill_chunk=16,
                                 max_new_tokens=8))
    try:
        served = list(eng.generate_sync(DOC[:20], 6))
        assert _gap(eng, DOC[:20], served) < 1e-4
        s = eng.stats()
        assert s["decode_steps"] == 5
        assert s["sparse_decode_impl"] == form == s["sparse_prefill_impl"]
        assert s["sparse_decode_kernel_steps_total"] \
            == (5 if form == "interpret" else 0)
        assert {"op": "sparse_decode", "impl": form,
                "why": "requested" if impl == form
                else "platform is not tpu"} in [
            {k: e[k] for k in ("op", "impl", "why")}
            for e in s["attention_dispatch"]]
    finally:
        eng.shutdown()
    if impl == "auto":
        dense = LLMEngine(
            TransformerConfig(**dict(MODEL_KW, index_topk=0, index_heads=0,
                                     index_dim=0)),
            EngineConfig(decode_slots=2, kv_block_size=4, max_seq_len=64,
                         prefill_chunk=16, max_new_tokens=8))
        try:
            assert not {"sparse_decode_impl", "sparse_prefill_impl",
                        "sparse_decode_kernel_steps_total"} \
                & set(dense.stats())
        finally:
            dense.shutdown()
