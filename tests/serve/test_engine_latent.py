"""``LLMEngine`` serving the latent-attention forms: what it answers is
the plain reference's own choice (``benchmarks/reference/pangu.py``,
seeded weights, a small size) with a prefix hit, a copy-on-write and an
eviction moving pages of the ONE latent pool; the same tokens as an
engine without the trie; and the pool travels whole when a page is
shipped or adopted."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import pangu
from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4,
                head_dim=12, d_ff=48, max_seq_len=96, rotary_dim=4,
                rope_base=1e4, block_style="llama", dtype=jnp.float32,
                remat_policy="none", norm_eps=1e-5, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
                v_head_dim=8, sandwich_norm=True, n_dense_layers=1,
                n_experts=8, experts_per_token=2, expert_width=16,
                shared_expert_width=16, router_score="sigmoid",
                routed_scale=2.5, experts_held=4, expert_first=2)
HP = tuple(sorted(dict(
    num_attention_heads=4, rms_norm_eps=1e-5, rope_theta=1e4,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    expert_first=2, experts_held=4).items()))
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
    want = pangu.forward(eng._params, ids[None], HP)[
        0, len(prompt) - 1:len(prompt) - 1 + len(served)]
    picked = jnp.take_along_axis(want, ids[len(prompt):, None], -1)[:, 0]
    return float(jnp.max((jnp.max(want, -1) - picked)
                         / jnp.max(jnp.abs(want))))


PROMPTS = [DOC + [9, 8, 7], DOC + [4, 4, 5, 6], DOC, DOC]


@pytest.mark.parametrize("case", ["cold_hit_cow", "evicted"])
def test_served_tokens_are_the_references(engine, case):
    """``cold_hit_cow``: a document, the same document under another
    question (prefix hit), and a page-aligned prompt sent twice (all of
    it matched: the tail page is copied on write). ``evicted``: other
    documents push it out of the 40-page pool first."""
    s0 = engine.stats()
    if case == "evicted":
        for i in range(6):
            other = [(7 * i + 11 * j) % 60 + 2 for j in range(37)]
            assert len(list(engine.generate_sync(other, 4))) == 4
        assert engine.stats()["prefix_evictions_total"] \
            > s0["prefix_evictions_total"]
    for prompt in PROMPTS:
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


def test_the_trie_changes_no_token(engine):
    """Hits, a copy-on-write and whatever the pool evicted, against an
    engine that prefills every prompt from its first token."""
    plain = _engine(enable_prefix_sharing=False)
    try:
        for prompt in PROMPTS:
            assert list(engine.generate_sync(prompt, 8)) \
                == list(plain.generate_sync(prompt, 8))
        assert plain.stats()["prefix_hit_blocks_total"] == 0
    finally:
        plain.shutdown()


def test_counters_and_bytes_follow_the_pool():
    """One request alone: 20 prompt tokens in chunks of 16 and 4, then
    5 decode steps. Two of the three layers route (the first is dense),
    and half the experts are held."""
    eng = _engine()
    try:
        list(eng.generate_sync(DOC[:20], 6))
        s = eng.stats()
        assert s["moe_assignments_total"] == 25 * 2 * 2
        # a decode step counts its latent pages as K/V pages are
        # counted: the one sequence's, none for the slot beside it
        assert s["decode_pages_live"] == sum(
            -(-(20 + i + 1) // 4) for i in range(5))
        assert s["decode_slots_skipped_total"] == 5
        # one pool, a row of 128 (16 + 4 numbers up to a lane tile) a
        # token and layer
        assert set(eng._cache) == {"latent"}
        assert eng._cache["latent"].shape[2:] == (1, 4, 128)
        assert eng.config.kv_bytes_per_token(eng.model_config) \
            == 3 * 128 * 4
        assert s["decode_pages_per_step"] >= 1
    finally:
        eng.shutdown()


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_a_shipped_page_is_its_latent_rows(wire):
    """Hand-off (prefill here, decode there) and warm-prefix migration:
    a cache with no k or v pool ships its one pool as it is under
    either wire, and the adopting engine answers as the one that
    prefilled in place."""
    ref, pre, dec = _engine(), _engine(kv_wire=wire), _engine(kv_wire=wire)
    try:
        prompt = DOC + [9, 8, 7]
        want = list(ref.generate_sync(prompt, 8))
        payload = pre.prefill_export(prompt)
        assert "k" not in payload["kv"]
        assert payload["kv"]["extra"]["latent"].shape[:2] == (3, 11)
        assert payload["kv"]["wire_bytes"] >= 3 * 11 * 4 * 128 * 4
        req = dec.submit_adopt(payload, max_new_tokens=8)
        got = []
        while len(got) < 8:
            item = req.out.get(timeout=60)
            if isinstance(item, BaseException):
                raise item
            got.append(item)
        assert got == want
        list(ref.generate_sync(DOC + [4, 4], 4))
        moved = ref.export_warm_prefixes(min_hits=1)
        assert moved is not None and "latent" in moved["kv"]["extra"]
        fresh = _engine()
        try:
            assert fresh.import_warm_prefixes(moved) == moved["n_blocks"]
            assert list(fresh.generate_sync(prompt, 8)) == want
            assert fresh.stats()["prefix_hit_blocks_total"] >= 10
            assert fresh.pool_audit() == []
        finally:
            fresh.shutdown()
        assert pre.pool_audit() == [] and dec.pool_audit() == []
    finally:
        for e in (ref, pre, dec):
            e.shutdown()
