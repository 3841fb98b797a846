"""``LLMEngine`` serving a key selection over a latent cache: what it
answers is the plain reference's own choice
(``benchmarks/reference/axk2.py``, seeded weights, a small size, prompts
past the top-k) with a prefix hit, a copy-on-write and an eviction
moving latent rows and index keys TOGETHER, as pages of two pools under
one table; the same tokens as an engine without the trie; and the
selection's counters booked by kind of program beside their totals."""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.reference import axk2
from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4,
                head_dim=12, d_ff=48, max_seq_len=96, rope_base=1e4,
                block_style="llama", dtype=jnp.float32,
                remat_policy="none", norm_eps=1e-6, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
                v_head_dim=8, n_dense_layers=1, n_experts=8,
                experts_per_token=2, expert_width=16,
                shared_expert_width=16, router_score="sigmoid",
                routed_scale=2.5, experts_held=4, expert_first=2,
                n_group=4, topk_group=2, router_bias=True,
                index_topk=8, index_heads=2, index_dim=8,
                index_q_lora=True, rope_yarn=(2.0, 32, 32.0, 1.0, 1.0),
                rope_softmax_scale=1.1434, head_gate=True,
                gated_norm_rank=2)
HP = tuple(sorted(dict(
    num_attention_heads=4, rms_norm_eps=1e-6, rope_theta=1e4,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
    yarn_factor=2.0, yarn_original=32, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0, index_n_heads=2,
    index_head_dim=8, index_topk=8, attention_output_gate=True,
    gated_norm=True, n_group=4, topk_group=2, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=2.5, expert_first=2,
    experts_held=4).items()))
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
    want = axk2.forward(eng._params, ids[None], HP)[
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
    documents push it out of the 40-page pool first. A page that came
    back under another id with its latent rows and NOT its index keys
    (or the reverse) would select other keys: every query here is past
    the top-k of 8."""
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
    assert set(engine._cache) == {"latent", "ki"}


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


def test_a_copied_page_carries_both_kinds_of_state(engine):
    """The copy-on-write program moves block ``src`` of EVERY pool: after
    a page-aligned prompt sent twice, some page of the pool holds the
    same latent rows and the same index keys as another."""
    list(engine.generate_sync(DOC, 2))
    list(engine.generate_sync(DOC, 2))
    lat = np.asarray(engine._cache["latent"])[0, 1:, 0]     # [N, bs, row]
    ki = np.asarray(engine._cache["ki"])[0, 1:, 0]
    twins = [(a, b) for a in range(len(lat)) for b in range(a + 1, len(lat))
             if lat[a].any() and np.array_equal(lat[a, :3], lat[b, :3])]
    assert twins
    assert all(np.array_equal(ki[a, :3], ki[b, :3]) for a, b in twins)


def test_the_counters_by_kind_add_up_to_the_totals():
    """One request alone: 20 prompt tokens in chunks of 16 and 4, then
    5 decode steps, top-k 8, three layers."""
    eng = _engine()
    try:
        list(eng.generate_sync(DOC[:20], 6))
        s = eng.stats()
        prefill = sum(min(p + 1, 8) for p in range(20))
        decode = 5 * 8
        assert s["keys_attended_prefill_total"] == prefill
        assert s["keys_attended_decode_total"] == decode
        assert s["keys_attended_total"] == prefill + decode
        assert s["indexer_keys_scored_prefill_total"] \
            == 3 * sum(p + 1 for p in range(20))
        assert s["indexer_keys_scored_decode_total"] \
            == 3 * sum(p + 1 for p in range(20, 25))
        assert s["indexer_keys_scored_total"] \
            == s["indexer_keys_scored_prefill_total"] \
            + s["indexer_keys_scored_decode_total"]
        assert s["keys_visible_total"] == sum(p + 1 for p in range(25))
        # two of the three layers route, half the experts are held
        assert s["moe_assignments_total"] == 25 * 2 * 2
        # two pools: a latent row of 128 and an index key of 8 a token
        # and layer
        assert eng._cache["latent"].shape[2:] == (1, 4, 128)
        assert eng._cache["ki"].shape[2:] == (1, 4, 8)
        assert eng.config.kv_bytes_per_token(eng.model_config) \
            == 3 * (128 + 8) * 4
    finally:
        eng.shutdown()
