"""``LLMEngine`` serving a stack with window and full attention layers
over two kinds of page: what it answers is the plain reference's own
choice (``benchmarks/reference/laguna.py``, seeded weights, a small
size) and an engine without the trie's, cold, on a hit at a document's
end, on a hit in mid-prompt more than a window behind the first
request's end (the harness's set-up), through a copy-on-write, and when
the hit's window tail was evicted and the hit cut back; a sequence pins
no more of the window pool than a window and a chunk; the pages it
passes go back; and what moves one kind of page alone refuses the
configuration by name."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import laguna
from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=5, n_heads=6, head_dim=8,
                n_kv_heads=2, d_ff=48, max_seq_len=192, rotary_dim=4,
                rope_base=5e5, block_style="llama", dtype=jnp.float32,
                remat_policy="none", norm_eps=1e-6,
                layer_pattern=["full", "window", "window", "window"],
                window_heads=8, sliding_window=16, window_rope_base=1e4,
                rope_yarn=[8.0, 32, 4.0, 1.0, 1.2], head_gate=True,
                n_dense_layers=1, n_experts=8, experts_per_token=2,
                expert_width=16, shared_expert_width=16,
                router_score="sigmoid", routed_scale=2.5)
HP = tuple(sorted(dict(
    num_attention_heads=6, window_heads=8, num_key_value_heads=2, head_dim=8,
    rms_norm_eps=1e-6, sliding_window=16, rope_theta=5e5,
    partial_rotary_factor=0.5, yarn_factor=8.0, yarn_original=32,
    yarn_beta_fast=4.0, yarn_beta_slow=1.0, yarn_attention_factor=1.2,
    window_rope_theta=1e4, num_experts_per_tok=2,
    moe_routed_scaling_factor=2.5, gating=True,
    layer_pattern="full window window window").items()))
BS, CHUNK, WINDOW = 4, 16, 16
#: pages a sequence may pin of the window pool: ceil((16 + 16) / 4) + 1
PINNED = 9
DOC = [(5 * i + 3) % 60 + 2 for i in range(80)]       # twenty pages of 4
OTHER = [(7 * i + 1) % 60 + 2 for i in range(80)]


def _engine(**kw):
    ekw = dict(decode_slots=2, kv_block_size=BS, max_seq_len=128,
               prefill_chunk=CHUNK, max_new_tokens=8, num_kv_blocks=81,
               num_window_blocks=61)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**MODEL_KW), EngineConfig(**ekw))


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def plain():
    eng = _engine(enable_prefix_sharing=False)
    yield eng
    eng.shutdown()


def _gap(eng, prompt, served):
    """How far below the reference's largest logit the served tokens'
    lie, over the largest magnitude (the benchmark's served check)."""
    ids = jnp.asarray(list(prompt) + list(served), jnp.int32)
    want = laguna.forward(eng._params, ids[None], HP)[
        0, len(prompt) - 1:len(prompt) - 1 + len(served)]
    picked = jnp.take_along_axis(want, ids[len(prompt):, None], -1)[:, 0]
    return float(jnp.max((jnp.max(want, -1) - picked)
                         / jnp.max(jnp.abs(want))))


def _serve(engine, plain, prompt, hits=None, cut=0):
    """``prompt`` through the engine with the trie: the reference's own
    tokens, a cold engine's tokens, the hit's depth and whether it was
    cut."""
    s0 = engine.stats()
    served = list(engine.generate_sync(prompt, 8))
    s = engine.stats()
    assert len(served) == 8 and _gap(engine, prompt, served) < 1e-4
    assert served == list(plain.generate_sync(prompt, 8))
    if hits is not None:
        assert s["prefix_hit_blocks_total"] \
            - s0["prefix_hit_blocks_total"] == hits
    assert s["prefix_hits_cut"] - s0["prefix_hits_cut"] == cut
    assert engine.pool_audit() == []
    return s


def test_cold_then_hits_at_the_end_and_in_mid_prompt(engine, plain):
    """The harness's set-up in small: a prompt served cold; a second
    that shares its leading 48 tokens, a boundary 35 positions (over two
    windows) behind the first's end, whose window tail slid out of the
    first request's window while it prefilled and was kept as cache; a
    re-ask at the document's end; and the same prompt again, whole pages
    long, through a copy-on-write of both kinds of page."""
    first = DOC[:80] + [9, 8, 7]
    s = _serve(engine, plain, first, hits=0)
    assert s["window_pages_released"] > 0
    _serve(engine, plain, DOC[:48] + OTHER[:30], hits=12)
    _serve(engine, plain, DOC[:80] + [4, 5, 6, 7, 8], hits=20)
    s0 = engine.stats()["cow_copies_total"]
    s = _serve(engine, plain, DOC[:80], hits=20)
    assert s["cow_copies_total"] == s0 + 1
    assert s["window_pages_pinned"] == 0 and s["active_slots"] == 0
    assert s["window_pages_pinned_max"] <= PINNED
    assert set(s["compiled_programs"]) == {"prefill", "copy", "decode"}
    assert set(s["compiled_programs"].values()) <= {0, 1}
    assert s["h2d_transfers_total"] \
        == s["prefill_chunks"] + s["decode_steps"]


def test_a_hit_whose_window_tail_was_evicted_is_cut_back(plain):
    """A window pool with no room beside what two sequences pin. One
    other document's prefill sheds twenty pages into it and the tail at
    DOC's end survives, because pages far behind their prompt's end go
    first; two at once pin most of the pool, and the tail goes too. A
    re-ask then finds its document's full pages and no window tail at
    its end: the hit is cut back, to nothing here, counted, and the
    answer is still a cold engine's."""
    import threading
    eng = _engine(num_window_blocks=1 + 2 * PINNED)
    try:
        _serve(eng, plain, DOC[:80] + [9, 8, 7], hits=0)
        _serve(eng, plain, OTHER[:80] + [3, 2], hits=0)
        _serve(eng, plain, DOC[:80] + [1, 2, 3], hits=20)
        both = [threading.Thread(target=lambda p=p: list(
            eng.generate_sync(p, 8))) for p in (
                OTHER[40:] + DOC[:40], OTHER[20:] + OTHER[:30])]
        for t in both:
            t.start()
        for t in both:
            t.join()
        s = _serve(eng, plain, DOC[:80] + [4, 5, 6], cut=1)
        assert s["window_evictions_total"] > 0
        assert s["window_pages_pinned_max"] <= PINNED
        # re-prefilled, its pages name window pages again: a full hit
        _serve(eng, plain, DOC[:80] + [6, 5], hits=20, cut=0)
    finally:
        eng.shutdown()


def test_row_blocks_by_kind():
    """40 prompt tokens in chunks of 16, 16 and 8 at a row block of 16
    rows: a full layer's call has 3 heads a kv head (48 rows, three
    blocks, the last chunk's 24 live rows in two of them), a window
    layer's 4 (64 rows, four blocks, 32 live rows in two)."""
    eng = LLMEngine(
        TransformerConfig(**dict(MODEL_KW, paged_block_r_prefill=16)),
        EngineConfig(decode_slots=2, kv_block_size=BS, max_seq_len=128,
                     prefill_chunk=CHUNK, max_new_tokens=8))
    try:
        list(eng.generate_sync(DOC[:40], 2))
        s = eng.stats()
        assert s["prefill_chunks"] == 3
        assert s["prefill_row_blocks"] == {"full": 9, "window": 12}
        assert s["prefill_row_blocks_live"] == {"full": 8, "window": 10}
        eng.warmup()                    # its end resets the books
        assert eng.stats()["prefill_row_blocks"] == {"full": 0, "window": 0}
    finally:
        eng.shutdown()


def test_pages_by_kind_and_the_pinned_bound():
    """One request alone: 40 prompt tokens in chunks of 16, 16 and 8,
    then 7 decode steps over a window of 16."""
    eng = _engine()
    try:
        list(eng.generate_sync(DOC[:40], 8))
        s = eng.stats()
        assert s["prefill_pages_live_full"] == 4 + 8 + 10
        # a chunk's window layers read from the page of its first
        # query's first key: 0..3, then 0..7 (keys 1..), then 4..9
        assert s["prefill_pages_live_window"] == 4 + 8 + 6
        steps = range(40, 47)
        assert s["decode_pages_live_full"] == s["decode_pages_live"] \
            == sum(p // 4 + 1 for p in steps)
        assert s["decode_pages_live_window"] \
            == sum(p // 4 - (p - 15) // 4 + 1 for p in steps)
        assert s["window_pages_pinned_max"] <= PINNED
        # every page behind the window went back as the sequence passed
        # it, the rest at its end
        assert s["window_pages_released"] == (46 - 15) // 4
        assert s["window_pages_pinned"] == 0
        assert s["window_total_blocks"] == 60
        assert s["window_free_blocks"] == 60
        assert s["moe_assignments_total"] == 47 * 2 * 4
        cache = eng._cache
        assert cache["k"].shape[:2] == (2, 81)
        assert cache["k_window"].shape[:2] == (3, 61)
        ec, mc = eng.config, eng.model_config
        assert ec.kv_bytes_per_token(mc) == 2 * 2 * 2 * 8 * 4
        assert ec.kv_bytes_per_token(mc, "window") == 2 * 3 * 2 * 8 * 4
        assert ec.window_blocks_per_seq(mc) == PINNED
        assert EngineConfig(decode_slots=2, kv_block_size=BS,
                            prefill_chunk=CHUNK).resolved_window_blocks(mc) \
            == 1 + 2 * PINNED
        assert eng.pool_audit() == []
    finally:
        eng.shutdown()


def test_what_moves_one_kind_of_page_refuses_the_configuration(engine):
    for call in (lambda: engine.prefill_export(DOC[:20]),
                 lambda: engine.submit_adopt({"block_size": BS}),
                 lambda: engine.export_warm_prefixes(),
                 lambda: engine.import_warm_prefixes({"block_size": BS})):
        with pytest.raises(NotImplementedError, match="sliding_window"):
            call()
    with pytest.raises(ValueError, match="spec_tokens"):
        _engine(spec_tokens=2)
    with pytest.raises(ValueError, match="num_window_blocks"):
        _engine(num_window_blocks=2 * PINNED)
