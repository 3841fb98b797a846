"""``LLMEngine`` sharing prefixes for a stack with "delta" layers: the
recurrent state as of every ``state_snapshot_stride`` tokens of a prompt
is kept in a snapshot row the trie's node names, and a prefix hit is cut
back to the deepest such node and copies the row into the request's
slot. What a request with a shared prefix answers is what an engine with
sharing off answers, and the plain reference's own choice
(``benchmarks/reference/olmo_hybrid.py``, seeded weights, a small size):
at hits that end on a boundary of the stride, between two, behind
generated tokens and at the whole prompt; after the snapshot it would
have used is gone; while other slots decode between its chunks; in a
slot used again. The counters count what was driven, the pool's audit is
clean after each, and what is still refused is refused by name."""
import threading

import pytest

import jax.numpy as jnp

from benchmarks.reference import olmo_hybrid
from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8,
                n_kv_heads=4, d_ff=48, max_seq_len=128, rotary_dim=0,
                block_style="llama", dtype=jnp.float32,
                remat_policy="none", norm_eps=1e-6,
                layer_pattern=["delta", "delta", "delta", "full"],
                delta_heads=4, delta_key_dim=8, delta_value_dim=16,
                delta_conv=4, delta_neg_eigval=True,
                output_norm=True, qk_norm_whole=True)
HP = tuple(sorted(dict(
    num_attention_heads=4, num_key_value_heads=4, rms_norm_eps=1e-6,
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    layer_types="linear_attention,linear_attention,linear_attention,"
                "full_attention").items()))
BS, CHUNK, STRIDE = 4, 16, 8
DOC = [(5 * i + 3) % 60 + 2 for i in range(44)]       # 11 pages, 5 strides
SNAP_KEYS = {"state_snapshots_total", "state_snapshots_live",
             "state_snapshots_taken_total", "state_snapshots_evicted_total",
             "state_hits_total", "state_matched_blocks_total",
             "state_cut_blocks_total"}
DELTA_KEYS = {"state_slots_total", "state_bytes_per_slot",
              "delta_decode_rows_total", "delta_prefill_tokens_total",
              "delta_prefill_calls_total"}


def _question(i, n=5):
    return [(11 * i + 7 * j) % 60 + 2 for j in range(n)]


def _engine(**kw):
    ekw = dict(decode_slots=3, kv_block_size=BS, max_seq_len=128,
               prefill_chunk=CHUNK, max_new_tokens=8, num_kv_blocks=97,
               enable_prefix_sharing=True, state_snapshot_stride=STRIDE,
               num_state_snapshots=12)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**MODEL_KW), EngineConfig(**ekw))


@pytest.fixture(scope="module")
def plain():
    """Sharing off: every prompt prefilled whole."""
    eng = _engine(enable_prefix_sharing=False, state_snapshot_stride=0,
                  num_state_snapshots=0)
    yield eng
    eng.shutdown()


@pytest.fixture()
def engine():
    eng = _engine()
    yield eng
    assert eng.pool_audit() == []
    eng.shutdown()


def _gap(eng, prompt, served):
    """How far below the reference's largest logit the served tokens'
    lie, over the largest magnitude (the benchmark's served check)."""
    ids = jnp.asarray(list(prompt) + list(served), jnp.int32)
    want = olmo_hybrid.forward(eng._params, ids[None], HP)[
        0, len(prompt) - 1:len(prompt) - 1 + len(served)]
    picked = jnp.take_along_axis(want, ids[len(prompt):, None], -1)[:, 0]
    return float(jnp.max((jnp.max(want, -1) - picked)
                         / jnp.max(jnp.abs(want))))


def _ask(eng, plain, prompt, n=8):
    """``prompt`` served by ``eng``: the reference's choice, and the
    tokens the engine without sharing serves. Returns what the request
    added to the counters."""
    s0 = eng.stats()
    served = list(eng.generate_sync(prompt, n))
    s = eng.stats()
    assert len(served) == n and _gap(eng, prompt, served) < 1e-4
    assert served == list(plain.generate_sync(prompt, n))
    assert eng.pool_audit() == []
    return {k: s[k] - s0[k] for k in s
            if k.endswith("_total") and isinstance(s[k], int)}


@pytest.mark.parametrize("doc_len,hit_tokens,cut_pages", [
    (40, 40, 0),      # the shared pages end on a boundary of the stride
    (44, 40, 1),      # between two: the eleventh page is recomputed
    (38, 32, 1),      # a ragged page: nine whole pages match, eight count
])
def test_a_hit_resumes_from_the_deepest_snapshot(engine, plain, doc_len,
                                                 hit_tokens, cut_pages):
    doc = DOC[:doc_len]
    first = _ask(engine, plain, doc + _question(1))
    assert first["state_hits_total"] == 0
    assert first["prefix_hit_blocks_total"] == 0
    # a snapshot at every boundary of the stride inside the prompt
    assert first["state_snapshots_taken_total"] \
        == (doc_len + 5) // STRIDE
    assert first["delta_prefill_tokens_total"] == doc_len + 5
    second = _ask(engine, plain, doc + _question(2))
    assert second["state_hits_total"] == 1
    assert second["prefix_hit_blocks_total"] == hit_tokens // BS
    assert second["state_matched_blocks_total"] \
        == hit_tokens // BS + cut_pages
    assert second["state_cut_blocks_total"] == cut_pages
    # only what lies behind the snapshot was prefilled, in one call
    assert second["delta_prefill_tokens_total"] == doc_len + 5 - hit_tokens
    assert second["delta_prefill_calls_total"] == 1
    assert second["cow_copies_total"] == 0
    s = engine.stats()
    assert SNAP_KEYS | DELTA_KEYS <= set(s)
    assert s["state_snapshots_total"] == 12
    assert s["state_snapshots_live"] == s["state_snapshots_taken_total"] \
        - s["state_snapshots_evicted_total"]
    assert set(s["compiled_programs"]) == {"prefill", "copy", "state_copy",
                                           "decode"}
    assert set(s["compiled_programs"].values()) <= {0, 1}


def test_the_whole_prompt_again_stops_short_of_its_last_token(engine, plain):
    prompt = DOC[:40]                    # ten pages, five strides
    _ask(engine, plain, prompt)
    again = _ask(engine, plain, prompt)
    # 40 tokens match; the hit may not hold the last one: back to 32
    assert again["state_hits_total"] == 1
    assert again["prefix_hit_blocks_total"] == 8
    assert again["state_cut_blocks_total"] == 2
    assert again["delta_prefill_tokens_total"] == 8
    assert again["cow_copies_total"] == 0


def test_a_hit_into_generated_tokens_is_cut_to_the_prompt(engine, plain):
    """The next turn of a conversation: the first prompt, its answer and
    a new question. The answer's tokens went through decode steps, which
    take no snapshot: the hit ends inside the first prompt."""
    prompt = DOC[:36] + _question(3, 6)              # 42 tokens
    answer = list(engine.generate_sync(prompt, 8))
    turn = prompt + answer + _question(4)
    second = _ask(engine, plain, turn)
    assert second["state_hits_total"] == 1
    assert second["prefix_hit_blocks_total"] == 40 // BS
    assert second["delta_prefill_tokens_total"] == len(turn) - 40


def test_an_evicted_snapshot_cuts_the_hit_to_a_shallower_one_or_none(plain):
    """Five rows: the second document's prefill takes the first's
    shallowest snapshots for its own (least recently touched first)."""
    eng = _engine(num_state_snapshots=5)
    try:
        doc_a, doc_b = DOC[:40], [t % 60 + 2 for t in range(7, 31)]
        first = _ask(eng, plain, doc_a + _question(1))
        assert first["state_snapshots_taken_total"] == 5
        other = _ask(eng, plain, doc_b + _question(2))
        assert other["state_snapshots_taken_total"] == 3
        assert other["state_snapshots_evicted_total"] == 3
        # boundaries 8, 16, 24 of the first document are gone; 32 and 40
        # stand, and the hit is the deepest
        back = _ask(eng, plain, doc_a + _question(3))
        assert back["state_hits_total"] == 1
        assert back["prefix_hit_blocks_total"] == 10
        # a prompt that shares 20 tokens of it finds pages and no state
        short = _ask(eng, plain, doc_a[:20] + _question(5, 9))
        assert short["state_hits_total"] == 0
        assert short["prefix_hit_blocks_total"] == 0
        assert short["state_matched_blocks_total"] == 5
        assert short["state_cut_blocks_total"] == 5
        assert short["delta_prefill_tokens_total"] == 29
        # ... and puts the snapshots back on the nodes that had lost them
        assert short["state_snapshots_taken_total"] == 3
        assert eng.pool_audit() == []
    finally:
        eng.shutdown()


def test_a_trie_node_evicted_gives_its_row_back(plain):
    """A pool of pages too small to keep two documents: the first's
    pages are evicted for the second's, their snapshots with them."""
    eng = _engine(num_kv_blocks=1 + 16, decode_slots=1)
    try:
        _ask(eng, plain, DOC[:40] + _question(1))
        s0 = eng.stats()
        assert s0["state_snapshots_live"] == 5
        _ask(eng, plain, [t % 60 + 2 for t in range(9, 49)] + _question(2))
        s = eng.stats()
        assert s["prefix_evictions_total"] > s0["prefix_evictions_total"]
        assert s["state_snapshots_live"] < 10
        assert s["state_snapshots_live"] == s["state_snapshots_taken_total"] \
            - s["state_snapshots_evicted_total"]
        miss = _ask(eng, plain, DOC[:40] + _question(3))
        assert miss["delta_prefill_tokens_total"] > 5
    finally:
        eng.shutdown()


def test_resumed_between_other_slots_decode_steps(engine, plain):
    """A resumed request's chunks run with another slot's decode steps
    between them, and its slot is used again afterwards by a request
    that misses: each answers as the engine without sharing does."""
    long_doc = DOC + [t % 60 + 2 for t in range(3, 43)]      # 84 tokens
    _ask(engine, plain, long_doc[:24] + _question(1))
    want = list(plain.generate_sync(long_doc + _question(2), 8))
    talker = [(9 * j + 1) % 60 + 2 for j in range(9)]   # shares nothing
    talk = list(plain.generate_sync(talker, 40))
    out = {}

    def run(name, prompt, n):
        out[name] = list(engine.generate_sync(prompt, n))
    s0 = engine.stats()
    first = threading.Thread(target=run, args=("talk", talker, 40))
    first.start()
    while engine.stats()["decode_steps"] == s0["decode_steps"]:
        pass                           # the short one is decoding
    second = threading.Thread(
        target=run, args=("long", long_doc + _question(2), 8))
    second.start()
    first.join(60), second.join(60)
    s = engine.stats()
    assert out == {"talk": talk, "long": want}
    assert s["state_hits_total"] - s0["state_hits_total"] == 1
    # 24 tokens came with the snapshot; the other 65 took five calls
    assert s["delta_prefill_calls_total"] \
        - s0["delta_prefill_calls_total"] == 1 + 5
    assert s["decode_steps"] - s0["decode_steps"] < 39 + 7
    # every slot again, by requests that find nothing to resume from
    for i in range(3):
        fresh = [(13 * i + 3 * j) % 60 + 2 for j in range(19)]
        _ask(engine, plain, fresh)


@pytest.mark.parametrize("kw,match", [
    (dict(spec_tokens=2), "spec_tokens > 0"),
    (dict(state_snapshot_stride=0, num_state_snapshots=0),
     "enable_prefix_sharing"),
])
def test_what_is_still_refused_at_construction(kw, match):
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        _engine(**kw)
    assert match in str(e.value)


@pytest.mark.parametrize("kw", [
    dict(state_snapshot_stride=6),          # not whole pages
    dict(state_snapshot_stride=12),         # does not divide the chunk
    dict(num_state_snapshots=1),            # under one chunk's boundaries
])
def test_the_stride_is_whole_pages_that_divide_the_chunk(kw):
    with pytest.raises(ValueError, match="state_snapshot_stride"):
        _engine(**kw)


def test_the_rows_default_to_one_a_stride_of_the_pools_tokens():
    """``num_state_snapshots`` 0: what the pages can hold a snapshot
    for, (97 - 1) pages over the stride."""
    ec = EngineConfig(kv_block_size=BS, num_kv_blocks=97,
                      prefill_chunk=CHUNK, state_snapshot_stride=STRIDE)
    assert ec.resolved_state_snapshots == 96 * BS // STRIDE
    assert EngineConfig(kv_block_size=BS, num_kv_blocks=97,
                        prefill_chunk=CHUNK).resolved_state_snapshots == 0
    eng = _engine(num_state_snapshots=0)
    try:
        assert eng.stats()["state_snapshots_total"] == 96 * BS // STRIDE
        assert eng.pool_audit() == []
    finally:
        eng.shutdown()


@pytest.mark.parametrize("call", [
    lambda e: e.prefill_export(DOC[:9]),
    lambda e: e.submit_adopt({"prompt": DOC[:9], "block_size": BS}),
    lambda e: e.export_warm_prefixes(),
    lambda e: e.import_warm_prefixes({"block_size": BS, "chains": []})],
    ids=["prefill_export", "submit_adopt", "export_warm_prefixes",
         "import_warm_prefixes"])
def test_what_moves_pages_alone_is_refused_by_name(engine, call, request):
    what = request.node.callspec.id
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        call(engine)
    assert what in str(e.value)


def test_a_model_whose_scan_hands_out_no_state_refuses_the_stride():
    granite = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                   head_dim=8, n_kv_heads=2, d_ff=48, max_seq_len=128,
                   rotary_dim=0, block_style="llama", dtype=jnp.float32,
                   remat_policy="none", layer_pattern=["mamba", "full"],
                   ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=8)
    ekw = dict(decode_slots=2, kv_block_size=BS, max_seq_len=128,
               prefill_chunk=CHUNK, enable_prefix_sharing=True)
    with pytest.raises(NotImplementedError,
                       match="state_snapshot_stride 8.*'mamba'"):
        LLMEngine(TransformerConfig(**granite), EngineConfig(
            **ekw, state_snapshot_stride=STRIDE, num_state_snapshots=4))
    # and with none it is refused as before, by the name it had
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        LLMEngine(TransformerConfig(**granite), EngineConfig(**ekw))
    assert "enable_prefix_sharing" in str(e.value)
    eng = LLMEngine(TransformerConfig(**granite), EngineConfig(
        **{**ekw, "enable_prefix_sharing": False}))
    try:
        s = eng.stats()
        assert not SNAP_KEYS & set(s) and "ssm_decode_rows_total" in s
        assert set(s["compiled_programs"]) == {"prefill", "copy", "decode"}
        assert set(eng._cache) == {"k", "v", "ssm", "conv"}
    finally:
        eng.shutdown()
