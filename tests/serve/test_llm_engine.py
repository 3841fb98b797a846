"""Continuous-batching engine tests: scheduler invariants (no slot or
block leaks across EOS/cancel/exception, admission under full occupancy
waits instead of recompiling), Serve streaming integration, and the
mid-decode replica-SIGKILL regression (typed failure, no hang)."""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.models import TransformerConfig
from ray_tpu.serve.llm_engine import (_DONE, EngineConfig,
                                      EngineDeadError, LLMEngine,
                                      RequestTooLargeError)

pytestmark = pytest.mark.serve_llm

MODEL_KW = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                head_dim=8, d_ff=32, max_seq_len=64, rotary_dim=8,
                dtype=jnp.float32, remat_policy="none")
MODEL_DICT = dict(MODEL_KW, dtype="float32")


def _engine(**kw):
    ekw = dict(decode_slots=4, kv_block_size=4, max_seq_len=48,
               prefill_chunk=8, max_new_tokens=16)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**MODEL_KW), EngineConfig(**ekw))


@pytest.fixture(scope="module")
def engine4():
    """One 4-slot engine shared by the read-only scheduler tests (each
    leaves it drained — _assert_clean — so sharing is safe and saves a
    prefill+decode compile per test)."""
    eng = _engine()
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def engine_off():
    """Prefix sharing + speculation OFF: the parity reference."""
    eng = _engine(enable_prefix_sharing=False)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def engine_spec():
    """Prefix sharing ON + prompt-lookup speculation (4 drafts)."""
    eng = _engine(spec_tokens=4)
    yield eng
    eng.shutdown()


def _assert_clean(eng, slots):
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        s = eng.stats()
        if s["free_slots"] == slots and \
                s["free_blocks"] == s["total_blocks"]:
            return
        time.sleep(0.05)
    raise AssertionError(f"slot/block leak: {eng.stats()}")


def test_concurrent_streams_no_leaks_and_deterministic(engine4):
    eng = engine4
    results = {}

    def client(i):
        results[i] = list(eng.generate_sync(
            [1 + i, 2, 3, 4, 5], max_new_tokens=8))

    ts = [threading.Thread(target=client, args=(i,))
          for i in range(6)]   # 6 clients on 4 slots
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert all(len(v) == 8 for v in results.values()), results
    _assert_clean(eng, 4)
    # continuous batching actually batched: some step ran >1 slot
    assert any(k > 1 for k in eng.stats()["occupancy_hist"])
    # greedy decode is deterministic per prompt
    a = list(eng.generate_sync([9, 8, 7], max_new_tokens=5))
    b = list(eng.generate_sync([9, 8, 7], max_new_tokens=5))
    assert a == b


def test_cancel_frees_slot_and_blocks(engine4):
    g = engine4.generate_sync([5, 5, 5], max_new_tokens=40)
    next(g)
    g.close()        # the generator-close cancellation path
    _assert_clean(engine4, 4)


def test_admission_under_full_occupancy_waits_not_recompiles():
    """More requests than slots+blocks: latecomers WAIT for free blocks;
    everything completes; the jitted shapes never grow (compile counts
    stay at one prefill + one decode program)."""
    eng = _engine(decode_slots=2, max_seq_len=16, max_new_tokens=8)
    try:
        # warm both programs
        list(eng.generate_sync([1, 2, 3], max_new_tokens=2))
        pre_sizes = (eng._jit_prefill._cache_size(),
                     eng._jit_decode._cache_size())
        results = []

        def client(i):
            results.append(list(eng.generate_sync(
                [1 + i, 2, 3], max_new_tokens=8)))

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(6)]  # 3x oversubscribed
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=90)
        assert len(results) == 6 and all(len(r) == 8 for r in results)
        assert (eng._jit_prefill._cache_size(),
                eng._jit_decode._cache_size()) == pre_sizes, \
            "admission recompiled a jitted program"
        _assert_clean(eng, 2)
    finally:
        eng.shutdown()


def test_eos_stops_stream_early(engine4):
    eng = engine4
    full = list(eng.generate_sync([3, 1, 4, 1], max_new_tokens=8))
    assert len(full) == 8
    # eos on the FIRST generated token: stream ends empty (prefill-side
    # eos branch), slot+blocks recycled
    assert list(eng.generate_sync([3, 1, 4, 1], max_new_tokens=8,
                                  eos_token_id=full[0])) == []
    # eos mid-stream (first index whose token hasn't appeared before,
    # if greedy decode didn't collapse to a repetition loop)
    cand = [i for i in range(1, 8) if full[i] not in full[:i]]
    if cand:
        idx = cand[0]
        trunc = list(eng.generate_sync([3, 1, 4, 1], max_new_tokens=8,
                                       eos_token_id=full[idx]))
        assert trunc == full[:idx]   # eos token itself not emitted
    _assert_clean(eng, 4)


def test_oversized_prompt_fails_typed():
    eng = _engine(max_seq_len=16)
    try:
        with pytest.raises(RequestTooLargeError):
            eng.submit(list(range(2, 20)))
    finally:
        eng.shutdown()


def test_step_loop_death_fails_requests_typed_no_hang():
    eng = _engine()
    try:
        list(eng.generate_sync([1, 2], max_new_tokens=2))  # warm

        def boom(*a, **kw):
            raise RuntimeError("injected decode fault")

        eng._jit_decode = boom
        with pytest.raises(EngineDeadError):
            list(eng.generate_sync([1, 2, 3], max_new_tokens=8))
        # engine is dead: later submissions fail typed immediately
        with pytest.raises(EngineDeadError):
            eng.submit([1, 2, 3])
    finally:
        eng.shutdown()


# ------------------------------------------- prefix sharing (radix KV)
LONG_PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
ALIGNED_PROMPT = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5]   # 3 full blocks


def test_prefix_sharing_bit_identical_with_hits(engine4, engine_off):
    """Same prompt through a cold pool, a warm (fully shared) pool, and
    a sharing-off engine: per-token output is bit-identical; the warm
    pass skips its matched blocks (hit counter moves); everything
    drains leak-free with the trie audit clean."""
    ref = list(engine_off.generate_sync(LONG_PROMPT, max_new_tokens=10))
    h0 = engine4.stats()["prefix_hit_blocks_total"]
    cold = list(engine4.generate_sync(LONG_PROMPT, max_new_tokens=10))
    warm = list(engine4.generate_sync(LONG_PROMPT, max_new_tokens=10))
    assert cold == ref and warm == ref
    s = engine4.stats()
    # 18-token prompt, block 4 -> 4 full blocks shared on the warm pass
    assert s["prefix_hit_blocks_total"] - h0 >= 4
    assert engine4.pool_audit() == []
    _assert_clean(engine4, 4)
    assert s["blocks_cached"] > 0      # warm cache, not leaked blocks


def test_cow_on_fully_aligned_prompt(engine4, engine_off):
    """A block-aligned prompt that matches ENTIRELY still yields its
    first token (the tail block is copy-on-write copied and the last
    token re-prefilled for logits) — bit-identical to no sharing."""
    ref = list(engine_off.generate_sync(ALIGNED_PROMPT,
                                        max_new_tokens=8))
    c0 = engine4.stats()["cow_copies_total"]
    a = list(engine4.generate_sync(ALIGNED_PROMPT, max_new_tokens=8))
    b = list(engine4.generate_sync(ALIGNED_PROMPT, max_new_tokens=8))
    assert a == ref and b == ref
    s = engine4.stats()
    assert s["cow_copies_total"] > c0
    assert engine4.pool_audit() == []
    _assert_clean(engine4, 4)


def test_concurrent_same_prompt_share_blocks(engine4):
    """Concurrent requests with one system prompt: outputs identical,
    insert races resolved cleanly (audit), no leaks."""
    results = {}

    def client(i):
        results[i] = list(engine4.generate_sync(
            LONG_PROMPT, max_new_tokens=8))

    ts = [threading.Thread(target=client, args=(i,)) for i in range(5)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert len(set(map(tuple, results.values()))) == 1
    assert engine4.pool_audit() == []
    _assert_clean(engine4, 4)


def test_cancel_and_eos_decref_not_leak(engine4):
    """EOS and cancel paths decref through the pool: reclaimable count
    returns to total, trie holds no dangling entries."""
    g = engine4.generate_sync(LONG_PROMPT, max_new_tokens=40)
    next(g)
    g.close()                          # cancel path
    full = list(engine4.generate_sync([6, 2, 8, 3, 1], max_new_tokens=6))
    list(engine4.generate_sync([6, 2, 8, 3, 1], max_new_tokens=6,
                               eos_token_id=full[2]))   # eos path
    assert engine4.pool_audit() == []
    _assert_clean(engine4, 4)


def test_pool_pressure_evicts_lru_and_admits(engine4):
    """Distinct prompts fill the trie beyond the pool; admission under
    pressure evicts cached LRU leaves instead of waiting forever."""
    e0 = engine4.stats()["prefix_evictions_total"]
    for i in range(14):                # 48-block pool, ~4 cached each
        prompt = [(7 * i + j) % 60 + 2 for j in range(17)]
        out = list(engine4.generate_sync(prompt, max_new_tokens=4))
        assert len(out) == 4
    s = engine4.stats()
    assert s["prefix_evictions_total"] > e0
    assert engine4.pool_audit() == []
    _assert_clean(engine4, 4)


# -------------------------------------------------- speculative decode
def test_speculative_decode_bit_identical(engine_spec, engine_off):
    """Greedy streams with speculation on vs off are bit-identical:
    repetitive prompts (drafts accept) and irregular prompts (drafts
    reject) both match the no-speculation reference token for token."""
    prompts = [
        ([5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7], 20),   # accept-friendly
        (LONG_PROMPT, 10),
        ([9, 8, 7], 5),
    ]
    for prompt, mnt in prompts:
        ref = list(engine_off.generate_sync(prompt, max_new_tokens=mnt))
        got = list(engine_spec.generate_sync(prompt, max_new_tokens=mnt))
        assert got == ref, (prompt, got, ref)
    s = engine_spec.stats()
    assert s["spec"]["drafted"] > 0          # speculation actually ran
    assert engine_spec.pool_audit() == []
    _assert_clean(engine_spec, 4)


def test_speculation_with_eos_mid_chain(engine_spec, engine_off):
    """EOS inside an accepted draft chain truncates the stream exactly
    where the no-speculation engine does."""
    prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7]
    full = list(engine_off.generate_sync(prompt, max_new_tokens=12))
    cand = [i for i in range(1, 12) if full[i] not in full[:i]]
    if not cand:
        pytest.skip("greedy stream collapsed; no unique eos candidate")
    idx = cand[0]
    trunc = list(engine_spec.generate_sync(
        prompt, max_new_tokens=12, eos_token_id=full[idx]))
    assert trunc == full[:idx]
    _assert_clean(engine_spec, 4)


def test_draft_prompt_lookup_unit(engine_spec):
    """_draft: continuation of the most recent earlier occurrence of
    the trailing n-gram, longest n first; no match -> no drafts."""
    from ray_tpu.serve.llm_engine import _Request
    req = _Request(1, [1, 2, 3, 4, 1, 2, 3], 8, None)
    # trailing 3-gram [1,2,3] recurs at 0 -> continuation [4,1,2,3][:k]
    assert engine_spec._draft(req, 3) == [4, 1, 2]
    assert engine_spec._draft(req, 1) == [4]
    req2 = _Request(2, [1, 2, 3, 4, 5, 6, 7], 8, None)
    assert engine_spec._draft(req2, 3) == []     # nothing recurs
    # most RECENT occurrence wins
    req3 = _Request(3, [1, 2, 9, 1, 2, 8, 1, 2], 8, None)
    assert engine_spec._draft(req3, 2) == [8, 1]
    assert engine_spec._draft(req3, 0) == []


def test_low_acceptance_disables_slot(engine_spec):
    """A request whose acceptance EWMA drops below the floor stops
    drafting (per-slot disable) — exercised on the engine's own EWMA
    arithmetic, then end-to-end via the disables counter."""
    from ray_tpu.serve.llm_engine import _Request
    req = _Request(9, [1, 2], 8, None)
    ec = engine_spec.config
    ewma = None
    for ratio in (0.0, 0.0):
        ewma = ratio if ewma is None else 0.8 * ewma + 0.2 * ratio
    assert ewma < ec.spec_min_acceptance


def test_compile_once_with_sharing_and_speculation(engine_spec):
    """The acceptance-criteria pin: after cold/warm/CoW/speculative
    traffic every jitted program has compiled exactly once."""
    list(engine_spec.generate_sync(LONG_PROMPT, max_new_tokens=6))
    list(engine_spec.generate_sync(LONG_PROMPT, max_new_tokens=6))
    list(engine_spec.generate_sync(ALIGNED_PROMPT, max_new_tokens=6))
    list(engine_spec.generate_sync(ALIGNED_PROMPT, max_new_tokens=6))
    assert engine_spec._jit_prefill._cache_size() == 1
    assert engine_spec._jit_verify._cache_size() == 1
    assert engine_spec._jit_copy._cache_size() == 1
    _assert_clean(engine_spec, 4)


def test_stats_decode_wall_split_and_page_accounting(engine4):
    """The prefill/decode device-wall split and the length-aware page
    accounting: the pages a decode step's sequences hold and the slots
    it staged with none, summed over steps."""
    was = engine4.stats()
    list(engine4.generate_sync([3, 1, 4, 1, 5], max_new_tokens=6))
    s = engine4.stats()
    assert s["decode_wall_s"] > 0 and s["prefill_wall_s"] > 0
    # five decode steps of the one sequence (5 prompt tokens): its
    # pages alone, the three slots beside it read nothing
    assert s["decode_pages_live"] - was["decode_pages_live"] == sum(
        -(-(5 + i + 1) // 4) for i in range(5))
    assert s["decode_slots_skipped_total"] \
        - was["decode_slots_skipped_total"] == 5 * 3
    assert s["kv_block_size"] == 4
    assert s["paged_impl"] == "auto"
    _assert_clean(engine4, 4)


def test_stats_expose_trie_root_fingerprints(engine4, engine_off):
    """The router's cold-session placement signal: after serving a
    block-long prompt the trie root's first-chunk fingerprint shows up
    in stats, and matches what a client computes from the same
    tokens. Sharing-off engines expose none."""
    from ray_tpu.serve import prefix_fingerprint
    prompt = list(range(2, 14))                      # 3 full blocks
    list(engine4.generate_sync(prompt, max_new_tokens=4))
    fps = engine4.stats()["prefix_fingerprints"]
    assert prefix_fingerprint(prompt, 4) in fps
    list(engine_off.generate_sync(prompt, max_new_tokens=4))
    assert engine_off.stats()["prefix_fingerprints"] == []
    _assert_clean(engine4, 4)


def test_warmup_compiles_then_resets_session_stats():
    """LLMServer warms its engine inside __init__ so a replica the
    autoscaler adds mid-load serves its first request hot; the warmup
    must not leak its compile wall into the TTFT EWMA the gauge router
    scores (a poisoned EWMA starves the new replica of traffic)."""
    eng = _engine(decode_slots=2)
    try:
        eng.warmup()
        s = eng.stats()
        assert s["ttft_ewma_s"] is None
        assert s["tokens_total"] == 0
        assert s["decode_wall_s"] == 0.0
        # EVERY program the engine can run is compiled, not only the
        # two a plain generate touches
        every = {"prefill": 1, "decode": 1, "copy": 1, "gather": 1,
                 "scatter": 1}
        assert s["compiled_programs"] == every
        # warm: traffic compiles nothing — a plain request, then a
        # block-aligned repeat (CoW copy) and a KV export + adoption
        # (gather / scatter)
        list(eng.generate_sync([7, 7, 7], max_new_tokens=3))
        prompt = list(range(1, 9))          # two whole blocks
        list(eng.generate_sync(prompt, max_new_tokens=2))
        list(eng.generate_sync(prompt, max_new_tokens=2))
        payload = eng.prefill_export([9, 8, 7, 6, 5, 4])
        req = eng.submit_adopt(payload, max_new_tokens=2)
        while req.out.get(timeout=30) is not _DONE:
            pass
        s = eng.stats()
        assert s["cow_copies_total"] == 1 and s["kv_adopts"] == 1
        assert s["compiled_programs"] == every
        assert s["ttft_ewma_s"] is not None
    finally:
        eng.shutdown()


def _wait_version(eng, version, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while eng.stats()["weight_version"] != version:
        assert time.monotonic() < deadline, "the swap never landed"
        time.sleep(0.005)


def test_refresh_in_f32_is_cast_on_the_stagers_thread_and_compiles_nothing():
    """An engine whose compute dtype is bf16 holds its tree in bf16
    (norm leaves f32) and no f32 masters. A learner's f32 tree staged
    into it is cast in ``stage_weights``, on the caller's thread: what
    the step thread swaps in has the avals the programs were compiled
    for, so the refresh compiles nothing, and the next tokens are those
    of an engine built on that tree."""
    import jax
    from ray_tpu.models import init_params
    cfg = TransformerConfig(**dict(MODEL_KW, dtype=jnp.bfloat16))
    ekw = dict(decode_slots=2, kv_block_size=4, max_seq_len=48,
               prefill_chunk=8, max_new_tokens=16)
    eng = LLMEngine(cfg, EngineConfig(**ekw))
    fresh = init_params(cfg, jax.random.PRNGKey(1))       # f32 masters
    other = LLMEngine(cfg, EngineConfig(**ekw), params=fresh)
    try:
        def dtypes(e):
            p = e._params
            return (p["embed"].dtype, p["layers"]["wq"].dtype,
                    p["layers"]["fc_in_b"].dtype, p["lm_head"]["w"].dtype,
                    p["layers"]["ln_scale"].dtype,
                    p["final_norm"]["bias"].dtype)
        held = (jnp.bfloat16,) * 4 + (jnp.float32,) * 2
        assert dtypes(eng) == held and dtypes(other) == held
        # a caller's tree is cast, not spent
        assert not fresh["embed"].is_deleted()
        eng.warmup()
        s = eng.stats()
        every = dict(s["compiled_programs"])
        assert set(every.values()) == {1}
        n_cast = sum(x.size for x in jax.tree.leaves(fresh)) \
            - sum(x.size for x in jax.tree.leaves(
                (fresh["final_norm"], fresh["layers"]["ln_scale"],
                 fresh["layers"]["ln_bias"])))
        n_all = sum(x.size for x in jax.tree.leaves(fresh))
        assert s["weight_bytes"] == 2 * n_cast + 4 * (n_all - n_cast)
        assert s["weight_casts_total"] == 0 and s["weight_swaps"] == 0

        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
        # (not this prompt: a refresh keeps the prefix cache's pages)
        before = list(eng.generate_sync(prompt[::-1], max_new_tokens=8))
        eng.stage_weights(fresh, version=5)
        staged = eng._staged_weights
        # cast before it was handed over, whoever swaps it in
        assert staged is None or staged[0]["embed"].dtype == jnp.bfloat16
        _wait_version(eng, 5)
        assert dtypes(eng) == held
        after = list(eng.generate_sync(prompt, max_new_tokens=8))
        s = eng.stats()
        assert s["compiled_programs"] == every, "the refresh recompiled"
        assert (s["weight_swaps"], s["weight_casts_total"]) == (1, 1)
        assert after == list(other.generate_sync(prompt, max_new_tokens=8))
        assert before != list(other.generate_sync(prompt[::-1],
                                                  max_new_tokens=8))

        # a tree that is already the engine's kind is staged as it is
        eng.stage_weights(other._params, version=6)
        _wait_version(eng, 6)
        assert eng._params is other._params
        s = eng.stats()
        assert (s["weight_swaps"], s["weight_casts_total"]) == (2, 1)
        assert s["compiled_programs"] == every
    finally:
        eng.shutdown()
        other.shutdown()


def test_warmup_failure_is_fatal_to_the_replica():
    """A program that cannot compile must fail LLMServer's constructor
    (and so the replica actor): forcing the compiled kernel on a host
    with no TPU is such a program. Swallowed, the replica would enter
    rotation and die on its first request."""
    from ray_tpu.serve.llm_engine import LLMServer
    with pytest.raises(EngineDeadError, match="needs a TPU"):
        LLMServer(model=dict(MODEL_DICT, paged_impl="kernel"),
                  engine=dict(decode_slots=2, kv_block_size=4,
                              max_seq_len=32, prefill_chunk=8))


def test_stats_record_what_attention_resolved_to(engine4):
    """On a CPU the engine's "auto" takes the XLA reference, and says
    why — the record chip_smoke.py fails on when it names anything but
    the compiled kernel."""
    list(engine4.generate_sync([3, 4, 5], max_new_tokens=2))
    assert any(e["op"] == "paged" and e["impl"] == "reference"
               and e["why"] == "platform is not tpu"
               for e in engine4.stats()["attention_dispatch"])


def test_kv_block_math():
    cfg = TransformerConfig(**MODEL_KW)
    ec = EngineConfig(decode_slots=4, kv_block_size=4, max_seq_len=48)
    # 2 (k+v) * layers * kv_heads * head_dim * 4B (f32)
    assert ec.kv_bytes_per_token(cfg) == \
        2 * cfg.n_layers * cfg.kv_heads * cfg.head_dim * 4
    assert ec.blocks_per_seq == 12
    assert ec.resolved_num_blocks == 1 + 4 * 12


# ---------------------------------------------------------------- serve
@pytest.mark.slow
def test_serve_streaming_integration(serve_session):
    from ray_tpu import serve

    app = serve.deployment(serve.LLMServer).bind(
        model=MODEL_DICT,
        engine={"decode_slots": 4, "kv_block_size": 4,
                "max_seq_len": 48, "prefill_chunk": 8})
    h = serve.run(app)
    toks = list(h.options(stream=True).generate.remote([1, 2, 3, 4], 8))
    assert len(toks) == 8 and all(isinstance(t, int) for t in toks)
    # per-replica engine stats are reachable through the handle (the
    # autoscaling signal surface) and show no leaks after the stream
    s = h.stats.remote().result(timeout_s=60)
    assert s["free_blocks"] == s["total_blocks"]
    assert s["tokens_total"] >= 8
    # early client break cancels the replica-side request and frees
    # its slot + blocks
    gen = h.options(stream=True).generate.remote([2, 2, 2], 40)
    next(gen)
    gen.cancel()
    deadline = time.time() + 15
    while time.time() < deadline:
        s = h.stats.remote().result(timeout_s=60)
        if s["free_blocks"] == s["total_blocks"]:
            break
        time.sleep(0.2)
    assert s["free_blocks"] == s["total_blocks"], s
    # the engine's flight-recorder events (the dashboard /timeline +
    # autoscaling signal surface) reach the controller: per-request
    # ENGINE_TTFT from the replica's recorder
    from ray_tpu.util.state import list_task_events
    deadline = time.time() + 20
    evs = []
    while time.time() < deadline and not evs:
        evs = list_task_events(filters=[("ev", "=", "ENGINE_TTFT")])
        time.sleep(0.3)
    assert evs, "no ENGINE_TTFT flight-recorder events reached the " \
                "controller"
    assert evs[0].get("ttft_s") is not None
    assert evs[0].get("prompt_len") in (3, 4)


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize(
    "seed",
    [int(s) for s in __import__("os").environ.get(
        "RAY_TPU_CHAOS_SOAK_SEEDS", "1101").split(",")])
def test_serve_fleet_chaos_soak(seed):
    """The chaos-matrix serve-fleet leg: a 2-replica fleet (prefix
    sharing + speculation on, gauge routing) streams shared-prefix
    requests under 5% message drops while one replica is SIGKILLed
    mid-decode. The router must fail over without a hang, retried
    streams must replay the SAME greedy token sequence (exactly-once
    accounting: every request ends with exactly one complete stream,
    and any partial pre-kill prefix is a prefix of the final stream),
    and the surviving fleet's block pools must audit clean."""
    import json
    import os
    import signal

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import chaos

    ray_tpu.shutdown()
    os.environ[chaos.ENV_SEED] = str(seed)
    os.environ[chaos.ENV_CONFIG] = json.dumps({"drop_prob": 0.05})
    rng = __import__("random").Random(seed)
    system = [rng.randrange(2, 60) for _ in range(8)]   # 2 full blocks
    n_req, mnt = 10, 12

    class PidLLM(serve.LLMServer):
        def pid(self):
            return os.getpid()

    try:
        ray_tpu.init(num_cpus=10, _num_initial_workers=4,
                     ignore_reinit_error=True)
        dep = serve.deployment(
            PidLLM, num_replicas=2, max_ongoing_requests=32)
        app = dep.bind(
            model=MODEL_DICT,
            engine={"decode_slots": 2, "kv_block_size": 4,
                    "max_seq_len": 48, "prefill_chunk": 8,
                    "spec_tokens": 2})
        h = serve.run(app)
        pids = set()
        deadline = time.time() + 60
        while len(pids) < 2 and time.time() < deadline:
            pids.add(h.options(
                routing_policy="round_robin").pid.remote().result(
                    timeout_s=60))
        assert len(pids) == 2, pids
        victim = sorted(pids)[seed % 2]
        done, partials, failures = {}, {}, []
        lock = threading.Lock()
        killed = threading.Event()

        def client(i):
            prompt = system + [2 + i, 3 + i]
            # deadline-based retries: a slow membership update (the
            # controller's health probe discovering the corpse under
            # drops) must not exhaust a fixed attempt count
            t_end = time.time() + 120
            while time.time() < t_end:
                got = []
                try:
                    gen = h.options(
                        stream=True,
                        session_id=f"s{i}").generate.remote(prompt, mnt)
                    for t in gen:
                        got.append(t)
                        if i == 0 and len(got) == 2 \
                                and not killed.is_set():
                            killed.set()
                            os.kill(victim, signal.SIGKILL)
                    with lock:
                        done[i] = got
                    return
                except Exception as e:  # noqa: BLE001
                    from ray_tpu.exceptions import RayTpuError
                    with lock:
                        failures.append((i, type(e).__name__))
                        partials.setdefault(i, []).append(got)
                    assert isinstance(e, RayTpuError), \
                        f"untyped stream failure: {e!r}"
                    # session affinity pins to the DEAD replica until
                    # membership bumps: force a resync so the retry
                    # fails over instead of burning the deadline
                    h._router.refresh(force=True)
                    time.sleep(1.0)    # controller restarts the replica
            raise AssertionError(f"client {i} never completed: "
                                 f"{failures}")

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(n_req)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in ts), \
            "fleet stream HUNG after replica SIGKILL"
        assert killed.is_set(), "victim replica never died — vacuous"
        # exactly-once accounting: one complete stream per request,
        # deterministic greedy => every pre-kill partial is a prefix
        assert sorted(done) == list(range(n_req)), (sorted(done),
                                                    failures)
        for i, full in done.items():
            assert len(full) == mnt, (i, full)
            for p in partials.get(i, []):
                assert full[:len(p)] == p, (i, p, full)
        # the surviving fleet's pools audit clean once drained
        deadline = time.time() + 30
        audits = None
        while time.time() < deadline:
            try:
                audits = [r for r in
                          [h.options(routing_policy="round_robin")
                           .pool_audit.remote().result(timeout_s=30)
                           for _ in range(2)]]
                if all(a == [] for a in audits):
                    break
            except Exception:
                pass
            time.sleep(1.0)
        assert audits is not None and all(a == [] for a in audits), \
            audits
    finally:
        # chaos-matrix sidecar: the slowest captured request waterfall
        # (render with `python tools/trace.py --input <file>`) next to
        # the Perfetto postmortem — the per-request view of what the
        # drops + SIGKILL did to latency
        wf_file = os.environ.get("RAY_TPU_CHAOS_WATERFALL_FILE")
        if wf_file:
            try:
                from ray_tpu.util.state import (get_request_trace,
                                                list_requests)
                rows = list_requests(limit=200)
                if rows:
                    slow = max(rows,
                               key=lambda r: r.get("dur_s") or 0.0)
                    w = get_request_trace(slow["request_id"])
                    if w is not None:
                        with open(wf_file, "w") as f:
                            json.dump(w, f, indent=1)
            except Exception:
                pass
        serve.shutdown()
        ray_tpu.shutdown()
        os.environ.pop(chaos.ENV_SEED, None)
        os.environ.pop(chaos.ENV_CONFIG, None)


@pytest.mark.chaos
@pytest.mark.slow
def test_mid_decode_replica_sigkill_fails_typed(serve_session):
    """Chaos regression: SIGKILL the replica worker mid-decode; the
    consumer's stream must fail with a TYPED error (or complete, if the
    kill raced EOF) — never hang."""
    import os
    import signal

    import ray_tpu
    from ray_tpu import serve

    class PidLLM(serve.LLMServer):
        def pid(self):
            return os.getpid()

    app = serve.deployment(PidLLM).bind(
        model=MODEL_DICT,
        engine={"decode_slots": 2, "kv_block_size": 4,
                "max_seq_len": 48, "prefill_chunk": 8})
    h = serve.run(app)
    pid = h.pid.remote().result(timeout_s=60)
    gen = h.options(stream=True).generate.remote([7, 7, 7], 40)
    got = [next(gen)]          # stream is live before the kill
    os.kill(pid, signal.SIGKILL)

    def consume():
        try:
            for t in gen:
                got.append(t)
        except Exception as e:
            errs.append(e)

    errs = []
    t = threading.Thread(target=consume)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "stream HUNG after replica SIGKILL"
    if errs:
        from ray_tpu.exceptions import RayTpuError
        assert isinstance(errs[0], RayTpuError), errs
    else:
        # kill raced the stream's natural end: it must have completed
        assert len(got) == 40, got


# ------------------------------------------------- the tick's host gap
# Greedy tokens of the build before the step programs took one staged
# array (commit 7d0386c, this engine, seed 4, the prompts in this
# order): a shorter-than-a-block prompt, one of three chunks, two that
# share three blocks, and the first two blocks of the long one again —
# fully matched and block-aligned, so its last block is copied on write.
_LONG = [(7 * i + 3) % 61 + 1 for i in range(20)]
_SHARED = [(5 * i + 2) % 59 + 1 for i in range(12)]
_PROMPTS = [[7, 9, 11], _LONG, _SHARED + [4, 5, 6], _SHARED + [8, 9],
            _LONG[:8]]
_PARENT_TOKENS = [
    [2, 1, 60, 21, 5, 48, 3, 17, 23, 29, 60, 21],
    [32, 58, 6, 61, 52, 12, 5, 48, 3, 17, 23, 29],
    [61, 52, 12, 5, 48, 3, 17, 23, 29, 60, 21, 5],
    [61, 52, 12, 5, 48, 3, 17, 23, 29, 60, 21, 5],
    [30, 28, 4, 41, 57, 21, 5, 48, 3, 17, 23, 29]]


def _seeded_engine(spec_tokens, **model_kw):
    eng = LLMEngine(
        TransformerConfig(**dict(MODEL_KW, **model_kw)),
        EngineConfig(decode_slots=4, kv_block_size=4, max_seq_len=48,
                     prefill_chunk=8, max_new_tokens=16,
                     spec_tokens=spec_tokens), seed=4)
    eng.warmup()
    return eng


@pytest.mark.parametrize("spec_tokens", [0, 4])
def test_tokens_are_the_parents_and_each_program_has_one_transfer(
        spec_tokens):
    eng = _seeded_engine(spec_tokens)
    try:
        served = [list(eng.generate_sync(p, max_new_tokens=12))
                  for p in _PROMPTS]
        _assert_clean(eng, 4)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert served == _PARENT_TOKENS
    assert st["prefill_chunks"] == 8 and st["cow_copies_total"] == 1
    assert st["prefix_hit_blocks_total"] == 5
    # a verify call is this engine's decode step
    assert st["h2d_transfers_total"] \
        == st["prefill_chunks"] + st["decode_steps"] > 40


@pytest.mark.parametrize("spec_tokens", [0, 4])
def test_stats_book_the_paged_kernels_grid_steps(spec_tokens,
                                                 monkeypatch):
    """``decode_grid_steps`` / ``decode_grid_steps_live``: what the
    paged kernel's innermost grid axis takes and what of it has a live
    page, at the P the kernel itself picks for the decode (or verify)
    call — here held to 2 so that a 12-slot table is six groups. Host
    arithmetic on the slot lengths: one transfer a program, as before."""
    import ray_tpu.ops.paged_flash as pf
    monkeypatch.setattr(pf, "_PAGE_GROUPS", (2,))
    eng = _seeded_engine(spec_tokens)
    try:
        for p in _PROMPTS:
            list(eng.generate_sync(p, max_new_tokens=12))
        st = eng.stats()
    finally:
        eng.shutdown()
    slots, groups = 4, 6
    assert st["decode_pages_per_step"] == 2
    assert st["decode_grid_steps"] == st["decode_steps"] * slots * groups
    # every sequence has its first group live and a slot that holds
    # none has no live step; no sequence here grows past 48 tokens, and
    # most are far shorter
    decoding = sum(k * n for k, n in st["occupancy_hist"].items())
    assert st["decode_slots_skipped_total"] + decoding \
        == st["decode_steps"] * slots
    assert decoding <= st["decode_grid_steps_live"] \
        < st["decode_grid_steps"] // 2
    assert st["decode_grid_steps_live"] * 2 >= st["decode_pages_live"]
    assert st["decode_grid_live_frac"] == pytest.approx(
        st["decode_grid_steps_live"] / st["decode_grid_steps"], abs=1e-3)
    assert st["h2d_transfers_total"] \
        == st["prefill_chunks"] + st["decode_steps"]


def test_stats_book_the_chunks_row_blocks():
    """``prefill_row_blocks`` / ``prefill_row_blocks_live``: what the
    paged kernel's row-block axis takes in a chunk's call and what of
    it has a live row, by kind of layer (one kind here), at the row
    block the call runs with: 16 token rows in two blocks of 8, prompts
    of 12, 12, 5 and 12 tokens with no page in common."""
    eng = LLMEngine(
        TransformerConfig(**dict(MODEL_KW, paged_block_r_prefill=8)),
        EngineConfig(decode_slots=4, kv_block_size=4, max_seq_len=48,
                     prefill_chunk=16, max_new_tokens=16))
    try:
        for first, n in ((7, 12), (8, 12), (9, 5), (10, 12)):
            list(eng.generate_sync([first] + _SHARED[1:n],
                                   max_new_tokens=2))
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["prefill_chunks"] == 4 and st["prefix_hit_blocks_total"] == 0
    assert st["prefill_row_blocks"] == {"full": 8}
    assert st["prefill_row_blocks_live"] == {"full": 7}
    assert st["h2d_transfers_total"] \
        == st["prefill_chunks"] + st["decode_steps"]


@pytest.mark.parametrize("spec_tokens", [0, 4])
def test_warm_ticks_run_no_eager_device_op_on_the_step_thread(
        spec_tokens):
    """Fifty warm ticks under a transfer guard that refuses every
    implicit host-to-device transfer (a ``jnp.full`` or ``jnp.zeros``
    scalar, an index into a device array, a numpy argument to a jitted
    call) and with ``jnp`` itself refused to the step thread. The one
    transfer a program is allowed, the numpy array it is called with,
    is made explicit here, at the call: nothing else may reach the
    device."""
    import jax
    eng = _seeded_engine(spec_tokens)

    class NoEagerOps:
        def __getattr__(self, name):
            if threading.current_thread() is eng._thread:
                raise AssertionError(f"jnp.{name} on the step thread")
            return getattr(jnp, name)

    def one_explicit_transfer(program):
        def call(params, rows, cache):
            assert type(rows) is np.ndarray and rows.dtype == np.int32
            return program(params, jax.device_put(rows), cache)
        call._cache_size = program._cache_size      # stats() reads it
        return call

    was = jax.config.jax_transfer_guard_host_to_device
    try:
        jax.config.update("jax_transfer_guard_host_to_device", "disallow")
        eng._jnp = NoEagerOps()
        eng._jit_prefill = one_explicit_transfer(eng._jit_prefill)
        eng._jit_decode = one_explicit_transfer(eng._jit_decode)
        if spec_tokens:
            eng._jit_verify = one_explicit_transfer(eng._jit_verify)
        tick0, before = eng._clock.tick_no, eng.stats()
        # prompts of one to three chunks, all but the first served out of
        # the trie from the second round on, none block-aligned: a CoW
        # copy takes two host scalars, which the guard would refuse too
        prompts = [[7, 9, 11], _LONG + [5], _SHARED + [4, 5, 6],
                   _SHARED + [8, 9]]
        reqs = [eng.submit(p, 12) for p in prompts * 5]
        for req in reqs:
            assert len(_drain_stream(req)) == 12
        st = eng.stats()
    finally:
        jax.config.update("jax_transfer_guard_host_to_device", was)
        eng.shutdown()
    assert eng._clock.tick_no - tick0 >= 50 and st["dead"] is None
    assert st["cow_copies_total"] == 0
    assert st["h2d_transfers_total"] - before["h2d_transfers_total"] \
        == st["prefill_chunks"] + st["decode_steps"] \
        - before["prefill_chunks"] - before["decode_steps"]


# ------------------------------------------------ one program ahead
class _Flights:
    """An engine's launches and fetches, logged from the step thread:
    every program's kind and ``rows`` as the jitted call got them, how
    many were out at once, a prompt's last chunk overtaken by a decode
    step, and a fetch that found another weight version than its launch
    had. ``on_chunk(req, last, ahead)`` / ``on_decode()`` run right
    after that launch, with the program still out."""

    def __init__(self, eng, on_chunk=None, on_decode=None):
        self.eng = eng
        self.programs, self.out = [], []
        self.most_out = 0
        self.overtaken, self.stale = [], []
        self.first_tree = eng._params
        self.on_chunk, self.on_decode = on_chunk, on_decode
        for name in ("_jit_prefill", "_jit_decode", "_jit_verify"):
            if getattr(eng, name) is not None:
                setattr(eng, name, self._program(name[5:],
                                                 getattr(eng, name)))
        for name in ("_launch_chunk", "_launch_decode", "_finish_chunk",
                     "_finish_decode"):
            setattr(eng, name, getattr(self, name)(getattr(eng, name)))

    def _program(self, kind, fn):
        def call(params, rows, cache):
            self.programs.append((kind, rows.tobytes()))
            # the tree a program runs on is the version it is stamped by
            if (params is self.first_tree) != \
                    (self.eng._weight_version == 0):
                self.stale.append((kind, "tree"))
            return fn(params, rows, cache)
        call._cache_size = fn._cache_size           # stats() reads it
        return call

    def _launched(self, kind, last):
        self.out.append((kind, last, self.eng._weight_version))
        self.most_out = max(self.most_out, len(self.out))

    def _fetched(self, kind):
        was, _, version = self.out.pop(0)           # in launch order
        assert was == kind
        if version != self.eng._weight_version:
            self.stale.append((kind, version))

    def _launch_chunk(self, launch):
        def call():
            ahead = bool(self.out)
            rec = launch()
            if rec is not None:
                req, start, n = rec[:3]
                last = start + n == len(req.prompt)
                self._launched("prefill", last)
                if self.on_chunk is not None:
                    self.on_chunk(req, last, ahead)
            return rec
        return call

    def _launch_decode(self, launch):
        def call(active):
            self.overtaken += [o for o in self.out if o[1]]
            rec = launch(active)
            self._launched("decode", False)
            if self.on_decode is not None:
                self.on_decode()
            return rec
        return call

    def _finish_chunk(self, finish):
        def call(rec):
            self._fetched("prefill")
            return finish(rec)
        return call

    def _finish_decode(self, finish):
        def call(rec):
            self._fetched("decode")
            return finish(rec)
        return call


def _submit_together(eng, submits):
    """Every request queued before the step thread sees the first: the
    thread is held inside a posted op meanwhile, so what it admits, and
    with it every program it launches, follows from the requests."""
    started, gate = threading.Event(), threading.Event()

    def hold():
        started.set()
        gate.wait(30)

    holder = threading.Thread(target=eng._run_on_step_thread,
                              args=(hold,))
    holder.start()
    assert started.wait(30)
    reqs = [eng.submit(*a, **kw) for a, kw in submits]
    gate.set()
    holder.join(30)
    return reqs


def _drain_stream(req, timeout=60):
    items = []
    while True:
        item = req.out.get(timeout=timeout)
        if item is _DONE:
            return items
        assert not isinstance(item, BaseException), item
        items.append(item)


# two more prompts of three chunks
_LONG2 = [(11 * i + 5) % 57 + 1 for i in range(19)]
_LONG3 = [(13 * i + 7) % 53 + 1 for i in range(21)]


def _together(prompts=_PROMPTS, **kw):
    return [((p, 12), kw) for p in prompts]


def test_launches_are_a_serial_replays_byte_for_byte():
    """No cancels, swaps or ops: the programs the engine launches, kind
    and ``rows``, are those of a strictly serial tick (the same engine
    with every launch made to wait for the fetch before it), in the
    same order; every stream is the parent's; and launches did pass
    ahead, two programs out at most, no prompt's last chunk overtaken
    by the decode step that needs its token."""
    runs = {}
    for serial in (False, True):
        eng = _seeded_engine(0)
        try:
            if serial:
                eng._go_ahead = lambda blocked: False
            log = _Flights(eng)
            served = [_drain_stream(r)
                      for r in _submit_together(eng, _together())]
            _assert_clean(eng, 4)
            runs[serial] = (log, served, eng.stats(), eng.pool_audit())
        finally:
            eng.shutdown()
    (log, served, st, audit), (slog, sserved, sst, saudit) = \
        runs[False], runs[True]
    assert served == sserved == _PARENT_TOKENS
    assert audit == saudit == []
    assert log.programs == slog.programs and len(log.programs) > 30
    assert {k for k, _ in log.programs} == {"prefill", "decode"}
    assert st["h2d_transfers_total"] == len(log.programs) \
        == st["prefill_chunks"] + st["decode_steps"]
    assert (log.most_out, slog.most_out) == (2, 1)
    assert log.overtaken == slog.overtaken == []
    assert 0 < st["programs_ahead_total"] < len(log.programs)
    assert sst["programs_ahead_total"] == 0
    blocked = st["ahead_blocked_total"]
    assert blocked["last_chunk"] > 0 and blocked["no_backlog"] > 0
    assert blocked["op_or_swap"] == blocked["speculative"] == 0
    # every decode step is one chance for the next chunk, and every
    # chunk that ran beside decoding sequences one for its decode step
    assert st["programs_ahead_total"] + sum(blocked.values()) \
        <= len(log.programs)


@pytest.mark.parametrize("spec_tokens", [0, 4])
def test_nothing_goes_ahead_without_a_backlog_or_with_drafts(
        spec_tokens):
    """Single-chunk requests one at a time: the chunk ends its prompt
    and no other is waiting, so each program is fetched before the next
    is staged, as ever. With ``spec_tokens`` nothing goes ahead under
    any traffic (drafting reads the host's history)."""
    eng = _seeded_engine(spec_tokens)
    try:
        log = _Flights(eng)
        for i in range(4):
            assert len(list(eng.generate_sync(
                [3 + i, 9, 11, 2 + i], max_new_tokens=6))) == 6
        alone = eng.stats()
        assert alone["programs_ahead_total"] == 0
        reason = "speculative" if spec_tokens else "no_backlog"
        assert alone["ahead_blocked_total"][reason] \
            == alone["decode_steps"] > 0
        assert sum(alone["ahead_blocked_total"].values()) \
            == alone["decode_steps"]
        served = [_drain_stream(r)
                  for r in _submit_together(eng, _together())]
        _assert_clean(eng, 4)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert served == _PARENT_TOKENS
    assert (st["programs_ahead_total"] > 0) == (spec_tokens == 0)
    assert log.most_out == (1 if spec_tokens else 2)
    assert log.overtaken == []


@pytest.mark.parametrize("spec_tokens", [0, 4])
def test_a_decode_step_leaves_a_prefilling_prompts_pages_alone(
        spec_tokens):
    """A decode step writes every slot's position ``length``; for a slot
    whose prompt is still prefilling that is position 0, so its row
    points at the trash block until the prompt ends. The three-chunk
    prompt's first page reads the same served beside a decoding
    sequence as served alone (it did not: position 0 held the K/V of
    the slot's stale token)."""
    pages = []
    for beside in (False, True):
        eng = _seeded_engine(spec_tokens)
        try:
            blocks = {}
            book = eng._book_prefill

            def booked(req, *a, _book=book, _blocks=blocks):
                _blocks[tuple(req.prompt)] = list(req.blocks)
                return _book(req, *a)

            eng._book_prefill = booked
            submits = _together([[7, 9, 11], _LONG] if beside
                                else [_LONG])
            served = [_drain_stream(r)
                      for r in _submit_together(eng, submits)]
            assert served[-1] == _PARENT_TOKENS[1]
            first = blocks[tuple(_LONG)][0]
            pages.append({name: np.asarray(pool)[:, first]
                          for name, pool in eng._cache.items()})
            beside_steps = eng.stats()["decode_steps"]
        finally:
            eng.shutdown()
    assert beside_steps > 3         # the short prompt decoded meanwhile
    for name in pages[0]:
        assert np.array_equal(pages[0][name], pages[1][name]), name


@pytest.mark.parametrize("paged_impl", ["reference", "interpret"])
def test_a_slot_with_no_decoding_sequence_is_staged_empty(paged_impl):
    """Five requests on four slots: every decode row of a slot with no
    decoding sequence, free or with its prompt still prefilling, is
    ``[0, -1, trash blocks]`` (the kernel reads nothing for it) and
    every other row a sequence's own; the streams are the parent's;
    the books count the sequences' pages alone, and the skipped slots
    are the occupancy's complement; and no pool ends with a NaN or an
    inf, the trash block (where every empty row's K/V lands) included:
    the reference gathers it into live rows under a zero weight."""
    from ray_tpu.serve.llm_engine import _DECODE, _PREFILL
    eng = _seeded_engine(0, paged_impl=paged_impl)
    staged = []
    try:
        decode = eng._jit_decode

        def logged(params, rows, cache):
            state = [None if r is None else r.state for r in eng._slots]
            staged.append((rows.copy(), state))
            return decode(params, rows, cache)

        logged._cache_size = decode._cache_size
        eng._jit_decode = logged
        served = [_drain_stream(r)
                  for r in _submit_together(eng, _together())]
        _assert_clean(eng, 4)
        st = eng.stats()
        pools = {name: np.asarray(pool)
                 for name, pool in eng._cache.items()}
        assert eng.pool_audit() == []
    finally:
        eng.shutdown()
    assert served == _PARENT_TOKENS
    assert len(staged) == st["decode_steps"] > 20
    pages = empty = beside_a_prompt = 0
    for rows, state in staged:
        for row, was in zip(rows, state):
            if was == _DECODE:
                assert row[1] > 0 and row[2] > 0
                pages += -(-(int(row[1]) + 1) // 4)
            else:
                assert row.tolist() == [0, -1] + [0] * 12
                empty += 1
                beside_a_prompt += was == _PREFILL
    assert beside_a_prompt > 0 and empty > beside_a_prompt
    assert st["decode_pages_live"] == pages
    assert st["decode_slots_skipped_total"] == empty
    assert empty + sum(k * n for k, n in st["occupancy_hist"].items()) \
        == st["decode_steps"] * 4
    assert eng._slot_rows[:, 1].tolist() == [-1] * 4
    for name, pool in pools.items():
        assert np.isfinite(pool).all(), name
        assert np.any(pool[:, 0]), name         # the trash block was hit


def test_eos_cancel_and_the_cap_with_a_program_out_leak_nothing():
    """Streams that end while a program is out: a request cancelled
    with its chunk launched ahead (one that ends its prompt, one that
    does not), EOS as a prompt's first token and mid-stream, a cap of
    one token. Slots, blocks and the trie come back whole, and the
    pages still hold what the parent's did."""
    eng = _seeded_engine(0)
    cancel_on = {}            # rid -> cancel at its last chunk, or not

    def on_chunk(req, last, ahead):
        if ahead and cancel_on.get(req.rid) == last:
            eng.cancel(req)
            cancelled.append((req.rid, last))

    cancelled = []
    try:
        log = _Flights(eng, on_chunk=on_chunk)
        submits = _together() + [
            ((_LONG2, 12), {}), ((_LONG3, 12), {}),
            ((_LONG + [9], 12), {"eos_token_id": _PARENT_TOKENS[1][0]}),
            ((_PROMPTS[2], 12), {"eos_token_id": _PARENT_TOKENS[2][0]}),
            ((_PROMPTS[3], 12), {"eos_token_id": _PARENT_TOKENS[3][4]}),
            ((_LONG2[::-1], 1), {}), ((_LONG3[::-1], 12), {})]
        first = eng._rid + 1
        cancel_on[first + 5], cancel_on[first + 6] = True, False
        cancel_on[first + 11] = True
        reqs = _submit_together(eng, submits)
        streams = [_drain_stream(r) for r in reqs]
        _assert_clean(eng, 4)
        assert eng.pool_audit() == []
        st = eng.stats()
        again = [list(eng.generate_sync(p, max_new_tokens=12))
                 for p in _PROMPTS]
        assert eng.pool_audit() == []
    finally:
        eng.shutdown()
    assert st["dead"] is None and log.overtaken == []
    assert streams[:5] == again == _PARENT_TOKENS
    assert sorted(cancelled) == [(first + 5, True), (first + 6, False),
                                 (first + 11, True)]
    assert streams[5] == streams[6] == streams[11] == []
    assert streams[8] == [] and streams[9] == _PARENT_TOKENS[3][:4]
    assert len(streams[10]) == 1 and st["programs_ahead_total"] > 0
    assert st["h2d_transfers_total"] \
        == st["prefill_chunks"] + st["decode_steps"]


def test_version_stamps_across_a_swap_staged_with_a_chunk_out():
    """A refresh staged while a chunk launched ahead is out, the chunk
    ending its prompt: the chunk is fetched and its first token stamped
    with the version it was launched on BEFORE the swap, and the swap
    still lands before the next launch. A second refresh, staged under
    a decode step with a backlog, keeps the next chunk from going
    ahead. Every program ran on the tree of the version its tokens
    carry."""
    import jax
    from ray_tpu.models import init_params
    eng = _seeded_engine(0)
    trees = [eng._inference_params(init_params(
        eng.model_config, jax.random.PRNGKey(k))) for k in (1, 2)]
    staged = {}

    def on_chunk(req, last, ahead):
        if ahead and last and 7 not in staged:
            staged[7] = req.rid
            eng.stage_weights(trees[0], version=7)

    def on_decode():
        if 7 in staged and 9 not in staged and eng._prefilling \
                and eng._weight_version == 7:
            staged[9] = True
            eng.stage_weights(trees[1], version=9)

    try:
        log = _Flights(eng, on_chunk=on_chunk, on_decode=on_decode)
        prompts = _PROMPTS + [_LONG2, _LONG3, _LONG2[::-1], _LONG3[::-1]]
        reqs = _submit_together(eng, _together(prompts, detailed=True))
        streams = {r.rid: _drain_stream(r) for r in reqs}
        _assert_clean(eng, 4)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert log.stale == [] and log.overtaken == []
    assert set(staged) == {7, 9}
    assert (st["weight_swaps"], st["weight_version"]) == (2, 9)
    assert st["ahead_blocked_total"]["op_or_swap"] >= 1
    assert st["programs_ahead_total"] > 0
    for items in streams.values():
        assert len(items) == 12
        versions = [v for _, v, _ in items]
        assert versions == sorted(versions)
        assert set(versions) <= {0, 7, 9}
    # the chunk that was out when 7 was staged gave its token under 0
    versions = [v for _, v, _ in streams[staged[7]]]
    assert versions[0] == 0 and versions[1] >= 7
    assert {v for items in streams.values() for _, v, _ in items} \
        == {0, 7, 9}
