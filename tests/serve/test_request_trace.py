"""Per-request distributed tracing tests: tail sampling + ship rules,
the SLO watchdog, controller-store exactly-once merging under chaos
drops/dups, engine waterfall phases (incl. the queue-wait TTFT split
regression), and the live-fleet e2e — a p99-slow request auto-captured
by the SLO watchdog renders a >=6-phase waterfall through both
/api/v0/requests/<id> and the `ray-tpu trace` renderer while a fast
unsampled request ships zero spans."""

import json
import os
import random
import threading
import time

import pytest

import jax.numpy as jnp

from ray_tpu.serve import request_trace as RT
from ray_tpu.serve.request_trace import (RequestTrace, RequestTracer,
                                         RequestTraceStore,
                                         new_request_id)
from ray_tpu.serve.slo import SLOBudget, SLOWatchdog

pytestmark = [pytest.mark.serve_llm, pytest.mark.observability]

MODEL_KW = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                head_dim=8, d_ff=32, max_seq_len=64, rotary_dim=8,
                dtype=jnp.float32, remat_policy="none")
MODEL_DICT = dict(MODEL_KW, dtype="float32")


def _engine(**kw):
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine
    ekw = dict(decode_slots=4, kv_block_size=4, max_seq_len=48,
               prefill_chunk=8, max_new_tokens=16, enable_trace=True)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**MODEL_KW), EngineConfig(**ekw))


# ----------------------------------------------------------- sampling
def test_request_id_format_and_uniqueness():
    ids = {new_request_id() for _ in range(64)}
    assert len(ids) == 64
    assert all(i.startswith("req-") and len(i) == 20 for i in ids)


def test_tracer_one_in_n_sampling_is_deterministic():
    tr = RequestTracer(sample_n=4)
    verdicts = [tr.begin().sampled for _ in range(8)]
    assert verdicts == [True, False, False, False,
                        True, False, False, False]


def test_tail_sampling_ship_rules():
    """Only sampled / failed / shed traces ship; a fast unsampled DONE
    is recorded locally and discarded (zero bytes on the wire)."""
    tr = RequestTracer(sample_n=10**9)
    t0 = tr.begin()            # counter 0: the 1-in-N hit
    assert t0.sampled
    # unsampled + DONE: no ship
    t = tr.begin()
    t.span(RT.DONE, time.time())
    assert not tr.finish(t)
    assert len(tr.shipped_local) == 0
    assert tr.recent[-1] is t              # but the local ring kept it
    # unsampled + FAILED: always ships
    t = tr.begin()
    tr.finish(t, err=ValueError("boom"))
    assert len(tr.shipped_local) == 1
    p = tr.shipped_local[-1]
    assert p["status"] == RT.FAILED
    assert p["spans"][-1]["attrs"]["error"] == "ValueError"
    # unsampled + SHED: always ships
    t = tr.begin()
    t.span(RT.SHED, time.time(), None, reason="tenant_over_quota")
    tr.finish(t)
    assert tr.shipped_local[-1]["status"] == RT.SHED
    # sampled + DONE: ships (the baseline sample)
    t0.span(RT.DONE, time.time())
    tr.finish(t0)
    assert tr.shipped_local[-1]["status"] == RT.DONE
    assert tr.shipped_local[-1]["sampled"] is True


def test_disabled_tracer_is_a_noop():
    tr = RequestTracer(sample_n=1)
    tr.enabled = False
    assert tr.begin() is None
    assert tr.finish(None) is False


def test_span_cap_drops_oldest_and_counts():
    t = RequestTrace("req-cap")
    for i in range(RT.MAX_SPANS_PER_REQUEST + 8):
        t.span(RT.DECODE, float(i), float(i) + 0.5, tokens=1)
    assert len(t.spans) == RT.MAX_SPANS_PER_REQUEST
    assert t.dropped == 8
    assert t.spans[0]["t0"] == 8.0         # oldest went first


def test_span_clock_skew_clamps_negative_width():
    t = RequestTrace("req-skew")
    t.span(RT.PREFILL, 10.0, 9.0)
    assert t.spans[0]["t1"] == 10.0


# ------------------------------------------------------- SLO watchdog
def test_slo_watchdog_trips_flip_ship_and_annotate():
    wd = SLOWatchdog(SLOBudget(queue_s=0.1, ttft_s=0.5,
                               inter_token_p99_s=0.05))
    t = RequestTrace("req-slo")
    assert not t.ship
    assert not wd.observe_queue(t, 0.05)       # inside budget
    assert wd.observe_queue(t, 0.2)
    assert t.ship and t.slo["queue"] == {"value": 0.2, "budget": 0.1}
    assert wd.observe_ttft(t, 0.6)
    assert t.slo["ttft"]["budget"] == 0.5
    # p99 of gaps: one gap over budget trips (nearest-rank p99 == max
    # below 100 samples — one bad stall should trip)
    t2 = RequestTrace("req-slo2")
    for _ in range(20):
        assert not wd.observe_gap(t2, 0.01)
    assert wd.observe_gap(t2, 0.2)
    assert t2.slo["inter_token_p99"]["value"] >= 0.2
    assert t2.ship


def test_slo_disabled_budget_never_trips():
    wd = SLOWatchdog(SLOBudget(queue_s=0.0, ttft_s=-1.0,
                               inter_token_p99_s=0.0))
    t = RequestTrace("req-off")
    assert not wd.observe_queue(t, 100.0)
    assert not wd.observe_ttft(t, 100.0)
    assert not wd.observe_gap(t, 100.0)
    assert not t.ship and not t.slo


# ------------------------------------------------- controller store
def _payload(rid, part="engine", seq=1, spans=None, status=RT.DONE,
             **kw):
    return dict({"request_id": rid, "part": part, "proc": f"p-{part}",
                 "seq": seq, "ts": 100.0 + seq, "status": status,
                 "sampled": True, "slo": {}, "meta": {}, "dropped": 0,
                 "spans": spans or []}, **kw)


def test_store_dedups_by_part_seq_and_merges_parts():
    st = RequestTraceStore()
    eng = _payload("req-a", spans=[
        {"request_id": "req-a", "phase": RT.QUEUED, "t0": 1.0, "t1": 2.0},
        {"request_id": "req-a", "phase": RT.DONE, "t0": 3.0, "t1": 3.0}])
    assert st.ingest(eng)
    assert not st.ingest(dict(eng))        # retransmit: no double
    assert st.deduped == 1
    rtr = _payload("req-a", part="router", seq=7, status=None, spans=[
        {"request_id": "req-a", "phase": RT.ADMITTED,
         "t0": 2.5, "t1": 2.5}])
    assert st.ingest(rtr)
    w = st.waterfall("req-a")
    assert [s["phase"] for s in w["spans"]] == [RT.QUEUED, RT.ADMITTED,
                                                RT.DONE]
    assert w["status"] == RT.DONE
    assert w["procs"] == {"engine": "p-engine", "router": "p-router"}
    assert st.waterfall("req-missing") is None


def test_store_status_precedence_failed_beats_done():
    st = RequestTraceStore()
    # either arrival order: the failing part saw the true end
    st.ingest(_payload("req-f1", part="engine", status=RT.DONE))
    st.ingest(_payload("req-f1", part="router", seq=2, status=RT.FAILED))
    assert st.waterfall("req-f1")["status"] == RT.FAILED
    st.ingest(_payload("req-f2", part="router", status=RT.FAILED))
    st.ingest(_payload("req-f2", part="engine", seq=2, status=RT.DONE))
    assert st.waterfall("req-f2")["status"] == RT.FAILED


def test_store_sorts_out_of_order_spans_monotone():
    st = RequestTraceStore()
    st.ingest(_payload("req-o", spans=[
        {"request_id": "req-o", "phase": RT.DONE, "t0": 9.0, "t1": 9.0},
        {"request_id": "req-o", "phase": RT.QUEUED, "t0": 1.0, "t1": 2.0},
        {"request_id": "req-o", "phase": RT.PREFILL, "t0": 2.0,
         "t1": 1.5}]))                      # skewed: t1 < t0
    w = st.waterfall("req-o")
    t0s = [s["t0"] for s in w["spans"]]
    assert t0s == sorted(t0s)
    assert all(s["t1"] >= s["t0"] for s in w["spans"])
    assert w["dur_s"] == pytest.approx(8.0)


def test_store_bounded_drop_oldest():
    st = RequestTraceStore(max_requests=4)
    for i in range(6):
        st.ingest(_payload(f"req-{i}"))
    rows = st.rows(limit=50)
    assert len(rows) == 4
    assert {r["request_id"] for r in rows} == {f"req-{i}"
                                               for i in range(2, 6)}
    # newest first in the listing
    assert rows[0]["request_id"] == "req-5"


def test_store_chaos_dups_exactly_one_complete_waterfall():
    """Seeded chaos-shaped delivery: every payload arrives 1-3 times in
    a shuffled interleave (the reliable layer's retransmits). Each
    request must end with exactly one complete waterfall — no dup
    spans, monotone timestamps, terminal status intact."""
    rng = random.Random(1101)
    st = RequestTraceStore()
    want = {}
    deliveries = []
    for i in range(12):
        rid = f"req-chaos{i:02d}"
        spans = [{"request_id": rid, "phase": ph,
                  "t0": 10.0 * i + j, "t1": 10.0 * i + j + 0.5}
                 for j, ph in enumerate(
                     (RT.QUEUED, RT.ADMITTED, RT.PREFILL,
                      RT.FIRST_TOKEN, RT.DECODE, RT.DONE))]
        p = _payload(rid, seq=i + 1, spans=spans)
        want[rid] = len(spans)
        deliveries += [p] * rng.randint(1, 3)
    rng.shuffle(deliveries)
    for p in deliveries:
        st.ingest(dict(p))
    for rid, n in want.items():
        w = st.waterfall(rid)
        assert w is not None and w["status"] == RT.DONE
        assert len(w["spans"]) == n        # dups never double a span
        t0s = [s["t0"] for s in w["spans"]]
        assert t0s == sorted(t0s)
        assert sum(d["count"] for d in w["phases"].values()) == n


def test_store_slowest_picks_longest_waterfall():
    st = RequestTraceStore()
    for i, dur in enumerate((1.0, 5.0, 2.0)):
        st.ingest(_payload(f"req-s{i}", spans=[
            {"request_id": f"req-s{i}", "phase": RT.QUEUED,
             "t0": 0.0, "t1": dur}]))
    assert st.slowest()["request_id"] == "req-s1"


# ------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def traced_engine():
    eng = _engine()
    yield eng
    eng.shutdown()


def _shipped(eng, rid, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for p in list(eng._tracer.shipped_local):
            if p["request_id"] == rid:
                return p
        time.sleep(0.02)
    raise AssertionError(
        f"no shipped payload for {rid}: "
        f"{[p['request_id'] for p in eng._tracer.shipped_local]}")


def test_engine_waterfall_has_six_phases(traced_engine):
    eng = traced_engine
    rid = new_request_id()
    toks = list(eng.generate_sync(
        [1, 2, 3, 4], max_new_tokens=8,
        trace_ctx={"request_id": rid, "sampled": True,
                   "enqueue_ts": time.time(), "policy": "gauge",
                   "admission": "admitted"}))
    assert len(toks) == 8
    p = _shipped(eng, rid)
    phases = [s["phase"] for s in p["spans"]]
    assert {RT.QUEUED, RT.ADMITTED, RT.PREFILL, RT.FIRST_TOKEN,
            RT.DECODE, RT.DONE} <= set(phases)
    assert len(set(phases)) >= 6
    # the engine is the single shipper: one payload, monotone spans
    assert phases.count(RT.DONE) == 1 and phases.count(RT.QUEUED) == 1
    t0s = [s["t0"] for s in sorted(p["spans"],
                                   key=lambda s: (s["t0"], s["t1"]))]
    assert t0s == sorted(t0s)
    done = p["spans"][-1]
    assert done["phase"] == RT.DONE and done["attrs"]["tokens"] == 8
    assert p["meta"] == {"policy": "gauge", "admission": "admitted"}
    assert p["status"] == RT.DONE


def test_engine_unsampled_fast_request_ships_zero_spans(traced_engine):
    eng = traced_engine
    rid = new_request_id()
    before = len(eng._tracer.shipped_local)
    list(eng.generate_sync(
        [5, 6, 7], max_new_tokens=4,
        trace_ctx={"request_id": rid, "sampled": False,
                   "enqueue_ts": time.time()}))
    time.sleep(0.2)
    assert all(p["request_id"] != rid
               for p in eng._tracer.shipped_local), \
        "unsampled fast request must ship zero spans"
    assert len(eng._tracer.shipped_local) == before
    # ...but the local postmortem ring recorded it
    assert any(t.request_id == rid for t in eng._tracer.recent)


def test_queue_wait_is_split_out_of_ttft(traced_engine):
    """Satellite regression: TTFT = queue_wait + engine time. A
    router-stamped enqueue 0.5s in the past must surface as
    queue_wait_s on the FIRST_TOKEN span, with full ttft_s >=
    queue_wait_s > engine_ttft_s; the engine's own part of the wait
    (submit -> slot) is ttft_queue_s over ttft_requests in stats()."""
    eng = traced_engine
    rid = new_request_id()
    list(eng.generate_sync(
        [9, 9, 9], max_new_tokens=4,
        trace_ctx={"request_id": rid, "sampled": True,
                   "enqueue_ts": time.time() - 0.5}))
    p = _shipped(eng, rid)
    ft = next(s for s in p["spans"] if s["phase"] == RT.FIRST_TOKEN)
    a = ft["attrs"]
    assert a["queue_wait_s"] >= 0.45
    assert a["ttft_s"] >= a["queue_wait_s"]
    assert a["engine_ttft_s"] < a["queue_wait_s"]
    assert a["ttft_s"] == pytest.approx(
        a["queue_wait_s"] + a["engine_ttft_s"], abs=0.25)
    # QUEUED span covers the router wait, not just the engine queue
    q = next(s for s in p["spans"] if s["phase"] == RT.QUEUED)
    assert q["t1"] - q["t0"] >= 0.45
    # the mean the benchmark reads starts at the engine's submit: the
    # router's half second is not in it
    st = eng.stats()
    assert 0 < st["ttft_queue_s"] / st["ttft_requests"] \
        < a["queue_wait_s"]


def test_future_enqueue_stamp_is_clamped(traced_engine):
    """Cross-process clock skew: an enqueue stamp from the future must
    not produce a negative queue wait or a QUEUED span starting after
    ADMITTED."""
    eng = traced_engine
    rid = new_request_id()
    list(eng.generate_sync(
        [4, 4, 4], max_new_tokens=2,
        trace_ctx={"request_id": rid, "sampled": True,
                   "enqueue_ts": time.time() + 60.0}))
    p = _shipped(eng, rid)
    q = next(s for s in p["spans"] if s["phase"] == RT.QUEUED)
    adm = next(s for s in p["spans"] if s["phase"] == RT.ADMITTED)
    assert q["t0"] <= adm["t0"]
    ft = next(s for s in p["spans"] if s["phase"] == RT.FIRST_TOKEN)
    assert ft["attrs"]["queue_wait_s"] >= 0.0


def test_rlhf_pinned_id_without_verdict_keeps_baseline_sampling():
    """An RLHF rollout stamps request_ids but no sampling verdict: the
    engine tracer's own 1-in-N must still apply (first request is the
    1-in-N hit), instead of never sampling pinned ids."""
    eng = _engine(decode_slots=2)
    try:
        rid = new_request_id()
        list(eng.generate_sync([2, 3, 5], max_new_tokens=2,
                               trace_ctx={"request_id": rid}))
        p = _shipped(eng, rid)
        assert p["sampled"] is True
    finally:
        eng.shutdown()


def test_engine_death_ships_failed_span_naming_typed_error():
    eng = _engine(decode_slots=2)
    try:
        list(eng.generate_sync([1, 2, 3], max_new_tokens=2))  # warm

        def boom(active):
            raise RuntimeError("injected decode fault")

        eng._launch_decode = boom
        rid = new_request_id()
        from ray_tpu.serve.llm_engine import EngineDeadError
        with pytest.raises(EngineDeadError):
            list(eng.generate_sync(
                [7, 7, 7], max_new_tokens=8,
                trace_ctx={"request_id": rid, "sampled": False}))
        p = _shipped(eng, rid)             # FAILED always ships
        assert p["status"] == RT.FAILED
        failed = p["spans"][-1]
        assert failed["phase"] == RT.FAILED
        assert failed["attrs"]["error"] == "EngineDeadError"
        assert "injected decode fault" in failed["attrs"]["detail"]
    finally:
        eng.shutdown()


def test_decode_tick_bounds_span_count():
    """A long generation records one DECODE span per
    ``trace_decode_tick`` tokens, not one per token."""
    eng = _engine(decode_slots=2, trace_decode_tick=8,
                  max_new_tokens=40, max_seq_len=48)
    try:
        rid = new_request_id()
        toks = list(eng.generate_sync(
            [3, 1], max_new_tokens=40,
            trace_ctx={"request_id": rid, "sampled": True}))
        p = _shipped(eng, rid)
        decode = [s for s in p["spans"] if s["phase"] == RT.DECODE]
        assert 1 <= len(decode) <= (len(toks) // 8) + 1
        assert sum(s["attrs"]["tokens"] for s in decode) == len(toks) - 1
    finally:
        eng.shutdown()


# ----------------------------------------------------- live fleet e2e
def _dashboard_address():
    import ray_tpu
    session_dir = ray_tpu.api._head.session_dir
    with open(os.path.join(session_dir, "dashboard.json")) as f:
        return json.load(f)["address"]


def _store_waterfall(rid, timeout_s=30.0):
    from ray_tpu.util.state import get_request_trace
    deadline = time.time() + timeout_s
    w = None
    while time.time() < deadline:
        w = get_request_trace(rid)
        if w is not None and w.get("status"):
            return w
        time.sleep(0.3)
    return w


@pytest.mark.slow
def test_e2e_slo_watchdog_captures_slow_request_with_waterfall():
    """The acceptance demo: under tail sampling (1-in-N effectively
    off), a p99-slow request — queued behind a long decode on a 1-slot
    replica — trips the queue SLO and is auto-captured: its waterfall
    renders >=6 distinct phases through BOTH /api/v0/requests/<id> and
    the `ray-tpu trace` renderer, while a fast un-flagged request ships
    zero spans (404 from the API)."""
    import urllib.error
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.shutdown()
    os.environ["RAY_TPU_TRACE_SAMPLE_N"] = "1000000000"
    os.environ["RAY_TPU_SLO_QUEUE_S"] = "0.02"
    try:
        ray_tpu.init(num_cpus=8, _num_initial_workers=3,
                     ignore_reinit_error=True)
        app = serve.deployment(serve.LLMServer).bind(
            model=MODEL_DICT,
            engine={"decode_slots": 1, "kv_block_size": 4,
                    "max_seq_len": 48, "prefill_chunk": 8})
        h = serve.run(app)
        # warm outside the window (this request is router-counter 0 —
        # the one 1-in-N hit even at N=1e9)
        list(h.options(stream=True).generate.remote([2, 3, 5], 2))

        # back up the single decode slot with several long generations,
        # then queue the victim behind them: its queue wait (the sum of
        # the blockers' decode walls) must blow the 20ms budget
        slow_rid = "req-e2e-slo-victim00"
        fast_rid = "req-e2e-fast-nosample"
        blockers = [threading.Thread(target=lambda i=i: list(
            h.options(stream=True).generate.remote([1 + i, 1, 1], 40)))
            for i in range(3)]
        for b in blockers:
            b.start()
        time.sleep(0.02)       # blockers reach the engine queue first
        toks = list(h.options(
            stream=True, request_id=slow_rid).generate.remote(
                [8, 6, 4], 8))
        assert len(toks) == 8
        for b in blockers:
            b.join(timeout=120)
        # a fast request on the now-idle replica: inside every budget,
        # not the 1-in-N hit -> ships nothing
        list(h.options(
            stream=True, request_id=fast_rid).generate.remote(
                [9, 9, 9], 4))

        w = _store_waterfall(slow_rid)
        assert w is not None, "SLO watchdog never captured the " \
            "slow request"
        assert "queue" in (w.get("slo") or {}), w.get("slo")
        phases = {s["phase"] for s in w["spans"]}
        assert {RT.QUEUED, RT.ADMITTED, RT.PREFILL, RT.FIRST_TOKEN,
                RT.DECODE, RT.DONE} <= phases
        assert len(phases) >= 6

        # surface 1: the dashboard API
        addr = _dashboard_address()
        with urllib.request.urlopen(
                addr + f"/api/v0/requests/{slow_rid}", timeout=10) as r:
            via_http = json.loads(r.read())
        assert via_http["request_id"] == slow_rid
        assert {s["phase"] for s in via_http["spans"]} >= phases
        with urllib.request.urlopen(
                addr + "/api/v0/requests", timeout=10) as r:
            rows = json.loads(r.read())["rows"]
        assert any(r["request_id"] == slow_rid for r in rows)

        # surface 2: the `ray-tpu trace` renderer — the in-process
        # cluster source (what the CLI subcommand calls after
        # _connect), then the tool as a real subprocess against the
        # dashboard, asserting the rendered gantt
        import subprocess
        import sys as _sys

        import tools.trace as trace_tool
        assert trace_tool.main([slow_rid]) == 0
        proc = subprocess.run(
            [_sys.executable, "tools/trace.py", slow_rid,
             "--dashboard", addr],
            capture_output=True, text=True, timeout=60,
            cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))
        assert proc.returncode == 0, proc.stderr
        out = proc.stdout
        for ph in (RT.QUEUED, RT.ADMITTED, RT.PREFILL,
                   RT.FIRST_TOKEN, RT.DECODE, RT.DONE):
            assert ph in out, out
        assert "SLO TRIP [queue]" in out

        # the fast un-flagged request shipped ZERO spans
        from ray_tpu.util.state import get_request_trace
        assert get_request_trace(fast_rid) is None
        try:
            urllib.request.urlopen(
                addr + f"/api/v0/requests/{fast_rid}", timeout=10)
            raise AssertionError("expected 404 for unsampled request")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_TRACE_SAMPLE_N", None)
        os.environ.pop("RAY_TPU_SLO_QUEUE_S", None)


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_drops_one_complete_waterfall_and_sigkill_failed_span():
    """Satellite chaos leg: with 5% REQUEST_SPANS drops on the wire and
    every request sampled, each request still ends with exactly ONE
    complete waterfall at the controller (reliable-layer retransmits +
    store dedup — monotone timestamps, no duplicated spans). Then a
    mid-decode replica SIGKILL: the victim request's trace must end in
    a FAILED span naming the typed error (shipped by the router — the
    dead replica can't)."""
    import signal

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import chaos
    from ray_tpu.util.state import get_request_trace

    ray_tpu.shutdown()
    os.environ[chaos.ENV_SEED] = "1101"
    os.environ[chaos.ENV_CONFIG] = json.dumps({"drop_prob": 0.05})
    os.environ["RAY_TPU_TRACE_SAMPLE_N"] = "1"

    class PidLLM(serve.LLMServer):
        def pid(self):
            return os.getpid()

    try:
        ray_tpu.init(num_cpus=8, _num_initial_workers=3,
                     ignore_reinit_error=True)
        app = serve.deployment(PidLLM).bind(
            model=MODEL_DICT,
            engine={"decode_slots": 2, "kv_block_size": 4,
                    "max_seq_len": 48, "prefill_chunk": 8})
        h = serve.run(app)
        list(h.options(stream=True).generate.remote([2, 3, 5], 2))

        rids = [f"req-chaosleg{i:06d}" for i in range(6)]
        for i, rid in enumerate(rids):
            toks = list(h.options(
                stream=True, request_id=rid).generate.remote(
                    [3 + i, 2, 1], 6))
            assert len(toks) == 6
        for rid in rids:
            w = _store_waterfall(rid, timeout_s=60.0)
            assert w is not None and w["status"] == RT.DONE, \
                f"{rid}: waterfall lost under drops: {w}"
            phases = [s["phase"] for s in w["spans"]]
            # exactly one complete waterfall: no dup spans
            for ph in (RT.QUEUED, RT.ADMITTED, RT.FIRST_TOKEN, RT.DONE):
                assert phases.count(ph) == 1, (rid, phases)
            t0s = [s["t0"] for s in w["spans"]]
            assert t0s == sorted(t0s)

        # --- mid-decode SIGKILL: FAILED span names the typed error
        pid = h.pid.remote().result(timeout_s=60)
        kill_rid = "req-chaosleg-sigkill"
        gen = h.options(
            stream=True, request_id=kill_rid).generate.remote(
                [7, 7, 7], 40)
        next(gen)                      # stream live before the kill
        os.kill(pid, signal.SIGKILL)
        try:
            for _ in gen:
                pass
        except Exception:
            pass                       # typed failure asserted below
        w = _store_waterfall(kill_rid, timeout_s=60.0)
        if w is not None and w.get("status") == RT.FAILED:
            failed = [s for s in w["spans"]
                      if s["phase"] == RT.FAILED]
            assert len(failed) == 1
            assert failed[0]["attrs"]["error"], failed
        else:
            # the kill can race the stream's natural end — then the
            # request completed and its waterfall says DONE
            assert w is not None and w.get("status") == RT.DONE, w
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        os.environ.pop(chaos.ENV_SEED, None)
        os.environ.pop(chaos.ENV_CONFIG, None)
        os.environ.pop("RAY_TPU_TRACE_SAMPLE_N", None)
