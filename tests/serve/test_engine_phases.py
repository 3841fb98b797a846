"""The engine's own clock: every tick's phases lie inside it and cover
it, ``host_gap_s`` within ``tick_wall_s``, the three parts of TTFT sum
to it for every request with request tracing on and off, traced spans
carry a tick the ring knows, the tick's annotations reach a profiler
trace, and the compiled programs' ops carry the named scopes."""
import glob

import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import TransformerConfig
from ray_tpu.serve import request_trace as RT
from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine
from ray_tpu.util import tracing

pytestmark = [pytest.mark.serve_llm, pytest.mark.observability]

MODEL_KW = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                head_dim=8, d_ff=32, max_seq_len=64, rotary_dim=8,
                dtype=jnp.float32, remat_policy="none")
TICK_CHILDREN = {
    "engine.ops", "engine.admit", "engine.prefill.stage",
    "engine.prefill.dispatch", "engine.prefill.wait",
    "engine.prefill.book", "engine.decode.stage",
    "engine.decode.dispatch", "engine.decode.wait", "engine.decode.emit",
    "engine.report"}
# a phase inside a phase: eviction, where an admission's blocks are not
# all on the free list (six prompts of five blocks through 24: it runs)
NESTED = {"engine.admit.evict": "engine.admit"}
TTFT_KEYS = ("ttft_queue_s", "ttft_prefill_wait_s", "ttft_prefill_s")


def _engine(**kw):
    ekw = dict(decode_slots=2, kv_block_size=4, max_seq_len=48,
               prefill_chunk=8, max_new_tokens=8)
    ekw.update(kw)
    return LLMEngine(TransformerConfig(**MODEL_KW), EngineConfig(**ekw),
                     replica_tag=f"t{len(tracing.clocks())}")


def _drain(req, timeout=60):
    toks = []
    while True:
        item = req.out.get(timeout=timeout)
        if not isinstance(item, int):
            assert not isinstance(item, BaseException), item
            return toks
        toks.append(item)


def _serve(eng, n=6, plen=20, new=6):
    """More requests than slots, two chunks and more each: a queue, and
    requests that wait behind another's chunks."""
    reqs = [eng.submit(list(range(2 + i, 2 + i + plen)), new)
            for i in range(n)]      # no two share a first block
    for r in reqs:
        assert len(_drain(r)) == new
    return reqs


def _served(traced):
    eng = _engine(enable_trace=traced)
    eng.warmup()
    reqs = _serve(eng)
    yield eng, reqs, traced
    eng.shutdown()


@pytest.fixture(params=[True, False], ids=["traced", "untraced"])
def served(request):
    yield from _served(request.param)


@pytest.fixture
def served_traced():
    yield from _served(True)


def test_every_ticks_phases_lie_inside_it_and_cover_it(served):
    eng, _, _ = served
    ticks = {}
    for name, tick, t0, t1, parent in eng._clock.spans():
        ticks.setdefault(tick, []).append((name, t0, t1, parent))
    whole = [t for t, spans in ticks.items()
             if any(s[0] == "engine.tick" for s in spans)]
    assert len(whole) >= 10
    covered = total = 0.0
    for t in whole:
        spans = ticks[t]
        (_, lo, hi, parent), = [s for s in spans if s[0] == "engine.tick"]
        assert parent is None
        last = lo
        for name, t0, t1, up in spans:
            if name in NESTED:
                assert up == NESTED[name] and any(
                    s[0] == up and s[1] <= t0 <= t1 <= s[2] for s in spans)
        for name, t0, t1, up in sorted(
                (s for s in spans
                 if s[0] != "engine.tick" and s[0] not in NESTED),
                key=lambda s: s[1]):
            assert name in TICK_CHILDREN and up == "engine.tick"
            assert last <= t0 <= t1 <= hi       # in order, no overlap
            last = t1
            covered += t1 - t0
        total += hi - lo
    # what no phase covers is the glue between them
    assert covered >= 0.8 * total
    # one program ahead: a launch (*.dispatch) with the program before
    # it still out, never a third; a chunk launched under a decode step
    # carries that tick on its dispatch and the next on its wait
    out, kinds, crossed = [], set(), 0
    for name, tick, t0, t1, _ in sorted(eng._clock.spans(),
                                        key=lambda s: s[2]):
        kind, _, what = name[len("engine."):].partition(".")
        if what == "dispatch":
            assert len(out) <= 1
            if out:
                kinds.add((out[0][0], kind))
            out.append((kind, tick))
        elif what == "wait":
            was, launched = out.pop(0)          # fetched in launch order
            assert was == kind and tick - launched in (0, 1)
            assert tick == launched or kind == "prefill"
            crossed += tick - launched
    assert not out
    assert kinds == {("prefill", "decode"), ("decode", "prefill")}
    st = eng.stats()
    assert 0 < crossed <= st["programs_ahead_total"] \
        < st["prefill_chunks"] + st["decode_steps"]


def _idle_inside_ticks(spans):
    """Seconds inside ``engine.tick`` spans with no program out: what
    ``host_gap_s`` means, recomputed from the ring."""
    marks = []
    for name, _, t0, t1, parent in spans:
        if name.endswith(".dispatch"):
            marks.append((t0, 1))
        elif name.endswith(".wait"):
            marks.append((t1, -1))
    idle = 0.0
    for lo, hi in sorted((t0, t1) for name, _, t0, t1, _ in spans
                         if name == "engine.tick"):
        flying = sum(d for t, d in marks if t < lo)
        last = lo
        for t, d in sorted(m for m in marks if lo <= m[0] <= hi):
            if not flying:
                idle += t - last
            flying += d
            last = t
        if not flying:
            idle += hi - last
    return idle


def test_stats_carry_the_phase_table_and_the_gap(served):
    eng, _, _ = served
    st = eng.stats()
    assert TICK_CHILDREN | {"engine.tick"} | set(NESTED) \
        == set(st["phases"])
    evictions, evict_s = st["phases"]["engine.admit.evict"]
    assert 0 < evictions <= st["prefix_evictions_total"]
    assert 0 < evict_s <= st["phases"]["engine.admit"][1]
    for name in ("prefill", "decode"):
        counts = {st["phases"][f"engine.{name}.{p}"][0]
                  for p in ("stage", "dispatch", "wait")}
        assert len(counts) == 1
    assert st["phases"]["engine.decode.wait"][0] == st["decode_steps"]
    assert st["phases"]["engine.prefill.wait"][0] == st["prefill_chunks"]
    assert st["tick_wall_s"] == st["phases"]["engine.tick"][1] > 0
    assert 0 < st["host_gap_s"] <= st["tick_wall_s"]
    # the device idles only with no program out, two being out at times
    spans = eng._clock.spans()
    assert st["host_gap_s"] == pytest.approx(_idle_inside_ticks(spans),
                                             abs=1e-4)
    serial = st["tick_wall_s"] - sum(
        st["phases"][f"engine.{name}.{p}"][1]
        for name in ("prefill", "decode") for p in ("dispatch", "wait"))
    assert st["host_gap_s"] < serial
    # the two outside clocks: a program's wall ends with its fetch and
    # starts with its staging or the fetch before it, whichever came
    # later, so each holds its own wait, neither holds the other's and
    # together they fit the ticks they ran in
    ticks = sorted((t0, t1) for name, _, t0, t1, _ in spans
                   if name == "engine.tick")
    walls = st["prefill_wall_s"] + st["decode_wall_s"]
    assert walls <= ticks[-1][1] - ticks[0][0] + 1e-3
    for name, wall in (("prefill", "prefill_wall_s"),
                       ("decode", "decode_wall_s")):
        assert st["phases"][f"engine.{name}.wait"][1] <= st[wall] + 1e-3
    assert st["h2d_transfers_total"] \
        == st["prefill_chunks"] + st["decode_steps"]
    assert 0 < st["programs_ahead_total"] < st["h2d_transfers_total"]
    assert set(st["ahead_blocked_total"]) == {
        "last_chunk", "no_backlog", "op_or_swap", "speculative"}
    assert tracing.clocks()[eng._clock.owner] is eng._clock


def test_ttft_parts_sum_to_ttft_for_every_request(served):
    eng, reqs, traced = served
    st = eng.stats()
    assert st["ttft_requests"] == len(reqs)
    sums = dict.fromkeys(TTFT_KEYS, 0.0)
    for r in reqs:
        parts = (r.t_slot - r.t_submit, r.t_first_chunk - r.t_slot,
                 r.t_first_token - r.t_first_chunk)
        assert all(p >= 0 for p in parts)
        assert sum(parts) == pytest.approx(
            r.t_first_token - r.t_submit, abs=1e-9)
        for k, p in zip(TTFT_KEYS, parts):
            sums[k] += p
    for k in TTFT_KEYS:
        assert st[k] == pytest.approx(sums[k], abs=1e-9)
    assert sum(st[k] for k in TTFT_KEYS) == pytest.approx(st["ttft_s"],
                                                          abs=1e-9)
    # three chunks a prompt, one a tick, other requests' in between
    assert st["ttft_prefill_chunks"] == 3 * len(reqs)
    assert st["ttft_prefill_ticks"] >= st["ttft_prefill_chunks"]
    # six requests over two slots queued, and waited behind chunks
    assert st["ttft_queue_s"] > 0 and st["ttft_prefill_wait_s"] > 0
    assert st["queue_wait_ewma_s"] > 0          # traced or not
    # tracing decides only whether a RequestTrace exists
    assert len(eng._tracer.recent) == (len(reqs) if traced else 0)


def test_traced_spans_carry_a_tick_the_ring_knows(served_traced):
    eng, reqs, _ = served_traced
    ring_ticks = {s[1] for s in eng._clock.spans()}
    for tr in eng._tracer.recent:
        phases = [s["phase"] for s in tr.spans]
        assert phases.index(RT.QUEUED) < phases.index(RT.ADMITTED) \
            < phases.index(RT.PREFILL_WAIT) < phases.index(RT.PREFILL)
        ticked = [s for s in tr.spans
                  if s["phase"] in (RT.PREFILL, RT.DECODE)]
        assert len(ticked) >= 4                  # 3 chunks and a decode
        assert all(s["attrs"]["tick"] in ring_ticks for s in ticked)
        chunk_ticks = [s["attrs"]["tick"] for s in ticked
                       if s["phase"] == RT.PREFILL]
        assert chunk_ticks == sorted(chunk_ticks)
        wait, = [s for s in tr.spans if s["phase"] == RT.PREFILL_WAIT]
        first = [s for s in tr.spans if s["phase"] == RT.PREFILL][0]
        assert wait["t1"] == pytest.approx(first["t0"], abs=1e-6)
    assert RT.PREFILL_WAIT in RT.PHASE_ORDER


def test_speculative_ticks_use_the_same_phase_names():
    eng = _engine(spec_tokens=2, enable_trace=True)
    try:
        eng.warmup()
        _serve(eng, n=3)
        st = eng.stats()
        assert set(st["phases"]) - set(NESTED) \
            == TICK_CHILDREN | {"engine.tick"}
        assert st["phases"]["engine.decode.wait"][0] == st["decode_steps"]
        assert st["host_gap_s"] <= st["tick_wall_s"]
        # drafting reads the host's history: nothing goes ahead, and
        # the gap is the tick outside [dispatch start, wait end]
        assert st["programs_ahead_total"] == 0
        assert st["ahead_blocked_total"]["speculative"] \
            == st["decode_steps"]
        assert st["host_gap_s"] == pytest.approx(
            _idle_inside_ticks(eng._clock.spans()), abs=1e-4)
        spec = [s for tr in eng._tracer.recent for s in tr.spans
                if s["phase"] == RT.SPEC_VERIFY]
        assert all("tick" in s["attrs"] for s in spec)
    finally:
        eng.shutdown()


def test_a_profiler_trace_holds_the_ticks_annotations(tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(enable_trace=False)
    try:
        eng.warmup()
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(eng, n=2)
        finally:
            jax.profiler.stop_trace()
        ring = {(s[0], s[1]) for s in eng._clock.spans()}
    finally:
        eng.shutdown()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    tick = dict(e.stats)["tick"]
                    seen.setdefault(e.name, []).append(
                        (tick, e.start_ns, e.start_ns + e.duration_ns))
    assert {"engine.tick", "engine.decode.wait", "engine.prefill.dispatch",
            "engine.admit"} <= set(seen)
    # the annotation and the ring's entry are one span: joined by tick
    assert {("engine.decode.wait", t) for t, _, _ in
            seen["engine.decode.wait"]} <= ring
    ticks = {t: (a, b) for t, a, b in seen["engine.tick"]}
    for t, a, b in seen["engine.decode.wait"]:
        if t in ticks:                # a tick the session saw whole
            assert ticks[t][0] <= a and b <= ticks[t][1]


def test_compiled_programs_ops_carry_the_scopes():
    eng = _engine()
    try:
        S, T = eng.config.decode_slots, eng.config.blocks_per_seq
        i32 = jnp.int32
        # each step program's integers are one array: [tokens | start |
        # n | table row] a prefill row, [token | length | table row] a
        # decode slot
        prefill = eng._jit_prefill.lower(
            eng._params, jnp.zeros((1, 8 + 2 + T), i32), eng._cache
        ).as_text(debug_info=True)
        decode = eng._jit_decode.lower(
            eng._params, jnp.zeros((S, 2 + T), i32), eng._cache
        ).as_text(debug_info=True)
        copy = eng._jit_copy.lower(
            eng._cache, jnp.int32(0), jnp.int32(0)
        ).as_text(debug_info=True)
        ids = jnp.zeros((T,), i32)
        gather = eng._jit_gather.lower(eng._cache, ids)
        slabs = jax.eval_shape(eng._jit_gather, eng._cache, ids)
        scatter = eng._jit_scatter.lower(
            eng._cache, ids, slabs).as_text(debug_info=True)
        gather = gather.as_text(debug_info=True)
    finally:
        eng.shutdown()
    for text in (prefill, decode):
        for scope in ("embed", "layer/attn/kv_write",
                      "layer/attn/paged_attn", "layer/mlp", "final_norm",
                      "lm_head", "sample"):
            assert scope in text, scope
    # the names today's readers match are the programs', not scopes
    assert "jit(_prefill_fn)" in prefill and "jit(_decode_fn)" in decode
    assert "kv_copy" in copy and "kv_gather" in gather \
        and "kv_scatter" in scatter
