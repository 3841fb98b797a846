"""The engine's own clock: every tick's phases lie inside it and cover
it, ``host_gap_s`` within ``tick_wall_s``, the three parts of TTFT sum
to it for every request with request tracing on and off, traced spans
carry a tick the ring knows, the tick's annotations reach a profiler
trace, the compiled programs' ops carry the named scopes, and each
program's wall is booked by class from the engine's own fetches: its
time on the device where it ran behind another, launch + wait where
nothing was out, neither where a fetch found its result ready; and
the tick's books by kind: a tick is booked under what it fetched, the
kinds add up to the tick's own totals, and a slow tick names the phase
that held it."""
import glob
import time

import numpy as np
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
    assert st["ttft_queue_s"] / st["ttft_requests"] > 0   # traced or not
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


UNITS = {"prefill": "chunks", "decode": "steps"}
ALL = {"prefill": "prefill_chunks", "decode": "decode_steps"}


def _classes(st, kind):
    """[programs, seconds] of ``kind``'s device and serial classes."""
    return [[st[f"{kind}_{cls}_{UNITS[kind]}"], st[f"{kind}_{cls}_s"]]
            for cls in ("device", "serial")]


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_the_walls_by_class_fit_inside_all_of_a_kind(served, kind):
    eng, _, _ = served
    st = eng.stats()
    (device, device_s), (serial, serial_s) = _classes(st, kind)
    found = st["fetch_found_ready_total"]
    assert set(found) == {"prefill", "decode"}
    assert 0 <= found[kind] <= st[ALL[kind]]
    # a program is in neither class for a fetch that found its result
    # ready: its own, or the one before a program launched behind it
    left_out = st[ALL[kind]] - device - serial
    assert found[kind] <= left_out <= sum(found.values())
    if not sum(found.values()):
        assert device + serial == st[ALL[kind]]
        assert device_s + serial_s == pytest.approx(st[f"{kind}_wall_s"],
                                                    abs=2e-4)
    assert device_s + serial_s <= st[f"{kind}_wall_s"] + 2e-4
    assert (device_s > 0) == (device > 0) and (serial_s > 0) == (serial > 0)


class _Result:
    """A step program's result as the fetch sees it: ``is_ready()`` says
    what ``ready[kind]`` holds when it is asked, and a fetch that has to
    wait takes ``delay``, 2 ms (a tick's other phases are tenths of
    that)."""
    delay = 0.002

    def __init__(self, array, kind, ready):
        self.array, self.kind, self.ready = array, kind, ready

    def is_ready(self):
        return self.ready[self.kind]

    def __array__(self, dtype=None, copy=None):
        if not self.is_ready():
            time.sleep(self.delay)
        return np.asarray(self.array, dtype)


def _stub_results(eng, ready):
    """Every result the step thread fetches answers ``is_ready()`` from
    ``ready``: {"prefill": bool, "decode": bool}."""
    def program(kind, fn):
        def call(params, rows, cache):
            first, *rest = fn(params, rows, cache)
            return (_Result(first, kind, ready), *rest)
        call._cache_size = fn._cache_size           # stats() reads it
        return call
    eng._jit_prefill = program("prefill", eng._jit_prefill)
    if eng._jit_verify is not None:
        eng._jit_verify = program("decode", eng._jit_verify)
    else:
        eng._jit_decode = program("decode", eng._jit_decode)


def _ahead_walls(spans):
    """kind -> [programs, seconds]: every program whose ``*.dispatch``
    began with the program before it not yet fetched, from that
    program's fetch (its ``*.wait``'s end) to its own."""
    events = sorted((s for s in spans
                     if s[0].endswith((".dispatch", ".wait"))),
                    key=lambda s: s[2])
    out = {"prefill": [0, 0.0], "decode": [0, 0.0]}
    flying, ahead, last_fetch = 0, [], None
    for name, _, t0, t1, _ in events:
        kind, _, what = name[len("engine."):].partition(".")
        if what == "dispatch":
            ahead.append(flying > 0)
            flying += 1
        else:
            flying -= 1
            if ahead.pop(0):
                out[kind][0] += 1
                out[kind][1] += t1 - last_fetch
            last_fetch = t1
    return out


@pytest.mark.parametrize("serial", [False, True], ids=["ahead", "serial"])
def test_a_program_behind_another_books_its_device_time_the_rest_serial(
        serial):
    """Every fetch blocks (stubbed): a program launched ahead is in the
    device class with the fetch-to-fetch wall, every other in the
    serial class, none left out; with nothing launched ahead all of
    them are serial."""
    eng = _engine()
    try:
        eng.warmup()
        _stub_results(eng, {"prefill": False, "decode": False})
        if serial:
            eng._go_ahead = lambda blocked: False
        _serve(eng)
        st = eng.stats()
        ring = _ahead_walls(eng._clock.spans())
    finally:
        eng.shutdown()
    assert st["fetch_found_ready_total"] == {"prefill": 0, "decode": 0}
    ahead = 0
    for kind in ("prefill", "decode"):
        (device, device_s), (serial_n, serial_s) = _classes(st, kind)
        assert device + serial_n == st[ALL[kind]] and serial_n > 0
        assert device_s + serial_s == pytest.approx(
            st[f"{kind}_wall_s"], abs=2e-4)
        assert device == ring[kind][0]
        # fetch to fetch (the ring's stamps are further calls of the
        # same clock), not staging to fetch, which holds the wait of
        # the program before as well: 2 ms more each
        assert device_s == pytest.approx(ring[kind][1],
                                         abs=5e-4 * max(device, 1))
        assert (device > 0) == (not serial)
        ahead += device
    assert ahead == st["programs_ahead_total"]


@pytest.mark.parametrize("kind,other", [("prefill", "decode"),
                                        ("decode", "prefill")])
def test_a_fetch_found_ready_keeps_both_its_sides_out(kind, other):
    """Every fetch of ``kind`` finds its result ready: none of its
    programs is a sample, and none of the ``other`` kind launched
    behind one of them either (that wall does not start at a
    completion); the others launched with nothing out stay serial."""
    eng = _engine()
    try:
        eng.warmup()
        _stub_results(eng, {kind: True, other: False})
        _serve(eng)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["fetch_found_ready_total"] == {kind: st[ALL[kind]], other: 0}
    assert _classes(st, kind) == [[0, 0.0], [0, 0.0]]
    (device, device_s), (serial, serial_s) = _classes(st, other)
    assert (device, device_s) == (0, 0.0)
    assert 0 < serial < st[ALL[other]] and serial_s > 0
    assert st["programs_ahead_total"] > 0
    # the walls the older metrics read hold every program as before
    assert st["prefill_wall_s"] > 0 and st["decode_wall_s"] > 0


def test_speculation_books_no_device_sample():
    eng = _engine(spec_tokens=2)
    try:
        eng.warmup()
        _stub_results(eng, {"prefill": False, "decode": False})
        _serve(eng, n=3)
        st = eng.stats()
    finally:
        eng.shutdown()
    for kind in ("prefill", "decode"):
        (device, device_s), (serial, _) = _classes(st, kind)
        assert (device, device_s) == (0, 0.0)
        assert serial == st[ALL[kind]] > 0


def test_warmup_resets_the_classes_and_the_ready_count(served):
    eng, _, _ = served
    st = eng.stats()
    assert sum(n for kind in ALL for n, _ in _classes(st, kind)) \
        + sum(st["fetch_found_ready_total"].values()) > 0
    eng.warmup()
    st = eng.stats()
    assert st["fetch_found_ready_total"] == {"prefill": 0, "decode": 0}
    for kind in ALL:
        assert _classes(st, kind) == [[0, 0.0], [0, 0.0]]
        assert st[ALL[kind]] == 0


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session over two served requests: the ring's (name,
    tick) pairs and, by name, the engine's annotations in the trace as
    (stats, start, end)."""
    from jax.profiler import ProfileData
    tmp_path = tmp_path_factory.mktemp("profile")
    eng = _engine(enable_trace=False)
    try:
        eng.warmup()
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(eng, n=2)
        finally:
            jax.profiler.stop_trace()
        ring = {(s[0], s[1]) for s in eng._clock.spans()}
        found = sum(eng.stats()["fetch_found_ready_total"].values())
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
                    seen.setdefault(e.name, []).append(
                        (dict(e.stats), e.start_ns,
                         e.start_ns + e.duration_ns))
    return ring, seen, found


def test_a_profiler_trace_holds_the_ticks_annotations(profiled):
    ring, seen, _ = profiled
    seen = {name: [(stats["tick"], a, b) for stats, a, b in spans]
            for name, spans in seen.items()}
    assert {"engine.tick", "engine.decode.wait", "engine.prefill.dispatch",
            "engine.admit"} <= set(seen)
    # the annotation and the ring's entry are one span: joined by tick
    assert {("engine.decode.wait", t) for t, _, _ in
            seen["engine.decode.wait"]} <= ring
    ticks = {t: (a, b) for t, a, b in seen["engine.tick"]}
    for t, a, b in seen["engine.decode.wait"]:
        if t in ticks:                # a tick the session saw whole
            assert ticks[t][0] <= a and b <= ticks[t][1]


def test_a_wait_annotation_says_whether_its_fetch_found_the_result(
        profiled):
    """``ready`` beside ``tick`` on every ``*.wait`` and on no other
    phase: a reader of the trace knows which waits end at a completion;
    as many say 1 as the engine counted."""
    _, seen, found = profiled
    said = 0
    for name, spans in seen.items():
        for stats, _, _ in spans:
            assert ("ready" in stats) == name.endswith(".wait"), name
            if "ready" in stats:
                assert stats["ready"] in (0, 1)
                said += stats["ready"]
    assert seen["engine.decode.wait"] and seen["engine.prefill.wait"]
    assert said == found


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


# ----------------------------------------------------- the books by kind
def _assert_the_kinds_add_up(st):
    """The four identities of ``stats()``: the kinds' counts are the
    ticks, their seconds ``tick_wall_s``, their gaps ``host_gap_s``, a
    kind's histogram its count."""
    assert sum(st["tick_kind_total"].values()) \
        == st["phases"]["engine.tick"][0]
    assert sum(st["tick_kind_s"].values()) == pytest.approx(
        st["tick_wall_s"], rel=1e-9)
    assert sum(st["tick_kind_gap_s"].values()) == pytest.approx(
        st["host_gap_s"], rel=1e-9)
    for kind, n in st["tick_kind_total"].items():
        assert sum(st[f"tick_hist_{kind}"].values()) == n
        assert 0 <= st["tick_kind_wait_s"][kind] <= st["tick_kind_s"][kind]
    assert sum(st["tick_kind_wait_s"].values()) == pytest.approx(
        st["phases"]["engine.prefill.wait"][1]
        + st["phases"]["engine.decode.wait"][1], rel=1e-9)


@pytest.mark.parametrize("spec_tokens,step", [(0, "decode"), (2, "verify")])
def test_a_tick_is_booked_under_what_it_fetched(spec_tokens, step):
    """Counted by hand: a prompt of half a chunk starts decoding in the
    tick that fetched its chunk (part + step); beside it a prompt of
    two chunks and a half gives full + step, full + step, part + step;
    every other tick fetched a step alone."""
    eng = _engine(spec_tokens=spec_tokens, max_new_tokens=40)
    try:
        eng.warmup()
        a = eng.submit(list(range(2, 6)), 36)
        for _ in range(2):                   # it is decoding
            assert isinstance(a.out.get(timeout=60), int)
        b = eng.submit(list(range(10, 30)), 4)      # 8 + 8 + 4 tokens
        assert len(_drain(b)) == 4 and len(_drain(a)) == 34
        st = eng.stats()
        spans = eng._clock.spans()
    finally:
        eng.shutdown()
    kinds = {k: n for k, n in st["tick_kind_total"].items() if n}
    assert kinds.pop("idle", 0) <= 1         # a reap with nothing to run
    assert kinds == {f"full_{step}": 2, f"part_{step}": 2,
                     step: st["decode_steps"] - 4}
    assert kinds[step] > 0 and st["prefill_chunks"] == 4
    _assert_the_kinds_add_up(st)
    # in that order, by the ring: the chunks' fetches fall in four
    # ticks, each with a step's fetch behind it; the long prompt's three
    # follow one another, and a tick with a step alone follows them
    fetched = {}
    for name, tick, *_ in spans:
        if name.endswith(".wait"):
            fetched.setdefault(tick, []).append(name.split(".")[1])
    chunk_ticks = [t for t, f in sorted(fetched.items()) if "prefill" in f]
    assert [fetched[t] for t in chunk_ticks] == [["prefill", "decode"]] * 4
    first = chunk_ticks[1]
    assert chunk_ticks[1:] == [first, first + 1, first + 2]
    assert fetched[first + 3] == ["decode"]


class _Recorder:
    """Stands where the worker's flight recorder does."""

    def __init__(self):
        self.events = []

    def record(self, ev, **data):
        self.events.append((ev, data))

    def maybe_flush(self):
        pass


def test_a_slow_fetch_lands_in_the_slow_ticks_under_its_wait():
    """Every fetch blocks 2 ms (stubbed) and one decode step's, after
    the kind has had its 32 ticks and a median, 0.3 s: that tick is
    slow, its overrun is ``engine.decode.wait``'s, it is kept whole and
    goes once to the flight recorder; the pass-through the benchmark
    reads ``stats()`` through keeps all of it but the list."""
    from benchmarks import serve_cell
    eng = _engine(max_new_tokens=40)
    try:
        eng.warmup()
        eng._recorder = rec = _Recorder()
        _stub_results(eng, {"prefill": False, "decode": False})
        inner, steps = eng._jit_decode, []

        def decode(params, rows, cache):
            first, *rest = inner(params, rows, cache)
            steps.append(first)
            if len(steps) == 45:
                first.delay = 0.3
            return (first, *rest)
        decode._cache_size = inner._cache_size
        eng._jit_decode = decode
        # 39 decode steps, 38 of them in ticks that fetched no chunk
        assert len(_drain(eng.submit(list(range(2, 6)), 40))) == 40
        before = eng.stats()
        assert before["tick_kind_total"]["decode"] == 38
        assert before["slow_ticks"] == [] or max(
            t[3] for t in before["slow_ticks"]) < 0.3
        # its sixth step is the 45th
        assert len(_drain(eng.submit(list(range(12, 16)), 40))) == 40
        st = eng.stats()
    finally:
        eng.shutdown()
    _assert_the_kinds_add_up(st)
    number, kind, began, seconds, held = max(
        st["slow_ticks"], key=lambda t: t[4].get("engine.decode.wait", 0))
    assert seconds >= 0.3
    assert kind == "decode" and began < time.time()
    assert max(held, key=held.get) == "engine.decode.wait"
    assert held["engine.decode.wait"] >= 0.3
    assert set(held) <= TICK_CHILDREN | {"engine.tick"}
    assert st["tick_slow_total"]["decode"] >= 1
    # the overrun: the tick less a median of a few ms
    assert 0.25 < st["tick_slow_s"]["engine.decode.wait"]
    sent = [data for ev, data in rec.events if ev == "ENGINE_TICK_SLOW"]
    assert len(sent) == len(st["slow_ticks"])
    mine, = [d for d in sent if d["tick"] == number]
    assert mine["kind"] == "decode" and mine["dur_s"] >= 0.3
    assert mine["phase"] == "engine.decode.wait"
    assert mine["held_s"]["engine.decode.wait"] >= 0.3
    # differenced as the benchmark differences it (no edit there)
    assert "slow_ticks" not in serve_cell.numerics(st)
    d = serve_cell.counters_delta(st, before)
    assert "slow_ticks" not in d
    assert d["tick_kind_total"]["decode"] == 38
    assert d["tick_kind_total"]["part_decode"] == 1
    assert sum(d["tick_kind_total"].values()) \
        == d["phases"]["engine.tick"]["count"]
    assert sum(d["tick_kind_s"].values()) == pytest.approx(
        d["tick_wall_s"], rel=1e-9)
    assert sum(d["tick_kind_gap_s"].values()) == pytest.approx(
        d["host_gap_s"], rel=1e-6)
    assert sum(d["tick_hist_decode"].values()) == 38
    assert d["tick_slow_s"]["engine.decode.wait"] > 0.25
    assert d["tick_kind_wait_s"]["decode"] >= 0.3 + 37 * 0.002


def test_warmup_zeroes_the_books_by_kind(served):
    eng, _, _ = served
    st = eng.stats()
    assert sum(st["tick_kind_total"].values()) > 10
    eng.warmup()
    st = eng.stats()
    # the warm-up request's own ticks ended before the reset
    assert set(st["tick_kind_total"].values()) <= {0, 1}
    assert sum(st["tick_kind_s"].values()) == pytest.approx(
        st["tick_wall_s"], abs=1e-12)
    assert st["tick_slow_s"] == {} and st["slow_ticks"] == []
    assert "queue_wait_ewma_s" not in st
