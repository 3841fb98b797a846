"""The trace reduction on a small trace recorded on a v5e (PR 24), on
the same trace with what the program writes into one added by hand
(annotations with their tick, a second thread, scope paths: PR 39), on a
small v5e trace file that holds them as recorded, and on hand-made
intervals."""
import copy
import json
import os

import pytest

from benchmarks import roofline, trace as T
from benchmarks.readers import device_trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "v5e_trace.json")) as f:
        return json.load(f)


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.total(T.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert T.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert T.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert T.clip([(0, 5), (7, 9)], 4, 8) == [(4, 5), (7, 8)]


def test_op_names_are_read_from_the_hlo_text():
    name = ("%flash_fwd.1 = (bf16[1,16,2048,256]{3,2,1,0:T(8,128)(2,1)S(1)}, "
            "f32[1,16,1,2048]{3,2,1,0:T(1,128)}) custom-call(bf16[1,16")
    assert T.op_id(name) == "flash_fwd.1"
    assert T.op_kind(name) == "flash_fwd"
    assert T.result_shape(name) == ("bf16", (1, 16, 2048, 256))
    assert T.short_name(name) == "flash_fwd.1_bf16_1_16_2048_256_"
    assert T.op_kind("%copy.148 = bf16[6,1921,16,16,256]{4,3} copy(") == "copy"
    assert T.is_collective("%all-gather-start.3 = (bf16[8,1024]")
    assert T.is_collective("%reduce-scatter.7 = f32[1024]{0} reduce-scatter(")
    assert not T.is_collective("%fusion.12 = bf16[4,4]{1,0} fusion(")


def test_recorded_trace_reduces_to_busy_idle_and_kernels(recorded):
    s = T.summarize(recorded)
    assert s["chips"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    gaps = sum(s["idle_gaps"].values())
    assert gaps + s["busy_s"] == pytest.approx(s["window_s"], rel=1e-6)
    assert s["exposed_collective_s"] == 0.0          # one chip: none
    kinds = {k.split("|")[1] for k in s["by_module_kind"]}
    assert {"flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq",
            "paged_attention"} <= kinds
    assert s["by_module_kind"]["jit_fwd|flash_fwd"] > 0
    top = s["breakdown"]["device_ops"]
    assert len(top) <= 10 and top[0][0].startswith("paged_attention")
    assert top == sorted(top, key=lambda kv: -kv[1])
    assert len(s["breakdown"]["idle_gaps"]) <= 10


def test_flash_roofline_from_the_recorded_trace_is_a_share(recorded):
    s = T.summarize(recorded)
    obs = {"trace": s, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = device_trace.read(obs, "flash_roofline")
    # 2048 x 2048 causal at head_dim 256: compute bound, well under peak
    assert 10.0 < share < 100.0
    assert 0.0 < device_trace.read(obs, "flash_share") < 100.0
    assert device_trace.read(obs, "idle_share") == pytest.approx(
        100.0 * (1 - s["busy_s"] / s["window_s"]))
    # nothing to read without a trace, or off the chip
    assert device_trace.read({"trace": None, "device": obs["device"]},
                             "idle_share") is None
    assert device_trace.read({"trace": s, "device": {"platform": "cpu"}},
                             "idle_share") is None


def test_exposed_collective_time_is_what_no_compute_covers():
    chip = {"ops": [["%fusion.1 = f32[8]{0} fusion(", 0.0, 4.0],
                    ["%fusion.2 = f32[8]{0} fusion(", 8.0, 2.0]],
            "async": [["%all-gather-start.1 = (f32[8]{0}) all-gather-start(",
                       3.0, 4.0]],
            "modules": [["jit_step(1)", 0.0, 10.0]]}
    tr = {"chips": {"/device:TPU:0": chip, "/device:TPU:1": chip},
          "host": [["t", T.OPEN_MARK, -1e-6, 1e-6],
                   ["t", T.CLOSE_MARK, 10.0 - 1e-6, 1e-6],
                   ["t", "$engine.py:1 step", 7.0, 1.0]]}
    s = T.summarize(tr)
    assert s["chips"] == 2 and s["window_s"] == pytest.approx(10.0)
    assert s["exposed_collective_s"] == pytest.approx(3.0)   # 4..7
    assert s["busy_s"] == pytest.approx(9.0)                 # idle 7..8
    # no annotation anywhere: the gap lies outside any tick, under the
    # frame the thread was in
    assert s["idle_gaps"] == {
        "(outside a tick): $engine.py:1 step": pytest.approx(1.0)}
    assert s["idle_by_phase"] == {T.OUTSIDE: pytest.approx(1.0)}
    assert s["idle_outside_tick_s"] == pytest.approx(1.0)
    assert s["by_scope"] == {T.NO_SCOPE: pytest.approx(6.0)}


def test_counting_rules_and_peaks():
    flops, nbytes = roofline.flash_call("flash_fwd", (1, 16, 2048, 256))
    assert flops == 2 * 16 * 2048 * 2048 * 256
    assert nbytes == 4 * 16 * 2048 * 256 * 2
    assert roofline.min_seconds(197e12, 0, "TPU v5 lite") == pytest.approx(1.0)
    assert roofline.min_seconds(0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")                  # unknown kind: an error
    m = {"kv_block_size": 16, "head_dim": 256, "kv_heads": 16,
         "n_heads": 16, "itemsize": 2}
    flops, nbytes = roofline.paged_decode(10, m)
    assert nbytes == 10 * 2 * 16 * 16 * 256 * 2
    assert flops == 10 * 16 * 4 * 16 * 256
    # a fully cached prompt needs no prefill work
    f0, b0 = roofline.paged_prefill(512, 512, 256, m)
    assert f0 == 0 and b0 == 0
    f1, _ = roofline.paged_prefill(512, 0, 256, m)
    assert f1 == 4.0 * 16 * 256 * (512 * 513 // 2)
    per_token = roofline.train_flops_per_token(10, 2, 4, 8, 16)
    assert per_token == 6 * 10 + 6 * 2 * 4 * 8 * 16


# ------------------------------------ what the program writes into a trace
#: the step thread's line (a Python thread's line is named after the
#: process; ``load`` numbers a name it meets again) and another thread's
STEP, OTHER = "python3#2", "python3#3"
#: (name, start, end, tick) on the step thread: a tick around the big
#: gap of the recorded trace (0.046976-0.090285, then 0.090286-0.101011)
#: and a second around the next iteration; 0.0915-0.1000 and everything
#: after 0.1070 lie outside any tick
ANNOTATIONS = [
    ("engine.tick", 0.0465, 0.0915, 7),
    ("engine.decode.dispatch", 0.0466, 0.0480, 7),
    ("engine.decode.wait", 0.0480, 0.0600, 7),
    ("engine.decode.emit", 0.0600, 0.0700, 7),
    ("engine.report", 0.0700, 0.0915, 7),
    ("engine.tick", 0.1000, 0.1070, 8),
    ("engine.decode.dispatch", 0.1002, 0.1012, 8),
    ("engine.decode.wait", 0.1012, 0.1065, 8)]
FRAMES = [(STEP, "$poll.py:80 poll", 0.0481, 0.0599),
          (STEP, "$socket.py:623 send", 0.0705, 0.0910),
          (STEP, "$llm_engine.py:1 _run", 0.0400, 0.1300),
          # shorter than anything of the step thread, all along
          (OTHER, "$other.py:1 spin", 0.0400, 0.1300),
          (OTHER, "$other.py:2 turn", 0.0800, 0.0803),
          (OTHER, "PjitFunction(f)", 0.0500, 0.0501)]
SCOPES = {"paged_attention": "jit(_decode_fn)/while/body/closed_call/layer/"
                             "attn/paged_attn/pallas_call:",
          "flash_fwd": "jit(fwd)/jvp()/while/body/closed_call/layer/attn/"
                       "pallas_call:",
          "flash_bwd_dkdv": "jit(fwd)/transpose(jvp())/while/body/"
                            "closed_call/layer/layer/checkpoint/attn/"
                            "pallas_call:",
          "copy_bitcast_fusion": "jit(fwd)/layer/mlp/bse,ef->bsf/"
                                 "dot_general:"}


@pytest.fixture()
def extended(recorded):
    tr = copy.deepcopy(recorded)
    for name, a, b, tick in ANNOTATIONS:
        tr["host"].append([STEP, name, a, b - a, tick])
    for line, name, a, b in FRAMES:
        tr["host"].append([line, name, a, b - a])
    for chip in tr["chips"].values():
        for op in chip["ops"]:
            op.append(SCOPES.get(T.op_kind(op[0]), ""))
    return tr


def test_scope_paths_keep_the_named_scopes_alone():
    assert T.scope_path(
        "jit(_decode_fn)/while/body/closed_call/layer/attn/kv_write/"
        "jit(floor_divide)/rem:") == "layer/attn/kv_write"
    assert T.scope_path(
        "jit(step_raw)/transpose(jvp())/while/body/closed_call/layer/layer/"
        "checkpoint/rematted_computation/attn/bse,ehd->bshd/dot_general:"
    ) == "layer/attn"
    assert T.scope_path(
        "jit(step_raw)/jvp(lm_head_loss)/lm_head_loss/while/body/"
        "closed_call/jit(take_along_axis)/gather") == "lm_head_loss"
    assert T.scope_path(
        "jit(step_raw)/transpose(jvp(embed))/jit(_take)/scatter-add:"
    ) == "embed"
    assert T.scope_path("jit(_copy_fn)/kv_copy/dynamic_update_slice:") \
        == "kv_copy"
    assert T.scope_path("jit(f)/cond/branch_1_fun/mlp/moe/ragged_dot:") \
        == "mlp/moe"
    # a fusion of several ops carries their paths joined: the first's
    assert T.scope_path(
        "jit(_decode_fn)/while/body/closed_call/layer/attn/paged_attn/"
        "reshape;layer/attn/paged_attn/transpose;layer/attn/paged_attn/"
        "reshape:") == "layer/attn/paged_attn"
    # what the compiler names after the layer scan itself has no scope
    assert T.scope_path("jit(_decode_fn)/while:") == ""
    assert T.scope_path("jit(_decode_fn)/while/body/dynamic_slice:") == ""
    assert T.scope_path("jit(f)/scatter:") == "" and T.scope_path("") == ""


def test_nested_annotations_flatten_to_their_innermost():
    assert T._innermost([(0, 10, "tick"), (1, 3, "a"), (3, 4, "b"),
                         (2, 2.5, "a.x"), (12, 13, "tick")]) == [
        (0, 1, "tick"), (1, 2, "a"), (2, 2.5, "a.x"), (2.5, 3, "a"),
        (3, 4, "b"), (4, 10, "tick"), (12, 13, "tick")]
    assert T._innermost([]) == []


def test_the_device_numbers_are_what_they_were(recorded, extended):
    """Busy seconds, the op table and the (program, kind) table as the
    benchmark read them before it kept scopes and ticks (the busy
    seconds pinned from the parent's code on this file)."""
    old, new = T.summarize(recorded), T.summarize(extended)
    assert old["busy_s"] == new["busy_s"] == 0.015760484999999935
    for key in ("window_s", "chips", "exposed_collective_s",
                "by_module_kind"):
        assert old[key] == new[key]
    assert set(old["op_calls"]) == set(new["op_calls"])
    for key, rec in old["op_calls"].items():
        for field in ("kind", "module", "name", "calls", "seconds"):
            assert new["op_calls"][key][field] == rec[field]
    assert set(new["breakdown"]) == {"device_ops", "idle_gaps"}


def test_idle_seconds_go_to_the_innermost_annotation_then_the_frame(extended):
    s = T.summarize(extended)
    assert s["driver_line"] == STEP
    assert s["host_lines"] == {STEP: [len(ANNOTATIONS) + 3,
                                      len(ANNOTATIONS), 0],
                               OTHER: [3, 0, 1]}
    by_phase = s["idle_by_phase"]
    assert sum(by_phase.values()) + s["busy_s"] == pytest.approx(
        s["window_s"], rel=1e-9)
    near = lambda x: pytest.approx(x, abs=4e-5)   # the gaps under 20 us
    # the big gap is cut along the tick's phases, not given to its middle
    assert by_phase["engine.decode.dispatch"] == near(
        (0.0480 - 0.046976) + (0.101011 - 0.1002))
    assert by_phase["engine.decode.wait"] == near(
        (0.0600 - 0.0480) + (0.1065 - 0.106278))
    assert by_phase["engine.decode.emit"] == near(0.0700 - 0.0600)
    assert by_phase["engine.report"] == near(
        (0.090285 - 0.0700) + (0.0915 - 0.090286))
    # inside a tick and under none of its phases: the tick's own
    assert by_phase["engine.tick"] == near(
        (0.1002 - 0.1000) + (0.1070 - 0.1065))
    lo, hi = T.window_of(extended)
    outside = (0.041710744 - lo) + (0.1000 - 0.0915) \
        + (0.117295092 - 0.1070) + (hi - 0.122561447)
    assert s["idle_outside_tick_s"] == by_phase[T.OUTSIDE] == near(outside)
    # second level: each part whole to the frame over its middle, the
    # step thread's own, never the other thread's shorter one
    frames = s["idle_by_phase_frame"]
    assert frames["engine.report"] == {
        "$socket.py:623 send": near(by_phase["engine.report"])}
    assert frames["engine.decode.wait"] == {
        "$poll.py:80 poll": near(0.0120),
        "$llm_engine.py:1 _run": near(0.1065 - 0.106278),
        T.SHORT: pytest.approx(0.0, abs=4e-5)}
    assert frames["engine.decode.emit"] == {
        "$llm_engine.py:1 _run": near(0.0100)}
    assert frames[T.OUTSIDE]["$llm_engine.py:1 _run"] == near(outside)
    flat = s["idle_gaps"]
    assert not [k for k in flat if "other.py" in k or "PjitFunction" in k]
    assert sum(flat.values()) == pytest.approx(sum(by_phase.values()))
    top = s["breakdown"]["idle_gaps"]
    assert top[0][0] == "engine.report: $socket.py:623 send"
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    # with the thread that launches picked by a name two threads share,
    # the other thread's frames named gaps: the lines are told apart
    merged = copy.deepcopy(extended)
    for e in merged["host"]:
        if e[0] in (STEP, OTHER):
            e[0] = "python3"
    assert "engine.report: $other.py:2 turn" in T.summarize(merged)[
        "idle_gaps"]


def test_busy_seconds_by_scope_sum_their_children(extended):
    s = T.summarize(extended)
    by_scope = s["by_scope"]
    assert set(by_scope) == {"layer", "layer/attn", "layer/attn/paged_attn",
                             "layer/mlp", T.NO_SCOPE}
    assert by_scope["layer"] == pytest.approx(
        by_scope["layer/attn"] + by_scope["layer/mlp"])
    paged = sum(r["seconds"] for r in s["op_calls"].values()
                if r["kind"] == "paged_attention")
    assert by_scope["layer/attn/paged_attn"] == pytest.approx(paged)
    flash = sum(r["seconds"] for r in s["op_calls"].values()
                if r["kind"] in ("flash_fwd", "flash_bwd_dkdv"))
    assert by_scope["layer/attn"] == pytest.approx(paged + flash)
    every = sum(r["seconds"] for r in s["op_calls"].values())
    assert by_scope["layer"] + by_scope[T.NO_SCOPE] == pytest.approx(every)
    top = s["breakdown"]["device_ops"]
    assert top[0][0] == "layer/attn/paged_attn: " \
                        "paged_attention.1_bf16_64_16_16_256_"
    assert [k for k, _ in top if ": " not in k]        # and ops without
    obs = {"trace": s, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    assert device_trace.read(obs, "scope_share", scopes=["layer/attn"]) \
        == pytest.approx(100.0 * (paged + flash) / s["busy_s"])
    assert device_trace.read(obs, "scope_share",
                             scopes=["lm_head_loss"]) is None
    in_tick = device_trace.read(obs, "idle_in_tick_share")
    assert in_tick == pytest.approx(
        100.0 * (1 - s["idle_outside_tick_s"]
                 / sum(s["idle_by_phase"].values())))
    assert 50.0 < in_tick < 100.0


def test_load_keeps_scope_paths_and_ticks_from_the_file():
    """A file made by hand with the protobuf classes (PR 39): one device
    plane whose metadata table holds ``tf_op`` as a string, as a
    reference into the stat names and not at all, the same HLO text in
    two programs (one id past 2**63), and two Python threads' lines
    under the one name the profiler gives them."""
    path = os.path.join(HERE, "data", "made_up_scopes.xplane.pb")
    table = T.op_scopes(path)["/device:TPU:0"]
    fusion = "%fusion.1 = bf16[8]{0} fusion("
    assert table == {
        (77, fusion): "jit(f)/layer/attn/dot_general:",
        (77, "%fusion.2 = bf16[8]{0} fusion("):
            "jit(f)/layer/mlp/dot_general:",
        (12345678901234567890, fusion): "jit(g)/lm_head_loss/dot_general:"}
    tr = T.load(path)
    ops = tr["chips"]["/device:TPU:0"]["ops"]
    assert [(op[0].split(" ")[0], op[3]) for op in ops] == [
        ("%fusion.1", "jit(f)/layer/attn/dot_general:"),
        ("%fusion.2", "jit(f)/layer/mlp/dot_general:"),
        ("%copy.1", ""),
        ("%fusion.1", "jit(g)/lm_head_loss/dot_general:")]
    assert tr["host"] == [
        ["python3", "engine.tick", pytest.approx(1e-6), pytest.approx(6e-6),
         41],
        ["python3#2", "$x.py:1 f", pytest.approx(1e-6), pytest.approx(6e-6)]]
    s = T.summarize(tr)
    assert s["driver_line"] == "python3"
    assert s["by_scope"] == {
        "layer": pytest.approx(2e-6), "layer/attn": pytest.approx(1e-6),
        "layer/mlp": pytest.approx(1e-6), "lm_head_loss": pytest.approx(1e-6),
        T.NO_SCOPE: pytest.approx(5e-7)}
    assert s["idle_by_phase"] == {"engine.tick": pytest.approx(2.5e-6)}
    assert s["idle_outside_tick_s"] == 0.0


def test_a_recorded_v5e_file_gives_up_its_scopes_and_ticks():
    """Recorded on a v5e by PR 39's probe (jax 0.9.0): three ticks of
    two programs under ``engine.*`` annotations carrying their tick, the
    ops under ``layer/attn``, ``layer/attn/kv_write``, ``layer/mlp``
    (forward, and through ``jax.grad`` of a scan) and ``lm_head_loss``,
    2 ms of sleep after each tick."""
    tr = T.load(os.path.join(HERE, "data", "v5e_scopes.xplane.pb"))
    marked = [(e[1], e[4]) for e in tr["host"] if len(e) > 4]
    assert marked == [(name, tick) for tick in range(3) for name in (
        "engine.tick", "engine.decode.dispatch", "engine.decode.wait",
        "engine.prefill.dispatch", "engine.prefill.wait")]
    ops = tr["chips"]["/device:TPU:0"]["ops"]
    assert all(len(op) == 4 for op in ops)
    found = {(T.op_kind(op[0]), T.scope_path(op[3])) for op in ops}
    assert {("dynamic-update-slice", "layer/attn/kv_write"),
            ("fusion", "layer/attn"), ("fusion", "layer/mlp"),
            ("multiply_convert_fusion", "lm_head_loss"),
            ("copy-start", ""), ("while", "")} <= found
    # the backward pass's ops come back under the forward's scope
    assert any("transpose(jvp())" in op[3]
               and T.scope_path(op[3]) == "layer/mlp" for op in ops)
    s = T.summarize(tr)
    assert s["driver_line"] == "python3"
    assert s["host_lines"] == {"python3": [53, 15, 12]}
    assert set(s["by_scope"]) == {"layer", "layer/attn", "layer/attn/kv_write",
                                  "layer/mlp", "lm_head_loss", T.NO_SCOPE}
    assert s["by_scope"]["layer"] == pytest.approx(
        s["by_scope"]["layer/attn"] + s["by_scope"]["layer/mlp"])
    by_phase = s["idle_by_phase"]
    assert set(by_phase) == {
        T.OUTSIDE, "engine.tick", "engine.decode.dispatch",
        "engine.decode.wait", "engine.prefill.dispatch",
        "engine.prefill.wait"}
    assert sum(by_phase.values()) + s["busy_s"] == pytest.approx(
        s["window_s"])
    # three sleeps of 2 ms and the profiler's own start and stop
    assert 0.006 < s["idle_outside_tick_s"] < 0.010
    assert s["breakdown"]["device_ops"][0][0].startswith("layer/mlp: fusion")
    assert s["breakdown"]["idle_gaps"][0][0] == "(outside a tick): $time sleep"
