"""The SPMD train step's own clock and names: train.step with dispatch,
wait and tail inside it, StepTraceAnnotations in a profiler trace, and
the named scopes on the compiled step's ops."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig
from ray_tpu.parallel.plan import ParallelPlan
from ray_tpu.util import tracing

KW = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2, head_dim=8,
          d_ff=32, max_seq_len=32, rotary_dim=8, dtype=jnp.float32,
          remat_policy="dots")


def _program(**plan):
    cfg = TransformerConfig(**KW)
    p = ParallelPlan(**plan)
    return p.build(cfg, learning_rate=1e-3, seed=0,
                   devices=jax.devices()[:p.world_size],
                   telemetry_interval_s=0)


def _batch(seed=0):
    ids = np.random.default_rng(seed).integers(2, 64, (4, 32))
    return {"input_ids": ids.astype(np.int32)}


def test_a_step_is_dispatch_wait_tail_inside_train_step():
    prog = _program()
    for i in range(4):
        prog.step(_batch(i))
    clock = tracing.clocks()["train"]
    assert clock is prog.clock
    totals = clock.totals()
    assert {n: c for n, (c, _) in totals.items()} == {
        "train.step": 4, "train.dispatch": 4, "train.wait": 4,
        "train.tail": 4}
    by_tick = {}
    for name, tick, t0, t1, parent in clock.spans():
        by_tick.setdefault(tick, {})[name] = (t0, t1, parent)
    assert sorted(by_tick) == [1, 2, 3, 4]
    for spans in by_tick.values():
        lo, hi, parent = spans["train.step"]
        assert parent is None
        last = lo
        for name in ("train.dispatch", "train.wait", "train.tail"):
            t0, t1, up = spans[name]
            assert up == "train.step" and last <= t0 <= t1 <= hi
            last = t1
    children = sum(s for n, (_, s) in totals.items() if n != "train.step")
    assert 0.9 * totals["train.step"][1] <= children \
        <= totals["train.step"][1]
    assert 0 < clock.gap_s <= totals["train.step"][1]


def test_a_profiler_trace_holds_train_step_with_its_step_number(tmp_path):
    from jax.profiler import ProfileData
    prog = _program()
    prog.step(_batch())                     # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            prog.step(_batch(i))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    steps, waits = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "train.step":
                    steps.append(dict(e.stats)["step_num"])
                elif e.name == "train.wait":
                    waits.append(dict(e.stats)["tick"])
    assert steps == [2, 3, 4] and waits == [2, 3, 4]
    assert {("train.step", n) for n in steps} \
        <= {(s[0], s[1]) for s in prog.clock.spans()}


@pytest.mark.parametrize(
    "plan", [{}, {"fsdp": 2, "shard_weight_update": True,
                  "grad_transport": "int8"}],
    ids=["one-device", "fsdp2-flat-int8"])
def test_the_compiled_steps_ops_carry_the_scopes(plan):
    prog = _program(**plan)
    batch = {"input_ids": jnp.zeros((4, 32), jnp.int32),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    text = prog.bundle.step_fn.lower(prog.state, batch).as_text(
        debug_info=True)
    for scope in ("embed", "layer/", "attn", "mlp", "final_norm",
                  "lm_head_loss", "optimizer"):
        assert scope in text, scope
    # forward and backward of the head both: the loss is a custom_vjp
    assert "transpose" in text and text.count("lm_head_loss") >= 2
    assert ("grad_transport" in text) == bool(plan)
    assert "jit(step_raw)" in text


def test_the_steps_books_hold_a_compiling_step_apart():
    """The train clock's books by kind: a step is ``step``, one that
    compiled its program ``compile`` (the first; a new batch shape is
    another), so the histogram a percentile is read from has no
    compile for its tail; the kinds add up to ``train.step``'s totals."""
    prog = _program()
    for i in range(5):
        prog.step(_batch(i))
    clock = prog.clock
    b = clock.books()
    assert b["tick_kind_total"] == {"compile": 1, "step": 4}
    assert sum(b["tick_kind_s"].values()) == pytest.approx(
        clock.seconds("train.step"), rel=1e-9)
    assert sum(b["tick_kind_gap_s"].values()) == pytest.approx(
        clock.gap_s, rel=1e-9)
    assert sum(b["tick_kind_wait_s"].values()) == pytest.approx(
        clock.seconds("train.wait"), rel=1e-9)
    assert sum(b["tick_hist_step"].values()) == 4
    assert b["tick_kind_s"]["compile"] > b["tick_kind_s"]["step"] / 4
    assert 0 < clock.quantile("step", 0.99) < b["tick_kind_s"]["compile"]
    ids = np.random.default_rng(9).integers(2, 64, (8, 32))
    prog.step({"input_ids": ids.astype(np.int32)})  # a shape not seen yet
    assert clock.books()["tick_kind_total"] == {"compile": 2, "step": 4}
