"""``step_host_share``: the reader's arithmetic on a clock it is handed,
nothing (and no error) where the program keeps no clock, and the metric
on the line of a traced training rehearsal."""
import json
import os
import subprocess
import sys

import manifest_by_name
from benchmarks import spec
from benchmarks.readers import program_span


class _Clock:
    def __init__(self, spans):
        self._spans = spans

    def spans(self):
        return self._spans


def _patch(monkeypatch, found):
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "clocks", lambda: found)


ARGS = dict(owner="train", parent="train.step", child="train.wait")


def test_the_median_share_of_a_step_not_spent_waiting(monkeypatch):
    spans = []
    # (step, wait) seconds: shares 50% (the compiling step), 2, 4, 6%
    for tick, (step, wait) in enumerate(
            [(10.0, 5.0), (1.0, 0.98), (1.0, 0.96), (1.0, 0.94)], 1):
        t = 100.0 * tick
        spans += [("train.dispatch", tick, t, t + 0.01, "train.step"),
                  ("train.wait", tick, t + 0.01, t + 0.01 + wait,
                   "train.step"),
                  ("train.step", tick, t, t + step, None)]
    # a wait of some other loop, and a step the ring lost the wait of
    spans += [("train.wait", 9, 0.0, 1.0, "other.step"),
              ("train.step", 5, 900.0, 901.0, None)]
    _patch(monkeypatch, {"train": _Clock(spans)})
    # shares 50, 2, 4, 6, 100 -> the median is 6
    assert abs(program_span.read({}, **ARGS) - 6.0) < 1e-9


def test_nothing_to_read_is_none_and_never_raises(monkeypatch):
    _patch(monkeypatch, {})
    assert program_span.read({}, **ARGS) is None
    _patch(monkeypatch, {"train": _Clock([])})
    assert program_span.read({}, **ARGS) is None
    # a program from before the phase clock has no clocks() at all
    from ray_tpu.util import tracing
    monkeypatch.delattr(tracing, "clocks")
    assert program_span.read({}, **ARGS) is None


def test_the_manifest_declares_it_for_the_training_cells():
    # found by name, its fields as written; the two training cells are
    # among its cells, and a third may join them
    entry, cells = manifest_by_name.metric("step_host_share")
    assert entry == {
        "name": "step_host_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "train step",
        "moves": "train_tok_s"}
    assert {"gptj-6b.train_2k", "mistral-7b-v0.3.train_fsdp4_4k"} \
        <= set(cells)
    read, args = spec.metric_reader("step_host_share")
    assert read is program_span.read and args == ARGS


def test_a_traced_training_rehearsal_prints_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "gptj-6b.train_2k", "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=spec.ROOT, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    share = line["metrics"]["step_host_share"]
    assert share["unit"] == "%" and 0.0 < share["value"] < 100.0
    # the outside clock of the same layer stays beside it
    assert "step_ms" in line["metrics"]
