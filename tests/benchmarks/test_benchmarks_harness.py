"""The harness end to end, on the CPU, through the same command the
driver runs plus ``--rehearse`` (tiny widths, interpreted kernels): the
shape of the result line, failing without a chip, failing alone in a
directory, and a later PR adding a configuration, a traffic mix and a
per-layer metric as files and entries only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import spec

KEYS = {"correct", "attempted", "failed", "metrics", "device",
        # beside what the driver reads: every process's worst oversleep
        # in the window, and last each number compared with its limit
        "heartbeat_late_s", "compared"}


def _run(*flags, cwd=spec.ROOT, pythonpath=None, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    # niced: a rehearsal is a tree of busy processes, and other tests of
    # the suite, run beside it by other workers, have deadlines
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *flags],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=cwd, preexec_fn=lambda: os.nice(15))


def _last(r):
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_train_rehearsal_prints_the_contract_line():
    line = _last(_run("--workload", "gptj-6b.train_2k", "--seed",
                      str(2**31 + 7), "--seconds", "1", "--trace", "0",
                      "--rehearse"))
    assert set(line) == KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["compared"]) == {"loss", "grad_norm", "loss_rise"}
    assert all(number <= limit for number, limit in line["compared"].values())
    assert set(line["heartbeat_late_s"]) == {"driver"}
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # a rehearsal says what it ran on: never a chip's name
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])


def test_traced_serve_rehearsal_prints_per_layer_metrics_and_a_breakdown():
    line = _last(_run("--workload", "gptj-6b.serve_chat", "--seed", "11",
                      "--seconds", "2", "--trace", "1", "--rehearse"))
    assert set(line) == KEYS | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    names = set(line["metrics"])
    assert {"prefill_chunk_ms.ttft", "decode_step_ms.tpot",
            "gen_late_p99_ms", "compiles_in_window"} <= names
    # since PR 35 the judged TTFT is the median over every request of
    # the window, and the slowest tenth's mean a per-layer view of it (no
    # bound the contract allows holds nine requests; PERF.md section 2)
    assert "ttft_slow10_ms" in names
    # and since PR 39 the judged time per token is the median request's,
    # the 90th percentile a per-layer view of it
    assert "tpot_p90_ms.chat" in names
    assert not names & {"ttft_p50_ms", "tpot_p50_ms", "tpot_p90_ms",
                        "setup_s"}
    # device numbers are not taken from a CPU
    assert not names & {"device_idle_share.tpot", "kv_write_share.tpot",
                        "idle_in_tick_share.tpot",
                        "paged_decode_roofline.tpot", "mfu"}
    # what the engine's own books give is read on any platform
    assert {"tick_ms.tpot", "host_ms_per_tick.tpot", "decode_launch_ms.tpot",
            "programs_ahead_share.tpot", "ttft_queue_ms.ttft"} <= names
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["device"]["platform"] == "cpu"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # an idle gap is named by the engine's phase it falls in, then the frame
    gaps = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert gaps and all(": " in name for name in gaps)
    assert any(name.startswith("engine.") for name in gaps)


def test_without_a_chip_it_fails_and_prints_no_result():
    r = _run("--workload", "gptj-6b.train_2k", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert r.returncode != 0
    assert "no accelerator" in r.stderr
    assert '"correct"' not in r.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run("--workload", "gptj-6b.train_2k", "--seed", "1", "--seconds",
             "1", "--trace", "0", "--rehearse", cwd=str(tmp_path),
             pythonpath="")
    assert r.returncode != 0
    assert "ray_tpu" in r.stderr
    assert '"correct"' not in r.stdout


def test_a_later_pr_adds_a_cell_and_a_metric_without_editing_a_file(tmp_path):
    """In a copy: a new configuration file, a new traffic file, a new
    metric file with a reader of its own, and entries in BENCHMARK.json.
    No file that was there is touched."""
    root = tmp_path
    shutil.copytree(os.path.join(spec.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    cfg = json.load(open(root / "benchmarks/configs/gptj-6b.json"))
    cfg["source"] = "https://example.org/another-model/config.json"
    cfg["rehearse"]["d_ff"] = 384
    (root / "benchmarks/configs/another.json").write_text(json.dumps(cfg))
    mix = json.load(open(root / "benchmarks/traffic/train_2k.json"))
    mix["rehearse"].update(batch=4, seq=64, check_seq=64)
    (root / "benchmarks/traffic/train_short.json").write_text(
        json.dumps(mix))
    (root / "benchmarks/metrics/slowest_step_ms.json").write_text(json.dumps(
        {"reader": "slowest_step", "args": {}, "what": "the worst step"}))
    (root / "benchmarks/readers/slowest_step.py").write_text(
        "from benchmarks import stats\n\n\n"
        "def read(obs):\n"
        "    tr = obs.get('train')\n"
        "    return 1e3 * max(stats.step_times(tr['step_ends'])) "
        "if tr else None\n")
    bench = spec.benchmark()
    bench["configs"].append({
        "name": "another", "source": cfg["source"],
        "file": "benchmarks/configs/another.json", "reduced": ["n_layer"],
        "why": "a test's configuration"})
    bench["workloads"].append({
        "name": "another.train_short", "config": "another",
        "traffic": "train_short", "chips": 1, "why": "a test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tok_s", "step_ms"):
            m["workloads"].append("another.train_short")
    bench["per_layer"].append({
        "name": "slowest_step_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tok_s", "workloads": ["another.train_short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = _last(_run("--workload", "another.train_short", "--seed", "5",
                      "--seconds", "1", "--trace", "1", "--rehearse",
                      cwd=str(root), pythonpath=spec.ROOT))
    assert line["correct"] is True
    assert {"slowest_step_ms", "step_ms"} <= set(line["metrics"])
    assert line["metrics"]["slowest_step_ms"]["value"] \
        >= line["metrics"]["step_ms"]["value"]
    after = {p: p.read_bytes() for p in before}
    assert after == before
