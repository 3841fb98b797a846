"""A closed-loop cell measures the same thing however fast the engine
under it is, and a cell that fails leaves what it knew: rehearsals on the
CPU through the command the driver runs (``--rehearse``: tiny widths,
interpreted kernels). A client whose replay does end inside what is
measured makes the run incorrect and says when; the clients have stopped
before the trace is reduced; a replica that dies leaves its logs."""
import json
import os
import shutil
import subprocess
import sys

from benchmarks import spec

DOCQA = "mistral-7b-v0.3.serve_docqa"


def _run(*flags, cwd=spec.ROOT, pythonpath=None, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *flags],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=cwd, preexec_fn=lambda: os.nice(15))


def _notes(r):
    line = [l for l in r.stderr.splitlines() if "[bench] notes: " in l][-1]
    return json.loads(line.split("notes: ", 1)[1])


def _copy_with(root, *, traffic=None, config=None):
    """A copy of the benchmark with one more docqa cell beside the
    accepted ones, on a traffic mix or a configuration of its own: what
    a later PR adds, files and entries only."""
    shutil.copytree(os.path.join(spec.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".bench_tmp"))
    bench = spec.benchmark()
    cell = dict(next(w for w in bench["workloads"] if w["name"] == DOCQA),
                name="a-test.serve_docqa", why="a test's cell")
    if traffic is not None:
        mix = json.load(open(root / "benchmarks/traffic/serve_docqa.json"))
        traffic(mix)
        (root / "benchmarks/traffic/serve_docqa_test.json").write_text(
            json.dumps(mix))
        cell["traffic"] = "serve_docqa_test"
    if config is not None:
        cfg = json.load(open(root / "benchmarks/configs/mistral-7b-v0.3.json"))
        cfg["source"] = "https://example.org/a-test/config.json"
        config(cfg)
        (root / "benchmarks/configs/a-test.json").write_text(json.dumps(cfg))
        bench["configs"].append({
            "name": "a-test", "source": cfg["source"],
            "file": "benchmarks/configs/a-test.json",
            "reduced": ["num_hidden_layers"], "why": "a test's configuration"})
        cell["config"] = "a-test"
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if DOCQA in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell["name"]


def test_a_client_that_runs_out_while_measured_fails_the_run_and_says_when(
        tmp_path):
    def two_documents(mix):
        mix["rehearse"]["docs_per_client"] = 2
    cell = _copy_with(tmp_path, traffic=two_documents)
    r = _run("--workload", cell, "--seed", str(2**31 + 5), "--seconds", "2",
             "--trace", "0", "--rehearse", cwd=str(tmp_path),
             pythonpath=spec.ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    # every other number compared is sound: this alone made it incorrect
    compared = line["compared"]
    assert list(line)[-1] == "compared"
    assert compared.pop("clients_ran_out") == [2, 0]
    assert all(number <= limit for number, limit in compared.values())
    assert "ran out of requests at" in r.stderr
    replay = _notes(r)["replay"]
    # two documents of two questions, less the stagger: 4 and 3 requests
    assert replay["asked_max"] == 4
    assert sorted(c for c, _ in replay["ran_out"]) == [0, 1]
    assert all(at < 2.0 for _, at in replay["ran_out"])       # the second
    assert r.stderr.strip().splitlines()[-1].startswith(
        "[bench] correct False; compared, [number, limit]: ")


def test_a_traced_run_stops_its_clients_before_the_trace_is_reduced():
    r = _run("--workload", DOCQA, "--seed", str(2**31 + 6), "--seconds", "2",
             "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert "decode_step_ms.tok" in line["metrics"]
    notes = _notes(r)
    at = notes["at"]
    # the window, the traced second, then: nothing new is sent, the run
    # listens on, the clients are gone, and only then the reduction
    assert 2.0 <= at["window_end"] < at["window_end"] + 1.0 <= at["closing"]
    assert at["closing"] <= at["listened"] <= at["reduce_from"] \
        < at["reduce_to"]
    # the replay has no end, and nobody reached one
    assert notes["replay"]["ran_out"] == []
    assert notes["replay"]["asked_max"] > 8
    assert line["compared"]["clients_ran_out"] == [0, 0]
    # every process's worst oversleep in the window, [seconds late, at]
    beats = line["heartbeat_late_s"]
    assert set(beats) == {"driver", "replica"}
    assert all(len(b) == 2 and 0.0 <= b[0] < 2.0 for b in beats.values())


def test_a_cell_that_fails_leaves_its_workers_logs_and_says_where(tmp_path):
    def dies_in_its_constructor(cfg):
        cfg["rehearse"]["no_such_width"] = 1
    cell = _copy_with(tmp_path, config=dies_in_its_constructor)
    r = _run("--workload", cell, "--seed", "3", "--seconds", "1", "--trace",
             "0", "--rehearse", cwd=str(tmp_path), pythonpath=spec.ROOT)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    last = r.stderr.strip().splitlines()[-1]
    assert "ended with code" in last and " are kept under " in last
    kept = last.rsplit(" are kept under ", 1)[1]
    assert kept.startswith(str(tmp_path / ".bench_tmp" / "failed"))
    assert cell in kept and "seed3" in kept
    tail = open(os.path.join(kept, "stderr_tail.txt")).read()
    assert "no_such_width" in tail            # the driver's side of it
    assert json.load(open(os.path.join(kept, "exit.json")))["code"] \
        == r.returncode
    logs = os.listdir(os.path.join(kept, "logs"))
    assert any(name.startswith("worker-") for name in logs)
    said = "".join(open(os.path.join(kept, "logs", name)).read()
                   for name in logs)
    assert "no_such_width" in said            # the replica's own words
    # and the end of each log is in what the run printed
    assert "benchmarks: the end of worker-" in r.stderr
    # the session it ran in is gone
    pid = json.load(open(os.path.join(kept, "exit.json")))["pid"]
    assert not os.path.exists(os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"rtb{pid}"))
