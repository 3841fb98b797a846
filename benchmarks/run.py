"""python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` the per-layer metrics, ``busy_s`` / ``window_s`` and a
``breakdown``). No chip, or fewer than the cell needs: a non-zero exit
code and no result line, never a CPU number.

This parent never imports JAX: a process that has touched JAX holds the
chip. It starts the cell in one child (a training cell's child drives
all of the cell's chips; a serving cell's child stays off JAX too and
the replica's worker holds the chip), waits for it, and ends whatever
the cell left behind. ``--rehearse`` (tests only) walks the same code
on the CPU at a narrow width and reports ``"platform": "cpu"``.
"""
import argparse
import collections
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: a run must end inside the driver's 360 s (1200 s when it compiles)
CHILD_LIMIT_S = 1150.0
#: of a failed run: this much of the end of what the cell's process
#: printed is kept, and of each worker's log shown
TAIL_BYTES, LOG_TAIL_BYTES = 64 * 1024, 1500


def _kill_marked(marker: str) -> None:
    """SIGKILL every process that carries this run's marker in its
    environment: the runtime's workers are session leaders of their
    own, so ending the child's process group does not reach them."""
    needle = f"RTB_RUN_MARK={marker}".encode()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if needle in f.read().split(b"\0"):
                    os.kill(int(pid), signal.SIGKILL)
        except (OSError, ValueError):
            continue


def keep_failed(args, rc: int, pid: int, tail: bytes) -> str:
    """What a failed run leaves, kept inside the checkout: the end of
    what the cell's process printed and the session's worker logs. The
    end of each log goes to stderr too, where whoever ran this keeps
    the last lines. Returns the directory."""
    dest = os.path.join(ROOT, ".bench_tmp", "failed",
                        f"{args.workload}.seed{args.seed}.trace{args.trace}")
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    with open(os.path.join(dest, "stderr_tail.txt"), "wb") as f:
        f.write(tail)
    with open(os.path.join(dest, "exit.json"), "w") as f:
        json.dump({"code": rc, "argv": sys.argv[1:], "pid": pid,
                   "seconds_in": time.time() - T_START}, f)
    from benchmarks.spec import session_dir      # no JAX in there
    logs = os.path.join(session_dir(pid), "logs")
    if os.path.isdir(logs):
        shutil.copytree(logs, os.path.join(dest, "logs"))
        for name in sorted(os.listdir(logs)):
            path = os.path.join(logs, name)
            if not os.path.isfile(path) or not os.path.getsize(path):
                continue
            with open(path, "rb") as f:
                f.seek(max(0, os.path.getsize(path) - LOG_TAIL_BYTES))
                end = f.read().decode(errors="replace")
            print(f"benchmarks: the end of {name}:\n{end.rstrip()}",
                  file=sys.stderr)
    shutil.rmtree(session_dir(pid), ignore_errors=True)
    return dest


def parent(args) -> int:
    marker = f"{os.getpid()}.{int(T_START)}"
    fd, result_path = tempfile.mkstemp(prefix="bench_result_", suffix=".json")
    os.close(fd)
    env = dict(os.environ, RTB_RUN_MARK=marker)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.run", "--child", result_path,
           "--t-start", repr(T_START), "--workload", args.workload,
           "--seed", str(args.seed), "--seconds", str(args.seconds),
           "--trace", str(args.trace)]
    if args.rehearse:
        cmd.append("--rehearse")
    # the child's own chatter goes to stderr (stdout carries the result)
    # through this process, which keeps its end for a run that fails
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, start_new_session=True)
    tail: collections.deque = collections.deque()

    def relay():
        kept = 0
        for chunk in iter(lambda: proc.stdout.read1(65536), b""):
            sys.stderr.buffer.write(chunk)
            sys.stderr.buffer.flush()
            tail.append(chunk)
            kept += len(chunk)
            while kept - len(tail[0]) >= TAIL_BYTES:
                kept -= len(tail.popleft())
    relayer = threading.Thread(target=relay, daemon=True)
    relayer.start()
    try:
        rc = proc.wait(timeout=CHILD_LIMIT_S)
    except subprocess.TimeoutExpired:
        rc = 124
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
        _kill_marked(marker)
    relayer.join(timeout=10)     # the workers held the pipe's other end
    try:
        with open(result_path) as f:
            line = f.read().strip()
    finally:
        os.unlink(result_path)
    if rc != 0 or not line:
        kept = keep_failed(args, rc, proc.pid, b"".join(tail)[-TAIL_BYTES:])
        print(f"benchmarks: the cell's process ended with code {rc} and "
              f"{'no' if not line else 'a'} result; its last output and "
              f"its workers' logs are kept under {kept}", file=sys.stderr)
        return rc or 1
    print(line, flush=True)
    return 0


def child(args) -> None:
    import faulthandler
    faulthandler.enable()       # a crash in native code names its frames
    sys.path.insert(0, ROOT)
    from benchmarks import spec
    cell = spec.load_cell(args.workload, rehearse=args.rehearse)
    trace = bool(args.trace)
    if cell.kind == "train":
        from benchmarks import train_cell as runner
    elif cell.kind in ("open_loop", "closed_loop"):
        from benchmarks import serve_cell as runner
    else:
        raise SystemExit(f"traffic kind {cell.kind!r}: the generator knows "
                         f"train, open_loop and closed_loop")
    out = runner.run(cell, args.seed, args.seconds, trace, args.t_start)
    obs = out["obs"]
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = spec.read_metrics(wanted, obs)
    device = {k: obs["device"][k] for k in
              ("platform", "kind", "count", "memory_peak_bytes")}
    breakdown = None
    if trace:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        breakdown = obs["trace"]["breakdown"]
    # everything measured, on an earlier line, for whoever reads the log
    both = spec.read_metrics(cell.end_to_end + cell.per_layer, obs)
    print("[bench] all metrics: " + json.dumps(
        {k: v["value"] for k, v in both.items()}), file=sys.stderr)
    print("[bench] notes: " + json.dumps(out["notes"], default=str),
          file=sys.stderr)
    print(f"[bench] heartbeats late, [s, at]: {json.dumps(out['beats'])}\n"
          f"[bench] correct {out['correct']}; compared, [number, limit]: "
          + json.dumps(out["compared"]), file=sys.stderr, flush=True)
    line = spec.result_line(out["correct"], out["attempted"], out["failed"],
                            metrics, device, breakdown, out["beats"],
                            out["compared"])
    with open(args.child, "w") as f:
        f.write(line)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU, narrow width, interpreted "
                         "kernels; the line says platform cpu")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--t-start", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child(args)
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
