"""A serving cell (``kind: open_loop`` or ``closed_loop``): one replica
through ``serve.run`` and handles, streaming, driven from this process,
which stays off JAX (the replica's worker holds the chip). Set-up is
deploy (weights, warm-up), the correctness check, warming the handles
and the traffic that runs before the window opens (an open loop's warm
blocks, a closed loop's cache fill); the window is ``--seconds`` of the
schedule; with ``--trace 1`` the traffic goes on after the window for
the traced stretch. After that nothing new is sent, the run listens on
for the first tokens of the requests that were due in the window, and
only when the clients have stopped is the trace reduced."""
import os
import queue
import shutil
import threading
import time
from typing import Any, Dict, List

import ray_tpu
from ray_tpu import serve
from ray_tpu.core.accelerators import jax_backend_initialized, tpu_chip_count

from benchmarks import spec, stats, traffic
from benchmarks.spec import log
from benchmarks.serve_replica import BenchLLMServer

APP = "bench_llm"


def _is_number(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _is_plain(v: Any) -> bool:
    """A number, a string, a truth value, or a list or dict of them."""
    if isinstance(v, dict):
        return all(_is_plain(x) for x in v.values())
    if isinstance(v, list):
        return all(_is_plain(x) for x in v)
    return isinstance(v, (int, float, str))


def observed_model(program: Dict[str, Any], model: Dict[str, Any],
                   engine: Dict[str, Any]) -> Dict[str, Any]:
    """``obs["model"]``: the configuration's ``program`` group whole,
    every plain key under its own name with the value the model was
    built from (a rehearsal's is the narrowed one), so that a reader
    which comes with a configuration reads whatever widths that
    configuration has; beside them what the readers here have always
    been given, which wins where a name is both."""
    return {**{k: model[k] for k, v in program.items() if _is_plain(v)},
            "n_layers": model["n_layers"],
            "n_heads": model["n_heads"],
            "kv_heads": model.get("n_kv_heads") or model["n_heads"],
            "head_dim": model["head_dim"],
            "kv_block_size": engine["kv_block_size"],
            "num_kv_blocks": engine["num_kv_blocks"],
            "prefill_chunk": engine["prefill_chunk"],
            "itemsize": 2 if model["dtype"] == "bfloat16" else 4}


def numerics(stats: Dict[str, Any]) -> Dict[str, Any]:
    """The numeric part of one ``LLMEngine.stats()`` snapshot: every
    numeric key, the numeric values of every dict-valued key one level
    down (``ahead_blocked_total`` by reason, ``occupancy_hist`` by batch
    size), and ``phases[name]`` as ``{"count", "seconds"}``. Strings,
    lists and ``None`` are dropped."""
    out: Dict[str, Any] = {}
    for key, v in stats.items():
        if key == "phases" and isinstance(v, dict):
            out[key] = {name: {"count": c, "seconds": s}
                        for name, (c, s) in v.items()}
        elif key == "occupancy_hist" and isinstance(v, dict):
            out[key] = {int(k): n for k, n in v.items()}
        elif isinstance(v, dict):
            out[key] = {k: n for k, n in v.items() if _is_number(n)}
        elif _is_number(v):
            out[key] = v
    return out


def counters_delta(after: Dict[str, Any], before: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """``numerics`` of two ``LLMEngine.stats()`` snapshots, differenced
    over the stretch between them (the window, or the traced stretch):
    ``obs["engine"]`` and ``obs["trace"]["engine"]``. A key that either
    snapshot lacks (an older program) is left out; one level down, a
    counter or a phase that first shows inside the stretch counts from
    nought. Counters (``*_total``, ``*_wall_s``, ``decode_steps``,
    ``phases``, ...) difference to what the stretch did. Gauges
    (``queue_depth``, ``free_blocks``, ``active_slots``, ``tokens_per_s``,
    ``weight_version``, ...) difference to nothing useful: their values
    when the window closed are ``obs["engine_end"]``, the same
    ``numerics`` of that one snapshot."""
    a, b = numerics(after), numerics(before)
    nought = {"count": 0, "seconds": 0.0}
    out: Dict[str, Any] = {}
    for key, v in a.items():
        if key not in b or isinstance(v, dict) != isinstance(b[key], dict):
            continue
        was = b[key]
        if key == "phases":
            out[key] = {name: {f: p[f] - was.get(name, nought)[f]
                               for f in nought} for name, p in v.items()}
        elif isinstance(v, dict):
            out[key] = {k: n - was.get(k, 0) for k, n in v.items()}
        else:
            out[key] = v - was
    return out


def phase_table(engine: Dict[str, Any]) -> str:
    """``name ms each x count`` of a differenced stretch's phases, the
    longest in total first: for the log, where a person reads it."""
    rows = sorted((engine.get("phases") or {}).items(),
                  key=lambda kv: -kv[1]["seconds"])
    return ", ".join(
        f"{name} {1e3 * p['seconds'] / p['count']:.3f} x {p['count']}"
        for name, p in rows if p["count"])


class Clients:
    """Worker threads, each with a handle of its own (a handle's router
    is not built for sharing between threads), that stream the requests
    handed to them and stamp every token's arrival."""

    def __init__(self, n: int):
        self.todo: "queue.Queue" = queue.Queue()
        self.closing = threading.Event()    # send nothing new
        self.stop = threading.Event()       # and let go of the streams
        self.records: List[Dict[str, Any]] = []
        self.t0 = time.perf_counter()       # moved to the window's start
        self._ready = threading.Barrier(n + 1)
        self.threads = [threading.Thread(target=self._work, daemon=True)
                        for _ in range(n)]
        for t in self.threads:
            t.start()
        self._ready.wait(timeout=300)

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _work(self) -> None:
        handle = serve.get_app_handle(APP)
        handle.stats.remote().result(timeout_s=120)   # warm this router
        self._ready.wait(timeout=300)
        while True:
            item = self.todo.get()
            if item is None:
                return
            req, done = item
            self.stream(handle, req)
            if done is not None:
                done.set()

    def stream(self, handle, req: Dict[str, Any]) -> None:
        rec = {"id": req["id"], "due": req.get("due"), "asked": req["asked"],
               "prompt_len": len(req["prompt"]),
               "shared": req.get("shared", 0), "tokens": [], "error": None}
        ids = rec.setdefault("ids", []) if req.get("keep_ids") else None
        rec["sent"] = self.now()
        if rec["due"] is None:              # closed loop: due when sent
            rec["due"] = rec["sent"]
        self.records.append(rec)
        gen = None
        try:
            gen = handle.options(stream=True).generate.remote(
                req["prompt"], req["asked"])
            for tok in gen:
                rec["tokens"].append(self.now())
                if ids is not None:
                    ids.append(int(tok))
                if self.stop.is_set():
                    break
        except BaseException as e:  # noqa: BLE001 — counted as failed
            rec["error"] = repr(e)[:300]
        finally:
            if gen is not None:
                gen.cancel()

    def submit_and_wait(self, reqs: List[Dict[str, Any]]) -> None:
        done = [threading.Event() for _ in reqs]
        for req, ev in zip(reqs, done):
            self.todo.put((req, ev))
        for ev in done:
            ev.wait(timeout=300)

    def shutdown(self) -> None:
        self.stop.set()
        for _ in self.threads:
            self.todo.put(None)
        for t in self.threads:
            t.join(timeout=60)


def _open_loop_feeder(p, clients: Clients, seed, total_s, vocab):
    """One thread that hands each request over at its due instant."""
    plan = traffic.open_loop(p, seed, total_s, vocab)

    def dispatch():
        for req in plan:
            wait = req["due"] - clients.now()
            if (wait > 0 and clients.closing.wait(wait)) \
                    or clients.closing.is_set():
                return
            clients.todo.put((req, None))
    return [threading.Thread(target=dispatch, daemon=True)]


def _closed_loop_feeders(cell, p, clients: Clients, seed, vocab,
                         asked: list, ran_out: list):
    """The clients' threads, after the cache fill (set-up; see
    ``traffic.closed_loop_start``). ``asked[c]`` counts what client c
    has sent. A replay has no end unless its file gives it one; a client
    that does reach it leaves the loop a client short from then on, and
    says when in ``ran_out``."""
    fills, replays = traffic.closed_loop_start(p, seed, vocab)
    clients.submit_and_wait(fills)
    asked.extend(0 for _ in replays)

    def client(c, reqs):
        for req in reqs:
            if clients.closing.is_set():
                return
            done = threading.Event()
            asked[c] += 1
            clients.todo.put((req, done))
            while not done.wait(0.5):
                if clients.stop.is_set():
                    return
        if clients.closing.is_set():
            return
        when = clients.now()
        ran_out.append([c, when])
        log(f"{cell.name}: client {c} ran out of requests at {when:.1f} s "
            f"of the window, after {asked[c]}")
    return [threading.Thread(target=client, args=(c, reqs), daemon=True)
            for c, reqs in enumerate(replays)]


def _served_check(p, clients: Clients, ask, seed, vocab) -> Dict[str, Any]:
    """Two requests through a handle, one after the other, the second
    sharing the first's leading pages; what the engine answered goes
    back to the replica, which holds it against the reference. With
    prefix sharing on, the second must have come out of the cache."""
    hits = [ask("bench_facts")["stats"]["prefix_hit_blocks_total"]]
    reqs = traffic.check_requests(p["engine"], seed, vocab)
    for req in reqs:
        clients.submit_and_wait([req])
        hits.append(ask("bench_facts")["stats"]["prefix_hit_blocks_total"])
    recs = {r["id"]: r for r in clients.records}
    verdict = ask("bench_check_served", [
        {"prompt": r["prompt"], "asked": r["asked"],
         "tokens": recs[r["id"]].get("ids", [])} for r in reqs])
    shared = traffic.check_sample(p["engine"])["shared"] \
        // p["engine"]["kv_block_size"]
    verdict["prefix_hit_blocks"] = [hits[1] - hits[0], hits[2] - hits[1]]
    if p["engine"].get("enable_prefix_sharing", True):
        verdict["ok"] = bool(verdict["ok"] and hits[2] - hits[1] >= shared)
    return verdict


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    if not cell.rehearse and tpu_chip_count() < cell.chips:
        raise SystemExit(f"benchmarks: the cell needs {cell.chips} chip(s) "
                         f"and this machine has {tpu_chip_count()}")
    session = spec.session_dir(os.getpid())
    ray_tpu.init(num_cpus=16, num_tpus=max(1, cell.chips),
                 _num_initial_workers=2, _session_dir=session)
    try:
        out = _drive(cell, seed, seconds, trace, t_start)
    except BaseException:
        # run.py's parent keeps them, and the end of this output
        log(f"{cell.name}: failed; the workers' logs are under {session}/logs")
        raise
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    shutil.rmtree(session, ignore_errors=True)
    return out


def _drive(cell, seed, seconds, trace, t_start) -> Dict[str, Any]:
    p = cell.params
    model = dict(cell.model_kwargs(), remat_policy="none",
                 max_seq_len=p["engine"]["max_seq_len"])
    # request tracing is on by default in the program and its cost is
    # unresolved (ROADMAP A0(c)): off in the runs that are judged
    engine = dict(p["engine"], enable_trace=bool(trace))
    dep = serve.deployment(
        BenchLLMServer, name=APP, num_replicas=1,
        ray_actor_options={"num_tpus": 1}, max_ongoing_requests=512)
    serve.run(dep.bind(model=model, engine=engine,
                       seed=spec.weight_seed(seed), cell=cell.name,
                       rehearse=cell.rehearse), name=APP)
    from ray_tpu.serve.api import CONTROLLER_NAME
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    replica = ray_tpu.get(controller.get_replicas.remote(APP))[0]

    def ask(method, *a):
        return ray_tpu.get(replica.handle_request.remote(method, *a),
                           timeout=900)

    facts = ask("bench_facts")
    dev = facts["device"]
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["device_count"]}
    if not cell.rehearse and device["platform"] != "tpu":
        raise SystemExit(f"benchmarks: no accelerator: the replica reports "
                         f"{device}")
    log(f"{cell.name}: replica ready in {facts['ready_s']:.1f} s on "
        f"{device}, at {time.time() - t_start:.1f} s; compile cache "
        f"{dev['compile_cache']}")
    verdict = ask("bench_check", seed)
    log(f"{cell.name}: check {verdict} at {time.time() - t_start:.1f} s")

    clients = Clients(p["client_threads"])
    vocab = model["vocab_size"]
    served = _served_check(p, clients, ask, seed, vocab)
    log(f"{cell.name}: served check {served} at "
        f"{time.time() - t_start:.1f} s")
    trace_s = float(p["trace_seconds"]) if trace else 0.0
    asked: list = []       # closed loop: requests each client has sent,
    ran_out: list = []     # and [client, when] if its replay ended
    if cell.kind == "open_loop":
        # a few short requests first: the token path back to this
        # process has run once
        clients.submit_and_wait([
            {"id": -1 - i, "prompt": [2 + i] * 8, "asked": 4}
            for i in range(p["warm_requests"])])
        warm_s = traffic.warm_seconds(p)
        feeders = _open_loop_feeder(p, clients, seed, seconds + trace_s,
                                    vocab)
    else:
        warm_s = float(p["warm_seconds"])
        feeders = _closed_loop_feeders(cell, p, clients, seed, vocab,
                                       asked, ran_out)
    clients.records.clear()
    log(f"{cell.name}: clients ready at {time.time() - t_start:.1f} s; "
        f"{warm_s:.1f} s of traffic before the window opens")
    clients.t0 = time.perf_counter() + warm_s      # the window opens then
    for f in feeders:
        f.start()
    time.sleep(warm_s)
    ask("bench_reset")
    heart = spec.Heartbeat()
    before = ask("bench_facts")
    setup_s = time.time() - t_start
    time.sleep(max(0.0, seconds - clients.now()))
    after = ask("bench_facts")
    at = {"window_end": clients.now()}      # seconds of the window
    beats = {"driver": list(heart.worst), "replica": after["heartbeat"]}
    traced = None
    if trace:
        s0 = ask("bench_trace_start")
        time.sleep(trace_s)
        traced = counters_delta(ask("bench_trace_stop"), s0)
    # what is measured ends here. Nothing new is sent; listen on for the
    # first tokens of the requests due in the window, so that one sent
    # at its very end is not a failure
    clients.closing.set()
    at["closing"] = clients.now()
    deadline = clients.now() + float(p["drain_seconds"])
    while clients.now() < deadline and any(
            not r["tokens"] and not r["error"]
            for r in stats.due_in_window(list(clients.records), seconds)):
        time.sleep(0.02)
    listen_s = at["listened"] = clients.now()
    clients.shutdown()
    records = sorted(clients.records, key=lambda r: r["due"])
    summary = None
    if trace:
        # with no client asking any more: stopping the profiler and
        # reducing its trace is tens of seconds of the replica's process
        at["reduce_from"] = clients.now()
        summary = ask("bench_trace_reduce")
        summary["engine"] = traced
        at["reduce_to"] = clients.now()
    end = ask("bench_facts")
    audit = ask("pool_audit")
    if jax_backend_initialized():
        raise RuntimeError("the serve driver initialised a JAX backend")
    # closed loop: what the busiest client sent, and [client, second of
    # the window] of each that reached a replay's end while measured
    replay = {"asked_max": max(asked), "ran_out": ran_out} if asked else None

    window = stats.due_in_window(records, seconds)
    failed = sum(stats.is_failed(r, listen_s) for r in window)
    programs = after["stats"]["compiled_programs"]
    engine_ok = after["stats"]["dead"] is None and not audit \
        and set(programs.values()) <= {0, 1} and not ran_out
    in_flight = [stats.in_flight_at(records, seconds / 2),
                 stats.in_flight_at(records, seconds)]
    log(f"{cell.name}: {len(window)} requests due in {seconds} s, "
        f"{failed} failed, {stats.tokens_in_window(records, seconds)} "
        f"tokens arrived; in flight at the middle and the end {in_flight}; "
        f"longest silence [s, at] {stats.longest_silence(records, seconds)}; "
        f"idle threads woke at worst [s late, at] {beats}; "
        f"seconds of the window at which {at}; programs {programs}; "
        f"closed-loop replay {replay}")
    obs = {
        "setup_s": setup_s, "window_s": float(seconds),
        "listen_s": listen_s, "requests": records,
        # the replica's own arrival-to-first-token times in the window
        "engine_ttft_s": [b - a for a, b in after["served"]],
        "engine": counters_delta(after["stats"], before["stats"]),
        "engine_end": numerics(after["stats"]),
        "engine_config": dict(p["engine"]),
        "model": observed_model(cell.config["program"], model, p["engine"]),
        # pages some request holds when the window closes (the prefix
        # cache's unreferenced pages count as free: eviction takes them)
        "pool": {"live_pages": after["stats"]["total_blocks"]
                 - after["stats"]["free_blocks"],
                 "total_pages": after["stats"]["total_blocks"]},
        "compiles_in_window": after["compiles"],
        "ready_s": facts["ready_s"],
        "device": {**device, **end["memory"]},
        "trace": summary,
    }
    log(f"{cell.name}: the window's phases: {phase_table(obs['engine'])}; "
        f"programs ahead {obs['engine'].get('programs_ahead_total')}, "
        f"blocked {obs['engine'].get('ahead_blocked_total')}")
    if summary:
        log(f"{cell.name}: the traced stretch's phases: "
            f"{phase_table(summary['engine'])}; idle seconds by phase "
            f"{ {k: round(v, 4) for k, v in summary['idle_by_phase'].items()} }")
    return {"correct": bool(verdict["ok"] and served["ok"] and engine_ok),
            "attempted": len(window), "failed": int(failed), "obs": obs,
            "beats": beats,
            # each number held to a limit, [number, limit]
            "compared": {
                "logits": [verdict["errors"]["logits"],
                           verdict["tol"]["logits"]],
                "served_token_gap": [served["errors"]["served_token_gap"],
                                     served["tol"]],
                "served_tokens_short": [served["tokens_short"], 0],
                "pool_audit_findings": [len(audit), 0],
                "clients_ran_out": [len(ran_out), 0]},
            "notes": {"check": verdict, "served_check": served,
                      "pool_audit": audit, "programs": programs,
                      "in_flight_mid_end": in_flight, "at": at,
                      "replay": replay}}
