#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once each through the entry points a user
calls, at the published widths of ``gptj-6b`` (d_model 4096, 16 heads x
256, d_ff 16384, rotary 64, vocab 50400, bf16) with only the depth cut
and seeded random weights:

  probe   a child prints what ``jax.devices()`` finds. Not a TPU: fail.
  train   ``ParallelPlan(...).build(cfg)`` -> a few optimizer steps of
          flash attention at seq 2048 on one repeated batch; loss finite
          and falling; flash fwd/bwd kernels against the XLA reference.
  serve   a driver that stays off JAX: ``ray_tpu.init`` ->
          ``serve.run(LLMServer, num_tpus=1)`` in a worker process ->
          streamed requests (shared prefix, multi-chunk prompt, CoW);
          the replica checks its paged kernel and its cached logits
          against the XLA reference and reports what holds the chip.
  train4 / serve4   when the probe finds four chips: the same with
          ``ParallelPlan(fsdp=4)`` and four one-chip replicas.

One process owns the chip at a time: this parent never imports JAX and
every phase is a child that exits before the next starts. A phase that
raises, times out, takes a reference or interpreted kernel where a
compiled one was due, or fails a comparison makes the exit code
non-zero, and then no result line is printed. On success the last line
of standard output is

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

``--rehearse`` walks the same phases on the CPU at a narrow width with
the kernels in Pallas interpret mode, to debug the control flow without
a chip. It never prints the result line.

Phase reports are also written to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: the one-chip run must end inside the driver's 1200 s; its phases
#: share this. A four-chip host gets the extra phases' budgets on top.
TOTAL_BUDGET_S = 1150.0
PHASE_BUDGET_S = {"probe": 180.0, "train": 480.0, "serve": 600.0,
                  "train4": 600.0, "serve4": 700.0}

TRAIN_STEPS = 4
#: depth by phase, and why (printed by the phase): only depth is cut
DEPTH = {"train": 1, "train4": 8, "serve": 4, "serve4": 4}
DEPTH_WHY = {
    "train": "f32 weights + AdamW + grads are 16 B/param: 9.2 GiB for "
             "one layer (614M params); XLA's memory analysis of the "
             "two-layer step is 15.6 GiB of a 15.75 GiB chip",
    "train4": "eight layers (2.02B params, 30.2 GiB of training state) "
              "need the fsdp=4 sharding and fit at 11.4 GiB a chip",
    "serve": "sized when the engine held f32 weights beside XLA's bf16 "
             "copy; it now holds bf16 alone (1.22B params, 2.3 GiB at "
             "four layers): more would fit, four keep the phase short",
}
DEPTH_WHY["serve4"] = DEPTH_WHY["serve"]
TRAIN_BATCH = {"train": 2, "train4": 4}

ENGINE = {"decode_slots": 8, "kv_block_size": 16, "max_seq_len": 1024,
          "prefill_chunk": 256, "max_new_tokens": 32}
#: one rehearsal engine: same block size (prefix maths unchanged), a
#: window the CPU interpreter can walk
ENGINE_REHEARSE = dict(ENGINE, decode_slots=4, max_seq_len=256,
                       prefill_chunk=64)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ----------------------------------------------------------------- parent
def _kill_session(session_dir: str) -> None:
    """SIGKILL every process that carries this run's session directory
    in its environment: workers are session leaders of their own, so a
    killed phase child does not take them along."""
    needle = f"RAY_TPU_SESSION_DIR={session_dir}".encode()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if needle in f.read().split(b"\0"):
                    os.kill(int(pid), signal.SIGKILL)
        except (OSError, ValueError):
            continue


def run_phase_child(phase: str, args, device: dict, deadline: float) -> dict:
    """Run one phase in a child process group and return its report.
    Raises SystemExit (after killing whatever the phase started) when
    the child fails or runs out of time."""
    os.makedirs(OUT_DIR, exist_ok=True)
    report = os.path.join(OUT_DIR, f"{phase}.json")
    if os.path.exists(report):
        os.unlink(report)
    session_dir = f"/tmp/ray_tpu/chip_smoke_{os.getpid()}_{phase}"
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--report", report, "--session-dir", session_dir,
           "--device", json.dumps(device)]
    if args.rehearse:
        cmd.append("--rehearse")
    budget = min(PHASE_BUDGET_S[phase], deadline - time.monotonic())
    if budget <= 0:
        fail(f"phase {phase}: no time left in the {TOTAL_BUDGET_S:.0f} s "
             f"budget")
    log(f"phase {phase}: start (budget {budget:.0f} s)")
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, start_new_session=True)
    try:
        rc = proc.wait(timeout=budget)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
        _kill_session(session_dir)
    wall = time.monotonic() - t0
    if rc is None:
        fail(f"phase {phase}: timed out after {budget:.0f} s")
    if rc != 0:
        fail(f"phase {phase}: child exited with code {rc}")
    if not os.path.exists(report):
        fail(f"phase {phase}: child wrote no report")
    with open(report) as f:
        out = json.load(f)
    if out.get("ok") is not True:
        fail(f"phase {phase}: report is not ok: {out.get('why')}")
    log(f"phase {phase}: ok in {wall:.1f} s")
    out["phase_wall_s"] = round(wall, 1)
    return out


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def parent(args) -> None:
    deadline = time.monotonic() + TOTAL_BUDGET_S
    device = run_phase_child("probe", args, {}, deadline)["device"]
    log(f"probe: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']}")
    if device["platform"] != "tpu" and not args.rehearse:
        fail(f"no accelerator: jax.devices() reports platform="
             f"{device['platform']!r} ({device['count']} x "
             f"{device['kind']!r}); this check runs on a TPU only")
    phases = ["train", "serve"]
    if device["count"] >= 4 and not args.rehearse:
        phases += ["train4", "serve4"]
        deadline += PHASE_BUDGET_S["train4"] + PHASE_BUDGET_S["serve4"]
    else:
        log(f"four-chip phases skipped: the probe found "
            f"{device['count']} device(s)")
    full = list(phases)
    if args.phases:
        phases = [ph for ph in phases if ph in args.phases.split(",")]
    reports = {ph: run_phase_child(ph, args, device, deadline)
               for ph in phases}
    summary = {"device": device, "rehearsal": bool(args.rehearse),
               "phases": reports}
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for ph, rep in reports.items():
        log(f"{ph}: " + json.dumps(rep.get("headline", {})))
    if args.rehearse:
        log("REHEARSAL complete: CPU, narrow width, interpreted kernels. "
            "This is not a result and says nothing about the chip.")
        return
    if phases != full:
        log(f"PARTIAL run ({phases} of {full}): not a result.")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)


# ----------------------------------------------------- shared by children
def smoke_config(phase: str, rehearse: bool):
    """``get_config("gptj-6b")`` with only ``n_layers`` overridden. The
    rehearsal also narrows d_model / heads / d_ff / vocab / seq and
    forces the interpreted kernels; head_dim stays 256."""
    from ray_tpu.models.registry import get_config
    if not rehearse:
        return get_config("gptj-6b", n_layers=DEPTH[phase])
    import jax.numpy as jnp
    return get_config(
        "gptj-6b", n_layers=1, d_model=256, n_heads=2, d_ff=512,
        vocab_size=512, max_seq_len=256, dtype=jnp.float32,
        attn_impl="interpret", paged_impl="interpret")


def _widths(cfg) -> str:
    return (f"d_model {cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"{cfg.dtype.__name__}")


def check_dispatch(entries, want_impl: str) -> None:
    """Every attention call the process traced must have resolved to
    the compiled kernel (rehearsal: the interpreted one); a reference
    is allowed only where the check itself asked for it."""
    bad = [e for e in entries
           if e["impl"] != want_impl
           and not (e["impl"] == "reference" and e["why"] == "requested")]
    if bad or not any(e["impl"] == want_impl for e in entries):
        raise RuntimeError(f"attention fell back: {entries}")


def check_truth(reports, rehearse: bool) -> None:
    for r in reports:
        if not r["ok"]:
            raise RuntimeError(f"kernel disagrees with reference: {r}")
        if r["compiled"] == rehearse:
            raise RuntimeError(
                f"kernel was {'compiled' if r['compiled'] else 'not compiled'}"
                f" where the opposite was due: {r}")


def chip_holders() -> dict:
    """pid -> device files, for every process with a TPU chip device
    (``/dev/accel*``, ``/dev/vfio/<n>``) open. Empty when the machine
    exposes the chip some other way."""
    import re
    pat = re.compile(r"^/dev/(accel\d+|vfio/\d+)$")
    out = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if pat.match(target):
                out.setdefault(int(pid), set()).add(target)
    return {pid: sorted(v) for pid, v in out.items()}


def write_report(path: str, report: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, default=str)
    os.replace(tmp, path)


# ------------------------------------------------------------ phase: probe
def phase_probe(args) -> dict:
    import jax
    devs = jax.devices()
    return {"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)},
        "tpu_env": {k: v for k, v in os.environ.items()
                    if k.startswith(("TPU_", "JAX_", "XLA_"))},
        "device_files": sorted(
            p for p in ("/dev/" + n for n in os.listdir("/dev"))
            if "accel" in p or "vfio" in p)}


# ------------------------------------------------------------ phase: train
def phase_train(args, phase: str) -> dict:
    import numpy as np

    from ray_tpu.util import compile_cache
    compile_cache.enable()
    compile_cache.stats()            # start counting loads vs compiles
    import jax

    from ray_tpu import _native
    from ray_tpu.models import kernel_truth as KT
    from ray_tpu.ops.attention import dispatch_log
    from ray_tpu.parallel.plan import ParallelPlan

    rehearse = args.rehearse
    fsdp = 4 if phase == "train4" else 1
    cfg = smoke_config(phase, rehearse)
    seq = cfg.max_seq_len
    batch = TRAIN_BATCH[phase]
    devs = jax.devices()
    log(f"{phase}: {len(devs)} x {devs[0].device_kind}; {_widths(cfg)}, "
        f"n_layers={cfg.n_layers} ({cfg.num_params / 1e6:.0f}M params), "
        f"batch {batch} x seq {seq}, fsdp={fsdp}")
    if not rehearse:
        log(f"{phase}: depth {cfg.n_layers} because {DEPTH_WHY[phase]}")

    # -- kernel truth, outside any timing
    t0 = time.perf_counter()
    truth = [KT.flash_truth(batch=1, heads=cfg.n_heads, seq=seq,
                            head_dim=cfg.head_dim, dtype=cfg.dtype,
                            impl=cfg.attn_impl)]
    check_truth(truth, rehearse)
    log(f"{phase}: flash fwd/bwd vs XLA reference {truth[0]['rel_err']} "
        f"(tol {truth[0]['tol']}, compiled={truth[0]['compiled']}) in "
        f"{time.perf_counter() - t0:.1f} s")

    # -- the train step through the normal entry points
    t0 = time.perf_counter()
    prog = ParallelPlan(fsdp=fsdp).build(
        cfg, learning_rate=1e-4, seed=0, telemetry_interval_s=0)
    jax.block_until_ready(prog.state)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    batch_d = {"input_ids": ids,
               "loss_mask": np.ones((batch, seq), np.float32)}
    losses, walls = [], []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        res = prog.step(batch_d)
        jax.block_until_ready(prog.state)
        walls.append(time.perf_counter() - t0)
        losses.append(res.loss)
        log(f"{phase}: step {i} loss {res.loss:.4f} grad_norm "
            f"{res.grad_norm:.3f} wall {walls[-1]:.3f} s"
            + (" (includes compilation)" if i == 0 else ""))
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise RuntimeError(f"loss not finite and falling: {losses}")

    # -- does block_until_ready wait for the whole step? Dispatch one
    # more step without touching its results, block, then fetch scalars
    # that depend on the optimizer update: if anything were still
    # running the fetch would have to wait for it.
    b = prog.bundle
    t0 = time.perf_counter()
    state, metrics = b.step(prog.state, batch_d)
    t1 = time.perf_counter()
    jax.block_until_ready((state, metrics))
    t2 = time.perf_counter()
    int(state["step"]), float(metrics["grad_norm"]), float(metrics["loss"])
    t3 = time.perf_counter()
    prog.state = state
    sync = {"dispatch_ms": round((t1 - t0) * 1e3, 2),
            "block_until_ready_ms": round((t2 - t1) * 1e3, 2),
            "scalar_fetch_after_block_ms": round((t3 - t2) * 1e3, 2)}
    log(f"{phase}: sync check {sync}")
    if t3 - t2 > 0.2 * (t2 - t0):
        raise RuntimeError(
            f"block_until_ready returned before the step was done: {sync}")

    # -- what ran, where it lives
    steady = sorted(walls[1:])[len(walls[1:]) // 2]
    dispatch = dispatch_log()
    check_dispatch(dispatch, "interpret" if rehearse else "kernel")
    hlo = b.step_fn.lower(prog.state, dict(batch_d)).as_text()
    mosaic_calls = hlo.count("tpu_custom_call")
    if (mosaic_calls == 0) != rehearse:
        raise RuntimeError(f"train step holds {mosaic_calls} Mosaic calls")
    big = prog.state["params"]["layers"]["fc_in"]
    per_device = [
        {"device": d.id, **{k: (d.memory_stats() or {}).get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}}
        for d in prog.mesh.devices.flat]
    shard_shapes = sorted({str(s.data.shape)
                           for s in big.addressable_shards})
    devices_holding = len({s.device.id for s in big.addressable_shards})
    if fsdp > 1:
        attn_batch = _attention_batch_per_device(b, prog.state, batch_d)
        if devices_holding != fsdp or len(shard_shapes) != 1 \
                or attn_batch != batch // fsdp:
            raise RuntimeError(
                f"state or attention not sharded over fsdp={fsdp}: "
                f"shards {shard_shapes} on {devices_holding} devices, "
                f"attention batch per device {attn_batch}")
    else:
        attn_batch = batch
    cache = compile_cache.stats()
    report = {
        "ok": True, "phase": phase, "n_layers": cfg.n_layers,
        "params_M": round(cfg.num_params / 1e6, 1), "batch": batch,
        "seq": seq, "fsdp": fsdp, "losses": [round(x, 4) for x in losses],
        "state_build_s": round(build_s, 2),
        "first_step_s": round(walls[0], 2),
        "steady_step_s": round(steady, 4),
        "compile_s_est": round(walls[0] - steady, 2),
        "step_walls_s": [round(w, 4) for w in walls],
        "tokens_per_step": batch * seq, "sync_check": sync,
        "kernel_truth": truth, "attention_dispatch": dispatch,
        "mosaic_calls_in_step": mosaic_calls,
        "fc_in_global_shape": str(big.shape),
        "fc_in_shard_shapes": shard_shapes,
        "attention_batch_per_device": attn_batch,
        "per_device_memory": per_device,
        "compile_cache": {"dir": compile_cache.cache_root(), **cache},
        "object_store": _native.store_kind(),
        "device_kind": devs[0].device_kind, "device_count": len(devs),
    }
    report["headline"] = {
        "depth": cfg.n_layers, "loss": report["losses"],
        "first_step_s": report["first_step_s"],
        "steady_step_s": report["steady_step_s"],
        "flash_rel_err": truth[0]["rel_err"],
        "cache_loads": cache["hits"], "cache_compiles": cache["misses"],
        "peak_GiB": [round((m["peak_bytes_in_use"] or 0) / 2**30, 2)
                     for m in per_device]}
    return report


def _attention_batch_per_device(bundle, state, batch_d) -> int:
    """Leading (batch) dim of the flash forward kernel's first operand
    in the COMPILED step: the per-device batch attention really runs
    on. ``batch / fsdp`` when attention is partitioned; the full batch
    if every chip ran it replicated."""
    import re
    text = bundle.step_fn.lower(state, dict(batch_d)).compile().as_text()
    m = re.search(r"flash_fwd[.\w]* = \(?\w+\[(\d+),", text)
    if m is None:
        raise RuntimeError("no flash_fwd custom call in the compiled step")
    return int(m.group(1))


# ------------------------------------------------------------ phase: serve
def _prompts(rng, vocab: int, engine: dict):
    """(name, prompt, max_new_tokens, wave). Wave 0 runs alone first so
    its prefix is in the trie when wave 1 (concurrent) arrives."""
    bs, chunk = engine["kv_block_size"], engine["prefill_chunk"]
    tok = lambda n: [int(t) for t in rng.integers(2, vocab, size=n)]  # noqa: E731
    prefix = tok(6 * bs)                       # six whole blocks
    long_n = min(2 * chunk + chunk // 2 + 7, engine["max_seq_len"] - 40)
    return [
        ("prefix_first", prefix + tok(11), 8, 0),
        ("prefix_second", prefix + tok(29), 12, 1),   # >= 4-block hit
        ("prefix_exact", list(prefix), 6, 1),         # aligned: CoW
        ("long_multichunk", tok(long_n), 16, 1),      # several chunks
        ("short", tok(5), 24, 1),
        ("mid", tok(40), 10, 1),
        ("odd", tok(min(301, engine["max_seq_len"] - 40)), 9, 1),
        ("one_token", tok(1), 5, 1),
    ]


def phase_serve(args, phase: str) -> dict:
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import _native, serve
    from ray_tpu.core.accelerators import jax_backend_initialized
    from ray_tpu.serve.llm_engine import LLMServer

    rehearse = args.rehearse
    device = json.loads(args.device)
    n_replicas = 4 if phase == "serve4" else 1
    cfg = smoke_config(phase, rehearse)
    engine = dict(ENGINE_REHEARSE if rehearse else ENGINE)
    import dataclasses
    model = dataclasses.asdict(cfg)
    model["dtype"] = np.dtype(cfg.dtype).name
    model["remat_policy"] = "none"
    log(f"{phase}: {n_replicas} replica(s) x 1 chip; {_widths(cfg)}, "
        f"n_layers={cfg.n_layers}, engine {engine}")
    if not rehearse:
        log(f"{phase}: depth {cfg.n_layers} because {DEPTH_WHY[phase]}")

    ray_tpu.init(num_cpus=16, num_tpus=max(n_replicas, device["count"]),
                 _num_initial_workers=2, _session_dir=args.session_dir)
    t0 = time.perf_counter()
    dep = serve.deployment(
        LLMServer, name="smoke_llm", num_replicas=n_replicas,
        ray_actor_options={"num_tpus": 1}, max_ongoing_requests=64)
    serve.run(dep.bind(model=model, engine=engine, seed=0),
              name="smoke_llm")
    from ray_tpu.serve.api import CONTROLLER_NAME
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    replicas = ray_tpu.get(controller.get_replicas.remote("smoke_llm"))
    assert len(replicas) == n_replicas, replicas

    def each(method, *a, timeout=600):
        return ray_tpu.get([r.handle_request.remote(method, *a)
                            for r in replicas], timeout=timeout)

    # constructor = weights + warmup (compiles every engine program);
    # a replica whose warmup failed raises here
    infos = each("device_info", timeout=PHASE_BUDGET_S[phase])
    ready_s = time.perf_counter() - t0
    log(f"{phase}: replicas ready in {ready_s:.1f} s: " + json.dumps(
        [{k: i[k] for k in ("pid", "platform", "device_count",
                            "visible_chips")} for i in infos]))
    holders = chip_holders()
    replica_pids = {i["pid"] for i in infos}

    # -- traffic
    rng = np.random.default_rng(1)
    plan = _prompts(rng, cfg.vocab_size, engine) * n_replicas
    results, errors = {}, []

    def one(idx, name, prompt, n_new):
        try:
            t_req = time.perf_counter()
            toks, ttft = [], float("nan")
            h = serve.get_app_handle("smoke_llm")   # one per thread
            for tok in h.options(stream=True).generate.remote(
                    prompt, n_new):
                if not toks:
                    ttft = time.perf_counter() - t_req
                toks.append(int(tok))
            results[idx] = {"name": name, "prompt_len": len(prompt),
                            "asked": n_new, "got": len(toks),
                            "ttft_s": round(ttft, 3),
                            "wall_s": round(time.perf_counter() - t_req, 3)}
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append((name, e))

    t0 = time.perf_counter()
    for wave in (0, 1):
        threads = [threading.Thread(target=one, args=(i, n, p, k))
                   for i, (n, p, k, w) in enumerate(plan) if w == wave]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=PHASE_BUDGET_S[phase])
    traffic_s = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"requests failed: {errors}") from errors[0][1]
    short = [r for r in results.values() if r["got"] != r["asked"]]
    if len(results) != len(plan) or short:
        raise RuntimeError(f"requests incomplete: {len(results)} of "
                           f"{len(plan)} answered, short: {short}")
    log(f"{phase}: {len(results)} streamed requests answered in full in "
        f"{traffic_s:.1f} s")

    # -- the replicas' own account
    truth = ray_tpu.get(replicas[0].handle_request.remote("kernel_truth"),
                        timeout=600)
    check_truth(truth, rehearse)
    for r in truth:
        log(f"{phase}: {r['name']} vs XLA reference {r['rel_err']} "
            f"(tol {r['tol']}, compiled={r['compiled']})")
    stats = each("stats")
    audits = each("pool_audit")
    want = "interpret" if rehearse else "kernel"
    for st, audit, info in zip(stats, audits, infos):
        if st["dead"] or audit or st["active_slots"] or st["queue_depth"] \
                or st["free_slots"] != engine["decode_slots"]:
            raise RuntimeError(f"engine not clean after traffic: dead="
                               f"{st['dead']} audit={audit} stats={st}")
        if st["tokens_total"] <= 0:
            raise RuntimeError(f"replica pid {info['pid']} served nothing")
        if set(st["compiled_programs"].values()) != {1}:
            raise RuntimeError("a program compiled under traffic (or never"
                               f" in warmup): {st['compiled_programs']}")
        check_dispatch(st["attention_dispatch"], want)
    hit_blocks = sum(st["prefix_hit_blocks_total"] for st in stats)
    if hit_blocks < 4 or sum(st["cow_copies_total"] for st in stats) < 1:
        raise RuntimeError("no prefix hit / no CoW: "
                           f"{[st['prefix_hit_blocks_total'] for st in stats]}")
    held = {i["pid"]: holders.get(i["pid"], []) for i in infos}
    if not rehearse:
        # each process sees its own chip as device 0 at (0,0,0), so
        # which chip it is shows in the device file it holds open
        # (failing those, in the TPU_VISIBLE_CHIPS it was given)
        chips = [tuple(v) for v in held.values()] if holders \
            else [(i["visible_chips"],) for i in infos]
        if any(i["platform"] != "tpu" or i["device_count"] != 1
               for i in infos) or any(len(c) != 1 for c in chips) \
                or len(set(chips)) != n_replicas \
                or len(replica_pids) != n_replicas:
            raise RuntimeError(f"replicas do not hold one distinct chip "
                               f"each: {held} {infos}")
        strangers = set(holders) - replica_pids
        if strangers:
            raise RuntimeError(f"processes other than the replicas hold a "
                               f"chip: {strangers} of {holders}")
    if jax_backend_initialized():
        raise RuntimeError("the serve driver initialised a jax backend")

    def delete_and_wait_for_chips():
        serve.delete("smoke_llm")
        deadline = time.monotonic() + 30
        while chip_holders() and time.monotonic() < deadline:
            time.sleep(0.25)
        if chip_holders():
            raise RuntimeError("chip still held 30 s after serve.delete: "
                               f"{chip_holders()}")

    delete_and_wait_for_chips()
    redeploy = None
    if n_replicas == 1:
        # serve.delete -> serve.run on the same chip: the killed
        # replica's worker has let go of it, and the new replica finds
        # its programs in the compile cache
        t0 = time.perf_counter()
        serve.run(dep.bind(model=model, engine=engine, seed=0),
                  name="smoke_llm")
        replicas = ray_tpu.get(controller.get_replicas.remote("smoke_llm"))
        info2 = each("device_info", timeout=PHASE_BUDGET_S[phase])[0]
        toks = list(serve.get_app_handle("smoke_llm").options(
            stream=True).generate.remote([5, 6, 7, 8, 9], 4))
        if len(toks) != 4 or info2["pid"] in replica_pids:
            raise RuntimeError(f"redeployed replica wrong: {toks} {info2}")
        redeploy = {"ready_s": round(time.perf_counter() - t0, 1),
                    "pid": info2["pid"],
                    "compile_cache": info2["compile_cache"]}
        log(f"{phase}: redeployed on the freed chip in "
            f"{redeploy['ready_s']} s, compile cache "
            f"{redeploy['compile_cache']}")
        delete_and_wait_for_chips()
    serve.shutdown()
    ray_tpu.shutdown()

    report = {
        "ok": True, "phase": phase, "n_layers": cfg.n_layers,
        "engine": engine, "replicas": infos,
        "replicas_ready_s": round(ready_s, 1),
        "traffic_s": round(traffic_s, 2),
        "requests": [results[i] for i in sorted(results)],
        "kernel_truth": truth, "stats": stats, "pool_audit": audits,
        "chip_holders_during_serve": {str(k): v
                                      for k, v in holders.items()},
        "redeploy_after_delete": redeploy,
        "driver_backend_initialised": False,
        "object_store": _native.store_kind(),
    }
    report["headline"] = {
        "depth": cfg.n_layers, "replicas": n_replicas,
        "requests": len(results), "ready_s": report["replicas_ready_s"],
        "prefix_hit_blocks": hit_blocks,
        "replica_chip": {str(i["pid"]): {
            "visible_chips": i["visible_chips"],
            "device_files": held[i["pid"]]} for i in infos},
        "redeploy": redeploy,
        "tokens_per_replica": [st["tokens_total"] for st in stats],
        "paged_rel_err": [r["rel_err"] for r in truth],
        "chip_holders": sorted(holders) or "no chip device files here"}
    return report


# ------------------------------------------------------------------ main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through at a narrow width with "
                         "interpreted kernels; never prints a result")
    ap.add_argument("--phases",
                    help="comma list: run only these of the phases that "
                         "apply (for spending chip time on one phase "
                         "while debugging); prints no result line")
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--report", help=argparse.SUPPRESS)
    ap.add_argument("--session-dir", help=argparse.SUPPRESS)
    ap.add_argument("--device", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not args.phase:
        parent(args)
        return
    if args.phase == "probe":
        report = phase_probe(args)
    elif args.phase in ("train", "train4"):
        report = phase_train(args, args.phase)
    elif args.phase in ("serve", "serve4"):
        try:
            report = phase_serve(args, args.phase)
        finally:
            # worker / replica logs, pass or fail: the replica's
            # traceback is the first thing a failed run needs
            import shutil
            shutil.copytree(os.path.join(args.session_dir, "logs"),
                            os.path.join(OUT_DIR, f"{args.phase}_logs"),
                            dirs_exist_ok=True)
    else:
        raise SystemExit(f"unknown phase {args.phase!r}")
    write_report(args.report, report)


if __name__ == "__main__":
    main()
