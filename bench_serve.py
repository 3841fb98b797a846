#!/usr/bin/env python
"""Serving benchmark: continuous-batching tokens/s/chip under a
synthetic many-client load (the SERVE metric, gated by
``tools/perf_gate.py --metric serve``).

Prints ONE JSON line:
``{"metric": "serve_tokens_per_s_chip", "value", "unit", "vs_serial",
"detail"}``.

Workload: ``--clients`` concurrent clients replay a seeded schedule of
``--requests`` requests with Poisson arrivals and sampled prompt/output
lengths against a Serve deployment of :class:`ray_tpu.serve.LLMServer`,
each consuming its token stream through
``handle.options(stream=True)`` — the full engine + streaming +
reliable-delivery path, not a model-only microbench. The same schedule
then replays against a ``decode_slots=1`` engine (serial per-request
decode, everything else identical): ``vs_serial`` is the
continuous-batching speedup, the headline claim of the engine.

Reported: tokens/s/chip (headline), TTFT p50/p99, inter-token latency
p50/p99, the engine's batch-occupancy histogram, and the engine/model
config that produced them. ``--smoke`` shrinks everything for CI.

**Fleet mode** (``detail.fleet``): the same harness against an
N-replica deployment under a many-client Poisson load where every
prompt opens with a COMMON system prompt (>= 4 KV blocks long — the
high-traffic shape prefix sharing exists for), with prompt-lookup
speculative decode on and the handle's gauge-aware routing; then the
identical schedule replays against a fleet with sharing+speculation
OFF and round-robin routing (the pre-PR baseline). Emits fleet
tokens/s/chip, fleet p99 TTFT, the aggregate prefix hit rate, the
speculation acceptance rate, and ``vs_baseline`` — the fleet rows
gated by ``tools/perf_gate.py --metric serve``.

**Paged-kernel legs** (``detail.paged_kernel`` / ``detail.mixed_len``):
the Pallas paged-attention kernel vs the XLA gather reference on one
mixed-length batch — exact parity (fp32-softmax tolerance) plus the
page-count work reduction that per-sequence length skipping buys
(FLOPs ∝ live tokens; on TPU the compiled kernel is also wall-clocked
against the reference, on CPU the kernel runs in interpret mode so
only the work accounting is meaningful) — and a live mixed short+long
engine run reporting ``decode_block_work_frac`` (pages touched / window
pages) and the engine's per-step prefill/decode device-wall split.

**Disaggregated prefill/decode** (``detail.disagg`` /
``detail.migration`` / ``detail.disagg_parity``): 1 prefill + 1 decode
replica with the KV-block hand-off shipping packed slabs between them
vs 2 colocated replicas at equal chip count, on a seeded
long-prefill/short-decode schedule — emits disagg tokens/s/chip, p99
TTFT, decode-slot occupancy, and the measured hand-off cost (KV bytes
+ wall per shipped request). ``detail.migration`` is the drain A/B: a
warmed victim's radix-trie chains migrate to one survivor and not the
other, and the same single-pass replay must score a strictly higher
prefix hit rate on the migrated survivor. ``detail.disagg_parity``
asserts greedy decode is bit-identical disagg on vs off on the exact
``bf16`` wire.

**Autoscaling under load** (``detail.scale_up``, ``--scale-up-mid-load``):
a deliberately backlogged single replica must scale up MID-RUN off its
engine gauges; the leg asserts routed traffic reaches the new replica
(``new_replica_share``) and records TTFT recovery against the same
schedule on a pinned 1-replica fleet (recovery > 1 needs one chip per
replica — on a shared CPU core a second replica only time-slices).

On TPU the model is sized up with the chip; on CPU a tiny config keeps
the harness runnable anywhere (the CPU record is a smoke point for the
serve series, like the CPU BENCH records).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
from typing import Dict, List, Optional


def _percentile(xs: List[float], p: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    i = min(int(p / 100.0 * len(xs)), len(xs) - 1)
    return xs[i]


def make_workload(n_requests: int, clients: int, seed: int,
                  mean_interarrival_s: float,
                  prompt_rng=(4, 48), out_rng=(8, 32),
                  system_prompt: Optional[List[int]] = None) -> List[dict]:
    """Seeded request schedule: Poisson arrivals (exponential
    inter-arrival gaps), uniform prompt/output lengths. The SAME
    schedule replays against both engine modes. ``system_prompt``
    (fleet mode) is prepended to every request's sampled tail — the
    shared-prefix traffic shape."""
    rng = random.Random(seed)
    sys_p = list(system_prompt or [])
    t = 0.0
    reqs = []
    for i in range(n_requests):
        t += rng.expovariate(1.0 / mean_interarrival_s)
        plen = rng.randint(*prompt_rng)
        reqs.append({
            "arrival_s": t,
            "prompt": sys_p + [rng.randrange(2, 128)
                               for _ in range(plen)],
            "max_new_tokens": rng.randint(*out_rng),
            "client": i % clients,
        })
    return reqs


def run_load(handle_factory, workload: List[dict], clients: int,
             timeout_s: float = 600.0,
             handle_opts: Optional[Dict] = None) -> Dict:
    """Replay the schedule with one thread + one handle per client;
    per-request TTFT / inter-token gaps are recorded client-side (what
    a user of the HTTP proxy would observe). ``handle_opts`` are extra
    ``handle.options`` (fleet mode: ``routing_policy``)."""
    per_client: Dict[int, List[dict]] = {c: [] for c in range(clients)}
    for r in workload:
        per_client[r["client"]].append(r)
    results: List[dict] = []
    errors: List[str] = []
    lock = threading.Lock()
    opts = dict(handle_opts or {})
    t0 = time.monotonic()

    def client_loop(cid: int):
        handle = handle_factory()
        for r in per_client[cid]:
            delay = r["arrival_s"] - (time.monotonic() - t0)
            if delay > 0:
                time.sleep(delay)
            rec = {"client": cid, "tokens": 0}
            t_submit = time.monotonic()
            rec["t_submit_s"] = t_submit - t0
            try:
                gen = handle.options(stream=True, **opts).generate.remote(
                    r["prompt"], r["max_new_tokens"])
                prev = None
                gaps = []
                for _tok in gen:
                    now = time.monotonic()
                    if prev is None:
                        rec["ttft_s"] = now - t_submit
                    else:
                        gaps.append(now - prev)
                    prev = now
                    rec["tokens"] += 1
                rec["gaps"] = gaps
                rec["t_last"] = prev if prev is not None else t_submit
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                return
            with lock:
                results.append(rec)

    threads = [threading.Thread(target=client_loop, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout_s
    for t in threads:
        t.join(timeout=max(deadline - time.monotonic(), 0.1))
    if any(t.is_alive() for t in threads):
        errors.append("client threads timed out")
    total_tokens = sum(r["tokens"] for r in results)
    t_last = max((r["t_last"] for r in results), default=t0)
    wall = max(t_last - t0, 1e-9)
    ttfts = [r["ttft_s"] for r in results if "ttft_s" in r]
    gaps = [g for r in results for g in r.get("gaps", ())]
    # submit-ordered (t_submit_s, ttft_s) pairs: the scale-up leg reads
    # early-vs-late TTFT off this series (compact — no per-token gaps)
    series = sorted(
        ((round(r["t_submit_s"], 3), round(r["ttft_s"], 4))
         for r in results if "ttft_s" in r))
    return {
        "tokens_total": total_tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / wall, 2),
        "requests_done": len(results),
        "ttft_ms": {"p50": _ms(_percentile(ttfts, 50)),
                    "p99": _ms(_percentile(ttfts, 99))},
        "inter_token_ms": {"p50": _ms(_percentile(gaps, 50)),
                           "p99": _ms(_percentile(gaps, 99))},
        "ttft_series": series,
        "errors": errors,
    }


def _ms(v: Optional[float]) -> Optional[float]:
    return round(v * 1e3, 2) if v is not None else None


# ------------------------------------------------- paged-kernel legs
def make_mixed_workload(n_requests: int, clients: int, seed: int,
                        engine: Dict,
                        mean_interarrival_s: float = 0.01) -> List[dict]:
    """Short+long requests sharing decode slots — the traffic shape
    length-aware block skipping exists for: alternate requests either
    stop after a few tokens or decode out to the engine window, so at
    any decode step the slot array holds wildly different live lengths
    while the XLA reference pays the full window for every slot."""
    rng = random.Random(seed)
    window = engine["max_seq_len"]
    t = 0.0
    reqs = []
    for i in range(n_requests):
        t += rng.expovariate(1.0 / mean_interarrival_s)
        plen = rng.randint(4, 8)
        long = i % 2 == 1
        out = (window - plen - 2) if long else rng.randint(4, 8)
        reqs.append({
            "arrival_s": t,
            "prompt": [rng.randrange(2, 128) for _ in range(plen)],
            "max_new_tokens": max(2, out),
            "client": i % clients,
            "long": long,
        })
    return reqs


def run_engine_load(engine, workload: List[dict],
                    timeout_s: float = 300.0) -> Dict:
    """Replay a schedule straight against one :class:`LLMEngine`
    (no serve layer — this leg measures engine decode work, not
    routing). One consumer thread per request, schedule-paced."""
    results: List[dict] = []
    errors: List[str] = []
    lock = threading.Lock()
    t0 = time.monotonic()

    def consume(r):
        delay = r["arrival_s"] - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        try:
            toks = list(engine.generate_sync(
                r["prompt"], r["max_new_tokens"], timeout_s=timeout_s))
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
            return
        with lock:
            results.append({"tokens": len(toks), "long": r.get("long")})

    threads = [threading.Thread(target=consume, args=(r,))
               for r in workload]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=timeout_s)
    wall = max(time.monotonic() - t0, 1e-9)
    return {"tokens_total": sum(r["tokens"] for r in results),
            "wall_s": round(wall, 3),
            "requests_done": len(results),
            "errors": errors}


def bench_mixed_lengths(model: Dict, engine: Dict, seed: int,
                        requests: int = 24, clients: int = 8) -> Dict:
    """The length-aware serving claim, measured on a live engine: a
    mixed short+long workload's decode steps touch
    ``decode_pages_live`` pages out of the ``decode_pages_window`` the
    gather reference pays — ``work_reduction = 1 − live/window`` is the
    FLOP fraction the Pallas kernel's block skipping removes (wall
    clock follows on TPU where the kernel dispatches; the accounting
    is backend-independent). Also reports the engine's device-wall
    split (prefill vs decode) per step."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine

    mconf = {k: v for k, v in model.items()}
    if "dtype" in mconf:
        from ray_tpu.serve.llm_engine import _resolve_dtype
        mconf["dtype"] = _resolve_dtype(mconf["dtype"])
    eng = LLMEngine(TransformerConfig(**mconf), EngineConfig(**engine),
                    seed=seed)
    try:
        # warm the jitted programs outside the window
        list(eng.generate_sync([3, 5, 7], 2))
        workload = make_mixed_workload(requests, clients, seed, engine)
        load = run_engine_load(eng, workload)
        s = eng.stats()
    finally:
        eng.shutdown()
    frac = s.get("decode_block_work_frac")
    steps = max(s.get("decode_steps") or 0, 1)
    return {
        "requests": requests,
        "tokens_total": load["tokens_total"],
        "wall_s": load["wall_s"],
        "errors": load["errors"],
        "decode_steps": s.get("decode_steps"),
        "decode_pages_live": s.get("decode_pages_live"),
        "decode_pages_window": s.get("decode_pages_window"),
        "decode_block_work_frac": frac,
        "work_reduction": (round(1.0 - frac, 4)
                           if frac is not None else None),
        "decode_wall_s": s.get("decode_wall_s"),
        "prefill_wall_s": s.get("prefill_wall_s"),
        "decode_step_ms": round(
            1e3 * (s.get("decode_wall_s") or 0.0) / steps, 3),
    }


def bench_trace_overhead(model: Dict, engine: Dict, seed: int,
                         requests: int = 16, clients: int = 4) -> Dict:
    """Per-request tracing overhead guard: the SAME seeded schedule
    replays against one engine with request tracing forced ON (every
    request records spans; tail sampling still decides shipping) and
    one with it OFF — tokens/s with tracing on must stay within 2% of
    off for the SERVE gate's claim that observability rides free. Also
    microbenches the span-record hot path itself (one dict build + one
    append at the per-request cap, the worst case) against its <=20µs
    bound. Wall-clock ratios on a noisy shared CPU are recorded, not
    hard-failed; the span bound is deterministic enough to gate."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.llm_engine import (EngineConfig, LLMEngine,
                                          _resolve_dtype)
    from ray_tpu.serve.request_trace import RequestTrace

    mconf = dict(model)
    if "dtype" in mconf:
        mconf["dtype"] = _resolve_dtype(mconf["dtype"])
    workload = make_workload(requests, clients, seed,
                             mean_interarrival_s=0.002,
                             prompt_rng=(4, 12), out_rng=(8, 16))
    runs: Dict[str, Dict] = {}
    for label, on in (("on", True), ("off", False)):
        eng = LLMEngine(TransformerConfig(**mconf),
                        EngineConfig(**dict(engine, enable_trace=on)),
                        seed=seed)
        try:
            list(eng.generate_sync([3, 5, 7], 2))   # warm the jits
            # best of two replays: at these wall times thread-spawn
            # jitter rivals the effect being measured
            load = min((run_engine_load(eng, workload)
                        for _ in range(2)),
                       key=lambda r: r["wall_s"])
        finally:
            eng.shutdown()
        runs[label] = {
            "tokens_total": load["tokens_total"],
            "wall_s": load["wall_s"],
            "tokens_per_s": round(
                load["tokens_total"] / max(load["wall_s"], 1e-9), 2),
            "errors": load["errors"],
        }
    on_tps = runs["on"]["tokens_per_s"]
    off_tps = runs["off"]["tokens_per_s"]
    # span-record microbench at the per-request cap (drop-oldest is the
    # steady state of a long decode — the worst case of the hot path)
    tr = RequestTrace("req-bench-span")
    iters = 20_000
    t0 = time.perf_counter()
    for _ in range(iters):
        tr.span("DECODE", 1.0, 2.0, tokens=16)
    span_us = (time.perf_counter() - t0) / iters * 1e6
    return {
        "requests": requests,
        "tracing_on": runs["on"],
        "tracing_off": runs["off"],
        "overhead_pct": (round(100.0 * (off_tps - on_tps) / off_tps, 2)
                         if off_tps else None),
        "within_2pct": (off_tps > 0
                        and on_tps >= 0.98 * off_tps),
        "span_record_us": round(span_us, 3),
        "span_budget_us": 20.0,
    }


def bench_paged_kernel(on_tpu: bool, seed: int = 0) -> Dict:
    """Kernel-vs-reference leg at the op level: one mixed-length paged
    batch (half the sequences near-empty, half filling the window).
    Everywhere: exact-parity check (fp32-softmax tolerance) and the
    page-count work reduction the lens skipping buys. On TPU: compiled
    wall-clock of kernel vs gather reference (the dispatch the engine
    takes); on CPU the kernel runs in interpret mode, so wall times are
    reported for the reference only and the FLOP proportionality
    stands in as the gain metric."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import paged_attention, paged_work_pages

    B, H, KVH = 8, 8, 2
    D = 128 if on_tpu else 32
    bs, T = (32, 32) if on_tpu else (16, 8)
    rng = np.random.default_rng(seed)
    N = 1 + B * T
    dt = np.float32
    kc = rng.normal(size=(N, KVH, bs, D)).astype(dt)
    vc = rng.normal(size=(N, KVH, bs, D)).astype(dt)
    q = rng.normal(size=(B, 1, H, D)).astype(dt)
    bt = rng.permutation(np.arange(1, N)).astype(np.int32).reshape(B, T)
    # mixed lengths: even slots hold a handful of tokens, odd slots a
    # full window — the serving slot array under ragged traffic
    lens = np.asarray([bs + 3 if i % 2 == 0 else T * bs
                       for i in range(B)], np.int32)
    pos = (lens - 1)[:, None].astype(np.int32)

    ref_fn = jax.jit(lambda *a: paged_attention(*a, impl="reference"))
    impl = "kernel" if on_tpu else "interpret"
    ker_fn = jax.jit(lambda q_, k_, v_, bt_, p_, l_: paged_attention(
        q_, k_, v_, bt_, p_, lens=l_, impl=impl))
    ref = np.asarray(ref_fn(q, kc, vc, bt, pos))
    ker = np.asarray(ker_fn(q, kc, vc, bt, pos, lens))
    parity = float(np.max(np.abs(ref - ker)))

    pages_live = int(np.sum(paged_work_pages(lens, bs)))
    pages_window = B * T
    out = {
        "batch": B, "block_size": bs, "table_len": T,
        "heads": H, "kv_heads": KVH, "head_dim": D,
        "lens": lens.tolist(),
        "parity_max_abs": round(parity, 8),
        "pages_live": pages_live,
        "pages_window": pages_window,
        "work_reduction": round(1.0 - pages_live / pages_window, 4),
        "kernel_mode": "compiled" if on_tpu else "interpret",
    }

    def _time(fn, args, iters=20):
        r = fn(*args)
        jax.block_until_ready(r)
        t0 = time.monotonic()
        for _ in range(iters):
            r = fn(*args)
        jax.block_until_ready(r)
        return (time.monotonic() - t0) / iters

    wall_ref = _time(ref_fn, (q, kc, vc, bt, pos))
    out["wall_ref_ms"] = round(wall_ref * 1e3, 4)
    if on_tpu:
        # interpret-mode wall is interpreter overhead, not kernel cost:
        # only the compiled TPU kernel is timed against the reference
        wall_ker = _time(ker_fn, (q, kc, vc, bt, pos, lens))
        out["wall_kernel_ms"] = round(wall_ker * 1e3, 4)
        out["kernel_speedup"] = round(wall_ref / wall_ker, 3) \
            if wall_ker else None
    return out


# ------------------------------------------------- disaggregated legs
def _disagg_fleet_run(name: str, model: Dict, engine: Dict,
                      workload: List[dict], clients: int,
                      decode_slots: int,
                      timeout_s: float = 600.0) -> Dict:
    """One disaggregated measurement: 1 prefill + 1 decode replica
    (2 procs), KV shipped between them, the decode replica running
    ``decode_slots`` slots since it never interleaves prefill chunks.
    Returns the load plus the hand-off accounting from both fleets."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import api as serve_api
    from ray_tpu.serve.disagg import deploy_disaggregated

    router = deploy_disaggregated(
        model, engine, name=name, num_prefill=1, num_decode=1,
        decode_slots=decode_slots,
        ray_actor_options=_REPLICA_ACTOR_OPTIONS,
        max_ongoing_requests=4 * clients + 8)
    # one throwaway request compiles both fleets' programs (and the
    # hand-off path) outside the measured window
    list(router.options(stream=True).generate.remote(
        workload[0]["prompt"][:4], 2))
    load = run_load(lambda: router, workload, clients,
                    timeout_s=timeout_s)
    ctrl = serve_api._controller_or_none()
    pf = ray_tpu.get(ctrl.get_replicas.remote(f"{name}-prefill"))
    dc = ray_tpu.get(ctrl.get_replicas.remote(f"{name}-decode"))
    pstats = [(ray_tpu.get(r.stats.remote(), timeout=60) or {}
               ).get("engine") or {} for r in pf]
    dstats = [(ray_tpu.get(r.stats.remote(), timeout=60) or {}
               ).get("engine") or {} for r in dc]
    audits = [ray_tpu.get(r.handle_request.remote("pool_audit"),
                          timeout=60) for r in pf + dc]
    serve.delete(f"{name}-prefill")
    serve.delete(f"{name}-decode")
    adopts = sum(e.get("kv_adopts") or 0 for e in dstats)
    ship_bytes = sum(e.get("kv_adopt_bytes") or 0 for e in dstats)
    ship_wall = sum(e.get("kv_ship_wall_s") or 0.0 for e in dstats)
    occ = {}
    for e in dstats:
        for k, v in (e.get("occupancy_hist") or {}).items():
            occ[int(k)] = occ.get(int(k), 0) + v
    steps = sum(occ.values())
    mean_occ = (sum(k * v for k, v in occ.items()) / steps
                if steps else 0.0)
    return {
        "replicas": 2,
        "decode_slots": decode_slots,
        "tokens_per_s": load["tokens_per_s"],
        "tokens_per_s_chip": round(load["tokens_per_s"] / 2, 2),
        "ttft_ms": load["ttft_ms"],
        "inter_token_ms": load["inter_token_ms"],
        "wall_s": load["wall_s"],
        "tokens_total": load["tokens_total"],
        "requests_done": load["requests_done"],
        "errors": load["errors"],
        "router": dict(router.stats),
        "kv_adopts": adopts,
        "kv_ship_bytes_total": ship_bytes,
        "kv_ship_wall_s": round(ship_wall, 4),
        "kv_ship_bytes_per_request": (round(ship_bytes / adopts)
                                      if adopts else None),
        "kv_ship_ms_per_request": (round(1e3 * ship_wall / adopts, 3)
                                   if adopts else None),
        "kv_exports": sum(e.get("kv_exports") or 0 for e in pstats),
        "decode_slot_occupancy": round(mean_occ / decode_slots, 4)
        if decode_slots else None,
        "pool_audits_clean": all(a == [] for a in audits),
    }


def bench_disagg(model: Dict, engine: Dict, seed: int, clients: int,
                 requests: int, mean_interarrival_s: float,
                 prompt_rng, out_rng, timeout_s: float = 600.0) -> Dict:
    """The disaggregation comparison at equal chip count: 1 prefill +
    1 decode replica (decode running 2x the slots — it never
    interleaves prefill) vs 2 colocated replicas, same seeded Poisson
    schedule of long-prefill/short-decode requests. Long prompts make
    colocated replicas stall decode behind chunk trains; the decode
    fleet never does, which is the tokens/s/chip claim. Also reports
    the hand-off's measured cost: KV bytes + wall per shipped
    request."""
    workload = make_workload(requests, clients, seed,
                             mean_interarrival_s=mean_interarrival_s,
                             prompt_rng=prompt_rng, out_rng=out_rng)
    coloc = _fleet_leg("llm_disagg_base", model, engine, workload,
                       clients, replicas=2, policy="gauge",
                       timeout_s=timeout_s)
    disagg = _disagg_fleet_run(
        "llm_disagg", model, engine, workload, clients,
        decode_slots=2 * engine["decode_slots"], timeout_s=timeout_s)
    disagg["clients"] = clients
    disagg["requests"] = requests
    disagg["kv_wire"] = engine.get("kv_wire", "bf16")
    disagg["colocated"] = coloc
    disagg["vs_colocated"] = (
        round(disagg["tokens_per_s_chip"] / coloc["tokens_per_s_chip"],
              3) if coloc["tokens_per_s_chip"] else None)
    return disagg


def bench_disagg_parity(model: Dict, engine: Dict, seed: int) -> Dict:
    """Greedy bit-parity, disagg on vs off: the same prompt decoded
    colocated and via prefill_export -> ship -> submit_adopt on a
    SECOND engine (same seed => identical params) must produce
    bit-identical token streams on the exact "bf16" wire; the int8
    wire must stay within quantization tolerance (identical tokens are
    typical but not guaranteed, so only exactness of the default wire
    gates)."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.llm_engine import (EngineConfig, LLMEngine,
                                          _resolve_dtype)

    mconf = dict(model)
    if "dtype" in mconf:
        mconf["dtype"] = _resolve_dtype(mconf["dtype"])
    rng = random.Random(seed + 7)
    prompt = [rng.randrange(2, 128)
              for _ in range(3 * engine["kv_block_size"] + 3)]
    out: Dict[str, Dict] = {}
    for wire in ("bf16", "int8"):
        a = LLMEngine(TransformerConfig(**mconf),
                      EngineConfig(**dict(engine, kv_wire=wire)),
                      seed=seed)
        b = LLMEngine(TransformerConfig(**mconf),
                      EngineConfig(**dict(engine, kv_wire=wire)),
                      seed=seed)
        try:
            ref = list(a.generate_sync(prompt, 16))
            payload = a.prefill_export(prompt)
            req = b.submit_adopt(payload, max_new_tokens=16)
            got = _drain_request(b, req)
            out[wire] = {
                "bit_identical": ref == got,
                "tokens": len(got),
                "wire_bytes": payload["wire_bytes"],
            }
        finally:
            a.shutdown()
            b.shutdown()
    out["ok"] = bool(out["bf16"]["bit_identical"])
    return out


def _drain_request(engine, req) -> List[int]:
    from ray_tpu.serve.llm_engine import _DONE
    toks: List[int] = []
    try:
        while True:
            item = req.out.get(timeout=60)
            if item is _DONE:
                return toks
            if isinstance(item, BaseException):
                raise item
            toks.append(item)
    finally:
        engine.cancel(req)


def bench_migration(model: Dict, engine: Dict, seed: int,
                    sessions: int = 4, turns: int = 3) -> Dict:
    """Warm-prefix migration across a drain, A/B: a victim engine is
    warmed with ``sessions`` distinct shared prefixes (``turns``
    requests each, so the trie chains carry hits), then its warm
    chains are exported and imported into survivor A; survivor B
    starts cold (the no-migration drain). The SAME single-pass replay
    (one request per session) runs on each: A's prefix hit rate must
    strictly beat B's, which only scores within-replay repeats (none
    here)."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.llm_engine import (EngineConfig, LLMEngine,
                                          _resolve_dtype)

    mconf = dict(model)
    if "dtype" in mconf:
        mconf["dtype"] = _resolve_dtype(mconf["dtype"])
    bs = engine["kv_block_size"]
    rng = random.Random(seed + 13)
    prefixes = [[rng.randrange(2, 128) for _ in range(3 * bs)]
                for _ in range(sessions)]

    def make(tag):
        return LLMEngine(TransformerConfig(**mconf),
                         EngineConfig(**engine), seed=seed,
                         replica_tag=tag)

    victim = make("victim")
    surv_a = make("survivor_migrated")
    surv_b = make("survivor_cold")
    try:
        for p in prefixes:
            for t in range(turns):
                list(victim.generate_sync(p + [40 + t], 4))
        payload = victim.export_warm_prefixes(min_hits=1)
        migrated = surv_a.import_warm_prefixes(payload) \
            if payload is not None else 0

        def replay(eng):
            for i, p in enumerate(prefixes):
                list(eng.generate_sync(p + [99, i], 4))
            s = eng.stats()
            return {
                "prefix_hit_blocks": s["prefix_hit_blocks_total"],
                "prompt_blocks": s["prompt_blocks_total"],
                "prefix_hit_rate": s["prefix_hit_rate"] or 0.0,
            }

        with_mig = replay(surv_a)
        without = replay(surv_b)
        audits = [victim.pool_audit(), surv_a.pool_audit(),
                  surv_b.pool_audit()]
    finally:
        victim.shutdown()
        surv_a.shutdown()
        surv_b.shutdown()
    return {
        "sessions": sessions,
        "turns": turns,
        "migrated_blocks": migrated,
        "payload_bytes": (payload or {}).get("wire_bytes"),
        "with_migration": with_mig,
        "without_migration": without,
        "hit_retention": round(
            with_mig["prefix_hit_rate"]
            - without["prefix_hit_rate"], 4),
        "migration_wins": with_mig["prefix_hit_rate"]
        > without["prefix_hit_rate"],
        "pool_audits_clean": all(a == [] for a in audits),
    }


def _scale_up_run(name: str, model: Dict, engine: Dict,
                  workload: List[dict], clients: int,
                  autoscale: bool, timeout_s: float):
    """One measurement of the scale-up comparison: deploy (with or
    without the gauge-driven autoscaler), replay the schedule, and
    return (load, per-replica token counts)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import api as serve_api

    kw: Dict = {"max_ongoing_requests": 4 * clients + 8}
    if autoscale:
        kw["autoscaling_config"] = {
            "min_replicas": 1, "max_replicas": 2,
            # classic ongoing-request pressure is hidden by continuous
            # batching; scale on the ENGINE backlog instead
            "target_ongoing_requests": 1e9,
            "target_queue_depth": 1.0,
            "upscale_delay_s": 0.5,
            "downscale_delay_s": 3600.0,
        }
    else:
        kw["num_replicas"] = 1
    dep = serve.deployment(
        name=name, ray_actor_options=_REPLICA_ACTOR_OPTIONS,
        **kw)(serve.LLMServer)
    serve.run(dep.bind(model=model, engine=engine), name=name)
    handle = serve.get_app_handle(name)
    list(handle.options(stream=True).generate.remote([2, 3, 5], 2))
    load = run_load(lambda: serve.get_app_handle(name), workload,
                    clients, timeout_s=timeout_s,
                    handle_opts={"routing_policy": "gauge"})
    ctrl = serve_api._controller_or_none()
    reps = ray_tpu.get(ctrl.get_replicas.remote(name))
    stats = [ray_tpu.get(r.stats.remote(), timeout=60) for r in reps]
    per_replica = [(s.get("engine") or {}).get("tokens_total") or 0
                   for s in stats]
    serve.delete(name)
    return load, per_replica


def _scale_up_leg(model: Dict, engine: Dict, seed: int, clients: int,
                  requests: int, mean_interarrival_s: float,
                  timeout_s: float = 300.0) -> Dict:
    """Autoscaling fleet under load: a deliberately backlogged single
    replica must scale up MID-RUN off its engine gauges, the gauge
    router must start sending traffic to the new replica, and tail
    TTFT must recover. Recovery is measured against the SAME seeded
    schedule on a pinned 1-replica fleet: ``ttft_recovery`` =
    late-half p99 TTFT without the autoscaler / with it (> 1 means
    the added replica absorbed the backlog)."""
    # sustained marginal overload, not a burst: arrivals spread across
    # the whole leg so the single-replica baseline's queue KEEPS
    # growing while the autoscaled fleet's second replica (joining
    # warm — LLMServer compiles in __init__) absorbs the tail
    workload = make_workload(requests, clients, seed,
                             mean_interarrival_s=mean_interarrival_s,
                             prompt_rng=(4, 12), out_rng=(32, 48))

    def late_p99(load) -> Optional[float]:
        ttfts = [t for _, t in load.get("ttft_series") or []]
        return _percentile(ttfts[len(ttfts) // 2:], 99)

    auto, per_replica = _scale_up_run(
        "llm_scaleup", model, engine, workload, clients,
        autoscale=True, timeout_s=timeout_s)
    base, _ = _scale_up_run(
        "llm_scaleup_base", model, engine, workload, clients,
        autoscale=False, timeout_s=timeout_s)
    late_auto, late_base = late_p99(auto), late_p99(base)
    total = sum(per_replica) or 1
    new_tokens = min(per_replica) if len(per_replica) > 1 else 0
    return {
        "requests": requests,
        "clients": clients,
        "replicas_end": len(per_replica),
        "per_replica_tokens": per_replica,
        "new_replica_tokens": new_tokens,
        # fraction of fleet tokens the mid-run replica served — the
        # machine-independent proof that routing reached it (wall-clock
        # recovery needs one chip per replica; on a shared CPU core a
        # second replica only time-slices, so ttft_recovery < 1 there)
        "new_replica_share": round(new_tokens / total, 4),
        "scaled_up": len(per_replica) > 1,
        "tokens_per_s": auto["tokens_per_s"],
        "ttft_ms": auto["ttft_ms"],
        "ttft_p99_late_ms": _ms(late_auto),
        "baseline_tokens_per_s": base["tokens_per_s"],
        "baseline_ttft_ms": base["ttft_ms"],
        "baseline_ttft_p99_late_ms": _ms(late_base),
        "ttft_recovery": (round(late_base / late_auto, 3)
                          if late_base and late_auto else None),
        "errors": auto["errors"] + base["errors"],
        "wall_s": auto["wall_s"],
    }


def _fleet_leg(name: str, model: Dict, engine: Dict, workload: List[dict],
               clients: int, replicas: int, policy: str,
               timeout_s: float = 600.0) -> Dict:
    """One fleet measurement: deploy ``replicas`` copies, warm every
    replica's jitted programs round-robin outside the window, replay
    the schedule with ``policy`` routing, and fold in the per-replica
    engine counters (prefix hits, speculation acceptance)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import api as serve_api

    dep = serve.deployment(
        name=name, num_replicas=replicas,
        ray_actor_options=_REPLICA_ACTOR_OPTIONS,
        max_ongoing_requests=4 * clients + 8)(serve.LLMServer)
    serve.run(dep.bind(model=model, engine=engine), name=name)
    handle = serve.get_app_handle(name)
    for _ in range(replicas):
        list(handle.options(
            stream=True, routing_policy="round_robin").generate.remote(
                workload[0]["prompt"][:4], 2))
    load = run_load(lambda: serve.get_app_handle(name), workload,
                    clients, timeout_s=timeout_s,
                    handle_opts={"routing_policy": policy})
    ctrl = serve_api._controller_or_none()
    reps = ray_tpu.get(ctrl.get_replicas.remote(name))
    stats = [ray_tpu.get(r.stats.remote(), timeout=60) for r in reps]
    engines = [s.get("engine") or {} for s in stats]
    hit = sum(e.get("prefix_hit_blocks_total") or 0 for e in engines)
    pblocks = sum(e.get("prompt_blocks_total") or 0 for e in engines)
    drafted = sum((e.get("spec") or {}).get("drafted") or 0
                  for e in engines)
    accepted = sum((e.get("spec") or {}).get("accepted") or 0
                   for e in engines)
    serve.delete(name)
    return {
        "replicas": replicas,
        "routing": policy,
        "tokens_per_s": load["tokens_per_s"],
        "tokens_per_s_chip": round(load["tokens_per_s"] / replicas, 2),
        "ttft_ms": load["ttft_ms"],
        "inter_token_ms": load["inter_token_ms"],
        "wall_s": load["wall_s"],
        "tokens_total": load["tokens_total"],
        "requests_done": load["requests_done"],
        "errors": load["errors"],
        "prefix_hit_blocks": hit,
        "prompt_blocks": pblocks,
        "prefix_hit_rate": round(hit / pblocks, 4) if pblocks else None,
        "spec_drafted": drafted,
        "spec_accepted": accepted,
        "spec_acceptance": (round(accepted / drafted, 4)
                            if drafted else None),
        "per_replica_tokens": [e.get("tokens_total") for e in engines],
    }


def bench_fleet(model: Dict, engine: Dict, replicas: int, clients: int,
                requests: int, seed: int, sys_prompt_tokens: int,
                prompt_rng, out_rng, mean_interarrival_s: float,
                timeout_s: float = 600.0) -> Dict:
    """The fleet comparison: prefix sharing + prompt-lookup speculation
    + gauge routing vs the sharing-off / speculation-off / round-robin
    baseline on the SAME seeded schedule. Every prompt opens with one
    common system prompt ``sys_prompt_tokens`` long (>= 4 KV blocks)."""
    rng = random.Random(seed + 1)
    system_prompt = [rng.randrange(2, 128)
                     for _ in range(sys_prompt_tokens)]
    workload = make_workload(requests, clients, seed,
                             mean_interarrival_s=mean_interarrival_s,
                             prompt_rng=prompt_rng, out_rng=out_rng,
                             system_prompt=system_prompt)
    eng_on = dict(engine, enable_prefix_sharing=True, spec_tokens=4)
    eng_off = dict(engine, enable_prefix_sharing=False, spec_tokens=0)
    fleet = _fleet_leg("llm_fleet", model, eng_on, workload, clients,
                       replicas, policy="gauge", timeout_s=timeout_s)
    base = _fleet_leg("llm_fleet_base", model, eng_off, workload,
                      clients, replicas, policy="round_robin",
                      timeout_s=timeout_s)
    fleet["system_prompt_tokens"] = sys_prompt_tokens
    fleet["clients"] = clients
    fleet["requests"] = requests
    fleet["baseline"] = base
    fleet["vs_baseline"] = (
        round(fleet["tokens_per_s_chip"] / base["tokens_per_s_chip"], 2)
        if base["tokens_per_s_chip"] else None)
    return fleet


#: actor options of every LLMServer replica the legs deploy: one chip
#: each once bench() has found a TPU (replicas are worker processes and
#: each must own its chip; this driver never opens one)
_REPLICA_ACTOR_OPTIONS: Dict = {}

_CLUSTERLESS_TAG = "CLUSTERLESS_LEGS "


def clusterless_legs(spec: Dict) -> Dict:
    """The five legs that need a device but no cluster — paged-kernel
    op comparison, mixed-length engine run, trace overhead, disagg
    parity, warm-prefix migration — in the process that calls this.
    bench() runs it in a child (``--clusterless-legs``) that owns the
    chip and exits before the cluster's replicas need it."""
    model, engine, seed = spec["model"], spec["engine"], spec["seed"]
    return {
        "paged": bench_paged_kernel(spec["on_tpu"], seed=seed),
        "mixed": bench_mixed_lengths(model, engine, seed=seed,
                                     **spec["mixed_kw"]),
        "trace": bench_trace_overhead(model, engine, seed=seed,
                                      **spec["trace_kw"]),
        "parity": bench_disagg_parity(model, engine, seed=seed),
        "migration": bench_migration(model, engine, seed=seed),
    }


def _clusterless_legs_in_child(spec: Dict) -> Dict:
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--clusterless-legs", json.dumps(spec)],
        stdout=subprocess.PIPE, text=True, timeout=7200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"clusterless legs child failed (rc={proc.returncode})")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_CLUSTERLESS_TAG):
            return json.loads(line[len(_CLUSTERLESS_TAG):])
    raise RuntimeError("clusterless legs child printed no result")


def bench(smoke: bool = False, clients: int = 8, requests: int = 24,
          seed: int = 0, fleet_replicas: int = 0,
          fleet_clients: int = 0, fleet_requests: int = 0,
          scale_up: bool = True) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.accelerators import probe_devices

    # a child asks jax what is here: this process starts the workers
    # that will own the chips and must not hold one itself
    probe = probe_devices()
    backend = probe["platform"]
    on_tpu = backend == "tpu"
    _REPLICA_ACTOR_OPTIONS.clear()
    if on_tpu:
        _REPLICA_ACTOR_OPTIONS["num_tpus"] = 1
    if smoke:
        clients, requests = min(clients, 4), min(requests, 6)
        model = {"vocab_size": 128, "d_model": 32, "n_layers": 2,
                 "n_heads": 4, "head_dim": 8, "d_ff": 64,
                 "max_seq_len": 128, "rotary_dim": 8, "dtype": "float32",
                 "remat_policy": "none"}
        engine = {"decode_slots": clients, "kv_block_size": 8,
                  "max_seq_len": 64, "prefill_chunk": 16}
        workload = make_workload(requests, clients, seed,
                                 mean_interarrival_s=0.02,
                                 prompt_rng=(4, 12), out_rng=(6, 10))
        fleet_kw = dict(replicas=fleet_replicas or 2,
                        clients=fleet_clients or 6,
                        requests=fleet_requests or 12,
                        sys_prompt_tokens=4 * engine["kv_block_size"],
                        prompt_rng=(2, 6), out_rng=(6, 10),
                        mean_interarrival_s=0.02, timeout_s=120.0)
        mixed_kw = dict(requests=10, clients=4)
        trace_kw = dict(requests=8, clients=4)
        scale_kw = dict(clients=8, requests=40,
                        mean_interarrival_s=0.06, timeout_s=150.0)
        disagg_kw = dict(clients=4, requests=8,
                         mean_interarrival_s=0.02,
                         prompt_rng=(16, 40), out_rng=(4, 8),
                         timeout_s=120.0)
    elif on_tpu:
        model = {"vocab_size": 32000, "d_model": 2048, "n_layers": 8,
                 "n_heads": 16, "head_dim": 128, "d_ff": 8192,
                 "max_seq_len": 2048, "rotary_dim": 64,
                 "dtype": "bfloat16", "remat_policy": "none"}
        engine = {"decode_slots": 32, "kv_block_size": 32,
                  "max_seq_len": 1024, "prefill_chunk": 256}
        workload = make_workload(requests, clients, seed,
                                 mean_interarrival_s=0.05,
                                 prompt_rng=(32, 512), out_rng=(32, 128))
        fleet_kw = dict(replicas=fleet_replicas or 4,
                        clients=fleet_clients or 200,
                        requests=fleet_requests or 400,
                        sys_prompt_tokens=4 * engine["kv_block_size"],
                        prompt_rng=(16, 128), out_rng=(32, 128),
                        mean_interarrival_s=0.02)
        mixed_kw = dict(requests=64, clients=32)
        trace_kw = dict(requests=48, clients=16)
        scale_kw = dict(clients=64, requests=128,
                        mean_interarrival_s=0.005)
        disagg_kw = dict(clients=64, requests=128,
                         mean_interarrival_s=0.01,
                         prompt_rng=(256, 768), out_rng=(16, 64))
    else:
        # CPU sizing: wide enough that a decode step is weight-stream /
        # gemv bound, so step cost is nearly batch-independent — the
        # same regime a real chip is in at decode batch 1 (MXU idle),
        # which is what continuous batching amortizes. Arrivals are
        # compressed so the queue saturates the slots (the serial
        # baseline queues identically).
        model = {"vocab_size": 1024, "d_model": 256, "n_layers": 2,
                 "n_heads": 4, "head_dim": 32, "d_ff": 1024,
                 "max_seq_len": 256, "rotary_dim": 16,
                 "dtype": "float32", "remat_policy": "none"}
        engine = {"decode_slots": clients, "kv_block_size": 16,
                  "max_seq_len": 128, "prefill_chunk": 32}
        workload = make_workload(requests, clients, seed,
                                 mean_interarrival_s=0.005,
                                 prompt_rng=(8, 24), out_rng=(24, 48))
        fleet_kw = dict(replicas=fleet_replicas or 2,
                        clients=fleet_clients or 32,
                        requests=fleet_requests or 64,
                        sys_prompt_tokens=4 * engine["kv_block_size"],
                        prompt_rng=(4, 16), out_rng=(16, 32),
                        mean_interarrival_s=0.01)
        mixed_kw = dict(requests=24, clients=8)
        trace_kw = dict(requests=16, clients=4)
        scale_kw = dict(clients=12, requests=100,
                        mean_interarrival_s=0.06)
        # long-prefill/short-decode shape: prompts span 2-3 prefill
        # chunks while outputs stay short of the prompt, so colocated
        # replicas interleave chunk trains with half-batch decode — the
        # regime disaggregation targets (the decode fleet runs 2x slots
        # at weight-stream-bound step cost, halving decode steps)
        disagg_kw = dict(clients=8, requests=32,
                         mean_interarrival_s=0.02,
                         prompt_rng=(48, 96), out_rng=(16, 32))

    # clusterless legs first, in one child: the paged-kernel op
    # comparison and the engine runs need a device, not the cluster
    legs = _clusterless_legs_in_child({
        "on_tpu": on_tpu, "model": model, "engine": engine,
        "seed": seed, "mixed_kw": mixed_kw, "trace_kw": trace_kw})
    paged, mixed, trace, parity, migration = (
        legs[k] for k in ("paged", "mixed", "trace", "parity",
                          "migration"))

    ray_tpu.init(num_cpus=max(8, clients + 4,
                              fleet_kw["clients"] // 2 + 6),
                 num_tpus=probe["count"] if on_tpu else None,
                 _num_initial_workers=3, ignore_reinit_error=True)
    modes = {}
    stats = {}
    try:
        for mode, slots in (("continuous", engine["decode_slots"]),
                            ("serial", 1)):
            ecfg = dict(engine, decode_slots=slots)
            name = f"llm_{mode}"
            dep = serve.deployment(
                name=name, ray_actor_options=_REPLICA_ACTOR_OPTIONS,
                max_ongoing_requests=4 * clients + 8)(
                    serve.LLMServer)
            serve.run(dep.bind(model=model, engine=ecfg), name=name)
            handle = serve.get_app_handle(name)
            # one throwaway request compiles prefill+decode outside the
            # measured window (admission itself never recompiles)
            list(handle.options(stream=True).generate.remote(
                workload[0]["prompt"][:4], 2))
            modes[mode] = run_load(
                lambda name=name: serve.get_app_handle(name),
                workload, clients)
            stats[mode] = handle.stats.remote().result(timeout_s=60)
            serve.delete(name)
        # fleet leg: shared system prompt, gauge routing, prefix
        # sharing + speculation vs the round-robin no-sharing baseline
        t_fleet = time.monotonic()
        fleet = bench_fleet(model, engine, seed=seed, **fleet_kw)
        fleet["leg_wall_s"] = round(time.monotonic() - t_fleet, 2)
        # disaggregated prefill/decode vs colocated at equal chip count,
        # same seeded long-prefill/short-decode schedule
        t_disagg = time.monotonic()
        disagg = bench_disagg(model, engine, seed=seed, **disagg_kw)
        disagg["leg_wall_s"] = round(time.monotonic() - t_disagg, 2)
        # autoscaling fleet under load: a backlogged single replica
        # must scale up MID-RUN and TTFT must recover (--scale-up-mid-
        # load; a deliberately small engine so the backlog forms fast)
        scale = None
        if scale_up:
            t_scale = time.monotonic()
            scale = _scale_up_leg(
                model, dict(engine, decode_slots=1), seed=seed,
                **scale_kw)
            scale["leg_wall_s"] = round(time.monotonic() - t_scale, 2)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    cont, ser = modes["continuous"], modes["serial"]
    n_chips = 1   # the engine decodes on one device
    vs_serial = (round(cont["tokens_per_s"] / ser["tokens_per_s"], 2)
                 if ser["tokens_per_s"] else None)
    return {
        "metric": "serve_tokens_per_s_chip",
        "value": round(cont["tokens_per_s"] / n_chips, 2),
        "unit": "tokens/s/chip",
        "vs_serial": vs_serial,
        "detail": {
            "backend": backend,
            "n_chips": n_chips,
            # record the host's core count: CPU-backend ratios (e.g.
            # vs_serial) compress when every replica time-slices one
            # core, and the baseline locks read this to judge them
            "host_cpus": os.cpu_count(),
            "clients": clients,
            "requests": requests,
            "seed": seed,
            "model": model,
            "engine": engine,
            "continuous": cont,
            "serial": ser,
            "occupancy_hist": stats["continuous"].get("occupancy_hist"),
            "engine_stats": {m: {k: s.get(k) for k in
                                 ("tokens_total", "decode_steps",
                                  "prefill_chunks", "free_blocks",
                                  "total_blocks")}
                             for m, s in stats.items()},
            "fleet": fleet,
            "disagg": disagg,
            "disagg_parity": parity,
            "migration": migration,
            "paged_kernel": paged,
            "mixed_len": mixed,
            "trace_overhead": trace,
            "scale_up": scale,
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload for CI (subprocess smoke test)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet-replicas", type=int, default=0,
                    help="fleet-leg replica count (0 = per-backend "
                         "default: 2 CPU / 4 TPU)")
    ap.add_argument("--fleet-clients", type=int, default=0,
                    help="fleet-leg Poisson clients (0 = default)")
    ap.add_argument("--fleet-requests", type=int, default=0,
                    help="fleet-leg request count (0 = default)")
    ap.add_argument("--clusterless-legs", help=argparse.SUPPRESS)
    ap.add_argument("--scale-up-mid-load",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="run the autoscaling-fleet-under-load leg "
                         "(one backlogged replica must scale up "
                         "mid-run; --no-scale-up-mid-load skips it)")
    args = ap.parse_args()
    if args.clusterless_legs:
        out = clusterless_legs(json.loads(args.clusterless_legs))
        print(_CLUSTERLESS_TAG + json.dumps(out), flush=True)
        return 0
    rec = bench(smoke=args.smoke, clients=args.clients,
                requests=args.requests, seed=args.seed,
                fleet_replicas=args.fleet_replicas,
                fleet_clients=args.fleet_clients,
                fleet_requests=args.fleet_requests,
                scale_up=args.scale_up_mid_load)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
