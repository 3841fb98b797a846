"""A ``kind: train`` cell: ``ParallelPlan(...).build(cfg)`` and its
``step``, as a user calls them, in the one process that holds the
cell's chips. Set-up is state, correctness check and warm-up; the
window is whole blocks of steps until ``--seconds`` have passed; with
``--trace 1`` a traced block follows the window."""
import time
from typing import Any, Dict

from benchmarks import spec, stats, traffic
from benchmarks.spec import log

#: the window's last loss may stand this far over its first, and no more
LOSS_RISE_LIMIT = 0.05


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    from ray_tpu.util import compile_cache
    compile_cache.enable()
    compile_cache.stats()
    import dataclasses

    import jax
    import numpy as np

    from benchmarks import check, harness
    from ray_tpu.models import TransformerConfig
    from ray_tpu.parallel.plan import ParallelPlan

    device = harness.device_facts(cell.chips, cell.rehearse)
    compiles = harness.CompileCounter()
    kw = cell.model_kwargs()
    kw["dtype"] = harness.resolve_dtype(kw["dtype"])
    p = cell.params
    cfg = dataclasses.replace(
        TransformerConfig(**kw), max_seq_len=p["seq"],
        remat_policy=p["remat_policy"])
    plan = ParallelPlan(**p["plan"])
    devices = jax.devices()[:plan.world_size]
    prog = plan.build(cfg, learning_rate=p["learning_rate"],
                      seed=spec.weight_seed(seed), devices=devices,
                      telemetry_interval_s=0)
    jax.block_until_ready(prog.state)
    log(f"{cell.name}: {plan.describe()} on {len(devices)} x "
        f"{device['kind']}, depth {cfg.n_layers}, "
        f"{harness.matmul_params(prog.state['params']) / 1e6:.0f}M matmul "
        f"params, state ready at {time.time() - t_start:.1f} s")

    verdict = check.train_check(cell, cfg, prog, seed)
    log(f"{cell.name}: check {verdict}")

    batches = traffic.train_batches(p, seed, cfg.vocab_size)
    tokens_per_step = p["batch"] * p["seq"]
    block = p["block_steps"]
    losses = []

    def step(i: int) -> float:
        res = prog.step(batches[i % len(batches)])
        jax.block_until_ready(prog.state)
        losses.append(res.loss)
        return time.perf_counter()

    for i in range(p["warm_steps"]):
        step(i)
    compiles.reset()
    heart = spec.Heartbeat()
    setup_s = time.time() - t_start
    # ---- the window: whole blocks until --seconds have passed
    ends = [time.perf_counter()]
    t0 = ends[0]
    n = 0
    while ends[-1] - t0 < seconds:
        for _ in range(block):
            ends.append(step(n))
            n += 1
    window_compiles = compiles.n
    measured = losses[p["warm_steps"]:]
    log(f"{cell.name}: {n} steps in {ends[-1] - t0:.2f} s; an idle thread "
        f"woke at worst [s late, at] {heart.worst}; step ms: "
        + " ".join(f"{1e3 * d:.1f}" for d in stats.step_times(ends)))
    log(f"{cell.name}: window mean "
        f"{stats.window_tokens_per_s(ends, tokens_per_step):.1f} tokens/s; "
        f"blocks {[round(b, 1) for b in stats.block_tokens_per_s(ends, tokens_per_step, block)]}"
        f"; loss by step (warm-up first): "
        + " ".join(f"{x:.4f}" for x in losses))

    summary = None
    if trace:
        tracer = harness.Tracer(harness.trace_dir(cell.name), cell.rehearse)
        tracer.start()
        for _ in range(p["trace_steps"]):
            step(n)
            n += 1
        tracer.close()
        summary = tracer.finish()
        log(f"{cell.name}: traced idle seconds by phase "
            f"{ {k: round(v, 5) for k, v in summary['idle_by_phase'].items()} }")

    # the loss must be finite at every step and must not have risen: on
    # random tokens it starts near ln(vocab) and can only creep down
    loss_rise = [float(measured[-1] - measured[0]), LOSS_RISE_LIMIT]
    loss_ok = bool(np.all(np.isfinite(losses))) and loss_rise[0] <= loss_rise[1]
    obs = {
        "setup_s": setup_s, "window_s": ends[-1] - t0,
        "train": {"step_ends": [e - t0 for e in ends],
                  "tokens_per_step": tokens_per_step, "block_steps": block,
                  "seq": p["seq"], "n_layers": cfg.n_layers,
                  "n_heads": cfg.n_heads, "head_dim": cfg.head_dim,
                  "matmul_params":
                      harness.matmul_params(prog.state["params"]),
                  "chips": len(devices)},
        "compiles_in_window": window_compiles,
        "device": {**device, "count": len(devices),
                   **harness.memory_facts(devices)},
        "trace": summary,
    }
    return {"correct": bool(verdict["ok"] and loss_ok),
            "attempted": n, "failed": 0 if loss_ok else 1, "obs": obs,
            "beats": {"driver": list(heart.worst)},
            "compared": {**{k: [verdict["errors"][k], verdict["tol"][k]]
                            for k in verdict["errors"]},
                         "loss_rise": loss_rise},
            "notes": {"check": verdict, "loss_first_last":
                      [measured[0], measured[-1]]}}
