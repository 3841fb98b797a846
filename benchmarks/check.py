"""The comparison that decides ``correct``: the system against the
configuration's plain float32 reference, on a small seeded sample at
the cell's own (published) widths, in set-up and outside the window.

Tolerances, and why these: the system computes in bf16 (8 mantissa
bits, relative rounding 2^-9 = 0.2%) with float32 statistics, softmax
and accumulation. On logits (max difference over max magnitude) that
reads 0.8-0.9% through GPT-J's six parallel blocks, on the chip and in
plain bf16 XLA on the CPU alike, and ``TOL`` allows 3%. A configuration
whose blocks compound rounding further states its own number and why
under ``tolerance`` in its file (Mistral: 3.6-3.9% through eight
sequential blocks, allowed 6%). Either is far under what an 8-bit
float or a dropped term gives: at the Mistral cells' widths and depth,
layer weights rounded to 3 mantissa bits move the logits by 26%, a
reversed GQA head mapping by 141%, attention left out by 144% (float32
on the CPU, PERF.md, PR 24). The loss is a mean over a thousand tokens,
so rounding averages out: 0.2%. The gradient norm sums bf16 backward
matmuls: 3%.

A serving cell is held to the reference twice. ``serve_check`` drives
the program's chunked prefill and paged decode on a cache of its own
and compares logits, which is what can see a lower precision.
``served_check`` takes what the engine itself answered to two requests
that came through the handle, the second out of the prefix cache, and
asks that every token served be the reference's own choice to within
rounding: that is what can see a stale or misplaced page, a slot mix-up
or a wrong copy, which no private cache would show. Random weights
flip the largest logit on rounding, so tokens are not compared for
equality: a token the system chose under logits within ``tol`` of the
reference's is at most ``2 * tol`` below the reference's largest.

The weights compared are the cell's own (``harness.scale_stream`` of
the program's init; the configuration's ``weights`` group says why).
"""
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference
from benchmarks import traffic
from benchmarks.harness import rel_err, scale_stream

TOL = {"logits": 0.03, "loss": 0.002, "grad_norm": 0.03}


def tolerances(cell) -> Dict[str, float]:
    """``TOL``, with what the configuration's file states for itself."""
    own = cell.config.get("tolerance", {})
    return {k: float(own.get(k, v)) for k, v in TOL.items()}


def train_check(cell, cfg, prog, seed: int) -> Dict[str, Any]:
    """Loss and gradient norm of the program's ``lm_loss`` (the function
    its train step differentiates, same kernels, same mesh) against the
    reference, on the train state's own initial parameters."""
    import optax
    from ray_tpu.models.transformer import lm_loss
    ref = reference.load(cell.config["reference"])
    rows = max(2, prog.plan.stage_world)
    seq = int(cell.params["check_seq"])
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(rows, seq)).astype(np.int32)
    batch = {"input_ids": ids, "loss_mask": np.ones(ids.shape, np.float32)}
    mesh, rules = prog.bundle.mesh, prog.bundle.rules

    # the batch is an argument: closed over, it would be a constant of
    # the program, and every seed would compile a new one
    @jax.jit
    def system(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, batch, mesh=mesh, rules=rules),
            has_aux=True)(params)
        return loss, optax.global_norm(grads)

    params = scale_stream(prog.state["params"], cell.config["weights"])
    got = [float(x) for x in system(params, batch)]
    want = [float(x) for x in ref.loss_and_grad_norm(
        params, jnp.asarray(ids), cell.reference_hp())]
    errs = {"loss": abs(got[0] - want[0]) / abs(want[0]),
            "grad_norm": abs(got[1] - want[1]) / abs(want[1])}
    tol = tolerances(cell)
    return {"ok": all(np.isfinite(got)) and all(
        errs[k] <= tol[k] for k in errs), "errors": errs, "tol": tol,
        "system": got, "reference": want, "sample": list(ids.shape)}


def serve_check(cell, model_config, params, engine: Dict[str, Any],
                seed: int) -> Dict[str, Any]:
    """One seeded sequence through the program's chunked prefill and
    then decode steps of its paged cache (the engine's chunk, page size
    and table length, so the kernels are the engine's), teacher-forced;
    the logits that predict each of the last tokens against the
    reference's full forward pass. ``params`` are the served ones."""
    from ray_tpu.models import decode_step, init_kv_cache, prefill
    ref = reference.load(cell.config["reference"])
    bs, chunk = engine["kv_block_size"], engine["prefill_chunk"]
    table = -(-engine["max_seq_len"] // bs)
    size = traffic.check_sample(engine)
    prompt_len, n_decode = size["prompt_len"], size["n_new"]
    ids = np.random.default_rng(seed).integers(
        0, model_config.vocab_size,
        size=(prompt_len + n_decode,)).astype(np.int32)
    cache = init_kv_cache(model_config, 1 + table, bs)
    bt = jnp.arange(1, 1 + table, dtype=jnp.int32)[None]
    jit_prefill = jax.jit(functools.partial(prefill, model_config),
                          donate_argnums=(2,))
    jit_decode = jax.jit(functools.partial(decode_step, model_config),
                         donate_argnums=(2,))
    got = []
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = ids[start:start + n]
        logits, cache = jit_prefill(
            params, jnp.asarray(toks), cache, bt,
            jnp.full((1,), start, jnp.int32), jnp.full((1,), n, jnp.int32))
    got.append(logits[0, n - 1])
    for i in range(n_decode):
        pos = prompt_len + i
        logits, cache = jit_decode(
            params, jnp.asarray(ids[pos:pos + 1]), cache, bt,
            jnp.full((1,), pos, jnp.int32))
        got.append(logits[0])
    got = jnp.stack(got).astype(jnp.float32)
    want = ref.forward(params, jnp.asarray(ids)[None],
                       cell.reference_hp())[0, prompt_len - 1:]
    err = rel_err(got, want)
    agree = int(np.sum(np.argmax(np.asarray(got), -1)
                       == np.argmax(np.asarray(want), -1)))
    tol = tolerances(cell)
    return {"ok": bool(np.isfinite(err)) and err <= tol["logits"],
            "errors": {"logits": err}, "tol": tol,
            "argmax_agree": [agree, n_decode + 1],
            "sample": {"prompt_len": prompt_len, "n_decode": n_decode}}


def served_check(cell, params, samples) -> Dict[str, Any]:
    """``samples``: the prompt and the tokens the engine served for it,
    greedy. Each token against the reference's logits for its position
    in a full forward pass over prompt and answer: how far below the
    reference's largest logit the served token's lies, over the largest
    magnitude, may not pass twice the logits tolerance."""
    ref = reference.load(cell.config["reference"])
    worst, agree, total, short = 0.0, 0, 0, 0
    for sample in samples:
        prompt, served = sample["prompt"], sample["tokens"]
        short += sample["asked"] - len(served)
        if not served:
            continue
        ids = jnp.asarray(list(prompt) + list(served), jnp.int32)
        want = ref.forward(params, ids[None], cell.reference_hp())[
            0, len(prompt) - 1:len(prompt) - 1 + len(served)]
        picked = jnp.take_along_axis(
            want, ids[len(prompt):, None], axis=-1)[:, 0]
        gap = (jnp.max(want, -1) - picked) / jnp.max(jnp.abs(want))
        worst = max(worst, float(jnp.max(gap)))
        agree += int(jnp.sum(gap == 0))
        total += len(served)
    limit = 2 * tolerances(cell)["logits"]
    return {"ok": bool(total and not short and np.isfinite(worst)
                       and worst <= limit),
            "errors": {"served_token_gap": worst}, "tol": limit,
            "argmax_agree": [agree, total], "tokens_short": short}
