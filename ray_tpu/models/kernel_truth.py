"""Checks that the Pallas kernels and the cached decode path compute
what the plain XLA formulation computes, on the device they run on.

A kernel that compiles can still be wrong, and a kernel that "ran" may
have been the interpreter or the reference. Each check here runs one
kernel through the model-facing dispatcher at a caller-given shape,
reads from the lowered program whether a Mosaic custom call is really
in it, and compares against the XLA reference computed in float32 at
``highest`` matmul precision (on a TPU a float32 matmul otherwise runs
in bf16 passes). They are meant for start-up and smoke runs, outside
any timed window: ``chip_smoke.py`` runs them in the training process
and, through :meth:`ray_tpu.serve.llm_engine.LLMServer.kernel_truth`,
inside a serving replica.

Tolerances compare ``max|a - b| / max|b|``. Both sides read the same
inputs and accumulate in float32, so what separates them is rounding of
the probabilities and of the output to the compute dtype: a few units
of that dtype's epsilon (bf16 2**-8, f32 2**-23), widened for the
reductions over hundreds of keys.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.transformer import (
    TransformerConfig, apply, decode_step, init_kv_cache, prefill)
from ray_tpu.ops.attention import (
    attention_reference, multihead_attention, paged_attention)

#: relative tolerance of one kernel against the reference, by dtype
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 2e-4}
#: relative tolerance of logits through the whole (depth-cut) model:
#: every layer adds its own rounding on both sides
LOGITS_TOL = {"bfloat16": 4e-2, "float32": 5e-4}


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))),
                                             1e-30))


def _has_mosaic_call(fn, *args) -> bool:
    """True when ``fn``'s lowered program holds a Mosaic (Pallas TPU)
    custom call — absent in interpret mode and on the reference path."""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _report(name: str, impl: str, compiled: bool, errs: Dict[str, float],
            tol: float, **shape) -> Dict[str, Any]:
    return {"name": name, "impl": impl, "compiled": compiled,
            "rel_err": {k: round(v, 6) for k, v in errs.items()},
            "tol": tol, "ok": all(v <= tol for v in errs.values()),
            **shape}


def flash_truth(*, batch: int, heads: int, seq: int, head_dim: int,
                dtype=jnp.bfloat16, impl: str = "auto",
                seed: int = 0) -> Dict[str, Any]:
    """Flash forward and backward kernels against ``attention_reference``
    (and its autodiff gradients) on causal self-attention of the given
    shape. ``impl`` is the dispatcher's: ``"auto"`` on a TPU,
    ``"interpret"`` for a CPU rehearsal."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (batch, seq, heads, head_dim)
    q, k, v, do = (jax.random.normal(kk, shape, dtype) for kk in ks)

    def kernel_loss(q, k, v):
        o = multihead_attention(q, k, v, causal=True, impl=impl)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

    def ref_loss(q, k, v):
        o = attention_reference(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

    kernel_grad = jax.value_and_grad(kernel_loss, argnums=(0, 1, 2),
                                     has_aux=True)
    (_, o), (dq, dk, dv) = jax.jit(kernel_grad)(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, o_r), (dq_r, dk_r, dv_r) = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    errs = {"out": _rel_err(o, o_r), "dq": _rel_err(dq, dq_r),
            "dk": _rel_err(dk, dk_r), "dv": _rel_err(dv, dv_r)}
    return _report(
        "flash_fwd_bwd", impl, _has_mosaic_call(kernel_grad, q, k, v),
        errs, KERNEL_TOL[jnp.dtype(dtype).name], batch=batch,
        heads=heads, seq=seq, head_dim=head_dim,
        dtype=jnp.dtype(dtype).name)


def paged_truth(*, batch: int, chunk: int, heads: int, kv_heads: int,
                head_dim: int, block_size: int, table_len: int,
                dtype=jnp.bfloat16, impl: str = "auto",
                seed: int = 0) -> Dict[str, Any]:
    """Paged kernel against the XLA gather reference for ``batch``
    sequences of ``chunk`` new tokens each (``chunk=1`` is a decode
    step) over a shuffled block pool, handed over as the step programs
    hand it: the whole (here one-layer) pool with a layer index; that
    the index picks the layer is :func:`cached_logits_truth`'s to show.
    Lengths are ragged and mid-block so length skipping and the in-page
    mask are both exercised."""
    rng = np.random.default_rng(seed)
    n_blocks = 1 + batch * table_len
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (1, n_blocks, kv_heads, block_size, head_dim)
    kc = jax.random.normal(ks[0], pool, dtype)
    vc = jax.random.normal(ks[1], pool, dtype)
    q = jax.random.normal(ks[2], (batch, chunk, heads, head_dim), dtype)
    bt = rng.permutation(np.arange(1, n_blocks)).astype(np.int32) \
        .reshape(batch, table_len)
    window = table_len * block_size
    lens = rng.integers(chunk, window + 1, size=(batch,)).astype(np.int32)
    if batch > 1:
        lens[0] = window                  # one full-length sequence
        lens[-1] = min(window, chunk + block_size // 2 + 1)  # one short
    pos = (lens - chunk)[:, None] + np.arange(chunk, dtype=np.int32)
    args = (q, kc, vc, jnp.asarray(bt), jnp.asarray(pos))

    kernel = functools.partial(paged_attention, layer=0, impl=impl)
    out = jax.jit(kernel)(*args, lens=jnp.asarray(lens))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(
            paged_attention, layer=0, impl="reference"))(*args)
    return _report(
        "paged_decode" if chunk == 1 else "paged_prefill_chunk", impl,
        _has_mosaic_call(kernel, *args), {"out": _rel_err(out, ref)},
        KERNEL_TOL[jnp.dtype(dtype).name], batch=batch, chunk=chunk,
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        block_size=block_size, table_len=table_len,
        dtype=jnp.dtype(dtype).name)


def cached_logits_truth(config: TransformerConfig, params, *,
                        block_size: int, chunk: int, table_len: int,
                        prompt_len: int, n_decode: int,
                        seed: int = 0) -> Dict[str, Any]:
    """One sequence through chunked prefill and ``n_decode`` decode
    steps of the paged cache, against :func:`apply` on the whole
    sequence with the XLA reference attention, same params. Decode is
    teacher-forced from a seeded token list so both sides see the same
    inputs. Compared: the logits that predict each of the last
    ``n_decode + 1`` tokens."""
    if prompt_len + n_decode > table_len * block_size:
        raise ValueError("sequence longer than the block table window")
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, config.vocab_size,
                       size=(prompt_len + n_decode,)).astype(np.int32)
    cache = init_kv_cache(config, 1 + table_len, block_size)
    bt = jnp.arange(1, 1 + table_len, dtype=jnp.int32)[None]
    jit_prefill = jax.jit(functools.partial(prefill, config),
                          donate_argnums=(2,))
    jit_decode = jax.jit(functools.partial(decode_step, config),
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
    compiled = False
    for i in range(n_decode):
        pos = prompt_len + i
        args = (params, jnp.asarray(ids[pos:pos + 1]), cache, bt,
                jnp.full((1,), pos, jnp.int32))
        if i == 0:
            compiled = "tpu_custom_call" in \
                jit_decode.lower(*args).as_text()
        logits, cache = jit_decode(*args)
        got.append(logits[0])
    ref_cfg = dataclasses.replace(config, attn_impl="reference")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(apply, ref_cfg))(
            params, jnp.asarray(ids)[None])[0, prompt_len - 1:]
    got = jnp.stack(got)
    dt = jnp.dtype(config.dtype).name
    return _report(
        "cached_logits_vs_apply", config.paged_impl, compiled,
        {"logits": _rel_err(got, ref)}, LOGITS_TOL[dt],
        prompt_len=prompt_len, n_decode=n_decode, chunk=chunk,
        block_size=block_size, n_layers=config.n_layers, dtype=dt,
        argmax_agree=int(np.sum(np.argmax(np.asarray(got, np.float32), -1)
                                == np.argmax(np.asarray(ref, np.float32),
                                             -1))))
