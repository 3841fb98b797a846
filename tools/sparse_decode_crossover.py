"""Times the two forms of a selecting decode step over per-head K/V on
the attached chip, for the two constants of
``ray_tpu.ops.sparse_attention.masked_read_wins``: XLA's gather of the
selected rows (``_decode_selected``, with its ``top_k``) against the
paged kernel's masked read of the live pages (``topk_mask`` and
``paged_flash_attention(chosen=...)``), at several windows and fills;
and a chunk's row block through ``_chunk_masked`` against the same
kernel. One JSON line a timing on stdout.

    python -m tools.sparse_decode_crossover --windows 32768 65536 131072
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.paged_flash import paged_flash_attention


def _timed(fn, *args, iters: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _case(seed, batch, rows, heads, kv_heads, dim, bs, window, lens):
    """Pools of ``batch`` full tables, queries at the last ``rows``
    positions under ``lens`` and random scores over the visible keys."""
    t = window // bs
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (1, 1 + batch * t, kv_heads, bs, dim)
    kc = jax.random.normal(ks[0], shape, jnp.bfloat16)
    vc = jax.random.normal(ks[1], shape, jnp.bfloat16)
    q = jax.random.normal(ks[2], (batch, rows, heads, dim), jnp.bfloat16)
    bt = jnp.asarray(np.random.default_rng(seed).permutation(
        np.arange(1, 1 + batch * t)).astype(np.int32).reshape(batch, t))
    lens = jnp.asarray(lens, jnp.int32)
    pos = lens[:, None] - rows + jnp.arange(rows, dtype=jnp.int32)
    scores = jax.random.normal(ks[3], (batch, rows, window), jnp.float32)
    scores = jnp.where(jnp.arange(window) <= pos[..., None], scores,
                       -jnp.inf)
    return q, kc, vc, bt, pos, lens, scores


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, nargs="+",
                    default=[32768, 65536, 131072])
    ap.add_argument("--fills", type=float, nargs="+", default=[1.0, 0.5])
    ap.add_argument("--chunk-contexts", type=int, nargs="*",
                    default=[8192, 17408, 29696])
    ap.add_argument("--block-r", type=int, nargs="+", default=[8])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true",
                    help="interpret the kernel off the chip: walks the "
                         "code, its times mean nothing")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        raise SystemExit(f"no chip: the device is {dev.platform}")
    base = {"device": dev.device_kind, "platform": dev.platform,
            "topk": a.topk, "kv_heads": a.kv_heads, "batch": a.batch}
    scale = a.dim ** -0.5
    layer = jnp.int32(0)

    def gather(q, kc, vc, bt, pos, lens, scores):
        return sa._decode_selected(q, kc, vc, bt, scores, layer, a.topk,
                                   scale)

    def masked(block_r):
        def run(q, kc, vc, bt, pos, lens, scores):
            return paged_flash_attention(
                q, kc, vc, bt, pos, lens, layer=layer, sm_scale=scale,
                block_r=block_r, interpret=a.rehearse,
                chosen=sa.topk_mask(scores, a.topk))
        return run

    def select_only(q, kc, vc, bt, pos, lens, scores):
        return sa.topk_mask(scores, a.topk)

    def sort_only(q, kc, vc, bt, pos, lens, scores):
        return jax.lax.top_k(scores[:, 0], a.topk)

    for window in a.windows:
        for fill in a.fills:
            lens = [int(window * fill)] * a.batch
            case = _case(1, a.batch, 1, a.heads, a.kv_heads, a.dim,
                         a.block_size, window, lens)
            row = dict(base, call="decode", window=window, live=lens[0])
            want = jax.jit(gather)(*case)
            row["gather_ms"] = 1e3 * _timed(jax.jit(gather), *case,
                                            iters=a.iters)
            row["topk_sort_ms"] = 1e3 * _timed(jax.jit(sort_only), *case,
                                               iters=a.iters)
            row["topk_mask_ms"] = 1e3 * _timed(jax.jit(select_only), *case,
                                               iters=a.iters)
            for block_r in a.block_r:
                fn = jax.jit(masked(block_r))
                got = fn(*case)
                row[f"masked_ms.r{block_r}"] = 1e3 * _timed(
                    fn, *case, iters=a.iters)
                row[f"max_abs_diff.r{block_r}"] = float(jnp.max(jnp.abs(
                    got.astype(jnp.float32) - want.astype(jnp.float32))))
            print(json.dumps(row), flush=True)

    window = min(a.windows)
    for ctx in a.chunk_contexts:
        if ctx > window:
            continue
        q, kc, vc, bt, pos, lens, scores = _case(
            2, 1, sa._ROW_BLOCK, a.heads, a.kv_heads, a.dim, a.block_size,
            window, [ctx])
        chosen = jax.jit(lambda s: sa.topk_mask(s, a.topk))(scores)
        args = (q, kc, vc, bt, pos, lens, chosen)

        def xla(q, kc, vc, bt, pos, lens, chosen):
            return sa._chunk_masked(q, kc, vc, bt, chosen, lens, layer,
                                    scale)
        row = dict(base, call="chunk_row_block", window=window, live=ctx)
        want = jax.jit(xla)(*args)
        row["chunk_masked_ms"] = 1e3 * _timed(jax.jit(xla), *args,
                                              iters=a.iters)
        for block_r in (128, 256):
            fn = jax.jit(lambda q, kc, vc, bt, pos, lens, chosen:
                         paged_flash_attention(
                             q, kc, vc, bt, pos, lens, layer=layer,
                             sm_scale=scale, block_r=block_r,
                             interpret=a.rehearse, chosen=chosen))
            got = fn(*args)
            row[f"kernel_ms.r{block_r}"] = 1e3 * _timed(fn, *args,
                                                        iters=a.iters)
            row[f"max_abs_diff.r{block_r}"] = float(jnp.max(jnp.abs(
                got.astype(jnp.float32) - want.astype(jnp.float32))))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
