"""Model-facing attention API with automatic kernel dispatch.

Layout here is (batch, seq, num_heads, head_dim) — the layout models carry
activations in. Both entry points take ``impl``:

- ``"auto"`` — on TPU, the compiled Pallas kernel when the shape rules
  below hold, else the pure-XLA reference; off TPU, the reference (what
  CPU tests exercise). The choice is made from shapes alone, never by
  catching a compile error.
- ``"flash"`` / ``"kernel"`` — the compiled Pallas kernel, or an error:
  off TPU there is nothing to compile it for.
- ``"interpret"`` — the same kernel in Pallas interpret mode (CPU parity
  tests and rehearsals; never a measurement).
- ``"reference"`` — the pure-XLA path, by request.

Shape rules. Flash: no arbitrary mask, ``head_dim % 128 == 0`` and both
sequence lengths divisible by their (clamped) blocks. Paged:
``head_dim % 128 == 0`` and ``block_size`` a multiple of the cache
dtype's sublane tile (8 rows of f32, 16 of bf16).

Every call records what it resolved to and why (:func:`dispatch_log`):
the record is made while JAX traces, so it lists each distinct attention
call site of each compiled program once.
"""

from __future__ import annotations

import collections
import math
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (
    default_flash_blocks, flash_attention)

_NEG_INF = -1e30

# Process-wide on purpose: jit caches are process-wide too, and the
# record answers "what did the programs this process compiled run".
_dispatch_lock = threading.Lock()
_dispatch: Dict[tuple, int] = collections.Counter()


def dispatch_log() -> List[Dict[str, object]]:
    """Every attention dispatch decision this process has traced:
    ``{"op": "flash"|"paged"|"latent"|"ssm_step"|"delta_step" (the
    one-token state updates, ``ops/ssm.py`` and ``ops/delta.py``)
    |"sparse_decode"|"sparse_chunk" (``ops/sparse_attention.py``)
    |"grouped_dot" (the experts' differentiated one, ``models/moe.py``),
    "impl": "kernel"|"interpret"|"reference",
    "why": ..., "count": n}``. ``why`` is ``"auto"`` or ``"requested"``
    for a kernel, and for a reference either ``"requested"`` or the
    shape rule (or platform) that ruled the kernel out."""
    with _dispatch_lock:
        return [{"op": op, "impl": impl, "why": why, "count": n}
                for (op, impl, why), n in sorted(_dispatch.items())]


def _resolve(op: str, impl: str, kernel_name: str,
             unfit: Optional[str], record: bool = True) -> str:
    """Shared dispatch rule -> "kernel" | "interpret" | "reference".
    ``unfit`` names the shape rule the call breaks (None = it tiles).
    ``record=False`` asks what a call would resolve to without adding
    one to :func:`dispatch_log`."""
    backend = jax.default_backend()
    if impl == "auto":
        if backend != "tpu":
            choice, why = "reference", "platform is not tpu"
        elif unfit:
            choice, why = "reference", unfit
        else:
            choice, why = "kernel", "auto"
    elif impl == kernel_name:
        if backend != "tpu":
            raise ValueError(
                f"{op} attention impl={impl!r} needs a TPU (platform is "
                f"{backend!r}); pass impl='interpret' to run the kernel "
                f"in Pallas interpret mode")
        choice, why = "kernel", "requested"
    elif impl in ("interpret", "reference"):
        choice, why = impl, "requested"
    else:
        raise ValueError(f"unknown {op} attention impl: {impl!r}")
    if record:
        with _dispatch_lock:
            _dispatch[(op, choice, why)] += 1
    return choice


def attention_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = False,
                        sm_scale: Optional[float] = None,
                        mask: Optional[jnp.ndarray] = None,
                        window: int = 0) -> jnp.ndarray:
    """Plain masked-softmax attention in f32, layout (B, S, H, D).
    ``window > 0`` (with ``causal``): a query attends the ``window`` keys
    up to and including its own position."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window:
            causal_mask &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool),
                                     k=sk - sq - window)
        s = jnp.where(causal_mask[None, None], s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _paged_unfit(q: jnp.ndarray, k_cache: jnp.ndarray) -> Optional[str]:
    """The shape rule a paged call breaks for the compiled kernel, or
    None: head_dim must tile the lanes and a page the sublanes."""
    from ray_tpu.ops.paged_flash import sublane_tile
    d, bs = q.shape[-1], k_cache.shape[-2]
    tile = sublane_tile(k_cache.dtype)
    if d % 128:
        return f"head_dim {d} % 128 != 0"
    if bs % tile:
        return f"block_size {bs} % {tile} != 0 ({k_cache.dtype} pages)"
    return None


def paged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                    q_positions: jnp.ndarray, *,
                    layer=None,
                    lens: Optional[jnp.ndarray] = None,
                    sm_scale: Optional[float] = None,
                    impl: str = "auto",
                    block_r: Optional[int] = None,
                    window: int = 0) -> jnp.ndarray:
    """Attention of new-token queries against a paged KV cache.

    The serving decode/prefill primitive: keys and values live in a pool
    of fixed-size blocks. ``k_cache``/``v_cache`` are the WHOLE pool
    ``[n_layers, num_blocks, kv_heads, block_size, head_dim]`` and
    ``layer`` (an int32 scalar, traced inside a layer scan) names the
    layer attended: every ``impl`` reads pages by ``(layer, block)``
    out of the pool's own buffer, so a step program that carries the
    pool through its layer scan never slices a layer out of it (a copy
    of that layer, in and out, every step). ``layer=None`` takes one
    layer's ``[num_blocks, kv_heads, block_size, head_dim]`` pool. Each
    sequence owns an ordered list of block ids (``block_tables[b, t]``
    holds the block storing absolute positions
    ``t*block_size .. t*block_size+bs-1`` of sequence ``b``). Queries
    ``q[b, i]`` sit at absolute position ``q_positions[b, i]`` and attend
    every cached position ``<= q_positions[b, i]`` — causal by
    construction, so the SAME call serves batched
    single-token decode (``q`` of shape ``[B, 1, H, D]``) and chunked
    prefill (``[B, C, H, D]``, the chunk's own keys having been written to
    the cache first). GQA caches store ``kv_heads < num_heads``; queries
    are grouped onto their kv head at read time — the cache is never
    repeated.

    ``impl``: "auto" | "kernel" | "interpret" | "reference" (module
    docstring). "kernel" is the Pallas paged kernel
    (:mod:`ray_tpu.ops.paged_flash`) — auto-selected on TPU when shapes
    tile. ``lens [B]`` is the per-sequence LIVE token count; the
    kernel skips whole blocks past it, making decode work proportional
    to live tokens instead of the table window. ``lens = None``
    derives a conservative bound from ``q_positions`` (every key the
    queries may attend). A sequence that holds nothing (``lens <= 0``,
    its rows below position 0) comes back zero under every ``impl``,
    and the kernel reads no page of its table.

    ``window > 0``: a sliding-window layer, a query attends the
    ``window`` keys up to and including its own position (``key > query
    - window``) under every ``impl``. Positions enter the masks as
    ``query - key`` only, so the table may start at any page of the
    sequence if ``q_positions`` and ``lens`` count from that page's
    first position (a window layer's short table).

    The reference path is the pure-XLA gather (one ``take`` per
    sequence over its block table, f32 softmax): work is
    O(B * C * T * block_size) regardless of true lengths; keep
    ``block_tables`` sized to the serving window, not the model max.
    """
    from ray_tpu.ops.paged_flash import (
        layered_pool, paged_flash_attention)
    k_cache, v_cache, layer = layered_pool(k_cache, v_cache, layer)
    kvh, bs, d = k_cache.shape[2:]
    b, c, h, _ = q.shape
    t = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    choice = _resolve("paged", impl, "kernel", _paged_unfit(q, k_cache))
    if choice != "reference":
        if lens is None:
            lens = jnp.max(q_positions, axis=1).astype(jnp.int32) + 1
        return paged_flash_attention(
            q, k_cache, v_cache, block_tables, q_positions, lens,
            layer=layer, sm_scale=sm_scale, block_r=block_r,
            interpret=choice == "interpret", window=window)
    # Gather each sequence's blocks out of the layer, one gather on the
    # 5-D pool: [B, T, KVH, bs, D] -> [B, K, KVH, D]
    def gather(cache):
        return cache[layer[0], block_tables] \
            .transpose(0, 1, 3, 2, 4).reshape(b, t * bs, kvh, d)
    k, v = gather(k_cache), gather(v_cache)
    # key slot j of the gathered view holds absolute position j
    key_pos = jnp.arange(t * bs, dtype=jnp.int32)
    mask = key_pos[None, None, :] <= q_positions[:, :, None]   # [B, C, K]
    if window:
        mask &= key_pos[None, None, :] > q_positions[:, :, None] - window
    if kvh != h:
        # GQA read without materializing a repeated cache copy: group
        # the (tiny) queries onto their kv head and einsum over the
        # grouped axes — XLA broadcasts k/v across the group in the
        # contraction instead of writing an h/kvh-times-larger gather.
        rep = h // kvh
        qg = q.reshape(b, c, kvh, rep, d).astype(jnp.float32)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                       k.astype(jnp.float32)) * sm_scale
        s = jnp.where(mask[:, None, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32)) \
            .reshape(b, c, h, d)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * sm_scale
        s = jnp.where(mask[:, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    # a row below position 0 sees no key (every row of a sequence that
    # holds nothing, which the kernel skips): zeros as the kernel's, not
    # the mean of the window that a softmax of equal scores would be
    return jnp.where((q_positions >= 0)[:, :, None, None], o,
                     0.0).astype(q.dtype)


def _flash_cannot(q, k, mask, block_q: int, block_k: int) -> Optional[str]:
    """Why the flash kernel cannot compute this call in any mode, or
    None: it knows only the causal structure and whole blocks."""
    sq, sk = q.shape[1], k.shape[1]
    if mask is not None:
        return "arbitrary mask"
    bq, bk = min(block_q, sq), min(block_k, sk)
    if sq % bq or sk % bk:
        return f"seq ({sq}, {sk}) not divisible by blocks ({bq}, {bk})"
    return None


def multihead_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = False,
                        sm_scale: Optional[float] = None,
                        mask: Optional[jnp.ndarray] = None,
                        impl: str = "auto",
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        window: int = 0) -> jnp.ndarray:
    """Attention over (batch, seq, heads, head_dim).

    ``window > 0`` (causal calls): a sliding-window layer, a query
    attends the ``window`` keys up to and including its own position,
    under every ``impl`` (the kernel: ``flash_attention(window=...)``).

    ``impl``: "auto" | "flash" | "interpret" | "reference" (module
    docstring). An arbitrary ``mask`` needs the reference path (the
    flash kernel handles only the causal structure): "auto" takes it,
    a forced kernel raises. ``block_q``/``block_k`` of ``None`` (or 0)
    resolve to chip-aware defaults
    (``flash_attention.default_flash_blocks``).
    """
    if not block_q or not block_k:
        dq_, dk_ = default_flash_blocks(
            q.shape[1], k.shape[1], q.shape[-1],
            chip="cpu" if impl == "interpret" else None)
        block_q = block_q or dq_
        block_k = block_k or dk_
    d = q.shape[-1]
    cannot = _flash_cannot(q, k, mask, block_q, block_k)
    unfit = cannot or (f"head_dim {d} % 128 != 0" if d % 128 else None)
    choice = _resolve("flash", impl, "flash", unfit)
    if choice == "reference":
        return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                                   mask=mask, window=window)
    if cannot:
        raise ValueError(f"flash attention kernel forced on a call it "
                         f"cannot compute: {cannot}")
    qt = jnp.swapaxes(q, 1, 2)    # (B, H, S, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = flash_attention(qt, kt, vt, causal=causal, sm_scale=sm_scale,
                        block_q=block_q, block_k=block_k,
                        interpret=choice == "interpret", window=window)
    return jnp.swapaxes(o, 1, 2)
