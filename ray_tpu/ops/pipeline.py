"""Pipeline parallelism as a collective GSPMD program.

TPU-first design: instead of the reference's per-stage process groups and
point-to-point sends (torch pipelining would map poorly to XLA), the
pipeline IS one jitted SPMD program over the ``pp`` mesh axis:

- layer parameters are stacked ``[n_stages, ...]`` and sharded on ``pp``
  (each device holds its stage's weights, nothing else);
- a ``lax.scan`` over ticks runs the classic GPipe schedule: at tick t,
  stage s computes microbatch ``t - s``; activations hop to the next
  stage with a single ``ppermute`` per tick (one ICI neighbor hop);
- reverse-mode AD through scan+ppermute yields the backward pipeline
  schedule automatically — no hand-written 1F1B state machine.

Bubble fraction is the GPipe ``(S-1)/(M+S-1)``; choose microbatches >>
stages. The scaling-book calls this the "collective pipelining" recipe.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(layer_params_list):
    """Stack per-stage parameter pytrees into ``[n_stages, ...]`` leaves
    (shard the leading axis on ``pp``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params_list)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params, x: jax.Array, mesh: Mesh,
                   n_microbatches: int, axis: str = "pp") -> jax.Array:
    """Run ``stage_fn`` as an ``S``-stage GPipe pipeline over ``axis``.

    stage_params: pytree with leading dim S (sharded on ``axis``).
    x: ``[batch, ...]`` global input; split into ``n_microbatches``.
    Returns ``[batch, ...]`` outputs (replicated over ``axis``).
    """
    S = mesh.shape[axis]
    M = n_microbatches
    if x.shape[0] % M:
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"{M} microbatches")
    xs = x.reshape((M, x.shape[0] // M) + x.shape[1:])

    # one device's view: params [1, ...] -> squeeze; xs/out replicated
    def spmd(params, xs):
        params = jax.tree.map(lambda a: a[0], params)
        s = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            h, ys = carry
            m = t - s  # microbatch this stage works on at this tick
            # stage 0 consumes fresh input; later stages, the hopped
            # activation. Out-of-range ticks compute garbage that is
            # masked out of ys (uniform compute keeps the program static)
            x_t = xs[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(s == 0, x_t, h)
            out = stage_fn(params, inp)
            live = (m >= 0) & (m < M)
            write = live & (s == S - 1)
            idx = jnp.clip(m, 0, M - 1)
            ys = ys.at[idx].set(jnp.where(write, out, ys[idx]))
            h_next = jax.lax.ppermute(out, axis, perm)
            return (h_next, ys), None

        h0 = jnp.zeros(mb_shape, xs.dtype)
        ys0 = jnp.zeros_like(xs)
        (_, ys), _ = jax.lax.scan(tick, (h0, ys0),
                                  jnp.arange(M + S - 1))
        # only the last stage wrote real outputs; give them to everyone
        ys = jax.lax.psum(jnp.where(s == S - 1, ys, jnp.zeros_like(ys)),
                          axis)
        return ys

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    rep = P()
    out = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(pspec_params, rep),
        out_specs=rep,
        check_vma=False,
    )(stage_params, xs)
    return out.reshape(x.shape[0:1] + out.shape[2:])
