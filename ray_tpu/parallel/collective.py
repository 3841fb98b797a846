"""Collective communication API.

Equivalent of the reference's ``ray.util.collective``
(``python/ray/util/collective/collective.py``: ``init_collective_group``
:120, ``allreduce`` :258, ``barrier`` :298, ``reduce`` :311, ``broadcast``
:373, ``allgather`` :423, ``reducescatter`` :472, ``send``/``recv``)
re-designed for TPU, where eager collectives don't exist — every collective
is staged into a compiled XLA program (SURVEY.md §7 hard part 1).

Backends:

- ``"xla"`` — in-graph collectives over a device mesh. Eager-looking calls
  dispatch cached jitted stubs keyed by (group, op, shape, dtype); within a
  process they run over the caller's local devices; once
  ``jax.distributed`` is initialized (multi-host rendezvous below), the
  same stubs are global-SPMD and ride ICI/DCN. This replaces NCCL.
- ``"host"`` — control-plane collectives for cross-actor *host* (CPU)
  values, via the controller KV store (the role GLOO plays in the
  reference). Rendezvous mirrors the reference's ``NCCLUniqueIDStore``
  named actor (``collective_group/nccl_collective_group.py:28-68``) using
  the internal KV instead.

``quantized_allreduce`` / ``quantized_reducescatter`` are the int8
blockwise-quantized variants (EQuARX, arXiv:2506.17615): local shards are
quantized against per-block f32 scales, reduced in f32 accumulators, the
reduced chunks requantized for the gather leg, and dequantized at the
edge. Wire format in ``parallel.quantization``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

_groups: Dict[str, "Group"] = {}
_lock = threading.Lock()


class Group:
    def __init__(self, name: str, world_size: int, rank: int, backend: str):
        self.name = name
        self.world_size = world_size
        self.rank = rank
        self.backend = backend
        # Sequence numbers are tracked per op kind (and per peer pair for
        # p2p) so an asymmetric op — a send between two ranks, say — can't
        # desynchronize the keys the whole group uses for its next barrier.
        self._seqs: Dict[str, int] = {}
        self._stubs: Dict[Tuple, object] = {}
        self._mesh = None
        # Host-backend KV hygiene: keys this rank wrote, per op kind, as
        # {kind: [(seq, key), ...]}; consumed lazily by _gc (see below).
        self._written: Dict[str, List[Tuple[int, bytes]]] = {}
        self._bcast_pending: List[Tuple[bytes, List[bytes]]] = []

    # ---- xla backend ----
    def mesh(self):
        if self._mesh is None:
            from ray_tpu.parallel.mesh import MeshSpec, build_mesh
            import jax
            devices = jax.devices()
            if self.world_size > len(devices):
                raise ValueError(
                    f"xla collective group {self.name!r}: world_size "
                    f"{self.world_size} exceeds {len(devices)} devices")
            self._mesh = build_mesh(MeshSpec(tp=self.world_size),
                                    devices[:self.world_size])
        return self._mesh

    def _stub(self, op: str, shape, dtype, **kw):
        key = (op, tuple(shape), str(dtype), tuple(sorted(kw.items())))
        stub = self._stubs.get(key)
        if stub is None:
            stub = _build_stub(self.mesh(), op, **kw)
            self._stubs[key] = stub
        return stub

    def next_seq(self, kind: str) -> int:
        self._seqs[kind] = self._seqs.get(kind, 0) + 1
        return self._seqs[kind]


def axis_world_size(mesh, axes) -> int:
    """Total rank count across the named mesh axes."""
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def quantized_psum(x, axes, world: int,
                   block_size: Optional[int] = None,
                   stochastic_rounding: bool = False,
                   key=None, mean: bool = False):
    """Two-leg int8-quantized all-reduce of a per-rank tensor, callable
    INSIDE a ``shard_map`` region (EQuARX, arXiv:2506.17615): quantize
    the local payload blockwise, accumulate partial sums in f32 via
    ``psum_scatter``, REquantize the reduced chunk, then all-gather
    int8 values + per-block f32 scales — so the gather leg moves real
    int8 bytes across the ``axes`` links, not f32 tensors — and
    dequantize at the edge. Chunk boundaries round up to whole quant
    blocks so no block straddles two ranks' chunks. Returns the reduced
    tensor in ``x``'s shape (f32).

    This is the reduction the eager collective stubs compile
    (:func:`_build_stub`) AND the one each pipeline stage runs over its
    own dp×fsdp mesh (``parallel.mpmd_pipeline``) — one wire format,
    every topology."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import quantization as qz

    block = int(block_size or qz.DEFAULT_BLOCK_SIZE)
    n = x.size
    chunk = qz._padded_len(-(-n // world), block)
    padded = jnp.pad(x.astype(jnp.float32).reshape(-1),
                     (0, chunk * world - n))
    q, s = qz.quantize_int8(padded, block, stochastic_rounding, key)
    sent = qz.dequantize_int8(q, s)                    # f32 accum leg
    mine = jax.lax.psum_scatter(sent.reshape(world, chunk), axes,
                                scatter_dimension=0, tiled=False)
    q2, s2 = qz.quantize_int8(mine, block)             # gather leg
    qg = jax.lax.all_gather(q2, axes, axis=0, tiled=False)
    sg = jax.lax.all_gather(s2, axes, axis=0, tiled=False)
    full = (qg.astype(jnp.float32) * sg[..., None]).reshape(-1)
    if mean:
        full = full / world
    return full[:n].reshape(x.shape)


def psum_tree(tree, axes, world: int, transport: str = "fp32",
              block_size: Optional[int] = None,
              stochastic_rounding: bool = False, key=None,
              mean: bool = False):
    """Reduce every leaf of a pytree across the named mesh axes, inside
    a ``shard_map`` region: ``transport="fp32"`` is a plain ``psum``
    (exact); ``"int8"`` routes each leaf through
    :func:`quantized_psum` — real int8 values + f32 scales on the
    gather leg. With ``stochastic_rounding`` each leaf folds its index
    into ``key`` so no two leaves share a rounding stream."""
    import jax

    if transport == "fp32":
        red = jax.lax.pmean if mean else jax.lax.psum
        return jax.tree.map(lambda g: red(g, axes), tree)
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, g in enumerate(leaves):
        k = jax.random.fold_in(key, i) if key is not None else None
        out.append(quantized_psum(
            g, axes, world, block_size=block_size,
            stochastic_rounding=stochastic_rounding, key=k, mean=mean))
    return jax.tree.unflatten(treedef, out)


def _build_stub(mesh, op: str, **kw):
    """Compile one collective as a shard_map program over the mesh.

    Eager-call semantics match ``ray.util.collective``'s multi-rank model
    mapped onto one SPMD program: the input is the per-rank tensors stacked
    on dim 0 (world, \\*shape); ranks = mesh devices in axis order.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    axes = mesh.axis_names
    reduce_op = kw.get("reduce_op", "sum")

    def _red(x, ax):
        return {"sum": jax.lax.psum, "max": jax.lax.pmax,
                "min": jax.lax.pmin, "mean": jax.lax.pmean}[reduce_op](x, ax)

    if op == "allreduce":
        # (world, *shape) sharded on dim 0 -> reduced (*shape), replicated
        def f(x):
            return _red(x[0], axes)
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(axes), out_specs=P(),
            check_vma=False))
    if op == "allgather":
        # (world, *shape) sharded -> (world, *shape) replicated everywhere
        def f(x):
            return jax.lax.all_gather(x[0], axes, axis=0, tiled=False)
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(axes), out_specs=P(),
            check_vma=False))
    if op == "reducescatter":
        # (world, *shape) -> (world, shape[0]/world, ...): rank i gets the
        # i-th chunk of the elementwise sum
        import jax.numpy as jnp
        world = int(mesh.devices.size)

        def f(x):
            summed = _red(x[0], axes)
            return jnp.stack(jnp.split(summed, world, axis=0))
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(axes), out_specs=P(),
            check_vma=False))
    if op in ("quantized_allreduce", "quantized_reducescatter"):
        # Two-leg quantized reduction (EQuARX, arXiv:2506.17615): each
        # rank int8-quantizes its local payload (send side), partial sums
        # accumulate in f32 via psum_scatter, the reduced chunk is
        # REquantized for the gather leg — so the all-gather moves int8
        # values + per-block f32 scales, not f32 tensors — and the edge
        # dequantizes. Chunk boundaries are rounded up to whole quant
        # blocks so no block ever straddles two ranks' chunks.
        import jax.numpy as jnp

        world = int(mesh.devices.size)
        block = kw.get("block_size")
        sr = bool(kw.get("stochastic_rounding", False))

        def f(x, seed):
            local = x[0]
            key = None
            if sr:
                idx = 0
                for a in axes:
                    idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
                key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
                key = jax.random.fold_in(key, idx)
            out = quantized_psum(local, axes, world, block_size=block,
                                 stochastic_rounding=sr, key=key,
                                 mean=reduce_op == "mean")
            if op == "quantized_reducescatter":
                return jnp.stack(jnp.split(out, world, axis=0))
            return out
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(axes), P()), out_specs=P(),
            check_vma=False))
    raise ValueError(f"unknown collective {op}")


# ------------------------------------------------------------------ API
def init_collective_group(world_size: int, rank: int,
                          backend: str = "xla",
                          group_name: str = "default") -> None:
    """Join a collective group (call from every participating actor)."""
    with _lock:
        _groups[group_name] = Group(group_name, world_size, rank, backend)
    if backend == "host":
        _host_rendezvous(group_name, world_size, rank)


def _actor_join(actor_self, world_size, rank, backend, group_name):
    init_collective_group(world_size, rank, backend, group_name)
    return rank


def create_collective_group(actors: List, world_size: int, ranks: List[int],
                            backend: str = "xla",
                            group_name: str = "default") -> None:
    """Declarative creation (reference: ``create_collective_group`` :151):
    tell each actor to join via the generic ``__ray_call__`` invoke."""
    import ray_tpu
    ray_tpu.get([
        a.__ray_call__.remote(_actor_join, world_size, r, backend, group_name)
        for a, r in zip(actors, ranks)], timeout=300)


def destroy_collective_group(group_name: str = "default") -> None:
    with _lock:
        _groups.pop(group_name, None)


def get_group(group_name: str = "default") -> Group:
    g = _groups.get(group_name)
    if g is None:
        raise ValueError(f"collective group {group_name!r} not initialized")
    return g


def get_rank(group_name: str = "default") -> int:
    return get_group(group_name).rank


def get_collective_group_size(group_name: str = "default") -> int:
    return get_group(group_name).world_size


def is_group_initialized(group_name: str = "default") -> bool:
    return group_name in _groups


# ---- xla-backend data-plane collectives (device arrays) ----
def allreduce(tensor, group_name: str = "default", op: str = "sum"):
    g = get_group(group_name)
    if g.backend == "host":
        return _host_allreduce(g, tensor, op)
    return g._stub("allreduce", tensor.shape, tensor.dtype,
                   reduce_op=op)(tensor)


def allgather(tensor, group_name: str = "default"):
    g = get_group(group_name)
    if g.backend == "host":
        return _host_allgather(g, tensor)
    return g._stub("allgather", tensor.shape, tensor.dtype)(tensor)


def reducescatter(tensor, group_name: str = "default", op: str = "sum"):
    g = get_group(group_name)
    if g.backend == "host":
        return _host_reducescatter(g, tensor, op)
    return g._stub("reducescatter", tensor.shape, tensor.dtype,
                   reduce_op=op)(tensor)


def _check_quant_op(op: str) -> None:
    if op not in ("sum", "mean"):
        raise ValueError(
            f"quantized collectives support op='sum'/'mean', got {op!r} "
            "(max/min don't survive blockwise requantization)")


def quantized_allreduce(tensor, group_name: str = "default",
                        op: str = "sum",
                        block_size: Optional[int] = None,
                        stochastic_rounding: bool = False):
    """All-reduce with int8 blockwise-quantized transport: quantize local
    shards, reduce in f32 accumulators, requantize for the gather leg,
    dequantize at the edge. Same calling convention as :func:`allreduce`;
    the result carries the quantization error of both wire legs (bounded
    by half a quantization step per leg per block — see
    ``parallel.quantization``)."""
    _check_quant_op(op)
    g = get_group(group_name)
    if g.backend == "host":
        return _host_quantized_allreduce(g, tensor, op, block_size)
    seed = g.next_seq("q_ar") if stochastic_rounding else 0
    stub = g._stub("quantized_allreduce", tensor.shape, tensor.dtype,
                   reduce_op=op, block_size=block_size,
                   stochastic_rounding=stochastic_rounding)
    return stub(tensor, np.uint32(seed))


def quantized_reducescatter(tensor, group_name: str = "default",
                            op: str = "sum",
                            block_size: Optional[int] = None,
                            stochastic_rounding: bool = False):
    """Reduce-scatter with int8-quantized transport; same calling
    convention (and chunking) as :func:`reducescatter`."""
    _check_quant_op(op)
    g = get_group(group_name)
    if g.backend == "host":
        summed = _host_quantized_allreduce(g, tensor, op, block_size)
        if tensor.shape[0] % g.world_size:
            raise ValueError(
                f"reducescatter dim 0 ({tensor.shape[0]}) not divisible "
                f"by world size {g.world_size}")
        return np.split(summed, g.world_size, axis=0)[g.rank]
    if tensor.shape[1] % g.world_size:
        raise ValueError(
            f"reducescatter chunk dim ({tensor.shape[1]}) not divisible "
            f"by world size {g.world_size}")
    seed = g.next_seq("q_rs") if stochastic_rounding else 0
    stub = g._stub("quantized_reducescatter", tensor.shape, tensor.dtype,
                   reduce_op=op, block_size=block_size,
                   stochastic_rounding=stochastic_rounding)
    return stub(tensor, np.uint32(seed))


def reduce(tensor, dst_rank: int = 0, group_name: str = "default",
           op: str = "sum"):
    out = allreduce(tensor, group_name, op)
    g = get_group(group_name)
    return out if g.rank == dst_rank else tensor


def broadcast(tensor, src_rank: int = 0, group_name: str = "default"):
    g = get_group(group_name)
    if g.backend == "host":
        return _host_broadcast(g, tensor, src_rank)
    # in-graph: a broadcast is an all-gather of the source shard; with a
    # replicated input this is identity under SPMD
    return tensor


def barrier(group_name: str = "default") -> None:
    g = get_group(group_name)
    if g.backend == "host":
        _host_barrier(g)
        return
    # device barrier: tiny allreduce
    import jax.numpy as jnp
    allreduce(jnp.zeros((g.world_size,), jnp.float32), group_name)


def send(tensor, dst_rank: int, group_name: str = "default") -> None:
    g = get_group(group_name)
    seq = g.next_seq(f"p2p/{g.rank}->{dst_rank}")
    _kv_put(_key(g, f"p2p/{g.rank}->{dst_rank}/{seq}"),
            _dumps(np.asarray(tensor)))


def recv(shape, dtype, src_rank: int, group_name: str = "default"):
    g = get_group(group_name)
    seq = g.next_seq(f"p2p/{src_rank}->{g.rank}")
    key = _key(g, f"p2p/{src_rank}->{g.rank}/{seq}")
    return _loads(_kv_take(key)).reshape(shape).astype(dtype)


# ------------------------------------------------ host backend internals
def _kv(self=None):
    from ray_tpu.core.global_state import global_worker
    return global_worker()


def _key(g: Group, suffix: str) -> bytes:
    return f"collective/{g.name}/{suffix}".encode()


def _dumps(arr: np.ndarray) -> bytes:
    import io
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _loads(blob: bytes) -> np.ndarray:
    import io
    return np.load(io.BytesIO(blob), allow_pickle=False)


def _kv_put(key: bytes, value: bytes) -> None:
    _kv().kv_put(key, value, ns="collective")


def _kv_take(key: bytes, timeout: float = 120.0) -> bytes:
    w = _kv()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = w.kv_get(key, ns="collective")
        if v is not None:
            w.kv_del(key, ns="collective")
            return v
        time.sleep(0.005)
    raise TimeoutError(f"collective recv timed out on {key!r}")


def _kv_wait(key: bytes, timeout: float = 120.0) -> bytes:
    w = _kv()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = w.kv_get(key, ns="collective")
        if v is not None:
            return v
        time.sleep(0.005)
    raise TimeoutError(f"collective wait timed out on {key!r}")


def _host_rendezvous(group_name: str, world_size: int, rank: int) -> None:
    # Join keys persist for the group's lifetime (one tiny key per rank):
    # stragglers that rendezvous late must still find every key.
    g = get_group(group_name)
    _kv_put(_key(g, f"join/{rank}"), b"1")
    for r in range(world_size):
        _kv_wait(_key(g, f"join/{r}"))


def _gc_symmetric(g: Group, kind: str, seq: int, key: bytes) -> None:
    """Lag-2 GC for symmetric ops (every rank writes and reads each seq).

    When this rank starts seq s, every rank has started s-1 (this rank
    finished s-1 only after reading all ranks' s-1 keys, which they write
    on entry), hence every rank has finished s-2 and read our s-2 key —
    so our keys with seq <= s-2 are dead and safe to delete.
    """
    written = g._written.setdefault(kind, [])
    w = _kv()
    while written and written[0][0] <= seq - 2:
        _, old_key = written.pop(0)
        w.kv_del(old_key, ns="collective")
    written.append((seq, key))


def _host_allreduce(g: Group, tensor, op: str):
    arr = np.asarray(tensor)
    seq = g.next_seq("ar")
    key = _key(g, f"ar/{seq}/{g.rank}")
    _gc_symmetric(g, "ar", seq, key)
    _kv_put(key, _dumps(arr))
    parts = [_loads(_kv_wait(_key(g, f"ar/{seq}/{r}")))
             for r in range(g.world_size)]
    stack = np.stack(parts)
    out = {"sum": stack.sum(0), "mean": stack.mean(0),
           "max": stack.max(0), "min": stack.min(0)}[op]
    return out


def _host_allgather(g: Group, tensor):
    arr = np.asarray(tensor)
    seq = g.next_seq("ag")
    key = _key(g, f"ag/{seq}/{g.rank}")
    _gc_symmetric(g, "ag", seq, key)
    _kv_put(key, _dumps(arr))
    return [_loads(_kv_wait(_key(g, f"ag/{seq}/{r}")))
            for r in range(g.world_size)]


def _host_reducescatter(g: Group, tensor, op: str):
    """Host-backend reduce-scatter: every rank contributes its local
    tensor and takes home the ``rank``-th dim-0 chunk of the elementwise
    reduction. Symmetric (every rank writes and reads each seq), so the
    lag-2 GC argument holds exactly as for allreduce/allgather."""
    arr = np.asarray(tensor)
    if arr.shape[0] % g.world_size:
        raise ValueError(
            f"reducescatter dim 0 ({arr.shape[0]}) not divisible by "
            f"world size {g.world_size}")
    seq = g.next_seq("rs")
    key = _key(g, f"rs/{seq}/{g.rank}")
    _gc_symmetric(g, "rs", seq, key)
    _kv_put(key, _dumps(arr))
    parts = [_loads(_kv_wait(_key(g, f"rs/{seq}/{r}")))
             for r in range(g.world_size)]
    stack = np.stack(parts)
    out = {"sum": stack.sum(0), "mean": stack.mean(0),
           "max": stack.max(0), "min": stack.min(0)}[op]
    return np.split(out, g.world_size, axis=0)[g.rank]


def _host_quantized_allreduce(g: Group, tensor, op: str,
                              block_size: Optional[int]):
    """Host-backend quantized all-reduce: each rank publishes int8 block
    values + f32 scales (the actual KV wire bytes shrink ~4x vs the f32
    payload of ``_host_allreduce``); readers dequantize into f32
    accumulators. Single-leg — there is no separate gather hop to
    requantize on the KV-store topology."""
    from ray_tpu.parallel import quantization as qz

    block = int(block_size or qz.DEFAULT_BLOCK_SIZE)
    arr = np.asarray(tensor)
    q, s = qz.quantize_int8_np(arr, block)
    seq = g.next_seq("qar")
    qkey = _key(g, f"qar/{seq}/q/{g.rank}")
    skey = _key(g, f"qar/{seq}/s/{g.rank}")
    _gc_symmetric(g, "qar.q", seq, qkey)
    _gc_symmetric(g, "qar.s", seq, skey)
    _kv_put(qkey, _dumps(q))
    _kv_put(skey, _dumps(s))
    out = np.zeros(arr.shape, np.float32)
    for r in range(g.world_size):
        rq = _loads(_kv_wait(_key(g, f"qar/{seq}/q/{r}")))
        rs = _loads(_kv_wait(_key(g, f"qar/{seq}/s/{r}")))
        out += qz.dequantize_int8_np(rq, rs, arr.shape)
    return out / g.world_size if op == "mean" else out


def _host_broadcast(g: Group, tensor, src_rank: int):
    # Broadcast is asymmetric (receivers never write), so lag-GC's
    # self-synchronization argument doesn't hold; receivers ack instead
    # and the source reaps fully-acked payloads on its next broadcast.
    seq = g.next_seq("bc")
    data_key = _key(g, f"bc/{seq}")
    if g.rank == src_rank:
        w = _kv()
        still_pending = []
        for old_data, acks in g._bcast_pending:
            if all(w.kv_get(a, ns="collective") is not None for a in acks):
                w.kv_del(old_data, ns="collective")
                for a in acks:
                    w.kv_del(a, ns="collective")
            else:
                still_pending.append((old_data, acks))
        g._bcast_pending = still_pending
        _kv_put(data_key, _dumps(np.asarray(tensor)))
        g._bcast_pending.append(
            (data_key, [_key(g, f"bc/{seq}/ack/{r}")
                        for r in range(g.world_size) if r != src_rank]))
        return tensor
    out = _loads(_kv_wait(data_key))
    _kv_put(_key(g, f"bc/{seq}/ack/{g.rank}"), b"1")
    return out


def _host_barrier(g: Group) -> None:
    seq = g.next_seq("bar")
    key = _key(g, f"bar/{seq}/{g.rank}")
    _gc_symmetric(g, "bar", seq, key)
    _kv_put(key, b"1")
    for r in range(g.world_size):
        _kv_wait(_key(g, f"bar/{seq}/{r}"))


# --------------------------------------------- multi-host jax rendezvous
def init_jax_distributed(group_name: str = "train",
                         coordinator_port: int = 8476,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Multi-host SPMD bring-up: the JAX-distributed equivalent of the
    reference's torch TCPStore rendezvous (``train/torch/config.py:64-116``).
    Rank 0 publishes its address in the internal KV; all ranks call
    ``jax.distributed.initialize`` against it. Call before any jax use in
    the process."""
    import socket
    if process_id is None or num_processes is None:
        raise ValueError(
            "init_jax_distributed requires explicit num_processes and "
            "process_id (rank 0 hosts the coordinator)")
    w = _kv()
    key = f"jaxdist/{group_name}/coordinator".encode()
    if process_id == 0:
        addr = f"{socket.gethostbyname(socket.gethostname())}:{coordinator_port}"
        w.kv_put(key, addr.encode(), ns="collective")
    else:
        addr = _kv_wait(key).decode()
    import jax
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=num_processes,
                               process_id=process_id)
