"""MPMD pipeline parallelism: actor-hosted stages, streamed activations,
interleaved virtual stages, per-stage fused optimizer step.

The SPMD pipeline in ``ops/pipeline.py`` compiles every stage into ONE
jitted GPipe program — one mesh, one compile, the full GPipe bubble.
This module is the MPMD alternative the task/actor runtime makes
possible (Scaling Deep Learning Training with MPMD Pipeline
Parallelism, arXiv:2412.14374; the decoupled-actor split mirrors
Podracer's sebulba, arXiv:2104.06272):

- each pipeline stage is a :class:`PipelineStage` **actor** pinned to
  its own device subset, holding ``n_virtual`` *virtual stage* slices
  of the model (``models.transformer.stage_slice_params`` over
  round-robin chunk ids — actor ``s`` hosts global chunks
  ``s, s+S, s+2S, ...`` of the ``K = S*v`` total, each a contiguous
  slab of the stacked layer leaves, bit-identical to the
  single-program weights) and THREE jitted program families:

  * stage-forward (per chunk role): ``jit(lambda p, x:
    jax.vjp(stage_fn, p, x))`` — returns the activation AND the vjp
    closure. ``jax.vjp``'s return is a pytree-registered ``Partial``
    whose leaves are the saved residuals, so it crosses the jit
    boundary as plain arrays;
  * stage-backward: ``jit(lambda vjp, g: vjp(g))`` — applies a saved
    vjp to the downstream gradient, REUSING the forward's residuals
    (no recompute), and emits the upstream input-gradient. Per-chunk
    parameter gradients accumulate in-actor across microbatches
    (donated accumulator buffers);
  * stage-optimizer (``train=True``): one fused jitted program that
    scales the accumulated grads by the global clip factor, runs the
    optax update on the stage's param slice, and applies it — params,
    optimizer state AND grads donated. Optimizer state never leaves
    the stage; after warmup the only per-step driver traffic is the
    scalar grad-norm reduction and the loss scalars.

- a driver-side **interleaved 1F1B scheduler** (:class:`MPMDPipeline`)
  streams per-microbatch activations chunk-to-chunk: each stage's step
  is one ``num_returns="streaming"`` actor call whose yields are the
  per-op outputs in the stage's deterministic
  :func:`one_f_one_b_order`, the driver waits on whichever stage
  produces next (``streaming.wait_any``) and routes the item *ref* —
  never the bytes — into the next chunk's mailbox. With ``n_virtual >
  1`` the warmup/cooldown bubble shrinks by the virtual-stage factor:
  analytic ``(S-1)/(v*M+S-1)`` vs GPipe's ``(S-1)/(M+S-1)``.

Every forward/backward/opt/idle interval is recorded as a
``STAGE_TICK`` flight-recorder event labelled with its phase and
virtual-stage (chunk) index, so the Perfetto ``/timeline`` export
doubles as the bubble visualization, and
:meth:`PipelineStage.step_stats` returns the measured busy/idle split
(the bubble fraction).

Checkpointing: :meth:`PipelineStage.stage_checkpoint` returns the
stage's param/opt-state slices keyed by global chunk id;
:func:`merge_stage_checkpoints` reassembles the canonical
single-program ``{"params", "opt_state", "step"}`` layout (the same
treedef ``models.training.make_train_step`` produces for the same
optimizer), and :func:`split_train_state` re-slices it for any other
``(n_stages, n_virtual)`` — a checkpoint saved at v=2 reloads into a
v=1 pipeline and vice versa.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "one_f_one_b_order",
    "interleaved_orders",
    "stage_virtual_chunks",
    "analytic_gpipe_bubble",
    "analytic_bubble",
    "PipelineStage",
    "MPMDPipeline",
    "PipelineStepResult",
    "merge_stage_checkpoints",
    "split_train_state",
]


def stage_virtual_chunks(stage: int, n_stages: int,
                         n_virtual: int = 1) -> Tuple[int, ...]:
    """Global chunk ids hosted by one stage actor: round-robin slabs
    ``stage, stage+S, stage+2S, ...`` of the ``K = S*v`` virtual
    stages (Megatron-style interleaving: chunk ``c`` lives on actor
    ``c % S``, so chunk ``c``'s output always feeds the NEXT actor)."""
    return tuple(range(stage, n_stages * n_virtual, n_stages))


def _classic_1f1b(stage: int, n_stages: int, n_microbatches: int
                  ) -> List[Tuple[str, int, int]]:
    """The v=1 1F1B schedule as seen by one stage (chunk == stage):
    warmup forwards fill the pipe (``n_stages - 1 - stage`` of them —
    the last stage has none), then the steady state alternates one
    forward with one backward, then the cooldown drains the remaining
    backwards."""
    m = n_microbatches
    warmup = min(n_stages - 1 - stage, m)
    order = [("F", i, stage) for i in range(warmup)]
    b = 0
    for f in range(warmup, m):
        order.append(("F", f, stage))
        order.append(("B", b, stage))
        b += 1
    order.extend(("B", i, stage) for i in range(b, m))
    return order


@functools.lru_cache(maxsize=256)
def interleaved_orders(n_stages: int, n_microbatches: int,
                       n_virtual: int
                       ) -> Tuple[Tuple[Tuple[str, int, int], ...], ...]:
    """Per-stage interleaved-1F1B op orders for a ``S x M x v`` grid,
    as a tuple (stage-indexed) of op tuples ``(op, microbatch, chunk)``.

    Built by a deterministic greedy tick simulation: at each tick every
    stage runs at most one *runnable* op (an op whose producers
    finished at a strictly earlier tick — one tick of transport
    latency), preferring backwards over forwards (1F1B steady state)
    and breaking ties with the Megatron-style group key ``(mb // S,
    chunk, mb % S)`` so forwards sweep chunk groups of S microbatches.
    The result is valid for ANY grid (no ``M % S`` constraint): the
    simulation only ever schedules dependency-satisfied ops, and a
    stage executing its list in order while blocking on mailboxes can
    never deadlock (every op's producers appear at earlier ticks).
    Deterministic in (S, M, v): the driver and every stage actor derive
    the same lists, so stream item *j* of stage *s* IS operation
    ``orders[s][j]`` — no tags ride the wire."""
    S, M, v = n_stages, n_microbatches, n_virtual
    K = S * v
    done_f: Dict[Tuple[int, int], int] = {}
    done_b: Dict[Tuple[int, int], int] = {}
    orders: List[List[Tuple[str, int, int]]] = [[] for _ in range(S)]
    total = 2 * M * K
    scheduled, t = 0, 0
    while scheduled < total:
        picks = []
        for s in range(S):
            chunks = stage_virtual_chunks(s, S, v)
            best = None
            # backwards first: B(c, i) needs F(c, i) and B(c+1, i)
            for c in chunks:
                for i in range(M):
                    if (c, i) in done_b:
                        continue
                    if done_f.get((c, i), t) >= t:
                        continue
                    if c < K - 1 and done_b.get((c + 1, i), t) >= t:
                        continue
                    key = ("B", i // S, K - 1 - c, i % S)
                    if best is None or key < best[0]:
                        best = (key, ("B", i, c))
            if best is None:
                # forwards: F(c, i) needs F(c-1, i)
                for c in chunks:
                    for i in range(M):
                        if (c, i) in done_f:
                            continue
                        if c > 0 and done_f.get((c - 1, i), t) >= t:
                            continue
                        key = ("F", i // S, c, i % S)
                        if best is None or key < best[0]:
                            best = (key, ("F", i, c))
            if best is not None:
                picks.append((s, best[1]))
        for s, (op, i, c) in picks:
            orders[s].append((op, i, c))
            (done_f if op == "F" else done_b)[(c, i)] = t
            scheduled += 1
        t += 1
    return tuple(tuple(o) for o in orders)


def one_f_one_b_order(stage: int, n_stages: int, n_microbatches: int,
                      n_virtual: int = 1) -> List[Tuple[str, int, int]]:
    """One stage's pipeline-step op order: ``[(op, microbatch, chunk),
    ...]`` with op "F"/"B" and ``chunk`` the global virtual-stage id.

    ``n_virtual == 1`` is the classic 1F1B schedule (chunk == stage);
    ``n_virtual > 1`` interleaves the stage's round-robin chunks via
    the deterministic greedy simulation in :func:`interleaved_orders`,
    cutting warmup/cooldown idle by the virtual-stage factor."""
    if n_virtual <= 1:
        return _classic_1f1b(stage, n_stages, n_microbatches)
    return list(interleaved_orders(n_stages, n_microbatches,
                                   n_virtual)[stage])


def analytic_bubble(n_stages: int, n_microbatches: int,
                    n_virtual: int = 1) -> float:
    """The analytic pipeline-bubble fraction with interleaved virtual
    stages, ``(S-1)/(v*M+S-1)``: warmup and cooldown are paid in
    CHUNK-sized quanta (1/v of a full stage visit), so the idle share
    of each device's timeline shrinks by the virtual-stage factor
    (arXiv:2412.14374; Megatron interleaved 1F1B)."""
    s, m, v = n_stages, n_microbatches, n_virtual
    return (s - 1) / (v * m + s - 1)


def analytic_gpipe_bubble(n_stages: int, n_microbatches: int) -> float:
    """The GPipe pipeline-bubble fraction ``(S-1)/(M+S-1)``: the share
    of each device's timeline spent idle when M microbatches flow
    through S stages with a full flush between steps. 1F1B has the
    same bubble in steady state; its win is activation memory."""
    return analytic_bubble(n_stages, n_microbatches, 1)


def _recorder():
    """This process's flight recorder (None outside a runtime)."""
    try:
        from ray_tpu.core.global_state import try_global_worker
        w = try_global_worker()
        return w.recorder if w is not None else None
    except Exception:
        return None


def _default_stage_optimizer(learning_rate: float, weight_decay: float):
    """The per-stage optimizer matching ``models.training``'s default
    MINUS the global-norm clip — clipping needs the cross-stage norm,
    so the driver reduces per-stage squared norms and every stage
    applies the same scale inside its fused opt program."""
    import optax
    return optax.adamw(learning_rate, b1=0.9, b2=0.95, eps=1e-8,
                       weight_decay=weight_decay)


# --------------------------------------------------------- checkpoints
def _map_param_subtrees(tree, params_treedef, fn):
    """Apply ``fn`` to every subtree of ``tree`` whose structure equals
    ``params_treedef`` (the stage's ``{chunk: param_tree}`` layout),
    passing other leaves through — the trick ``models.training`` uses
    to find param-shaped subtrees (Adam moments) inside an arbitrary
    optax state."""
    import jax

    def is_p(x):
        try:
            return jax.tree.structure(x) == params_treedef
        except Exception:
            return False

    return jax.tree.map(lambda sub: fn(sub) if is_p(sub) else sub,
                        tree, is_leaf=is_p)


def _collect_param_subtrees(tree, params_treedef) -> List[Any]:
    out: List[Any] = []
    _map_param_subtrees(tree, params_treedef,
                        lambda sub: (out.append(sub), sub)[1])
    return out


def merge_stage_checkpoints(config, parts: Sequence[Dict]) -> Dict:
    """Reassemble per-stage checkpoints (from
    :meth:`PipelineStage.stage_checkpoint`) into the canonical
    single-program train state ``{"params", "opt_state", "step"}`` —
    the exact pytree layout ``make_train_step(optimizer=<same
    optimizer>)`` builds, so the pipeline checkpoint round-trips
    against the single-program one. Param-shaped subtrees inside the
    optax state (Adam mu/nu) are found by treedef match and merged
    chunk-wise; counters are taken from stage 0 (identical across
    stages by construction)."""
    import jax

    from ray_tpu.models.transformer import merge_stage_params

    parts = sorted(parts, key=lambda p: p["stage"])
    chunks: Dict[int, Any] = {}
    for p in parts:
        chunks.update(p["chunks"])
    out: Dict[str, Any] = {
        "params": merge_stage_params(config, chunks),
        "step": parts[0].get("step", 0),
    }
    if parts[0].get("opt_state") is not None:
        per_stage = [
            _collect_param_subtrees(p["opt_state"],
                                    jax.tree.structure(p["chunks"]))
            for p in parts]
        counts = {len(s) for s in per_stage}
        if len(counts) != 1:
            raise ValueError(
                f"stage opt states disagree on param-subtree count: "
                f"{sorted(counts)}")
        merged = []
        for j in range(counts.pop()):
            union: Dict[int, Any] = {}
            for s in per_stage:
                union.update(s[j])
            merged.append(merge_stage_params(config, union))
        it = iter(merged)
        out["opt_state"] = _map_param_subtrees(
            parts[0]["opt_state"],
            jax.tree.structure(parts[0]["chunks"]), lambda _: next(it))
    return out


def split_train_state(config, state: Dict, n_stages: int,
                      n_virtual: int = 1) -> List[Dict]:
    """Slice a canonical train state into per-stage load parts for any
    ``(n_stages, n_virtual)`` — the reload target need not match the
    layout the checkpoint was saved under. Inverse of
    :func:`merge_stage_checkpoints` (chunk slices of the stacked layer
    leaves are views of the same weights)."""
    import jax

    from ray_tpu.models.transformer import stage_slice_params

    K = n_stages * n_virtual
    full_td = jax.tree.structure(state["params"])

    def slice_for(s):
        chs = stage_virtual_chunks(s, n_stages, n_virtual)
        part: Dict[str, Any] = {
            "params": {c: stage_slice_params(config, state["params"],
                                             c, K) for c in chs},
            "step": state.get("step", 0),
        }
        if state.get("opt_state") is not None:
            part["opt_state"] = _map_param_subtrees(
                state["opt_state"], full_td,
                lambda sub: {c: stage_slice_params(config, sub, c, K)
                             for c in chs})
        return part

    return [slice_for(s) for s in range(n_stages)]


class PipelineStage:
    """One pipeline stage, hosted in its own actor process.

    Holds the stage's ``n_virtual`` parameter chunks on its pinned
    device, the per-chunk-role jitted forward programs, the shared
    backward program (backward-from-saved-residuals), and — with
    ``train=True`` — the fused optimizer program plus resident optax
    state. Activations and gradients arrive through mailboxes keyed by
    ``(chunk, microbatch)`` (:meth:`put_activation` / :meth:`put_grad`
    / :meth:`put_targets` — tiny actor calls whose object args are
    pulled worker-to-worker), and one streaming :meth:`run` call per
    step yields the stage's per-op outputs in its deterministic
    interleaved-1F1B order.

    Run with ``max_concurrency >= 2``: ``run`` blocks on mailboxes
    while the feed calls execute on sibling threads.
    """

    def __init__(self, config, stage: int, n_stages: int, seed: int = 0,
                 device_index: Optional[int] = None,
                 remat_policy: Optional[str] = None,
                 n_virtual: int = 1,
                 train: bool = False,
                 learning_rate: float = 1e-5,
                 weight_decay: float = 0.0,
                 clip_norm: Optional[float] = 1.0,
                 optimizer_factory=None,
                 mailbox_deadline_s: Optional[float] = None,
                 dp: int = 1,
                 fsdp: int = 1,
                 grad_transport: str = "fp32",
                 shard_weight_update: bool = False,
                 quant_block_size: Optional[int] = None,
                 quant_stochastic: bool = False,
                 stage_mesh: Optional[bool] = None,
                 device_indices: Optional[Sequence[int]] = None):
        import threading

        import jax

        from ray_tpu.core.config import get_config
        from ray_tpu.models.transformer import (
            init_params, stage_slice_params)
        from ray_tpu.parallel.quantization import DEFAULT_BLOCK_SIZE

        if remat_policy is not None:
            config = dataclasses.replace(config, remat=None,
                                         remat_policy=remat_policy)
        if grad_transport not in ("fp32", "int8"):
            raise ValueError(f"grad_transport must be 'fp32' or 'int8', "
                             f"got {grad_transport!r}")
        self.config = config
        self.stage = stage
        self.n_stages = n_stages
        self.n_virtual = n_virtual
        self.n_chunks = n_stages * n_virtual
        self.chunks = stage_virtual_chunks(stage, n_stages, n_virtual)
        #: the stage's own data-parallel grid: every mailbox microbatch
        #: is sharded batch-wise over a dp×fsdp mesh of this actor's
        #: devices, and the fused optimizer runs the cross-replica
        #: sharded-update path over the same axes (3D = pp × dp × fsdp)
        self.dp = int(dp)
        self.fsdp = int(fsdp)
        self.n_model = self.dp * self.fsdp
        self.grad_transport = grad_transport
        self.shard_weight_update = bool(shard_weight_update)
        self.quant_block_size = int(quant_block_size
                                    or DEFAULT_BLOCK_SIZE)
        self.quant_stochastic = bool(quant_stochastic)
        #: shard_map'd stage programs: automatic when the stage grid is
        #: nontrivial; ``stage_mesh=True`` forces the path onto a
        #: 1-device mesh (the clusterless tests use this to exercise
        #: the 3D programs without multiple devices)
        self.use_mesh = (self.n_model > 1 if stage_mesh is None
                         else bool(stage_mesh))
        #: seconds a mailbox take may starve before the stage fails
        #: typed (a dead neighbor must surface as an error, never a
        #: hang) — config.pipeline_mailbox_deadline_s unless overridden
        self.mailbox_deadline_s = float(
            mailbox_deadline_s if mailbox_deadline_s is not None
            else get_config().pipeline_mailbox_deadline_s)
        devices = jax.devices()
        self.mesh = None
        if self.use_mesh:
            from ray_tpu.parallel.mesh import MeshSpec, build_mesh
            if device_indices is None:
                base = (stage if device_index is None
                        else device_index) * self.n_model
                device_indices = [(base + j) % len(devices)
                                  for j in range(self.n_model)]
            mine = [devices[i % len(devices)] for i in device_indices]
            if len({d.id for d in mine}) < self.n_model:
                raise ValueError(
                    f"stage {stage} needs {self.n_model} distinct "
                    f"devices for its dp={self.dp} x fsdp={self.fsdp} "
                    f"mesh, process has {len(devices)}")
            self.mesh = build_mesh(
                MeshSpec(dp=self.dp, fsdp=self.fsdp), mine)
            self.device = mine[0]
        else:
            self.device = devices[(stage if device_index is None
                                   else device_index) % len(devices)]
        # full init from the shared seed, then slice: the stage weights
        # are bit-identical to the single-program model's (parity is a
        # slicing invariant, not a tolerance)
        params = init_params(config, jax.random.PRNGKey(seed))
        self.params = {
            c: self._place_params(
                stage_slice_params(config, params, c, self.n_chunks))
            for c in self.chunks}
        del params
        self._build_programs()
        self.optimizer = None
        self.opt_state = None
        self.clip_norm = clip_norm
        #: flat 1/N optimizer shards only make sense on a stage mesh
        self._opt_flat = self.use_mesh and self.shard_weight_update
        if train:
            factory = optimizer_factory or _default_stage_optimizer
            self.optimizer = factory(learning_rate, weight_decay)
            if self._opt_flat:
                # optimizer state lives flat-sharded over the stage
                # mesh (1/N resident per device): init inside jit so
                # the flat constraint shards the moments at creation
                from ray_tpu.parallel.sharding import flatten_tree
                world, block = self.n_model, self.quant_block_size
                flat_sh = self._flat_sharding()
                init_prog = jax.jit(lambda p: self.optimizer.init(
                    flatten_tree(p, world, block,
                                 constrain_to=flat_sh)))
                self.opt_state = init_prog(self.params)
            elif self.use_mesh:
                self.opt_state = self._place_params(
                    self.optimizer.init(self.params))
            else:
                self.opt_state = jax.device_put(
                    self.optimizer.init(self.params), self.device)
            self._build_opt_program()
        self._step_count = 0
        self._cond = threading.Condition()
        self._acts: Dict[Tuple[int, int], Any] = {}
        self._grads_in: Dict[Tuple[int, int], Any] = {}
        self._targets: Dict[int, Any] = {}
        self._abort = False
        self._vjps: Dict[Tuple[int, int], Any] = {}
        self._inputs: Dict[Tuple[int, int], Any] = {}
        self._grads: Dict[int, Any] = {}
        self._red_cache = None
        self._sqn = None
        self._stats = self._fresh_stats()
        # live mailbox-depth gauge (fleet metrics plane): how many
        # microbatches are parked waiting for this stage — the queue
        # signal behind the bubbles the timeline shows
        self._mbx_gauge = None
        try:
            from ray_tpu.core.metric_defs import runtime_metrics
            self._mbx_gauge = runtime_metrics().pipeline_mailbox_depth
        except Exception:
            pass
        self._mbx_tags = {"stage": str(stage)}

    def _mbx_report_locked(self) -> None:
        """Refresh the mailbox-depth gauge (``self._cond`` held)."""
        if self._mbx_gauge is None:
            return
        try:
            self._mbx_gauge.set(
                len(self._acts) + len(self._grads_in) +
                len(self._targets), tags=self._mbx_tags)
        except Exception:
            pass

    @staticmethod
    def _fresh_stats() -> Dict[str, float]:
        return {"busy_s": 0.0, "idle_s": 0.0, "fwd_s": 0.0,
                "bwd_s": 0.0, "opt_s": 0.0, "ops": 0, "span_s": 0.0}

    # ---------------------------------------------- mesh placement
    def _batch_spec(self):
        from jax.sharding import PartitionSpec as P
        return P(("dp", "fsdp"))

    def _flat_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(("dp", "fsdp")))

    def _place_params(self, tree):
        """Stage params (and param-shaped state) live replicated over
        the stage mesh — the dp×fsdp axes shard the BATCH; the fsdp
        distinction shows up in the flat 1/N optimizer shards of the
        cross-replica update, not the compute layout."""
        import jax
        if self.mesh is None:
            return jax.device_put(tree, self.device)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _place_batch(self, x):
        """Ship one mailbox payload to the stage's devices: batch dim 0
        sharded over (dp, fsdp) on a mesh stage, plain device_put on a
        single-device stage."""
        import jax
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding
        return jax.device_put(x, NamedSharding(self.mesh,
                                               self._batch_spec()))

    def _place_scalar(self, x):
        import jax
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    # ------------------------------------------------------- programs
    def _build_programs(self):
        if self.mesh is not None:
            return self._build_mesh_programs()
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import stage_forward, stage_loss

        c, K = self.config, self.n_chunks
        progs: Dict[str, Any] = {}
        if 0 in self.chunks:
            # token ids are int32: differentiate wrt params only
            def fwd_first(p, x):
                return jax.vjp(lambda q: stage_forward(c, 0, K, q, x), p)
            progs["first"] = jax.jit(fwd_first)
        if K - 1 in self.chunks:
            def fwd_loss(p, x, ids, mask):
                def f(q, xx):
                    h = stage_forward(c, K - 1, K, q, xx)
                    return stage_loss(c, q, h, ids, mask)[0]
                return jax.vjp(f, p, x)
            progs["loss"] = jax.jit(fwd_loss)
        if any(0 < ch < K - 1 for ch in self.chunks):
            # any middle chunk: same program, retraced per param shape
            def fwd_mid(p, x):
                return jax.vjp(
                    lambda q, xx: stage_forward(c, 1, K, q, xx), p, x)
            progs["mid"] = jax.jit(fwd_mid)
        # device pinning rides the params: they are committed to
        # self.device, so jit places every stage program there. The
        # grad accumulator donates the OLD accumulator buffer (CPU
        # doesn't support donation — skip it there to avoid a
        # per-compile warning; the arithmetic is identical).
        self._donate = jax.default_backend() != "cpu"
        self._fwd_progs = progs
        self._bwd = jax.jit(lambda vjp, g: vjp(g))
        self._acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=(0,) if self._donate else ())

    def _build_mesh_programs(self):
        """The dp×fsdp stage programs: every forward/backward is a
        ``shard_map`` over the stage's own mesh — params replicated in,
        the microbatch sharded batch-wise over ``("dp", "fsdp")``.

        Backwards RECOMPUTE the stage forward from the saved input
        (stage-level remat): residuals never cross the shard_map
        boundary, so the sharded path needs no per-residual specs. Each
        rank's parameter gradients come back STACKED on a leading
        world axis (per-rank partial sums, no reduction in the
        backward); one :func:`collective.psum_tree` pass at optimizer
        time puts the whole step's gradient bytes on the wire at once —
        f32 ``psum`` for ``grad_transport="fp32"``, the two-leg
        int8-quantized reduction (REAL int8 values + f32 scales in the
        all-gather) for ``"int8"``."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ray_tpu.models.transformer import stage_forward, stage_loss
        from ray_tpu.parallel.collective import psum_tree
        c, K = self.config, self.n_chunks
        mesh, world = self.mesh, self.n_model
        axes = ("dp", "fsdp")
        bspec = self._batch_spec()
        rep = P()

        def smap(f, in_specs, out_specs):
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=False))

        def stack(tree):
            return jax.tree.map(lambda a: a[None], tree)

        fwd: Dict[str, Any] = {}
        bwd: Dict[str, Any] = {}
        if 0 in self.chunks:
            fwd["first"] = smap(
                lambda p, x: stage_forward(c, 0, K, p, x),
                (rep, bspec), bspec)

            def bwd_first(p, x, g):
                _, vjp = jax.vjp(
                    lambda q: stage_forward(c, 0, K, q, x), p)
                (gp,) = vjp(g)
                return stack(gp)
            bwd["first"] = smap(bwd_first, (rep, bspec, bspec), bspec)
        if K - 1 in self.chunks:
            def fwd_loss(p, x, ids, mask):
                h = stage_forward(c, K - 1, K, p, x)
                loss, n = stage_loss(c, p, h, ids, mask)
                n_tot = jax.lax.psum(n, axes)
                loss_w = jax.lax.psum(loss * n, axes) \
                    / jnp.maximum(n_tot, 1.0)
                return loss_w, n_tot
            fwd["loss"] = smap(fwd_loss, (rep, bspec, bspec, bspec),
                               (rep, rep))

            def bwd_loss(p, x, ids, mask, seed):
                # local loss is the mean over the LOCAL shard's tokens;
                # the cotangent rescales it so summed-over-ranks grads
                # equal the global-mean gradient: seed is the driver's
                # n_mb/N, local seed = seed * n_loc/n_mb = n_loc/N
                def f(q, xx):
                    h = stage_forward(c, K - 1, K, q, xx)
                    return stage_loss(c, q, h, ids, mask)[0]
                _, vjp = jax.vjp(f, p, x)
                n_loc = jnp.sum(mask[:, 1:])
                n_mb = jax.lax.psum(n_loc, axes)
                gp, gx = vjp(seed * n_loc / jnp.maximum(n_mb, 1.0))
                return stack(gp), gx
            bwd["loss"] = smap(bwd_loss,
                               (rep, bspec, bspec, bspec, rep),
                               (bspec, bspec))
        if any(0 < ch < K - 1 for ch in self.chunks):
            fwd["mid"] = smap(
                lambda p, x: stage_forward(c, 1, K, p, x),
                (rep, bspec), bspec)

            def bwd_mid(p, x, g):
                _, vjp = jax.vjp(
                    lambda q, xx: stage_forward(c, 1, K, q, xx), p, x)
                gp, gx = vjp(g)
                return stack(gp), gx
            bwd["mid"] = smap(bwd_mid, (rep, bspec, bspec),
                              (bspec, bspec))

        # the once-per-step gradient reduction: stacked per-rank
        # accumulators in, reduced (replicated) gradients out — the
        # stage's REAL bytes on the wire
        tr, block = self.grad_transport, self.quant_block_size
        sr = self.quant_stochastic

        def reduce_body(stacked, seed):
            local = jax.tree.map(lambda a: a[0], stacked)
            key = None
            if sr:
                idx = 0
                for a in axes:
                    idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
                key = jax.random.fold_in(jax.random.PRNGKey(0xE8), seed)
                key = jax.random.fold_in(key, idx)
            return psum_tree(local, axes, world, transport=tr,
                             block_size=block, stochastic_rounding=sr,
                             key=key)
        self._reduce_prog = smap(reduce_body, (bspec, rep), rep)

        self._donate = jax.default_backend() != "cpu"
        self._m_fwd = fwd
        self._m_bwd = bwd
        self._acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=(0,) if self._donate else ())

    def _role_for(self, chunk: int) -> str:
        if chunk == 0:
            return "first"
        if chunk == self.n_chunks - 1:
            return "loss"
        return "mid"

    def _fwd_for(self, chunk: int):
        if chunk == 0:
            return self._fwd_progs["first"]
        if chunk == self.n_chunks - 1:
            return self._fwd_progs["loss"]
        return self._fwd_progs["mid"]

    def _build_opt_program(self):
        """The fused per-stage optimizer step: clip-scale the
        accumulated grads by the DRIVER-reduced global norm, run the
        optax update on this stage's param slice, apply it — params,
        opt state and grads all donated, so the update is in-place on
        the stage and nothing heavier than a scalar ever crosses the
        driver."""
        import jax
        import jax.numpy as jnp
        import optax

        clip = self.clip_norm
        optimizer = self.optimizer
        opt_flat = self._opt_flat
        if opt_flat:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ray_tpu.parallel.sharding import flatten_tree
            world, block = self.n_model, self.quant_block_size
            flat_sh = self._flat_sharding()
            rep_sh = NamedSharding(self.mesh, P())

        def opt_step(params, opt_state, grads, global_sq_norm):
            if clip is not None:
                gn = jnp.sqrt(global_sq_norm.astype(jnp.float32))
                # exactly optax.clip_by_global_norm's select, with the
                # cross-stage norm in place of the local one
                scale = jnp.where(gn < clip, 1.0, clip / gn)
                grads = jax.tree.map(lambda g: g * scale, grads)
            if opt_flat:
                # cross-replica sharded update over the stage mesh
                # (arXiv:2004.13336): scatter grads + master params to
                # flat 1/N shards, update only the local optimizer
                # shard, gather fresh params via the constraint back
                gflat = flatten_tree(grads, world, block,
                                     constrain_to=flat_sh)
                pflat = flatten_tree(params, world, block,
                                     constrain_to=flat_sh)
                updates, new_opt = optimizer.update(
                    gflat, opt_state, pflat)
                new_pflat = optax.apply_updates(pflat, updates)
                new_params = jax.tree.map(
                    lambda p, f: jax.lax.with_sharding_constraint(
                        f[:p.size].reshape(p.shape), rep_sh),
                    params, new_pflat)
                return new_params, new_opt
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt

        self._opt_prog = jax.jit(
            opt_step,
            donate_argnums=(0, 1, 2) if self._donate else ())

    # ------------------------------------------------------- mailboxes
    def feed(self, acts=None, grads=None, targets=None) -> None:
        """Batched mailbox fill: the driver front-loads a whole step's
        token microbatches, targets and loss seeds in ONE actor call
        per stage (``acts``/``grads`` keyed ``(chunk, mb)``,
        ``targets`` keyed ``mb``) instead of 3M unary puts — on a
        busy box the per-call overhead is the pipeline's fixed tax."""
        with self._cond:
            if acts:
                self._acts.update(acts)
            if grads:
                self._grads_in.update(grads)
            if targets:
                self._targets.update(targets)
            self._mbx_report_locked()
            self._cond.notify_all()

    def put_activation(self, chunk: int, i: int, x) -> None:
        with self._cond:
            self._acts[(chunk, i)] = x
            self._mbx_report_locked()
            self._cond.notify_all()

    def put_grad(self, chunk: int, i: int, g) -> None:
        with self._cond:
            self._grads_in[(chunk, i)] = g
            self._mbx_report_locked()
            self._cond.notify_all()

    def put_targets(self, i: int, input_ids, loss_mask=None) -> None:
        """Last stage only: the labels (and mask) microbatch the loss
        needs — fed by the driver alongside stage 0's token feed."""
        with self._cond:
            self._targets[i] = (input_ids, loss_mask)
            self._cond.notify_all()

    def abort(self) -> None:
        """Unblock any pending mailbox take with a typed error (driver
        cleanup after a neighbor stage died) AND drain every queued
        mailbox item. Mailbox keys are ``(chunk, microbatch)`` and
        repeat every step, so an item stranded by an aborted step would
        otherwise be silently consumed by the NEXT step's matching op —
        stale activations in, and the op that should have produced them
        starving into the mailbox deadline. Draining here makes an
        aborted stage immediately reusable."""
        with self._cond:
            self._abort = True
            self._acts.clear()
            self._grads_in.clear()
            self._targets.clear()
            self._vjps.clear()
            self._inputs.clear()
            self._grads = {}
            self._red_cache = None
            self._mbx_report_locked()
            self._cond.notify_all()

    def _take(self, box: Dict, key):
        deadline = time.monotonic() + self.mailbox_deadline_s
        with self._cond:
            while key not in box:
                if self._abort:
                    raise RuntimeError(
                        f"stage {self.stage} aborted waiting for "
                        f"{key}")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"stage {self.stage} starved waiting for "
                        f"{key} beyond pipeline_mailbox_deadline_s="
                        f"{self.mailbox_deadline_s} (neighbor stage "
                        f"dead?)")
                self._cond.wait(0.1)
            out = box.pop(key)
            self._mbx_report_locked()
            return out

    # ----------------------------------------------------- op helpers
    def _fwd_op(self, ch: int, i: int, x, tgt):
        """One forward op: returns the yieldable output — the
        activation, or the ``{"loss", "n_tokens"}`` dict on the last
        chunk — and saves what the matching backward needs: the vjp
        residuals on a single-device stage, the raw (placed) inputs on
        a mesh stage (whose backward recomputes)."""
        import jax
        import jax.numpy as jnp

        K = self.n_chunks
        if self.mesh is not None:
            x = self._place_batch(x)
            if ch == K - 1:
                ids, mask = tgt
                if mask is None:
                    import numpy as np
                    mask = np.ones(np.asarray(ids).shape, np.float32)
                ids = self._place_batch(ids)
                mask = self._place_batch(mask)
                loss, n = self._m_fwd["loss"](self.params[ch], x,
                                              ids, mask)
                self._inputs[(ch, i)] = (x, ids, mask)
                return {"loss": float(loss), "n_tokens": float(n)}
            out = self._m_fwd[self._role_for(ch)](self.params[ch], x)
            self._inputs[(ch, i)] = (x,)
            jax.block_until_ready(out)
            return out
        if ch == K - 1:
            ids, mask = tgt
            if mask is None:
                mask = jnp.ones_like(ids, dtype=jnp.float32)
            loss, vjp = self._fwd_for(ch)(self.params[ch], x, ids, mask)
            n = float(jnp.sum(mask[:, 1:]))
            out: Any = {"loss": float(loss), "n_tokens": n}
        else:
            out, vjp = self._fwd_for(ch)(self.params[ch], x)
            jax.block_until_ready(out)
        self._vjps[(ch, i)] = vjp
        return out

    def _bwd_op(self, ch: int, i: int, g):
        """One backward op: accumulates this chunk's parameter
        gradients in-actor and returns the upstream input-gradient
        (None on chunk 0). Mesh stages recompute the forward from the
        saved input and accumulate per-rank STACKED partials — the
        cross-rank reduction waits for :meth:`_reduced_grads`."""
        import jax

        if self.mesh is not None:
            saved = self._inputs.pop((ch, i))
            role = self._role_for(ch)
            if role == "loss":
                x, ids, mask = saved
                gp, gx = self._m_bwd["loss"](
                    self.params[ch], x, ids, mask,
                    self._place_scalar(g))
                out = gx if ch > 0 else None
            elif role == "first":
                gp = self._m_bwd["first"](self.params[ch], saved[0],
                                          self._place_batch(g))
                out = None
            else:
                gp, out = self._m_bwd["mid"](self.params[ch], saved[0],
                                             self._place_batch(g))
            self._red_cache = None
        else:
            parts = self._bwd(self._vjps.pop((ch, i)), g)
            gp = parts[0]
            out = parts[1] if ch > 0 else None
        self._grads[ch] = gp if self._grads.get(ch) is None \
            else self._acc(self._grads[ch], gp)
        jax.block_until_ready(out if out is not None
                              else self._grads[ch])
        return out

    # ------------------------------------------------------------ step
    def run(self, n_microbatches: int):
        """One pipeline step as a streaming generator: walks this
        stage's (interleaved) 1F1B order, blocking on the mailbox each
        op needs, and yields the op's output as its own stream item —
        the activation (F, non-last chunk), the (loss, n_tokens) pair
        (F, last chunk), the upstream input-gradient (B, chunk > 0) or
        the op duration (B, chunk 0). Records a ``STAGE_TICK`` span
        per compute AND per idle interval, labelled with phase and
        virtual-stage index: the timeline shows the bubbles."""
        import jax

        rec = _recorder()
        K = self.n_chunks
        self._stats = self._fresh_stats()
        with self._cond:
            self._abort = False
        self._vjps.clear()
        self._inputs.clear()
        self._grads = {}
        self._red_cache = None
        t_start = time.perf_counter()
        for op, i, ch in one_f_one_b_order(
                self.stage, self.n_stages, n_microbatches,
                self.n_virtual):
            t_wait = time.perf_counter()
            if op == "F":
                x = self._take(self._acts, (ch, i))
                tgt = self._take(self._targets, i) if ch == K - 1 \
                    else None
            else:
                g = self._take(self._grads_in, (ch, i))
            idle = time.perf_counter() - t_wait
            if rec is not None and idle > 1e-4:
                rec.record("STAGE_TICK", stage=self.stage, mb=i, vs=ch,
                           phase="idle", dur_s=round(idle, 6))
            t0 = time.perf_counter()
            if op == "F":
                out = self._fwd_op(ch, i, x, tgt)
            else:
                out = self._bwd_op(ch, i, g)
            dur = time.perf_counter() - t0
            st = self._stats
            st["busy_s"] += dur
            st["idle_s"] += idle
            st["fwd_s" if op == "F" else "bwd_s"] += dur
            st["ops"] += 1
            if rec is not None:
                rec.record("STAGE_TICK", stage=self.stage, mb=i, vs=ch,
                           phase="forward" if op == "F" else "backward",
                           dur_s=round(dur, 6))
                rec.maybe_flush()
            yield out if out is not None else {"dur_s": dur}
        self._stats["span_s"] = time.perf_counter() - t_start

    # ------------------------------------------- fused optimizer step
    def _require_grads(self) -> None:
        missing = [c for c in self.chunks if self._grads.get(c) is None]
        if missing:
            raise RuntimeError(
                f"stage {self.stage}: no accumulated grads for chunks "
                f"{missing} (run a step first)")

    def _reduced_grads(self):
        """The step's accumulated gradients, reduced across the stage
        mesh (identity on single-device stages). On mesh stages this is
        THE stage communication op — one ``psum_tree`` pass over the
        whole accumulated gradient per step: plain f32 ``psum`` for
        fp32 transport, the two-leg int8 reduction (real int8 bytes in
        the gather) for int8. Cached until the next backward/step."""
        if self.mesh is None:
            return {c: self._grads[c] for c in self.chunks}
        if self._red_cache is None:
            import numpy as np
            stacked = {c: self._grads[c] for c in self.chunks}
            self._red_cache = self._reduce_prog(
                stacked, np.uint32(self._step_count))
        return self._red_cache

    def grad_sq_norm(self) -> float:
        """Squared L2 norm of this stage's accumulated grads — the
        stage's contribution to the global clip norm (a single f32
        scalar; the only gradient-derived value that ever reaches the
        driver in train mode)."""
        import jax
        import jax.numpy as jnp

        self._require_grads()
        if self._sqn is None:
            self._sqn = jax.jit(lambda g: sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in jax.tree.leaves(g)))
        return float(self._sqn(self._reduced_grads()))

    def apply_opt(self, global_sq_norm: float) -> Dict[str, float]:
        """The per-stage fused optimizer step: one jitted program
        (clip-scale + optax update + apply, donated buffers) over the
        stage's accumulated grads. Grads/params/opt-state never leave
        the actor; returns only scalar metrics."""
        import jax
        import jax.numpy as jnp

        if self.optimizer is None:
            raise RuntimeError("stage built with train=False has no "
                               "optimizer (pass train=True)")
        self._require_grads()
        t0 = time.perf_counter()
        grads = self._reduced_grads()
        self.params, self.opt_state = self._opt_prog(
            self.params, self.opt_state, grads,
            jnp.float32(global_sq_norm))
        jax.block_until_ready(self.params)
        self._grads = {}
        self._red_cache = None
        self._step_count += 1
        dur = time.perf_counter() - t0
        st = self._stats
        st["busy_s"] += dur
        st["opt_s"] += dur
        rec = _recorder()
        if rec is not None:
            rec.record("STAGE_TICK", stage=self.stage, phase="opt",
                       dur_s=round(dur, 6))
            rec.maybe_flush()
        return {"grad_norm": float(global_sq_norm) ** 0.5,
                "opt_s": dur, "step": self._step_count}

    # ----------------------------------------------------- checkpoint
    def stage_checkpoint(self) -> Dict[str, Any]:
        """Host copy of the stage's train state, keyed by global chunk
        id — :func:`merge_stage_checkpoints` reassembles the canonical
        single-program layout from all stages' parts."""
        import numpy as np

        import jax

        host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        chunks = {c: host(p) for c, p in self.params.items()}
        opt = None
        if self.opt_state is not None:
            opt = host(self.opt_state)
            if self._opt_flat:
                # flat 1/N shards back to the canonical param-shaped
                # layout, so a 3D checkpoint merges/reloads like any
                # other (the flat layout is a residency optimization,
                # not a checkpoint format)
                from ray_tpu.parallel.sharding import unflatten_like
                opt = _map_param_subtrees(
                    opt, jax.tree.structure(chunks),
                    lambda sub: unflatten_like(chunks, sub))
        part: Dict[str, Any] = {
            "stage": self.stage,
            "n_stages": self.n_stages,
            "n_virtual": self.n_virtual,
            "chunks": chunks,
            "opt_state": opt,
            "step": self._step_count,
        }
        return part

    def load_state(self, part: Dict[str, Any]) -> None:
        """Load one part from :func:`split_train_state` (params keyed
        by this stage's chunk ids, opt state in the stage layout)."""
        import jax

        want = set(self.chunks)
        got = set(part["params"])
        if want != got:
            raise ValueError(
                f"stage {self.stage} hosts chunks {sorted(want)}, "
                f"checkpoint part carries {sorted(got)}")
        self.params = self._place_params(
            {int(c): p for c, p in part["params"].items()})
        if part.get("opt_state") is not None:
            if self.optimizer is None:
                raise RuntimeError("cannot load optimizer state into a "
                                   "train=False stage")
            opt = part["opt_state"]
            if self._opt_flat:
                # canonical param-shaped state back into flat 1/N
                # shards over the stage mesh
                from ray_tpu.parallel.sharding import flatten_tree
                world, block = self.n_model, self.quant_block_size
                flat_sh = self._flat_sharding()
                td = jax.tree.structure(self.params)
                place = jax.jit(lambda o: _map_param_subtrees(
                    o, td, lambda sub: flatten_tree(
                        sub, world, block, constrain_to=flat_sh)))
                self.opt_state = place(opt)
            else:
                self.opt_state = self._place_params(opt)
        self._step_count = int(part.get("step", 0))

    def stream_checkpoint(self):
        """:meth:`stage_checkpoint` as a stream: one block per param
        chunk, then one meta block carrying the (canonicalized) opt
        state and step count. Each block is its own stream item —
        exactly-once over the reliable layer — so the driver can
        forward a chunk's ref to its new owner while later chunks are
        still being host-copied, and the bytes move worker-to-worker
        (:meth:`load_state_blocks`) instead of round-tripping through
        the driver."""
        import numpy as np

        import jax

        host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        chunks: Dict[int, Any] = {}
        for c in self.chunks:
            chunks[c] = host(self.params[c])
            yield {"block": "params", "stage": self.stage, "chunk": c,
                   "params": chunks[c]}
        opt = None
        if self.opt_state is not None:
            opt = host(self.opt_state)
            if self._opt_flat:
                from ray_tpu.parallel.sharding import unflatten_like
                opt = _map_param_subtrees(
                    opt, jax.tree.structure(chunks),
                    lambda sub: unflatten_like(chunks, sub))
        yield {"block": "meta", "stage": self.stage,
               "n_stages": self.n_stages, "n_virtual": self.n_virtual,
               "opt_state": opt, "step": self._step_count}

    def load_state_blocks(self, *blocks) -> None:
        """Assemble a stage part from :meth:`stream_checkpoint` blocks
        and load it. The blocks arrive as actor-call object args, so
        when the driver passes the REFS a peer stage streamed, the
        payload is pulled worker-to-worker — the driver never
        materializes the bytes (the elastic same-grid reload path)."""
        part: Dict[str, Any] = {"params": {}}
        for b in blocks:
            if b.get("block") == "params":
                part["params"][int(b["chunk"])] = b["params"]
            else:
                part["opt_state"] = b.get("opt_state")
                part["step"] = b.get("step", 0)
        self.load_state(part)

    # ------------------------------------- serial (unpipelined) path
    def forward_one(self, chunk: int, i: int, x, input_ids=None,
                    loss_mask=None):
        """Unary forward for the serial chunk-by-chunk baseline: same
        jitted programs, no mailbox, one (chunk, microbatch) per
        call."""
        t0 = time.perf_counter()
        tgt = (input_ids, loss_mask) \
            if chunk == self.n_chunks - 1 and chunk > 0 else None
        res = self._fwd_op(chunk, i, x, tgt)
        self._tick("forward", i, chunk, time.perf_counter() - t0)
        return res

    def backward_one(self, chunk: int, i: int, g):
        t0 = time.perf_counter()
        out = self._bwd_op(chunk, i, g)
        self._tick("backward", i, chunk, time.perf_counter() - t0)
        return out

    def _tick(self, phase: str, i: int, chunk: int, dur: float) -> None:
        st = self._stats
        st["busy_s"] += dur
        st[("fwd_s" if phase == "forward" else "bwd_s")] += dur
        st["ops"] += 1
        rec = _recorder()
        if rec is not None:
            rec.record("STAGE_TICK", stage=self.stage, mb=i, vs=chunk,
                       phase=phase, dur_s=round(dur, 6))
            rec.maybe_flush()

    def reset_step(self) -> None:
        """Serial-path step reset (the streaming ``run`` resets
        itself)."""
        with self._cond:
            self._abort = False
        self._vjps.clear()
        self._inputs.clear()
        self._grads = {}
        self._red_cache = None
        self._stats = self._fresh_stats()
        self._t_reset = time.perf_counter()

    # ------------------------------------------------------- queries
    def step_stats(self) -> Dict[str, float]:
        st = dict(self._stats)
        if not st["span_s"] and getattr(self, "_t_reset", None):
            st["span_s"] = time.perf_counter() - self._t_reset
        st["device"] = str(self.device)
        st["stage"] = self.stage
        st["chunks"] = list(self.chunks)
        return st

    def get_grads(self):
        """Host copy of the accumulated parameter gradients, keyed by
        global chunk id (legacy fwd+bwd mode — in train mode grads are
        consumed in-actor by :meth:`apply_opt`). Mesh stages return the
        cross-rank REDUCED gradients (one reduction, cached)."""
        import numpy as np

        import jax
        if self.mesh is not None:
            if any(self._grads.get(c) is None for c in self.chunks):
                return {}
            return {c: jax.tree.map(np.asarray, g)
                    for c, g in self._reduced_grads().items()}
        return {c: jax.tree.map(np.asarray, g)
                for c, g in self._grads.items()}

    def ping(self) -> int:
        return self.stage


@dataclasses.dataclass
class PipelineStepResult:
    loss: float
    n_tokens: float
    #: per-microbatch (loss, n) pairs in microbatch order
    microbatch_losses: List[Tuple[float, float]]
    #: per-stage step_stats dicts
    stage_stats: List[Dict[str, float]]
    wall_s: float
    #: global gradient norm (train mode; None for fwd+bwd steps)
    grad_norm: Optional[float] = None
    #: optimizer step count after this step (train mode)
    step: Optional[int] = None

    @property
    def bubble_fraction(self) -> float:
        """Measured bubble: the mean over stages of the fraction of
        the step's wall clock each stage spent NOT computing."""
        if not self.wall_s:
            return 0.0
        fr = [1.0 - min(s["busy_s"] / self.wall_s, 1.0)
              for s in self.stage_stats]
        return sum(fr) / len(fr)


class MPMDPipeline:
    """Driver-side interleaved-1F1B scheduler over
    :class:`PipelineStage` actors.

    ``step(batch)`` splits the batch into ``n_microbatches`` along the
    batch axis, feeds chunk 0's token microbatches / the last chunk's
    targets and loss seeds, launches one streaming ``run`` per stage,
    and routes items (by ref) between neighbor chunks as
    ``streaming.wait_any`` reports them ready. The combined loss is
    the token-weighted mean of the per-microbatch losses — exactly the
    single-program ``lm_loss`` of the full batch.

    ``n_virtual > 1`` hosts that many round-robin virtual stage chunks
    per actor and drives the interleaved schedule — analytic bubble
    ``(S-1)/(v*M+S-1)``.

    ``train=True`` makes ``step`` a full train step: after the streams
    drain, the driver reduces the per-stage squared grad norms (one
    scalar per stage), then every stage runs its fused optimizer
    program concurrently — gradients, parameters and optimizer state
    never transit the driver. ``save_checkpoint()`` /
    ``load_checkpoint()`` move the canonical single-program state
    layout in and out (any ``n_virtual``).

    ``serial=True`` drives the same actors chunk-by-chunk with unary
    calls and full barriers — the no-overlap baseline the measured
    bubble fraction is compared against.
    """

    def __init__(self, config, n_stages: int = 2,
                 n_microbatches: int = 4, seed: int = 0,
                 serial: bool = False,
                 step_timeout_s: float = 300.0,
                 actor_options: Optional[Dict[str, Any]] = None,
                 remat_policies: Optional[Sequence[Optional[str]]] = None,
                 n_virtual: int = 1,
                 train: bool = False,
                 learning_rate: float = 1e-5,
                 weight_decay: float = 0.0,
                 clip_norm: Optional[float] = 1.0,
                 optimizer_factory=None,
                 mailbox_deadline_s: Optional[float] = None,
                 dp: int = 1,
                 fsdp: int = 1,
                 grad_transport: str = "fp32",
                 shard_weight_update: bool = False,
                 quant_block_size: Optional[int] = None,
                 quant_stochastic: bool = False,
                 stage_mesh: Optional[bool] = None,
                 placement_group=None):
        import ray_tpu
        from ray_tpu.core.config import get_config

        if n_stages < 2:
            raise ValueError("MPMDPipeline needs n_stages >= 2 "
                             "(use the plain train step otherwise)")
        if n_virtual < 1:
            raise ValueError(f"n_virtual must be >= 1, got {n_virtual}")
        if n_stages * n_virtual > config.n_layers:
            raise ValueError(
                f"n_stages*n_virtual = {n_stages * n_virtual} virtual "
                f"stages need at least that many layers, model has "
                f"{config.n_layers}")
        if dp < 1 or fsdp < 1:
            raise ValueError(f"dp/fsdp must be >= 1, got {dp}/{fsdp}")
        self.config = config
        self.n_stages = n_stages
        self.n_microbatches = n_microbatches
        self.n_virtual = n_virtual
        self.n_chunks = n_stages * n_virtual
        self.serial = serial
        self.train = train
        self.dp = dp
        self.fsdp = fsdp
        self.n_model = dp * fsdp
        self._stage_mesh = (self.n_model > 1 if stage_mesh is None
                            else bool(stage_mesh))
        self.placement_group = placement_group
        self.step_timeout_s = step_timeout_s
        # resolve the mailbox deadline on the DRIVER (its config sees
        # _system_config overrides) and ship the value to every stage
        deadline = (mailbox_deadline_s if mailbox_deadline_s is not None
                    else get_config().pipeline_mailbox_deadline_s)
        opts = {"max_concurrency": 4, "max_restarts": 0}
        opts.update(actor_options or {})
        policies = remat_policies or [None] * n_stages
        self.stages = []
        for s in range(n_stages):
            stage_opts = dict(opts)
            if placement_group is not None:
                # gang → mesh hand-off: one stage actor per bundle of a
                # (typically SLICE_SPREAD) placement group — each stage
                # builds its dp×fsdp mesh from the devices of the host
                # its bundle reserved
                from ray_tpu.util.scheduling_strategies import (
                    PlacementGroupSchedulingStrategy)
                stage_opts["scheduling_strategy"] = \
                    PlacementGroupSchedulingStrategy(
                        placement_group,
                        placement_group_bundle_index=s)
                device_indices = list(range(self.n_model))
            else:
                device_indices = list(range(s * self.n_model,
                                            (s + 1) * self.n_model))
            cls = ray_tpu.remote(**stage_opts)(PipelineStage)
            self.stages.append(cls.remote(
                config, s, n_stages, seed=seed, device_index=s,
                remat_policy=policies[s], n_virtual=n_virtual,
                train=train, learning_rate=learning_rate,
                weight_decay=weight_decay, clip_norm=clip_norm,
                optimizer_factory=optimizer_factory,
                mailbox_deadline_s=deadline,
                dp=dp, fsdp=fsdp, grad_transport=grad_transport,
                shard_weight_update=shard_weight_update,
                quant_block_size=quant_block_size,
                quant_stochastic=quant_stochastic,
                stage_mesh=stage_mesh,
                device_indices=(device_indices if self._stage_mesh
                                else None)))
        ray_tpu.get([a.ping.remote() for a in self.stages], timeout=300)

    # ---------------------------------------------------------- steps
    def _split(self, batch: Dict[str, Any]):
        import numpy as np

        ids = np.asarray(batch["input_ids"])
        mask = batch.get("loss_mask")
        mask = np.asarray(mask) if mask is not None else None
        m = self.n_microbatches
        if ids.shape[0] % m:
            raise ValueError(f"batch {ids.shape[0]} not divisible by "
                             f"{m} microbatches")
        if self._stage_mesh and (ids.shape[0] // m) % self.n_model:
            raise ValueError(
                f"microbatch rows ({ids.shape[0] // m}) not divisible "
                f"by the stage mesh dp*fsdp = {self.dp}*{self.fsdp} "
                f"= {self.n_model}")
        ids_mb = np.split(ids, m)
        mask_mb = np.split(mask, m) if mask is not None else [None] * m
        # per-microbatch label-token counts — known to the driver
        # without running the model, so the last chunk's backward seeds
        # (d total / d loss_i = n_i / N) can be fed up front
        ns = [float(mk[:, 1:].sum()) if mk is not None
              else float(i.shape[0] * (i.shape[1] - 1))
              for i, mk in zip(ids_mb, mask_mb)]
        return ids_mb, mask_mb, ns

    def step(self, batch: Dict[str, Any]) -> PipelineStepResult:
        res = (self._step_serial if self.serial
               else self._step_1f1b)(batch)
        self._record_step_telemetry(batch, res)
        return res

    def _record_step_telemetry(self, batch: Dict[str, Any],
                               res: PipelineStepResult) -> None:
        """Per-step training telemetry into the fleet metrics plane:
        step wall, tokens/s, measured bubble, grad norm and an MFU
        gauge from the configuration's FLOP model."""
        try:
            from ray_tpu.core.metric_defs import runtime_metrics
            m = runtime_metrics()
            m.train_step_wall.observe(res.wall_s)
            m.pipeline_bubble.set(res.bubble_fraction)
            m.train_loss.set(res.loss)
            if res.grad_norm is not None:
                m.train_grad_norm.set(res.grad_norm)
            if res.wall_s > 0:
                import numpy as np
                ids = np.asarray(batch["input_ids"])
                tokens_per_s = float(ids.size) / res.wall_s
                m.train_tokens_per_s.set(tokens_per_s)
                try:
                    from ray_tpu.parallel.mesh import chip_spec
                    achieved = tokens_per_s * \
                        self.config.flops_per_token(ids.shape[1])
                    peak = chip_spec().bf16_flops * self.n_stages
                    m.train_mfu.set(100.0 * achieved / peak)
                except Exception:
                    pass
            rec = _recorder()
            if rec is not None:
                rec.maybe_flush()
            w = None
            try:
                from ray_tpu.core.global_state import try_global_worker
                w = try_global_worker()
            except Exception:
                pass
            if w is not None and getattr(w, "metrics_reporter",
                                         None) is not None:
                w.metrics_reporter.maybe_report()
        except Exception:
            pass

    def _opt_tail(self) -> Tuple[Optional[float], Optional[int]]:
        """Train-mode tail after the backwards drain: reduce the
        per-stage squared grad norms (scalars), fan the global value
        back out, and run every stage's fused optimizer step
        concurrently. No gradient or parameter bytes through the
        driver — the reduction is S floats each way."""
        import ray_tpu

        if not self.train:
            return None, None
        sq = ray_tpu.get([a.grad_sq_norm.remote() for a in self.stages],
                         timeout=self.step_timeout_s)
        gsq = float(sum(sq))
        mets = ray_tpu.get([a.apply_opt.remote(gsq)
                            for a in self.stages],
                           timeout=self.step_timeout_s)
        return mets[0]["grad_norm"], mets[0]["step"]

    def _step_1f1b(self, batch: Dict[str, Any]) -> PipelineStepResult:
        import numpy as np

        import ray_tpu
        from ray_tpu.core import streaming

        S, M, v = self.n_stages, self.n_microbatches, self.n_virtual
        K = self.n_chunks
        ids_mb, mask_mb, ns = self._split(batch)
        total_n = sum(ns)
        t0 = time.perf_counter()
        hold = []  # keep routed refs alive until the step completes
        last = self.stages[-1]  # chunk K-1 lives on the last actor
        # batched prefeed: stage 0's token microbatches in one call,
        # the last stage's targets + loss cotangents (scalar n_i / N,
        # known up front) in another — 2 actor calls instead of 3M
        hold.append(self.stages[0].feed.remote(
            acts={(0, i): ids_mb[i] for i in range(M)}))
        hold.append(last.feed.remote(
            targets={i: (ids_mb[i], mask_mb[i]) for i in range(M)},
            grads={(K - 1, i): np.float32(ns[i] / total_n)
                   for i in range(M)}))
        gens = [a.run.options(num_returns="streaming").remote(M)
                for a in self.stages]
        orders = [one_f_one_b_order(s, S, M, v) for s in range(S)]
        cursors = [0] * S
        loss_refs: Dict[int, Any] = {}
        by_gen = {id(g): s for s, g in enumerate(gens)}
        active = list(gens)
        deadline = time.monotonic() + self.step_timeout_s
        try:
            while active:
                ready, _ = streaming.wait_any(
                    active, timeout=max(deadline - time.monotonic(), 0.0))
                if not ready:
                    raise TimeoutError(
                        f"pipeline step stalled: no stage produced an "
                        f"item within {self.step_timeout_s}s")
                for g in ready:
                    s = by_gen[id(g)]
                    try:
                        ref = g.next_ref(timeout=1.0)
                    except StopIteration:
                        active.remove(g)
                        continue
                    op, i, ch = orders[s][cursors[s]]
                    cursors[s] += 1
                    if op == "F" and ch < K - 1:
                        hold.append(
                            self.stages[(ch + 1) % S]
                            .put_activation.remote(ch + 1, i, ref))
                    elif op == "F":
                        # tiny loss dicts: batch the gets after drain
                        loss_refs[i] = ref
                    elif op == "B" and ch > 0:
                        hold.append(
                            self.stages[(ch - 1) % S]
                            .put_grad.remote(ch - 1, i, ref))
                    hold.append(ref)
            items = ray_tpu.get([loss_refs[i] for i in range(M)],
                                timeout=60)
            losses = {i: (it["loss"], it["n_tokens"])
                      for i, it in enumerate(items)}
            grad_norm, opt_step = self._opt_tail()
        except BaseException:
            self._cleanup(gens)
            raise
        wall = time.perf_counter() - t0
        stats = ray_tpu.get(
            [a.step_stats.remote() for a in self.stages], timeout=60)
        mb = [losses[i] for i in range(M)]
        loss = sum(l * n for l, n in mb) / total_n
        return PipelineStepResult(
            loss=loss, n_tokens=total_n, microbatch_losses=mb,
            stage_stats=stats, wall_s=wall, grad_norm=grad_norm,
            step=opt_step)

    def _step_serial(self, batch: Dict[str, Any]) -> PipelineStepResult:
        """No-overlap baseline: each microbatch walks every chunk's
        forward, then every chunk's backward, with a full barrier per
        call — what pipelining exists to beat."""
        import numpy as np

        import ray_tpu

        S, M, K = self.n_stages, self.n_microbatches, self.n_chunks
        ids_mb, mask_mb, ns = self._split(batch)
        total_n = sum(ns)
        t0 = time.perf_counter()
        ray_tpu.get([a.reset_step.remote() for a in self.stages],
                    timeout=60)
        losses = []
        for i in range(M):
            act = ray_tpu.get(
                self.stages[0].forward_one.remote(0, i, ids_mb[i]),
                timeout=self.step_timeout_s)
            for ch in range(1, K):
                actor = self.stages[ch % S]
                out = actor.forward_one.remote(
                    ch, i, act, ids_mb[i], mask_mb[i]) \
                    if ch == K - 1 else \
                    actor.forward_one.remote(ch, i, act)
                act = ray_tpu.get(out, timeout=self.step_timeout_s)
            losses.append((act["loss"], act["n_tokens"]))
            g: Any = np.float32(ns[i] / total_n)
            for ch in range(K - 1, -1, -1):
                g = ray_tpu.get(
                    self.stages[ch % S].backward_one.remote(ch, i, g),
                    timeout=self.step_timeout_s)
        grad_norm, opt_step = self._opt_tail()
        wall = time.perf_counter() - t0
        stats = ray_tpu.get(
            [a.step_stats.remote() for a in self.stages], timeout=60)
        loss = sum(l * n for l, n in losses) / total_n
        return PipelineStepResult(
            loss=loss, n_tokens=total_n, microbatch_losses=losses,
            stage_stats=stats, wall_s=wall, grad_norm=grad_norm,
            step=opt_step)

    # ---------------------------------------------------- checkpoints
    def save_checkpoint(self) -> Dict[str, Any]:
        """Gather per-stage parts and merge them into the canonical
        single-program ``{"params", "opt_state", "step"}`` layout
        (checkpointing is an explicit call, not per-step traffic)."""
        import ray_tpu
        parts = ray_tpu.get(
            [a.stage_checkpoint.remote() for a in self.stages],
            timeout=self.step_timeout_s)
        return merge_stage_checkpoints(self.config, parts)

    def load_checkpoint(self, state: Dict[str, Any]) -> None:
        """Load a canonical train state — saved from ANY
        ``(n_stages, n_virtual)`` layout — into this pipeline."""
        import ray_tpu
        parts = split_train_state(self.config, state, self.n_stages,
                                  self.n_virtual)
        ray_tpu.get(
            [a.load_state.remote(p)
             for a, p in zip(self.stages, parts)],
            timeout=self.step_timeout_s)

    def stream_checkpoint_refs(self, timeout_s: Optional[float] = None
                               ) -> List[List[Any]]:
        """Per-stage block-ref lists from
        :meth:`PipelineStage.stream_checkpoint`, gathered over the
        streaming layer with a bounded overall deadline. The refs can
        be forwarded straight into another pipeline's
        ``load_state_blocks`` calls (worker-to-worker byte movement) or
        fetched and merged via :func:`merge_stage_checkpoints`. A stage
        actor dying mid-stream surfaces the streaming layer's typed
        error here — never a hang."""
        from ray_tpu.core import streaming

        timeout_s = timeout_s if timeout_s is not None \
            else self.step_timeout_s
        gens = [a.stream_checkpoint.options(
            num_returns="streaming").remote() for a in self.stages]
        blocks: List[List[Any]] = [[] for _ in self.stages]
        by_gen = {id(g): s for s, g in enumerate(gens)}
        active = list(gens)
        deadline = time.monotonic() + timeout_s
        try:
            while active:
                ready, _ = streaming.wait_any(
                    active,
                    timeout=max(deadline - time.monotonic(), 0.0))
                if not ready:
                    raise TimeoutError(
                        f"checkpoint stream stalled: no stage produced "
                        f"a block within {timeout_s}s")
                for g in ready:
                    try:
                        ref = g.next_ref(timeout=1.0)
                    except StopIteration:
                        active.remove(g)
                        continue
                    blocks[by_gen[id(g)]].append(ref)
        except BaseException:
            for g in gens:
                try:
                    g.close()
                except Exception:
                    pass
            raise
        return blocks

    def save_checkpoint_streaming(self,
                                  timeout_s: Optional[float] = None,
                                  refs: Optional[List[List[Any]]] = None
                                  ) -> Dict[str, Any]:
        """The canonical checkpoint via the streaming gather — same
        result as :meth:`save_checkpoint`, but each stage's state
        arrives as per-chunk blocks (exactly-once stream items) instead
        of one monolithic unary return. Pass ``refs`` from an earlier
        :meth:`stream_checkpoint_refs` call to merge without streaming
        the stages a second time (the elastic path forwards the same
        refs peer-to-peer AND keeps a driver-side merged copy)."""
        import ray_tpu

        timeout_s = timeout_s if timeout_s is not None \
            else self.step_timeout_s
        if refs is None:
            refs = self.stream_checkpoint_refs(timeout_s)
        parts = []
        for stage_refs in refs:
            items = ray_tpu.get(stage_refs, timeout=timeout_s)
            part: Dict[str, Any] = {"chunks": {}}
            for b in items:
                if b.get("block") == "params":
                    part["chunks"][int(b["chunk"])] = b["params"]
                else:
                    part.update(
                        stage=b["stage"], n_stages=b["n_stages"],
                        n_virtual=b["n_virtual"],
                        opt_state=b.get("opt_state"),
                        step=b.get("step", 0))
            parts.append(part)
        return merge_stage_checkpoints(self.config, parts)

    # -------------------------------------------------------- cleanup
    def abort(self) -> None:
        """Quiesce every stage: unblock pending mailbox takes with a
        typed error and drain queued items, waiting (bounded) for the
        acks — the elastic re-plan entry point. After this the stages
        are idle and immediately reusable; nothing is left to trip the
        mailbox take-deadline."""
        self._cleanup([])

    def _cleanup(self, gens) -> None:
        """Failure path: unblock + drain every stage mailbox, then
        drop all stream state — typed error out, no hang, no leaked
        stream refs. The abort acks are awaited (bounded, dead actors
        skipped) so a fire-and-forget abort cannot land inside the
        NEXT step's freshly-started ``run`` and kill it spuriously."""
        import ray_tpu
        refs = []
        for a in self.stages:
            try:
                refs.append(a.abort.remote())
            except Exception:
                pass
        for g in gens:
            try:
                g.close()
            except Exception:
                pass
        for r in refs:
            try:
                ray_tpu.get(r, timeout=5.0)
            except Exception:
                pass

    def grads(self, timeout: float = 120.0):
        """Per-stage accumulated parameter-gradient trees (host),
        keyed by global chunk id; with ``n_virtual == 1`` each stage's
        single chunk tree is returned bare (legacy shape)."""
        import ray_tpu
        parts = ray_tpu.get(
            [a.get_grads.remote() for a in self.stages],
            timeout=timeout)
        if self.n_virtual == 1:
            return [p[s] for s, p in enumerate(parts)]
        return parts

    def shutdown(self) -> None:
        import ray_tpu
        for a in self.stages:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
