"""ParallelPlan: one config, every parallelism.

The composition layer ROADMAP item 4 asks for — a single declarative
grid

    ParallelPlan(pp=S, virtual=v, dp=D, fsdp=F,
                 grad_transport="fp32"/"int8",
                 shard_weight_update=..., slice_strategy=...)

that **lowers** to whichever runtime shape the grid implies, behind one
``TrainProgram`` interface (``step`` / ``save_checkpoint`` /
``load_checkpoint`` / ``shutdown``):

- ``pp == 1`` → the **SPMD** GSPMD train step
  (``models.training.make_train_step`` over a dp×fsdp mesh: in-graph
  collectives, int8 transport modeled by ``fake_quant``, cross-replica
  flat 1/N sharded weight update);
- ``pp >= 2, dp == fsdp == 1`` → the **MPMD** interleaved pipeline
  (``parallel.mpmd_pipeline.MPMDPipeline(train=True)``: actor-hosted
  stages, streamed activations, per-stage fused optimizer);
- ``pp >= 2, dp*fsdp >= 2`` → **both nested** (the Megatron-LM 3D
  recipe, arXiv:1909.08053, composed with EQuARX int8 collectives,
  arXiv:2506.17615): every pipeline stage actor hosts a shard_map'd
  dp×fsdp program over its own device mesh, with the stage's gradient
  reduction carrying REAL int8 bytes (values + per-block f32 scales in
  the all-gather leg) when ``grad_transport="int8"``, and the fused
  clip+adamw step running under the cross-replica sharded-update path.

``slice_strategy`` ("SLICE_SPREAD"/"SLICE_PACK") reserves a gang
placement group — one bundle per pipeline stage on the distinct hosts
of ONE TPU slice (``util/placement_group.py``) — and schedules each
stage actor onto its bundle; when no slice capacity (or no runtime) is
available within ``placement_timeout_s`` the plan falls back to local
devices, so the same script runs on a laptop and on a gang-scheduled
slice.

Checkpoints are **lowering-independent**: every program saves/loads the
same canonical single-program layout ``{"params", "opt_state", "step"}``
(the treedef of plain AdamW state — the pipeline's merge target), so a
state saved under ``(pp=2, v=2, dp=2)`` reloads into ``(pp=1, dp=1)``
and vice versa with exact value AND treedef parity.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, Optional, Sequence

from ray_tpu.models.training import GRAD_TRANSPORTS

logger = logging.getLogger(__name__)

__all__ = ["ParallelPlan", "PlanStepResult", "TrainProgram",
           "SLICE_STRATEGIES"]

SLICE_STRATEGIES = ("SLICE_PACK", "SLICE_SPREAD")


@dataclasses.dataclass
class PlanStepResult:
    """Uniform per-step result across lowerings."""
    loss: float
    grad_norm: Optional[float]
    step: Optional[int]
    wall_s: float
    n_tokens: Optional[float] = None
    #: measured pipeline bubble (MPMD lowerings; None for SPMD)
    bubble_fraction: Optional[float] = None
    #: the native result object (PipelineStepResult / metrics dict)
    detail: Any = None


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Declarative parallelism grid; ``build()`` lowers it.

    ``pp`` pipeline stages × ``virtual`` interleaved chunks per stage,
    each stage running a ``dp`` × ``fsdp`` data-parallel program on its
    own devices. ``n_microbatches`` is the 1F1B microbatch count
    (ignored by the SPMD lowering). ``grad_transport`` /
    ``shard_weight_update`` / ``quant_*`` pick the gradient byte path
    (PR-6 knobs, now honored by every lowering). ``slice_strategy``
    asks for a gang placement group over one TPU slice's hosts."""
    pp: int = 1
    virtual: int = 1
    dp: int = 1
    fsdp: int = 1
    n_microbatches: int = 4
    grad_transport: str = "fp32"
    shard_weight_update: bool = False
    slice_strategy: Optional[str] = None
    quant_block_size: Optional[int] = None
    quant_stochastic: bool = False

    def __post_init__(self):
        if min(self.pp, self.virtual, self.dp, self.fsdp,
               self.n_microbatches) < 1:
            raise ValueError(
                f"every ParallelPlan axis must be >= 1, got {self}")
        if self.virtual > 1 and self.pp < 2:
            raise ValueError(
                f"virtual={self.virtual} needs pp >= 2 (interleaved "
                f"chunks are a pipeline concept)")
        if self.grad_transport not in GRAD_TRANSPORTS:
            raise ValueError(
                f"grad_transport must be one of {GRAD_TRANSPORTS}, "
                f"got {self.grad_transport!r}")
        if self.slice_strategy is not None and \
                self.slice_strategy not in SLICE_STRATEGIES:
            raise ValueError(
                f"slice_strategy must be one of {SLICE_STRATEGIES} "
                f"or None, got {self.slice_strategy!r}")

    # ------------------------------------------------------- queries
    @property
    def lowering(self) -> str:
        """"spmd" (pp=1), "mpmd" (pp>=2, dp=fsdp=1) or "mpmd3d"."""
        if self.pp == 1:
            return "spmd"
        return "mpmd" if self.dp * self.fsdp == 1 else "mpmd3d"

    @property
    def stage_world(self) -> int:
        """Devices per pipeline stage (dp × fsdp)."""
        return self.dp * self.fsdp

    @property
    def world_size(self) -> int:
        """Total devices the plan wants (pp × dp × fsdp)."""
        return self.pp * self.stage_world

    def describe(self) -> str:
        bits = []
        if self.pp > 1:
            bits.append(f"pp={self.pp}" + (f"(v={self.virtual})"
                                           if self.virtual > 1 else "")
                        + f" M={self.n_microbatches}")
        if self.dp > 1:
            bits.append(f"dp={self.dp}")
        if self.fsdp > 1:
            bits.append(f"fsdp={self.fsdp}")
        if not bits:
            bits.append("single-device")
        bits.append(self.grad_transport)
        if self.shard_weight_update:
            bits.append("sharded-update")
        if self.slice_strategy:
            bits.append(self.slice_strategy)
        return f"{self.lowering}[" + " ".join(bits) + "]"

    def validate_batch(self, batch_rows: int) -> None:
        """Fail fast on a batch the grid cannot split evenly."""
        per_mb = batch_rows
        if self.pp > 1:
            if batch_rows % self.n_microbatches:
                raise ValueError(
                    f"batch {batch_rows} not divisible by "
                    f"{self.n_microbatches} microbatches")
            per_mb = batch_rows // self.n_microbatches
        if per_mb % self.stage_world:
            raise ValueError(
                f"{'microbatch' if self.pp > 1 else 'batch'} rows "
                f"({per_mb}) not divisible by dp*fsdp = "
                f"{self.stage_world}")

    def validate_config(self, config) -> None:
        if self.pp > 1 and (config.layer_pattern or config.sliding_window
                            or config.rope_yarn):
            raise NotImplementedError(
                "pp > 1 splits one stack of layers; a stack by kind of "
                "layer (layer_pattern, sliding_window, rope_yarn) trains "
                "under pp = 1")
        if self.pp > 1 and self.pp * self.virtual > config.n_layers:
            raise ValueError(
                f"pp*virtual = {self.pp * self.virtual} chunks need at "
                f"least that many layers, model has {config.n_layers}")

    # -------------------------------------------------------- lowering
    def build(self, config, *,
              learning_rate: float = 1e-5,
              weight_decay: float = 0.0,
              clip_norm: Optional[float] = 1.0,
              seed: int = 0,
              devices: Optional[Sequence] = None,
              actor_options: Optional[Dict[str, Any]] = None,
              step_timeout_s: float = 300.0,
              placement_bundle: Optional[Dict[str, float]] = None,
              placement_timeout_s: float = 60.0,
              stage_mesh: Optional[bool] = None,
              telemetry_interval_s: float = 0.5) -> "TrainProgram":
        """Lower the plan against ``config`` into a live
        :class:`TrainProgram`. SPMD lowers in-process; MPMD lowerings
        spawn one stage actor per ``pp`` (requires a running
        ``ray_tpu`` cluster), gang-scheduled onto a slice placement
        group when ``slice_strategy`` is set and capacity exists."""
        self.validate_config(config)
        from ray_tpu.models.transformer import refuse_training
        refuse_training(config)
        if self.pp == 1:
            return _SPMDProgram(
                self, config, learning_rate=learning_rate,
                weight_decay=weight_decay, clip_norm=clip_norm,
                seed=seed, devices=devices,
                telemetry_interval_s=telemetry_interval_s)
        return _PipelineProgram(
            self, config, learning_rate=learning_rate,
            weight_decay=weight_decay, clip_norm=clip_norm, seed=seed,
            actor_options=actor_options, step_timeout_s=step_timeout_s,
            placement_bundle=placement_bundle,
            placement_timeout_s=placement_timeout_s,
            stage_mesh=stage_mesh)


# ------------------------------------------------------------ programs
class TrainProgram:
    """What every lowering exposes: step / checkpoint / shutdown."""

    plan: ParallelPlan
    config: Any

    @property
    def lowering(self) -> str:
        return self.plan.lowering

    def step(self, batch: Dict[str, Any]) -> PlanStepResult:
        raise NotImplementedError

    def save_checkpoint(self) -> Dict[str, Any]:
        raise NotImplementedError

    def load_checkpoint(self, state: Dict[str, Any]) -> None:
        raise NotImplementedError

    def shutdown(self) -> None:
        pass


def _wrap_chain_state(adam_state):
    """AdamW-shaped canonical opt state -> the chain(clip, adamw)
    layout ``make_train_step``'s default optimizer builds (the clip leg
    is stateless)."""
    import optax
    return (optax.EmptyState(), adam_state)


def _unwrap_chain_state(opt_state):
    """Inverse of :func:`_wrap_chain_state`."""
    return opt_state[1]


class _SPMDProgram(TrainProgram):
    """pp=1: ``make_train_step`` over a dp×fsdp mesh, state held
    in-program so the interface matches the pipeline lowerings."""

    def __init__(self, plan: ParallelPlan, config, *, learning_rate,
                 weight_decay, clip_norm, seed, devices,
                 telemetry_interval_s):
        import jax

        from ray_tpu.models.training import (
            default_optimizer, make_train_step)
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.quantization import DEFAULT_BLOCK_SIZE

        self.plan = plan
        self.config = config
        self.clip_norm = clip_norm
        n = plan.stage_world
        devices = list(devices) if devices is not None \
            else jax.devices()[:n]
        if len(devices) < n:
            raise ValueError(
                f"plan {plan.describe()} wants {n} devices, have "
                f"{len(devices)}")
        self.mesh = build_mesh(MeshSpec(dp=plan.dp, fsdp=plan.fsdp),
                               devices[:n])
        self.bundle = make_train_step(
            config, self.mesh,
            optimizer=default_optimizer(learning_rate, weight_decay,
                                        clip_norm),
            grad_transport=plan.grad_transport,
            shard_weight_update=plan.shard_weight_update,
            quant_block_size=plan.quant_block_size or DEFAULT_BLOCK_SIZE,
            quant_stochastic=plan.quant_stochastic,
            telemetry_interval_s=telemetry_interval_s)
        logger.info("plan %s: LM-head loss %s, %d chunks a step",
                    plan.describe(), self.bundle.loss_form,
                    self.bundle.loss_chunks)
        self.state = self.bundle.init(seed=seed)
        # the step's own clock: train.step and, inside it, the host's
        # part before the device has the step (dispatch), the blocking
        # fetch of the loss (wait) and what follows it (tail). Its
        # books by kind hold a step as `step`, and one that compiled
        # its program as `compile`: a histogram of steps with the
        # compiling ones in it has them for its tail
        from ray_tpu.util.tracing import PhaseClock
        self.clock = PhaseClock("train", steps=True)
        self._compiled = 0      # variants of the step program so far

    def step(self, batch: Dict[str, Any]) -> PlanStepResult:
        import numpy as np
        clock = self.clock
        clock.tick()
        with clock.phase("train.step"):
            with clock.phase("train.dispatch"):
                self.plan.validate_batch(
                    int(np.asarray(batch["input_ids"]).shape[0]))
                t0 = time.perf_counter()
                self.state, metrics = self.bundle.step(self.state, batch)
                compiled = self.bundle.step_fn._cache_size()
                if compiled != self._compiled:
                    self._compiled, clock.kind = compiled, "compile"
                    products = self.bundle.grouped_products
                    if compiled == 1 and any(products.values()):
                        logger.info(
                            "plan %s: experts' grouped products: %d "
                            "pallas_gmm, %d xla_ragged_dot",
                            self.plan.describe(), products["pallas_gmm"],
                            products["xla_ragged_dot"])
            with clock.phase("train.wait"):
                loss = float(metrics["loss"])
            with clock.phase("train.tail"):
                wall = time.perf_counter() - t0
                res = PlanStepResult(
                    loss=loss, grad_norm=float(metrics["grad_norm"]),
                    step=int(self.state["step"]), wall_s=wall,
                    n_tokens=float(metrics["n_tokens"]), detail=metrics)
        return res

    # ------------------------------------------------------ checkpoint
    def save_checkpoint(self) -> Dict[str, Any]:
        import numpy as np

        import jax

        from ray_tpu.parallel.mpmd_pipeline import _map_param_subtrees
        from ray_tpu.parallel.sharding import unflatten_like

        host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        params = host(self.state["params"])
        opt = host(self.state["opt_state"])
        if self.plan.shard_weight_update:
            # flat 1/N update shards back to the param-shaped layout
            opt = _map_param_subtrees(
                opt, jax.tree.structure(params),
                lambda sub: unflatten_like(params, sub))
        if self.clip_norm is not None:
            opt = _unwrap_chain_state(opt)
        return {"params": params, "opt_state": opt,
                "step": int(self.state["step"])}

    def load_checkpoint(self, state: Dict[str, Any]) -> None:
        import jax
        import jax.numpy as jnp

        from ray_tpu.parallel.mpmd_pipeline import _map_param_subtrees
        from ray_tpu.parallel.quantization import DEFAULT_BLOCK_SIZE
        from ray_tpu.parallel.sharding import flatten_tree

        opt = state["opt_state"]
        if self.clip_norm is not None:
            opt = _wrap_chain_state(opt)
        if self.plan.shard_weight_update:
            n_shards = 1
            for a in ("dp", "fsdp"):
                if self.mesh.shape[a] > 1:
                    n_shards *= self.mesh.shape[a]
            block = self.plan.quant_block_size or DEFAULT_BLOCK_SIZE
            opt = _map_param_subtrees(
                opt, jax.tree.structure(state["params"]),
                lambda sub: flatten_tree(sub, n_shards, block))
        full = {"params": state["params"], "opt_state": opt,
                "step": jnp.asarray(state.get("step", 0), jnp.int32)}
        self.state = jax.device_put(full, self.bundle.state_shardings)


class _PipelineProgram(TrainProgram):
    """pp>=2: the MPMD pipeline, optionally with dp×fsdp stage meshes
    (nested 3D) and a slice-gang placement group."""

    def __init__(self, plan: ParallelPlan, config, *, learning_rate,
                 weight_decay, clip_norm, seed, actor_options,
                 step_timeout_s, placement_bundle, placement_timeout_s,
                 stage_mesh):
        from ray_tpu.parallel.mpmd_pipeline import MPMDPipeline

        self.plan = plan
        self.config = config
        self.pg = None
        if plan.slice_strategy is not None:
            self.pg = self._reserve_gang(placement_bundle,
                                         placement_timeout_s)
        self.pipeline = MPMDPipeline(
            config, n_stages=plan.pp,
            n_microbatches=plan.n_microbatches, seed=seed,
            n_virtual=plan.virtual, train=True,
            learning_rate=learning_rate, weight_decay=weight_decay,
            clip_norm=clip_norm, step_timeout_s=step_timeout_s,
            actor_options=actor_options,
            dp=plan.dp, fsdp=plan.fsdp,
            grad_transport=plan.grad_transport,
            shard_weight_update=plan.shard_weight_update,
            quant_block_size=plan.quant_block_size,
            quant_stochastic=plan.quant_stochastic,
            stage_mesh=stage_mesh,
            placement_group=self.pg)

    def _reserve_gang(self, placement_bundle, timeout_s):
        """One bundle per pipeline stage on a single slice's hosts —
        the gang → mesh hand-off. Falls back to local devices (None)
        when no runtime is up or no slice admits the gang in time, so
        the plan stays runnable anywhere."""
        try:
            import ray_tpu
            from ray_tpu.util.placement_group import (
                placement_group, remove_placement_group)
            if not ray_tpu.is_initialized():
                logger.warning(
                    "plan %s: no runtime for slice_strategy=%s — "
                    "falling back to local devices",
                    self.plan.describe(), self.plan.slice_strategy)
                return None
            bundle = dict(placement_bundle or {"CPU": 1})
            pg = placement_group([dict(bundle)
                                  for _ in range(self.plan.pp)],
                                 strategy=self.plan.slice_strategy)
            if pg.ready(timeout=timeout_s):
                logger.info("plan %s: gang placed on slice %s",
                            self.plan.describe(), pg.slice_id())
                return pg
            remove_placement_group(pg)
            logger.warning(
                "plan %s: no slice admitted the %d-bundle %s gang "
                "within %.0fs — falling back to local devices",
                self.plan.describe(), self.plan.pp,
                self.plan.slice_strategy, timeout_s)
            return None
        except Exception:
            logger.exception("plan %s: gang reservation failed — "
                             "falling back to local devices",
                             self.plan.describe())
            return None

    def step(self, batch: Dict[str, Any]) -> PlanStepResult:
        res = self.pipeline.step(batch)
        return PlanStepResult(
            loss=res.loss, grad_norm=res.grad_norm, step=res.step,
            wall_s=res.wall_s, n_tokens=res.n_tokens,
            bubble_fraction=res.bubble_fraction, detail=res)

    def save_checkpoint(self) -> Dict[str, Any]:
        return self.pipeline.save_checkpoint()

    def load_checkpoint(self, state: Dict[str, Any]) -> None:
        self.pipeline.load_checkpoint(state)

    def shutdown(self) -> None:
        self.pipeline.shutdown()
        if self.pg is not None:
            try:
                from ray_tpu.util.placement_group import (
                    remove_placement_group)
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
