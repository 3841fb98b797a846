"""GSPMD train-step assembly: model + mesh + rules + optimizer -> one
jitted SPMD program.

This is the TPU-native replacement for the reference's whole
DDP/DeepSpeed integration surface (``train/torch/config.py``,
``examples/deepspeed/deepspeed_torch_trainer.py``): instead of wrapping
the model in a distributed module and an engine, the parallelism is a
(mesh, rule-table) pair; ``jax.jit`` with explicit in/out shardings
compiles the collectives (psum for grads on dp, all-gather/reduce-scatter
for fsdp params) into the step itself.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.moe import grouped_product_counts
from ray_tpu.models.transformer import (
    TransformerConfig, head_loss_form, init_params, logical_axes,
    lm_loss, refuse_training)
from ray_tpu.ops.cross_entropy import n_chunks
from ray_tpu.parallel.quantization import DEFAULT_BLOCK_SIZE, fake_quant
from ray_tpu.parallel.sharding import (
    ShardingRules, FSDP_RULES, shard_params, batch_sharding, replicated,
    flatten_tree, unflatten_like)
from ray_tpu.util import compile_cache

GRAD_TRANSPORTS = ("fp32", "int8")


@dataclasses.dataclass
class TrainStepBundle:
    """Everything a worker needs to run sharded training steps."""
    config: TransformerConfig
    mesh: Any
    rules: ShardingRules
    init_fn: Callable[[jax.Array], Dict]       # key -> sharded state
    step_fn: Callable[[Dict, Dict], Tuple[Dict, Dict]]  # (state, batch)
    state_shardings: Dict
    batch_spec: Any
    grad_transport: str = "fp32"
    shard_weight_update: bool = False
    #: the form the LM-head loss took in ``step_fn`` (the program is
    #: static: every step or none), ``transformer.head_loss_form``'s:
    #: ``"per_chip"`` (dW summed on the chip, reduced once a step),
    #: ``"gspmd"`` (reduced every chunk) or ``"logits"`` (not fused)
    loss_form: str = "gspmd"
    #: chunks the fused loss scans a step of ``config.max_seq_len`` tokens
    #: a sequence (0 where the logits are materialized)
    loss_chunks: int = 0
    #: the dropless experts' differentiated grouped products in ``step_fn``
    #: by form, ``{"pallas_gmm": n, "xla_ragged_dot": m}`` (a product in
    #: a scan's body counts once; ``moe.grouped_product_counts``), filled
    #: in when the step program is traced: empty before the first step
    grouped_products: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    #: live-telemetry cadence (see :meth:`_telemetry`); <= 0 disables
    telemetry_interval_s: float = 0.5
    _tel_last: float = dataclasses.field(default=0.0, repr=False)
    _tel_tokens: float = dataclasses.field(default=0.0, repr=False)
    _tel_steps: int = dataclasses.field(default=0, repr=False)

    def init(self, seed: int = 0) -> Dict:
        return self.init_fn(jax.random.PRNGKey(seed))

    def step(self, state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        if "loss_mask" not in batch:
            batch = dict(batch, loss_mask=jnp.ones_like(
                batch["input_ids"], dtype=jnp.float32))
        out = self.step_fn(state, batch)
        self._telemetry(batch, out[1])
        return out

    def _telemetry(self, batch: Dict, metrics: Dict) -> None:
        """Per-step training telemetry into the fleet metrics plane.
        Steps are only *counted* on the hot path; every
        ``telemetry_interval_s`` the accumulated window is closed: block
        on the (already dispatched) step metrics, then set tokens/s, an
        MFU gauge from the configuration's FLOP model (flops_per_token x
        tokens/s over the chip's bf16 peak across the mesh), loss and
        grad norm, and observe the mean step wall. Never raises; the gate
        keeps device syncs off the steady-state step path."""
        if self.telemetry_interval_s <= 0:
            return
        import time
        now = time.monotonic()
        if not self._tel_last:
            self._tel_last = now
        ids = batch["input_ids"]
        self._tel_tokens += float(ids.size)
        self._tel_steps += 1
        elapsed = now - self._tel_last
        if elapsed < self.telemetry_interval_s:
            return
        tokens, steps = self._tel_tokens, self._tel_steps
        self._tel_last = now
        self._tel_tokens = 0.0
        self._tel_steps = 0
        try:
            from ray_tpu.core.metric_defs import runtime_metrics
            m = runtime_metrics()
            jax.block_until_ready(metrics)
            tokens_per_s = tokens / elapsed
            m.train_tokens_per_s.set(tokens_per_s)
            m.train_step_wall.observe(elapsed / steps)
            m.train_loss.set(float(metrics["loss"]))
            m.train_grad_norm.set(float(metrics["grad_norm"]))
            if "moe_balance" in metrics:
                m.train_moe_balance.set(float(metrics["moe_balance"]))
                m.train_moe_load_max_over_mean.set(
                    float(metrics["moe_load_max_over_mean"]))
                m.train_moe_held_assignments.set(
                    float(metrics["moe_held_assignments"]))
                for form, n in self.grouped_products.items():
                    m.train_moe_grouped_products.set(
                        float(n), tags={"form": form})
            try:
                from ray_tpu.parallel.mesh import chip_spec
                achieved = tokens_per_s * \
                    self.config.flops_per_token(ids.shape[-1])
                peak = chip_spec().bf16_flops * max(1, self.mesh.size)
                m.train_mfu.set(100.0 * achieved / peak)
            except Exception:
                pass
            from ray_tpu.core.global_state import try_global_worker
            w = try_global_worker()
            if w is not None and getattr(w, "metrics_reporter",
                                         None) is not None:
                w.metrics_reporter.maybe_report()
        except Exception:
            pass


def default_optimizer(learning_rate: float, weight_decay: float = 0.0,
                      clip_norm: Optional[float] = 1.0):
    """The standard training optimizer: global-norm clip (when
    ``clip_norm`` is set) chained onto AdamW. ``parallel.plan`` builds
    the SAME optimizer for every lowering so checkpoints round-trip
    between the SPMD step and the MPMD pipeline with identical
    treedefs (the pipeline applies the clip leg manually with the
    cross-stage norm — arithmetically the same update)."""
    adamw = optax.adamw(learning_rate, b1=0.9, b2=0.95, eps=1e-8,
                        weight_decay=weight_decay)
    if clip_norm is None:
        return adamw
    return optax.chain(optax.clip_by_global_norm(clip_norm), adamw)


def _default_optimizer(learning_rate: float, weight_decay: float):
    return default_optimizer(learning_rate, weight_decay, 1.0)


def make_train_step(config: TransformerConfig, mesh,
                    rules: Optional[ShardingRules] = None,
                    optimizer=None,
                    learning_rate: float = 1e-5,
                    weight_decay: float = 0.0,
                    donate_state: bool = True,
                    remat_policy: Optional[str] = None,
                    ce_chunk_size: Optional[int] = None,
                    grad_transport: str = "fp32",
                    shard_weight_update: bool = False,
                    quant_block_size: int = DEFAULT_BLOCK_SIZE,
                    quant_stochastic: bool = False,
                    telemetry_interval_s: float = 0.5
                    ) -> TrainStepBundle:
    """Build sharded init + train-step functions over ``mesh``.

    The optimizer state inherits each parameter's sharding (ZeRO-style
    optimizer sharding falls out of FSDP rules for free — Adam moments are
    param-shaped pytree leaves).

    ``remat_policy`` / ``ce_chunk_size`` override the config's
    rematerialization policy and fused-CE chunking for this train step
    without touching the caller's config (the compute-path knobs a
    trainer wants to sweep without redefining the model).

    Communication-path knobs (the gradient byte path from loss to
    weight):

    - ``grad_transport``: ``"fp32"`` (exact) or ``"int8"`` — gradients
      cross the reduction wire int8 blockwise-quantized (per-block f32
      scales, f32 accumulators; EQuARX, arXiv:2506.17615). Inside one
      SPMD program the reduction itself is compiled by XLA, so the knob
      injects the transport's quantization error via
      ``quantization.fake_quant`` on each gradient leaf — numerically
      the requantize leg of the quantized all-reduce; the eager
      ``collective.quantized_allreduce`` carries real int8 payloads.
      ``quant_block_size`` / ``quant_stochastic`` tune the wire format
      (stochastic rounding makes the quantizer unbiased, keyed per step
      and leaf).
    - ``shard_weight_update``: reduce-scatter gradients over the data
      axes (dp×fsdp), have each replica update only its 1/N flat
      optimizer shard, then all-gather fresh params
      (arXiv:2004.13336). Optimizer state lives in the flat sharded
      layout (1/N per replica even for leaves the rule table
      replicates); ``state["params"]`` keeps its normal layout, so
      eval/checkpoint paths are unchanged. Flat shards are padded to
      whole quant blocks so both transports share one state treedef.
    """
    if grad_transport not in GRAD_TRANSPORTS:
        raise ValueError(f"grad_transport must be one of "
                         f"{GRAD_TRANSPORTS}, got {grad_transport!r}")
    refuse_training(config)
    compile_cache.enable()
    rules = rules if rules is not None else FSDP_RULES
    if remat_policy is not None:
        config = dataclasses.replace(config, remat=None,
                                     remat_policy=remat_policy)
    if ce_chunk_size is not None:
        config = dataclasses.replace(config, ce_chunk_size=ce_chunk_size)
    if optimizer is None:
        optimizer = _default_optimizer(learning_rate, weight_decay)

    axes_tree = logical_axes(config)
    param_sh = shard_params({}, axes_tree, rules, mesh)
    batch_sh = batch_sharding(mesh, rules, ("batch", "sequence"))
    rep = replicated(mesh)

    # Cross-replica sharded weight update: gradients and master-param
    # working copies are flattened to 1-D, padded to n_shards * k quant
    # blocks, and sharded over the data axes. A sharding constraint to
    # ``flat_sh`` on a freshly reduced gradient compiles to the
    # reduce-scatter; the constraint back to the parameter's compute
    # sharding on the updated leaf compiles to the all-gather.
    from jax.sharding import NamedSharding, PartitionSpec as P
    update_axes = tuple(a for a in ("dp", "fsdp") if mesh.shape[a] > 1)
    n_shards = 1
    for a in update_axes:
        n_shards *= mesh.shape[a]
    flat_sh = NamedSharding(mesh, P(update_axes) if update_axes else P())

    def _flatten_tree(tree, constrain_to=None):
        return flatten_tree(tree, n_shards, quant_block_size,
                            constrain_to=constrain_to)

    def init_raw(key):
        params = init_params(config, key)
        if shard_weight_update:
            opt_state = optimizer.init(_flatten_tree(params))
        else:
            opt_state = optimizer.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    # Optimizer-state leaves that are param-shaped get the param's
    # sharding; scalars/counters replicate. Resolve via a throwaway
    # eval_shape of the whole state.
    state_shapes = jax.eval_shape(init_raw, jax.random.PRNGKey(0))

    flat_params, params_treedef = jax.tree.flatten(
        state_shapes["params"])
    flat_param_sh = jax.tree.flatten(param_sh)[0]
    param_sh_tree = jax.tree.unflatten(params_treedef, flat_param_sh)

    # Optax state (adam mu/nu, etc.) nests whole param-shaped subtrees;
    # substitute each such subtree with the params' sharding tree and
    # replicate everything else (counters). Matching by treedef — not by
    # leaf shape — keeps same-shaped params with different shardings
    # (e.g. wq/wk/wv/wo when n_heads*head_dim == d_model) distinct.
    def is_param_tree(x):
        try:
            return jax.tree.structure(x) == params_treedef
        except Exception:
            return False

    opt_leaf_sh_tree = param_sh_tree
    if shard_weight_update:
        # Flat layout: every optimizer leaf (moments etc.) is a 1-D
        # shard over the data axes, 1/N resident per replica.
        opt_leaf_sh_tree = jax.tree.unflatten(
            params_treedef, [flat_sh] * len(flat_params))
    opt_sh = jax.tree.map(
        lambda sub: opt_leaf_sh_tree if is_param_tree(sub) else rep,
        state_shapes["opt_state"], is_leaf=is_param_tree)

    state_sh = {
        "params": param_sh_tree,
        "opt_state": opt_sh,
        "step": rep,
    }

    init_fn = jax.jit(init_raw, out_shardings=state_sh)

    def _quantize_grads(grads, step):
        """int8 transport: each gradient leaf picks up one wire leg's
        blockwise quantization error (per-step, per-leaf keys when
        stochastic rounding is on)."""
        base = jax.random.fold_in(jax.random.PRNGKey(0x5eed), step) \
            if quant_stochastic else None
        leaves, treedef = jax.tree.flatten(grads)
        out = []
        for i, g in enumerate(leaves):
            key = jax.random.fold_in(base, i) if quant_stochastic else None
            out.append(fake_quant(g, quant_block_size,
                                  quant_stochastic, key))
        return jax.tree.unflatten(treedef, out)

    grouped_products: Dict[str, int] = {}

    def step_raw(state, batch):
        def loss_fn(p):
            return lm_loss(config, p, batch, mesh=mesh, rules=rules)
        traced = grouped_product_counts()
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        # this runs as the program is traced: what the trace added
        grouped_products.update(
            {form: n - traced[form]
             for form, n in grouped_product_counts().items()})
        if grad_transport == "int8":
            with jax.named_scope("grad_transport"):
                grads = _quantize_grads(grads, state["step"])
        if shard_weight_update:
            # Reduce-scatter grads to flat 1/N shards, update only the
            # local optimizer shard, all-gather fresh params (the
            # constraint back to the param sharding via out_shardings).
            with jax.named_scope("grad_transport"):
                gflat = _flatten_tree(grads, constrain_to=flat_sh)
                pflat = _flatten_tree(state["params"],
                                      constrain_to=flat_sh)
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(
                    gflat, state["opt_state"], pflat)
                new_pflat = optax.apply_updates(pflat, updates)
                new_params = unflatten_like(state["params"], new_pflat)
        else:
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(
                    grads, state["opt_state"], state["params"])
                new_params = optax.apply_updates(state["params"],
                                                 updates)
        new_state = {"params": new_params, "opt_state": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, "n_tokens": aux["n_tokens"],
                   "grad_norm": optax.global_norm(grads)}
        # the dropless experts' routing counters of this step
        # (``moe.route_stats``): ``moe_held_assignments``,
        # ``moe_load_max_over_mean``, ``moe_balance``
        metrics.update({f"moe_{k}": v
                        for k, v in aux.get("moe", {}).items()})
        return new_state, metrics

    step_fn = jax.jit(
        step_raw,
        in_shardings=(state_sh, {"input_ids": batch_sh,
                                 "loss_mask": batch_sh}),
        out_shardings=(state_sh, rep),
        donate_argnums=(0,) if donate_state else (),
    )

    loss_form = head_loss_form(config, mesh, rules)[0]
    return TrainStepBundle(config=config, mesh=mesh, rules=rules,
                           init_fn=init_fn, step_fn=step_fn,
                           state_shardings=state_sh, batch_spec=batch_sh,
                           grad_transport=grad_transport,
                           shard_weight_update=shard_weight_update,
                           loss_form=loss_form,
                           loss_chunks=0 if loss_form == "logits" else
                           n_chunks(config.max_seq_len - 1,
                                    config.ce_chunk_size),
                           grouped_products=grouped_products,
                           telemetry_interval_s=telemetry_interval_s)


def make_eval_step(config: TransformerConfig, mesh,
                   rules: Optional[ShardingRules] = None,
                   state_shardings=None):
    """Jitted forward-only loss, honoring the train step's layouts."""
    rules = rules if rules is not None else FSDP_RULES
    batch_sh = batch_sharding(mesh, rules, ("batch", "sequence"))
    if state_shardings is not None:
        param_sh = state_shardings["params"]
    else:
        param_sh = shard_params({}, logical_axes(config), rules, mesh)

    @functools.partial(
        jax.jit,
        in_shardings=(param_sh, {"input_ids": batch_sh,
                                 "loss_mask": batch_sh}),
        out_shardings=replicated(mesh))
    def eval_step(params, batch):
        loss, aux = lm_loss(config, params, batch, mesh=mesh, rules=rules)
        return {"loss": loss, "n_tokens": aux["n_tokens"]}
    return eval_step
