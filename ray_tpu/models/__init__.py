"""Model family: TPU-first transformer LMs.

The reference ships no in-tree model implementations (its models arrive
through torch user code and HF integrations, e.g. the GPT-J recipe
``release/air_examples/gptj_deepspeed_finetuning/``). This framework makes
the flagship models first-class so trainers/serving/benchmarks share one
GSPMD-ready implementation:

- functional param-pytree models (no framework object graph): ``init`` /
  ``apply`` plus a parallel pytree of logical sharding axes consumed by
  ``ray_tpu.parallel.sharding.shard_params``;
- ``lax.scan`` over stacked layer params (O(1) compile time in depth) with
  ``jax.checkpoint`` rematerialization per block;
- attention via ``ray_tpu.ops`` (Pallas flash on TPU, ring attention when
  the mesh has a nontrivial ``sp`` axis).
"""

from ray_tpu.models.transformer import (
    TransformerConfig,
    Transformer,
    lm_loss,
    hidden_states,
    init_params,
    inference_params,
    init_kv_cache,
    prefill,
    decode_step,
    logical_axes,
    REMAT_POLICIES,
    remat_policy_fn,
)
from ray_tpu.models.registry import get_config, register_config, MODEL_CONFIGS
from ray_tpu.models.training import (
    make_train_step,
    make_eval_step,
    TrainStepBundle,
)

__all__ = [
    "TransformerConfig",
    "Transformer",
    "lm_loss",
    "hidden_states",
    "init_params",
    "inference_params",
    "init_kv_cache",
    "prefill",
    "decode_step",
    "logical_axes",
    "REMAT_POLICIES",
    "remat_policy_fn",
    "get_config",
    "register_config",
    "MODEL_CONFIGS",
    "make_train_step",
    "make_eval_step",
    "TrainStepBundle",
]
