"""Named model configurations.

The flagship ``gptj-6b`` mirrors the architecture the reference's GPT-J
fine-tune recipe trains (EleutherAI GPT-J-6B: 28 layers, d_model 4096,
16 heads x 256, rotary_dim 64, vocab 50400 — see
``release/air_examples/gptj_deepspeed_finetuning/`` in the reference);
``llama2-7b`` covers the reference's Llama-2 release tests;
``keye-vl-2.0-30b-a3b`` is the benchmark's routed, key-selecting
configuration at its published depth. ``*-tiny`` variants keep the same
block structure at test scale.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig

MODEL_CONFIGS: Dict[str, TransformerConfig] = {
    "gptj-6b": TransformerConfig(
        vocab_size=50400, d_model=4096, n_layers=28, n_heads=16,
        head_dim=256, d_ff=16384, max_seq_len=2048, rotary_dim=64,
        block_style="gptj"),
    "moe-tiny": TransformerConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=128, max_seq_len=128, rotary_dim=8, block_style="gptj",
        n_experts=4, dtype=jnp.float32, remat=False),
    "gptj-tiny": TransformerConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=256, max_seq_len=128, rotary_dim=8, block_style="gptj",
        dtype=jnp.float32, remat=False),
    "llama2-7b": TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        head_dim=128, d_ff=11008, max_seq_len=4096, rotary_dim=128,
        block_style="llama"),
    # Kwai-Keye/Keye-VL-2.0-30B-A3B, the language model (served only:
    # dropless top-8 of 128 experts, q/k norm, a top-2048 key indexer)
    # at the published depth: 61 GB in bf16, a pipeline over eight chips.
    # The benchmark serves six layers, one stage (benchmarks/configs/)
    "keye-vl-2.0-30b-a3b": TransformerConfig(
        vocab_size=151936, d_model=2048, n_layers=48, n_heads=32,
        head_dim=128, n_kv_heads=4, d_ff=6144, max_seq_len=262144,
        rotary_dim=128, rope_base=1e7, block_style="llama",
        n_experts=128, experts_per_token=8, expert_width=768,
        qk_norm=True, index_topk=2048, index_heads=16, index_dim=64),
    "llama2-tiny": TransformerConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        n_kv_heads=2, d_ff=128, max_seq_len=128, rotary_dim=16,
        block_style="llama", dtype=jnp.float32, remat=False),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    if name not in MODEL_CONFIGS:
        raise KeyError(
            f"unknown model {name!r}; have {sorted(MODEL_CONFIGS)}")
    cfg = MODEL_CONFIGS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def register_config(name: str, config: TransformerConfig) -> None:
    MODEL_CONFIGS[name] = config
