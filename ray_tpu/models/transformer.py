"""Decoder-only transformer LM, TPU-first.

Supports two block styles behind one config:

- ``"gptj"`` — parallel attention+MLP residual off a single LayerNorm
  (GPT-J 6B: rotary over the first 64 of 256 head dims, untied lm_head
  with bias). The flagship matches the reference's GPT-J fine-tune recipe
  (``release/air_examples/gptj_deepspeed_finetuning/``) architecturally.
- ``"llama"`` — sequential pre-RMSNorm blocks, SwiGLU MLP, full-dim neox
  rotary, optional GQA (num_kv_heads < num_heads).

Both, and every served form of the 'llama' block (latent attention,
window layers, experts, a key selection: ``TransformerConfig``), are ONE
block function (``_block``) over a description of each kind of layer
that ``_layer_plan`` builds from the configuration, once; training and
the cache path hand it their attention.

Design (TPU-first, not a port):
- params are a plain dict pytree; per-layer weights are STACKED on a
  leading ``layers`` axis and the forward pass is one ``lax.scan`` over
  layers (+ ``jax.checkpoint`` per block) — constant compile time in
  depth, XLA-friendly.
- every weight has an entry in :func:`logical_axes` — the same treedef
  with tuples of logical names ("embed", "mlp", "heads", "vocab", …);
  ``parallel.sharding.ShardingRules`` maps those to mesh axes, so DP /
  FSDP / TP / SP are rule-table changes, not model changes.
- TRAINING's master params live in f32; ``config.dtype`` (bf16 on TPU)
  is the compute dtype, cast at use sites so the MXU sees bf16 while
  layernorm statistics and the softmax stay f32 (ops layer contract).
  The serving engine holds no f32 masters: it takes its tree through
  :func:`inference_params` (every matmul/lookup leaf already in
  ``config.dtype``, norm leaves f32), on which the same use-site casts
  are no-ops, so no step program casts a weight.
- rematerialization is a named policy (``remat_policy``), not a bool:
  ``"dots"`` (default) saves projection/MLP matmul outputs and the
  attention output (``checkpoint_name``) while recomputing elementwise
  work and attention internals in the backward; ``"full"``/``"none"``
  are the old all-or-nothing extremes; ``"offload"`` parks block inputs
  in pinned host memory.
- the LM loss never materializes the full ``[b, s, vocab]`` logits
  tensor: ``ops.fused_lm_head_loss`` projects + reduces in sequence
  chunks of ``ce_chunk_size`` tokens (``ce_chunk_size=0`` restores the
  materialized-logits reference path).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import (
    apply_rotary,
    layer_norm,
    multihead_attention,
    paged_attention,
    ring_attention,
    rms_norm,
    rotary_table,
    cross_entropy_loss,
    fused_lm_head_loss,
)
from ray_tpu.ops.cross_entropy import PerChip
from ray_tpu.ops.norms import gated_rms_norm
from ray_tpu.ops.rotary import rotary_at, yarn_inv_freq

REMAT_POLICIES = ("full", "none", "dots", "dots_all", "offload")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50400
    d_model: int = 4096
    n_layers: int = 28
    n_heads: int = 16
    head_dim: int = 256
    n_kv_heads: Optional[int] = None        # GQA; None = n_heads
    d_ff: int = 16384
    max_seq_len: int = 2048
    rotary_dim: int = 64                     # gptj rotates a prefix
    rope_base: float = 10000.0
    block_style: str = "gptj"               # "gptj" | "llama"
    dtype: Any = jnp.bfloat16                # compute dtype
    # init_params draws the embedding at this standard deviation (every
    # matmul leaf at 0.02). At 0.02 a token's own embedding is no larger
    # in the stream than the attention's running mean over its keys, which
    # neighbouring tokens share: a router then sends neighbours alike, and
    # which experts fill is the seed's draw. Well above it (PyTorch's
    # nn.Embedding draws at 1) a token routes by its own embedding and the
    # experts' loads average out over the batch.
    embed_init_std: float = 0.02
    # Legacy bool (True -> "full", False -> "none"); None defers to
    # remat_policy. Kept so existing configs keep their exact behavior.
    remat: Optional[bool] = None
    remat_policy: str = "dots"               # see REMAT_POLICIES
    # Fused LM-head loss: tokens per CE chunk (0 = materialized logits).
    ce_chunk_size: int = 512
    attn_impl: str = "auto"                  # ops.multihead_attention impl
    attn_block_q: int = 0                    # 0 = chip-aware default
    attn_block_k: int = 0
    # Paged decode path (serving): "auto" dispatches the Pallas paged
    # kernel on TPU when shapes tile; "kernel" demands it (an error off
    # TPU); "interpret" runs it in Pallas interpret mode (CPU parity);
    # "reference" pins the pure-XLA gather — ops.attention has the
    # rules. paged_block_r = 0 picks the chip-aware query-row block
    # (ops.paged_flash.default_paged_block_r).
    paged_impl: str = "auto"
    paged_block_r: int = 0
    # Chunked prefill runs the same paged kernel at chunk*(heads/kv)
    # query rows — far more than decode's heads/kv — so a larger row
    # block can win there. 0 = use paged_block_r; the engine autotunes
    # this at long windows (allowing > 128) and records the winner.
    paged_block_r_prefill: int = 0
    # MoE (0 = dense): every layer's MLP becomes n_experts experts with
    # Switch top-1 routing, weights sharded on the ep mesh axis
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Served forms ("llama" blocks; the cache path, and of these keys
    # the training path takes ``TRAINED_KEYS`` below: ``refuse_training``
    # names the rest). experts_per_token > 0: every token to its k best of
    # n_experts gated (SwiGLU) experts of width expert_width (0 = d_ff),
    # renormalised softmax weights, none dropped (models/moe.py).
    experts_per_token: int = 0
    expert_width: int = 0
    # RMSNorm over head_dim, a learned weight a head, on q and k before
    # rotary
    qk_norm: bool = False
    # Learned key selection (ops/sparse_attention.py): index_heads x
    # index_dim indexer queries over one shared key head cached beside K
    # and V (beside the latent rows, with kv_lora_rank); attention reads
    # the index_topk keys it ranks highest. 0 = attend every key. The
    # indexer's queries come from the normed hidden state, or with
    # index_q_lora from the latent model's normed query bottleneck; it
    # rotates all of index_dim at rope_base, or with kv_lora_rank its
    # first qk_rope_dim numbers by the layer's own table.
    index_topk: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_q_lora: bool = False
    # Latent (MLA) attention (ops/latent_attention.py), kv_lora_rank > 0:
    # queries through a q_lora_rank bottleneck with its own RMSNorm, to
    # n_heads of qk_nope_dim + qk_rope_dim (head_dim is their sum); keys
    # and values from one normed latent of kv_lora_rank a token, beside
    # one rotated key of qk_rope_dim that every head shares; heads of
    # v_head_dim out. The cache is ONE pool of latent rows, no head axis
    # (with index_topk a second, of index keys, under the same table).
    # rope_yarn and head_gate (below) are forms of this sublayer too;
    # rope_softmax_scale multiplies the softmax scale (YaRN's mscale
    # squared, where a model puts it on the scores and not on sin and
    # cos). gated_norm_rank > 0: the two norms ahead of a layer's
    # sublayers pass a low-rank sigmoid gate of their own output
    # (ops.norms.gated_rms_norm).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # a second RMSNorm on each sublayer's output, ahead of the residual
    sandwich_norm: bool = False
    norm_eps: float = 1e-6
    rope_softmax_scale: float = 1.0
    gated_norm_rank: int = 0
    # the first n_dense_layers of n_layers have a dense SwiGLU MLP of
    # d_ff ahead of the expert layers (params["dense_layers"])
    n_dense_layers: int = 0
    # the dropless experts (models/moe.py): a SwiGLU expert of this
    # width every token passes, beside the routed ones; the router's
    # score ("softmax" over all experts | "sigmoid" of each) and the
    # scale on the renormalised weights; and the share of the experts
    # this program HOLDS: experts_held of n_experts from expert_first
    # (0 = all). Every token is routed over all n_experts; the held
    # ones' part is computed and the rest left to the chips that hold
    # them, no exchange. n_group > 0 (with the latent model): the
    # experts in n_group equal groups, a token's choice limited to its
    # topk_group best groups (a group's score the sum of its two best
    # experts'); router_bias: a learned bias an expert on the scores
    # that SELECT, not on the weights (moe.route_topk).
    shared_expert_width: int = 0
    router_score: str = "softmax"
    routed_scale: float = 1.0
    experts_held: int = 0
    expert_first: int = 0
    n_group: int = 0
    topk_group: int = 0
    router_bias: bool = False
    # A stack described BY KIND OF LAYER (served, and of "full" and
    # "window" layers trained; the 'llama' block over
    # per-head K/V): layer l is of kind layer_pattern[l % len] ("full" |
    # "window"; () = every layer "full"). A "full" layer has n_heads
    # query heads and rotates the first rotary_dim of head_dim at
    # rope_base, by a YaRN table where rope_yarn = (factor, original
    # length, beta_fast, beta_slow, attention factor) is given; a
    # "window" layer has window_heads (0 = n_heads), rotates
    # window_rotary_dim (0 = head_dim) at window_rope_base, plain, and
    # attends the sliding_window keys up to its own position; both over
    # kv_heads. Each kind's leaves are stacked apart (params["layers"],
    # ["window_layers"]), its K/V pages live in pools of their own
    # (init_kv_cache) and the leading n_dense_layers, of one kind, in
    # ["dense_layers"]. head_gate: sigmoid(h Wg), one number a head, on
    # the head's output ahead of wo.
    layer_pattern: Tuple[str, ...] = ()
    window_heads: int = 0
    sliding_window: int = 0
    window_rotary_dim: int = 0
    window_rope_base: float = 10000.0
    rope_yarn: Tuple[float, ...] = ()
    head_gate: bool = False
    # A "mamba" layer in layer_pattern (served): a Mamba-2 mixer
    # (ops/ssm.py) in the attention's place, ssm_heads heads of
    # ssm_head_dim over a state of ssm_state numbers a head and channel,
    # one group of B and C, a depthwise causal convolution of ssm_conv
    # taps ahead of it, scanned in blocks of ssm_chunk. Such a layer
    # holds no page: a sequence's recurrent state and the convolution's
    # last inputs live in per-slot arrays beside the pools
    # (init_kv_cache). rotary_dim 0: a "full" layer rotates nothing
    # (NoPE). attn_scale: the softmax scale (0 = head_dim^-1/2).
    # embed_scale multiplies the embedding, residual_scale each
    # sublayer's output ahead of its residual, logit_scale the logits;
    # tie_embeddings: the head is the embedding, transposed (the tree
    # has no "lm_head").
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    attn_scale: float = 0.0
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    tie_embeddings: bool = False
    # A "delta" layer in layer_pattern (served): a gated-delta-rule mixer
    # (ops/delta.py; Gated DeltaNet) in the attention's place,
    # delta_heads heads with keys and queries of delta_key_dim and values
    # of delta_value_dim, a depthwise causal convolution of delta_conv
    # taps (no bias) over q | k | v ahead of it, scanned in the published
    # blocks of 64 (ops/delta.py BLOCK); delta_neg_eigval: the write
    # strength is 2 sigmoid, not sigmoid. Such a layer holds no page: a
    # sequence's state and the convolution's last inputs live in per-slot
    # arrays, and with init_kv_cache(state_snapshots=...) in snapshot
    # rows beside them.
    # output_norm: no norm ahead of a sublayer and one RMSNorm on its
    # output, ahead of the residual (the Olmo 2 block). qk_norm_whole:
    # RMSNorm with a learned weight over ALL of a "full" layer's
    # projected q channels, and k's, before the heads are split
    # (qk_norm is a weight a head over head_dim).
    delta_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 4
    delta_neg_eigval: bool = False
    output_norm: bool = False
    qk_norm_whole: bool = False
    # A layer of ONE sublayer (served; a stack by kind of layer). An
    # "ffn" layer in layer_pattern is a feed-forward alone (the dense
    # SwiGLU or the dropless experts) behind one norm: no mixer, no
    # page, no state. mixer_only: a layer of any OTHER kind is its mixer
    # alone behind one norm and has no feed-forward. ssm_groups: the
    # "mamba" layers' B and C come a group of ssm_heads / ssm_groups
    # heads, and the gated norm norms each group's channels apart (1:
    # one group, one norm over all channels). The dropless experts'
    # form, expert_act: "swiglu" | "relu2" (relu(x W_up)^2 W_down: two
    # matrices, no gate; the shared expert takes the same form);
    # moe_latent R > 0: the routed experts live in a latent of R numbers
    # between one down- and one up-projection a token (models/moe.py).
    mixer_only: bool = False
    ssm_groups: int = 1
    expert_act: str = "swiglu"
    moe_latent: int = 0

    def __post_init__(self):
        # plain JSON hands lists over: the config stays hashable
        for name in ("layer_pattern", "rope_yarn"):
            object.__setattr__(self, name, tuple(getattr(self, name)))

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def d_expert(self) -> int:
        return self.expert_width or self.d_ff

    @property
    def expert_matrices(self) -> int:
        """Matrices of one dropless expert: gate, up, down, or no gate."""
        return 3 if self.expert_act == "swiglu" else 2

    @property
    def expert_params(self) -> int:
        """Parameters of one routed expert (in its latent where it has
        one)."""
        return self.expert_matrices * (self.moe_latent or self.d_model) \
            * self.d_expert

    @property
    def expert_layers(self) -> int:
        """Layers whose feed-forward is the dropless experts."""
        if not self.experts_per_token:
            return 0
        return sum(_kind_halves(self, self.layer_kind(l))[1]
                   for l in range(self.n_dense_layers, self.n_layers))

    @property
    def ssm_inner(self) -> int:
        """Channels the scan runs over (the published d_model x expand)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels through the convolution: x and each group's B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def delta_inner(self) -> int:
        """Channels the delta rule's values, gate and output run over."""
        return self.delta_heads * self.delta_value_dim

    @property
    def delta_conv_width(self) -> int:
        """Channels through the convolution: every head's q, k and v."""
        return self.delta_heads * (2 * self.delta_key_dim
                                   + self.delta_value_dim)

    #: the keys of the forms only ``prefill`` / ``decode_step`` implement
    SERVED_KEYS = ("experts_per_token", "qk_norm", "index_topk",
                   "kv_lora_rank", "sandwich_norm", "n_dense_layers",
                   "layer_pattern", "window_heads", "sliding_window",
                   "rope_yarn", "head_gate", "index_q_lora",
                   "rope_softmax_scale", "gated_norm_rank", "n_group",
                   "topk_group", "router_bias", "ssm_heads",
                   "ssm_head_dim", "ssm_state", "attn_scale", "embed_scale",
                   "residual_scale", "logit_scale", "tie_embeddings",
                   "delta_heads", "delta_key_dim", "delta_value_dim",
                   "delta_neg_eigval", "output_norm", "qk_norm_whole",
                   "mixer_only", "ssm_groups", "expert_act", "moe_latent")

    @property
    def served_keys(self) -> Tuple[str, ...]:
        """The served-only keys this configuration sets."""
        fields = self.__dataclass_fields__
        return tuple(k for k in self.SERVED_KEYS
                     if getattr(self, k) != fields[k].default)

    @property
    def served_only(self) -> bool:
        """A form only ``prefill`` / ``decode_step`` implement."""
        return bool(self.served_keys)

    def layer_kind(self, layer: int) -> str:
        pattern = self.layer_pattern or ("full",)
        return pattern[layer % len(pattern)]

    def kind_heads(self, kind: str) -> int:
        return (self.window_heads or self.n_heads) if kind == "window" \
            else self.n_heads

    def paged_row_block(self, tokens: int) -> Optional[int]:
        """The paged kernel's row block for a call of ``tokens`` query
        tokens a sequence (static under jit): a prefill chunk's much
        larger row count can carry a bigger block than the one-token
        decode step compiled from the same sublayer. None: the kernel's
        own default."""
        return (self.paged_block_r_prefill if tokens > 1 else 0) \
            or self.paged_block_r or None

    @property
    def resolved_remat_policy(self) -> str:
        """Effective remat policy, honoring the legacy ``remat`` bool."""
        if self.remat is not None:
            return "full" if self.remat else "none"
        return self.remat_policy

    @property
    def num_params(self) -> int:
        """Parameter count (for MFU accounting)."""
        e, v, h = self.d_model, self.vocab_size, self.n_heads * self.head_dim
        kvh = self.kv_heads * self.head_dim
        if _tree_form(self) == "kinds":
            # embed, head (the embedding again where tied), final norm
            total = (1 if self.tie_embeddings else 2) * v * e + e
            for l in range(self.n_layers):
                kind = self.layer_kind(l)
                mixer, mlp = _kind_halves(self, kind)
                total += e * (mixer + mlp)            # a norm a sublayer
                if not mixer:
                    pass
                elif kind == "mamba":
                    di, cw = self.ssm_inner, self.ssm_conv_width
                    total += e * (di + cw + self.ssm_heads) + di * e \
                        + cw * (self.ssm_conv + 1) + 3 * self.ssm_heads \
                        + di
                elif kind == "delta":
                    # w_qkv, w_g, w_ab, w_out, the taps, A_log, dt_bias,
                    # the output norm a head wide
                    di, cw = self.delta_inner, self.delta_conv_width
                    total += e * (cw + di + 2 * self.delta_heads) \
                        + di * e + cw * self.delta_conv \
                        + 2 * self.delta_heads + self.delta_value_dim
                else:
                    hk = self.kind_heads(kind)
                    total += 2 * e * hk * self.head_dim + 2 * e * kvh \
                        + (e * hk if self.head_gate else 0) \
                        + (hk * self.head_dim + kvh
                           if self.qk_norm_whole else 0)
                if not mlp:
                    continue
                if l < self.n_dense_layers or not self.experts_per_token:
                    total += 3 * e * self.d_ff
                else:
                    total += e * self.n_experts \
                        + self.n_experts * self.router_bias \
                        + 2 * e * self.moe_latent \
                        + self.n_experts_held * self.expert_params \
                        + self.expert_matrices * e * self.shared_expert_width
            return total
        per_layer = e * h + 2 * e * kvh + h * e          # q, k, v, o
        if self.kv_lora_rank:    # wq_a, wq_b, wkv_a, wkv_b, wo, 2 norms
            qr, r = self.q_lora_rank, self.kv_lora_rank
            per_layer = e * qr + qr * h + e * (r + self.qk_rope_dim) \
                + r * self.n_heads * (self.qk_nope_dim + self.v_head_dim) \
                + self.n_heads * self.v_head_dim * e + qr + r
            if self.index_topk:              # wq, wk, ww, k layernorm
                per_layer += (qr if self.index_q_lora else e) \
                    * self.index_heads * self.index_dim \
                    + e * (self.index_dim + self.index_heads) \
                    + 2 * self.index_dim
            per_layer += e * self.n_heads * self.head_gate \
                + 4 * e * self.gated_norm_rank
        if self.sandwich_norm:
            per_layer += 2 * e
        dense_layer = per_layer + 3 * e * self.d_ff + 2 * e
        if self.qk_norm:
            per_layer += 2 * self.head_dim
        if self.index_topk and not self.kv_lora_rank:
            per_layer += e * self.index_dim * (self.index_heads + 1) \
                + e * self.index_heads + 2 * self.index_dim
        if self.experts_per_token:
            per_layer += e * self.n_experts + 2 * e + 3 * e * (
                self.n_experts_held * self.d_expert
                + self.shared_expert_width) \
                + self.n_experts * self.router_bias
        elif self.n_experts:
            per_layer += e * self.n_experts \
                + self.n_experts * 2 * e * self.d_ff     # router + experts
            per_layer += 2 * e                           # norms
        elif self.block_style == "llama":
            per_layer += 3 * e * self.d_ff + 2 * e       # swiglu + 2 rmsnorm
        else:
            per_layer += 2 * e * self.d_ff + self.d_ff + e  # fc biases
            per_layer += 2 * e                           # ln scale+bias
        total = v * e + (self.n_layers - self.n_dense_layers) * per_layer \
            + self.n_dense_layers * dense_layer
        total += e if self.block_style == "llama" else 2 * e  # final norm
        total += e * v + (v if self.block_style == "gptj" else 0)  # lm head
        return total

    @property
    def num_active_params(self) -> int:
        """Params touched per token: with Switch top-1 routing only ONE
        expert's MLP runs per token — FLOPs must not count the rest; the
        dropless form runs ``experts_per_token`` gated experts."""
        if not self.n_experts:
            return self.num_params
        if self.experts_per_token:
            # of the held experts a token meets its share of the k
            met = self.experts_per_token * self.n_experts_held \
                / self.n_experts
            return int(self.num_params - self.expert_layers
                       * self.expert_params * (self.n_experts_held - met))
        inactive = self.n_layers * (self.n_experts - 1) \
            * 2 * self.d_model * self.d_ff
        return self.num_params - inactive

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Approximate train FLOPs/token (6·N active params + attention)."""
        s = seq_len or self.max_seq_len
        attn = 12 * self.n_layers * self.n_heads * self.head_dim * s
        if self.sliding_window and self.sliding_window < s:
            # a window layer's queries meet its window's keys, not the
            # sequence's: s - (w - 1) / 2 positions' worth a full layer's
            # s, the first w - 1 queries seeing fewer
            w = self.sliding_window
            seen = (w * (w + 1) / 2 + (s - w) * w) / (s * (s + 1) / 2)
            windowed = sum(self.layer_kind(l) == "window"
                           for l in range(self.n_layers))
            attn = 12 * self.head_dim * s * (
                (self.n_layers - windowed) * self.n_heads
                + windowed * self.kind_heads("window") * seen)
        return 6.0 * self.num_active_params + attn


# ------------------------------------------------------------------ init
# scaled where it lies: beside the draw, a second f32 buffer of a stacked
# leaf's size would set the peak of an engine's whole life
_scale_in_place = jax.jit(lambda w, scale: scale * w, donate_argnums=(0,))


def _dense_init(key, shape, scale=0.02, dtype=jnp.float32):
    w = _scale_in_place(jax.random.normal(key, shape, jnp.float32), scale)
    return w.astype(dtype)          # f32: the array itself


@functools.partial(jax.jit, static_argnames=("n", "shape", "dtype"))
def _layered_init(key, scale, n, shape, dtype):
    """``[n, *shape]`` drawn a layer at a time into the stored dtype: a
    stack of experts in f32 would be twice what it is to be held in."""
    def one(i, out):
        w = scale * jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
        return out.at[i].set(w.astype(dtype))
    return jax.lax.fori_loop(0, n, one, jnp.zeros((n,) + shape, dtype))


def init_params(config: TransformerConfig, key,
                dtype=jnp.float32) -> Dict:
    """Seeded initial parameters: float32 masters by default (training).
    ``dtype`` is what the randomly initialised leaves — the embedding
    and every matmul weight, all but a sliver of the tree — are STORED
    in: each is drawn in f32 and rounded as it is made, so a serving
    engine asking for ``config.dtype`` never holds an f32 tree
    (:func:`inference_params` then casts the few bias leaves left). The
    bits are those of casting the f32 tree afterwards."""
    c = config
    keys = jax.random.split(key, 9)
    h = c.n_heads * c.head_dim
    kvh = c.kv_heads * c.head_dim
    L = c.n_layers
    dense = functools.partial(_dense_init, dtype=dtype)

    def stack(k, shape, scale=0.02):
        return dense(k, (L,) + shape, scale)

    out_scale = 0.02 / (2 * L) ** 0.5    # scaled residual-out init
    tree = _layer_plan(c).tree          # and the refusals
    if tree == "latent":
        return _init_latent_params(c, key, jnp.dtype(dtype), out_scale)
    if tree == "kinds":
        return _init_kind_params(c, key, jnp.dtype(dtype), out_scale)
    llama = c.block_style == "llama"
    layers: Dict[str, jnp.ndarray] = {
        "wq": stack(keys[0], (c.d_model, h)),
        "wk": stack(keys[1], (c.d_model, kvh)),
        "wv": stack(keys[2], (c.d_model, kvh)),
        "wo": stack(keys[3], (h, c.d_model), out_scale),
    }
    # new leaves draw from keys folded out of ``key``: the nine above
    # stay what they were
    if c.qk_norm:
        layers.update({"q_norm": jnp.ones((L, c.head_dim), jnp.float32),
                       "k_norm": jnp.ones((L, c.head_dim), jnp.float32)})
    if c.index_topk:
        ik = jax.random.split(jax.random.fold_in(key, 101), 3)
        layers.update({
            "wq_idx": stack(ik[0], (c.d_model,
                                    c.index_heads * c.index_dim)),
            "wk_idx": stack(ik[1], (c.d_model, c.index_dim)),
            "ww_idx": stack(ik[2], (c.d_model, c.index_heads)),
            "k_idx_scale": jnp.ones((L, c.index_dim), jnp.float32),
            "k_idx_bias": jnp.zeros((L, c.index_dim), jnp.float32)})
    if c.experts_per_token:
        from ray_tpu.models.moe import topk_moe_param_shapes
        ek = jax.random.fold_in(key, 102)
        for i, (name, shape) in enumerate(
                sorted(topk_moe_param_shapes(c).items())):
            layers[name] = _layered_init(
                jax.random.fold_in(ek, i),
                out_scale if name == "we_down" else 0.02, L, shape,
                jnp.dtype(dtype))
    elif c.n_experts:
        from ray_tpu.models.moe import moe_param_shapes
        mk = jax.random.split(keys[6], 3)
        layers.update({
            name: stack(mk[i], shape,
                        out_scale if name == "moe_wo" else 0.02)
            for i, (name, shape) in
            enumerate(sorted(moe_param_shapes(c).items()))})
    elif llama:
        layers.update({
            "w_gate": stack(keys[4], (c.d_model, c.d_ff)),
            "w_up": stack(keys[5], (c.d_model, c.d_ff)),
            "w_down": stack(keys[6], (c.d_ff, c.d_model), out_scale)})
    else:
        layers.update({
            "fc_in": stack(keys[4], (c.d_model, c.d_ff)),
            "fc_in_b": jnp.zeros((L, c.d_ff), jnp.float32),
            "fc_out": stack(keys[5], (c.d_ff, c.d_model), out_scale),
            "fc_out_b": jnp.zeros((L, c.d_model), jnp.float32)})
    # the block's norms, the final norm and the head: RMS and no bias
    # anywhere, or LayerNorm and a head with one
    final = {"scale": jnp.ones((c.d_model,), jnp.float32)}
    head = {"w": dense(keys[8], (c.d_model, c.vocab_size))}
    if llama:
        layers.update({
            "attn_norm": jnp.ones((L, c.d_model), jnp.float32),
            "mlp_norm": jnp.ones((L, c.d_model), jnp.float32)})
    else:
        layers.update({
            "ln_scale": jnp.ones((L, c.d_model), jnp.float32),
            "ln_bias": jnp.zeros((L, c.d_model), jnp.float32)})
        final["bias"] = jnp.zeros((c.d_model,), jnp.float32)
        head["b"] = jnp.zeros((c.vocab_size,), jnp.float32)

    return {
        "embed": dense(keys[7], (c.vocab_size, c.d_model),
                       c.embed_init_std),
        "layers": layers,
        "final_norm": final,
        "lm_head": head,
    }


def _check_served_forms(c: TransformerConfig) -> None:
    if c.served_only and c.block_style != "llama":
        raise ValueError(
            f"the served forms ({', '.join(c.SERVED_KEYS)}) are forms of "
            f"the 'llama' block; got {', '.join(c.served_keys)} with "
            f"block_style {c.block_style!r}")
    if c.rope_yarn and len(c.rope_yarn) != 5:
        raise ValueError("rope_yarn is (factor, original length, "
                         "beta_fast, beta_slow, attention factor)")
    if c.kv_lora_rank:
        if c.qk_norm:
            raise ValueError("qk_norm is a form of per-head K/V, not of "
                             "a latent cache")
        if c.index_topk and not (c.index_heads and c.index_dim
                                 >= c.qk_rope_dim):
            raise ValueError(
                "a key selection over a latent cache (index_topk) needs "
                "index_heads and index_dim >= qk_rope_dim, got "
                f"{c.index_heads} and {c.index_dim}")
        if c.head_dim != c.qk_nope_dim + c.qk_rope_dim or not (
                c.q_lora_rank and c.v_head_dim):
            raise ValueError(
                "latent attention needs q_lora_rank, v_head_dim and "
                f"head_dim == qk_nope_dim + qk_rope_dim, got {c}")
    latent_only = ("sandwich_norm", "index_q_lora", "rope_softmax_scale",
                   "gated_norm_rank", "n_group", "topk_group")
    if not c.kv_lora_rank and set(c.served_keys) & set(latent_only):
        raise ValueError(f"{', '.join(latent_only)} are served with "
                         "latent attention (kv_lora_rank > 0)")
    if c.index_q_lora and not c.index_topk:
        raise ValueError("index_q_lora names where the indexer's queries "
                         "come from: it needs index_topk")
    if c.n_group or c.topk_group:
        group = c.n_experts // max(c.n_group, 1)
        if c.n_group < 1 or c.n_experts % c.n_group or group < 2 \
                or not 0 < c.topk_group <= c.n_group \
                or c.experts_per_token > c.topk_group * group:
            raise ValueError(
                f"{c.n_experts} experts in n_group {c.n_group} groups of "
                f"two or more, of which topk_group {c.topk_group} hold a "
                f"token's {c.experts_per_token}")
    if (c.shared_expert_width or c.experts_held or c.router_bias
            or c.router_score != "softmax" or c.moe_latent
            or c.expert_act != "swiglu") and not c.experts_per_token:
        raise ValueError("shared_expert_width, experts_held, router_bias, "
                         "router_score, expert_act and moe_latent belong "
                         "to the dropless experts (experts_per_token > 0)")
    if c.expert_act not in ("swiglu", "relu2"):
        raise ValueError(f"expert_act {c.expert_act!r}: 'swiglu' or 'relu2'")
    if c.ssm_groups < 1 or c.ssm_heads % c.ssm_groups:
        raise ValueError(f"ssm_groups {c.ssm_groups} divides ssm_heads "
                         f"{c.ssm_heads}")
    if c.kv_lora_rank and not (
            c.experts_per_token and 0 <= c.n_dense_layers < c.n_layers):
        raise ValueError("latent attention is served ahead of dropless "
                         "experts, n_dense_layers < n_layers of them dense")
    if c.experts_held and not (
            0 <= c.expert_first <= c.n_experts - c.experts_held):
        raise ValueError(
            f"experts {c.expert_first}..{c.expert_first + c.experts_held} "
            f"held of {c.n_experts}")


#: where the tree keeps each kind's layers (all of them, or with
#: ``n_dense_layers`` those behind ``dense_layers``)
KIND_STACKS = {"full": "layers", "window": "window_layers",
               "mamba": "mamba_layers", "delta": "delta_layers",
               "ffn": "ffn_layers"}


def _kind_halves(c: "TransformerConfig", kind: str) -> Tuple[bool, bool]:
    """(a mixer, a feed-forward): which sublayers a layer of ``kind``
    has. An "ffn" layer is its feed-forward alone; with ``mixer_only``
    every other kind is its mixer alone."""
    return kind != "ffn", kind == "ffn" or not c.mixer_only

#: a window layer's pools, beside the full layers' "k" / "v"
WINDOW_POOLS = ("k_window", "v_window")

#: what the cache holds beside its pools: the "mamba" layers' recurrent
#: state and their convolution's last inputs, the "delta" layers' the
#: same, ``[layers, slots, ...]``, a row a decode slot and no page; and
#: the "delta" layers' snapshot rows, ``[layers, 1 + snapshots, ...]``
#: (:func:`init_kv_cache`)
STATE_ARRAYS = ("ssm", "conv", "delta", "delta_conv", "delta_snap",
                "delta_conv_snap")


def state_snapshot_arrays(config: "TransformerConfig") -> Dict[str, str]:
    """Per-slot state array -> its snapshot rows' array, for every state
    whose mixer hands it out at a chunk's boundaries (``prefill``'s
    ``snap_rows``); empty for a model that has none."""
    return {state.name: state.snap for kind in _layer_plan(config).kinds
            for state in kind.state if state.snap}


def state_counters(config: "TransformerConfig") -> Optional[str]:
    """The name the engine counts the stack's recurrence under (the
    layer plan's: "ssm", "delta"); None for a model of pages alone."""
    return next((kind.counters for kind in _layer_plan(config).kinds
                 if kind.counters), None)


def cache_pools(cache: Dict[str, Any]) -> Dict[str, Any]:
    """The paged pools of a cache (blocks on axis 1), without the
    per-slot state arrays a model with "mamba" layers keeps beside them:
    what copies, ships, adopts or sizes a PAGE goes over these."""
    return {name: a for name, a in cache.items()
            if name not in STATE_ARRAYS}


# ------------------------------------------------------ the layer plan
# What a configuration's keys ask of a layer is decided HERE, once:
# :func:`_layer_plan` turns them into a description of each kind of layer
# the stack has and of the runs of layers the forward pass scans. The one
# block (:func:`_block`), the two mixers (:func:`_paged_attn_sublayer`,
# :func:`_latent_attn_sublayer`), the scan driver
# (:func:`_forward_with_cache`), :func:`run_layers` and
# :func:`init_kv_cache` read the description and test no key themselves.

class _Rotary(NamedTuple):
    """A kind of layer's rotary embedding."""
    layout: str                  # "gptj": pairs interleaved | "neox": halves
    dim: int                     # rotated width, a prefix of the head
    base: float
    yarn: Tuple[float, ...]      # () plain, else ``rope_yarn``'s five
    # computed at the positions asked (``rotary_at``), or a table as long
    # as the block table reaches, gathered at them: one result, two
    # programs, and each configuration keeps the one it has (ROADMAP
    # C1(c))
    at_positions: bool


class _Pool(NamedTuple):
    """One of the cache's pools: ``[layers, blocks, heads, block_size,
    width]`` under ``name``."""
    name: str
    heads: int
    width: int


class _State(NamedTuple):
    """One of the cache's per-slot arrays: ``[layers, slots, *shape]``
    under ``name``; ``dtype`` None is the compute dtype. ``snap``: the
    name of its snapshot rows, ``[layers, 1 + snapshots, *shape]`` (row
    0 the trash row), for a mixer that hands out its state at a call's
    boundaries; None for one that does not."""
    name: str
    shape: Tuple[int, ...]
    dtype: Any
    snap: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class _LayerKind:
    """One kind of layer, as the forward pass needs to know it."""
    name: str            # "full" | "window" | "mamba" | "delta" | "ffn"
    # ahead of each sublayer: "layer" (with bias) | "rms" | "gated" |
    # "none" (the sublayer reads the residual stream as it is)
    norm: str
    # attention and MLP read the one normed input and are added together
    # (the 'gptj' form) | the MLP follows the attention's residual
    parallel: bool
    post_norm: bool              # an RMSNorm on each sublayer's output
    # "paged": per-head K/V | "latent": MLA rows | "scan": Mamba-2's
    # recurrence | "delta": the gated delta rule's | None: the layer is
    # its feed-forward alone, with the norm of that half
    mixer: Optional[str]
    heads: int                   # query heads
    window: int                  # keys attended behind a position, 0 = all
    qk_norm: bool                # RMSNorm q and k, a weight a head
    head_gate: bool
    index_topk: int              # keys a learned selection keeps, 0 = all
    # the pools a layer writes (its keys, its values, ``ki`` last where it
    # selects) and the table it reads them through: "main" (entry 0 is
    # position 0) | "window" (a short table that starts behind the window)
    pools: Tuple[_Pool, ...]
    # ... | "state" (no table: the slot of each row, for ``state``) |
    # None (no mixer)
    table: Optional[str]
    scope: Optional[str]         # the named scope below ``layer/attn``
    rotary: _Rotary              # ``dim`` 0: nothing is rotated
    index_rotary: Optional[_Rotary]      # None: the layer's own
    # what a "scan" layer carries a sequence, a row a slot
    state: Tuple[_State, ...] = ()
    # RMSNorm q and k over the WHOLE projected width, before the heads
    # are split
    qk_norm_whole: bool = False
    # the name the engine counts this kind's recurrence under
    # (``<counters>_decode_rows_total`` ...); None: no state, no counter
    counters: Optional[str] = None
    # the feed-forward half is there (False: the layer is its mixer alone,
    # with the norm of that half)
    mlp: bool = True


class _Run(NamedTuple):
    """Consecutive layers of one kind in one stack of the tree: one scan."""
    stack: str                   # the stack's name in the tree
    kind: _LayerKind
    experts: bool                # dropless experts | a dense MLP
    at: int                      # the first layer's index in the stack
    n: int
    cache_layer: int             # the first layer's, in the kind's pools


class _LayerPlan(NamedTuple):
    tree: str                    # which builder makes the tree (_tree_form)
    kinds: Tuple[_LayerKind, ...]
    runs: Tuple[_Run, ...]


def _tree_form(c: TransformerConfig) -> str:
    """Which of the three builders of the parameter tree a configuration
    takes (ROADMAP C1(b)): "latent" (``kv_lora_rank``), "kinds" (stacks
    by kind of layer: any of the keys below) or "plain" (one stack)."""
    if c.kv_lora_rank:
        return "latent"
    return "kinds" if (
        c.layer_pattern or c.n_dense_layers or c.window_heads
        or c.sliding_window or c.rope_yarn or c.head_gate) else "plain"


@functools.lru_cache(maxsize=None)
def _layer_plan(c: TransformerConfig) -> _LayerPlan:
    """The description of ``c``'s stack; raises for a combination of keys
    no path implements."""
    from ray_tpu.ops.latent_attention import latent_row_width
    _check_served_forms(c)
    tree = _tree_form(c)
    latent, by_kind = tree == "latent", tree == "kinds"
    pattern = {c.layer_kind(l) for l in range(c.n_layers)}
    if latent and (pattern != {"full"} or c.sliding_window
                   or c.window_heads):
        raise ValueError("layer_pattern, sliding_window and window_heads "
                         "are forms of per-head K/V, not of a latent cache")
    if not pattern <= set(KIND_STACKS):
        raise ValueError(f"layer_pattern {c.layer_pattern}: a layer "
                         f"is 'full', 'window', 'mamba', 'delta' or 'ffn'")
    if ("delta" in pattern) != bool(c.delta_heads) or c.delta_heads and not (
            c.delta_key_dim and c.delta_value_dim and c.delta_conv > 1
            ) or c.delta_heads and c.ssm_heads:
        raise ValueError("'delta' layers in layer_pattern come with "
                         "delta_heads, delta_key_dim, delta_value_dim "
                         "and delta_conv > 1, and not with "
                         f"'mamba' layers, got {c.layer_pattern} and "
                         f"{c.delta_heads}, {c.delta_key_dim}, "
                         f"{c.delta_value_dim}, {c.delta_conv}")
    if ("mamba" in pattern) != bool(c.ssm_heads) or c.ssm_heads and not (
            c.ssm_head_dim and c.ssm_state and c.ssm_conv > 1
            and c.ssm_chunk > 0):
        raise ValueError("'mamba' layers in layer_pattern come with "
                         "ssm_heads, ssm_head_dim, ssm_state, ssm_conv > 1 "
                         f"and ssm_chunk, got {c.layer_pattern} and "
                         f"{c.ssm_heads}, {c.ssm_head_dim}, {c.ssm_state}, "
                         f"{c.ssm_conv}, {c.ssm_chunk}")
    by_kind_only = [k for k in ("attn_scale", "embed_scale",
                                "residual_scale", "logit_scale",
                                "tie_embeddings", "output_norm",
                                "qk_norm_whole", "delta_neg_eigval",
                                "mixer_only", "ssm_groups", "expert_act",
                                "moe_latent")
                    if k in c.served_keys]
    if by_kind_only and not by_kind:
        raise ValueError(f"{', '.join(by_kind_only)}: forms of a stack by "
                         "kind of layer (layer_pattern, n_dense_layers, "
                         "head_gate, rope_yarn)")
    if c.router_bias and not (latent or by_kind):
        raise ValueError("router_bias is served with latent attention "
                         "(kv_lora_rank > 0) or in a stack by kind of layer")
    if ("window" in pattern) != bool(c.sliding_window):
        raise ValueError("'window' layers in layer_pattern and "
                         "sliding_window > 0 come together, got "
                         f"{c.layer_pattern} and {c.sliding_window}")
    if by_kind:
        if c.qk_norm or c.index_topk:
            raise ValueError("qk_norm and index_topk are not forms of a "
                             "stack by kind of layer (layer_pattern, "
                             "n_dense_layers, head_gate, rope_yarn)")
        if len({c.layer_kind(l) for l in range(c.n_dense_layers)}) > 1 \
                or not 0 <= c.n_dense_layers <= c.n_layers:
            raise ValueError("the leading n_dense_layers are of one kind")
        if c.n_dense_layers and not (
                c.experts_per_token and c.n_dense_layers < c.n_layers):
            raise ValueError("n_dense_layers lead layers of dropless "
                             "experts (experts_per_token > 0)")
    gptj = c.block_style == "gptj"
    page = (c.kv_heads, c.head_dim)

    def kind(name: str) -> _LayerKind:
        window = name == "window"
        common = dict(
            name=name, mlp=_kind_halves(c, name)[1],
            norm="layer" if gptj else "none" if c.output_norm
            else "gated" if c.gated_norm_rank else "rms", parallel=gptj,
            post_norm=c.sandwich_norm or c.output_norm,
            qk_norm=c.qk_norm, qk_norm_whole=c.qk_norm_whole,
            head_gate=c.head_gate, index_topk=c.index_topk)
        if name == "ffn":
            # no mixer: no pool, no state, no table, nothing rotated
            return _LayerKind(
                **{**common, "head_gate": False, "qk_norm_whole": False},
                mixer=None, heads=0, window=0, pools=(), table=None,
                scope=None, rotary=_Rotary("neox", 0, c.rope_base, (), True),
                index_rotary=None)
        if name == "delta":
            # no page, as a "mamba" layer: the state a head (float32, the
            # key width ahead of heads and value channels as ONE axis:
            # ops/delta.py) and the convolution's last raw inputs, a row a
            # slot; and a snapshot row of each a trie node that has one
            return _LayerKind(
                **{**common, "head_gate": False, "qk_norm_whole": False},
                mixer="delta", heads=c.delta_heads, window=0, pools=(),
                table="state", scope=None,
                rotary=_Rotary("neox", 0, c.rope_base, (), True),
                index_rotary=None, state=(
                    _State("delta", (c.delta_key_dim, c.delta_inner),
                           jnp.float32, "delta_snap"),
                    _State("delta_conv",
                           (c.delta_conv - 1, c.delta_conv_width), None,
                           "delta_conv_snap")), counters="delta")
        if name == "mamba":
            # no page: the state a head (float32: the recurrence adds into
            # it at every token) and the convolution's last inputs, a row
            # a slot
            return _LayerKind(
                **{**common, "head_gate": False, "qk_norm_whole": False},
                mixer="scan",
                heads=c.ssm_heads, window=0, pools=(), table="state",
                scope=None, rotary=_Rotary("neox", 0, c.rope_base, (), True),
                index_rotary=None, state=(
                    # heads and their channels as ONE axis: a program
                    # that could order them either way would relay the
                    # whole array to its own order on the way in and out
                    _State("ssm", (c.ssm_state, c.ssm_inner), jnp.float32),
                    _State("conv", (c.ssm_conv - 1, c.ssm_conv_width),
                           None)), counters="ssm")
        if latent:
            # ONE pool, a row a token and layer for every head (the
            # normed latent | the rotated shared key | zeros up to whole
            # lane tiles), under the one "head" the page layout keeps:
            # key and, in its first kv_lora_rank columns, value
            pools = (_Pool("latent", 1, latent_row_width(
                c.kv_lora_rank, c.qk_rope_dim)),)
            rotary = _Rotary("neox", c.qk_rope_dim, c.rope_base,
                             c.rope_yarn, bool(c.rope_yarn))
        elif window:
            pools = tuple(_Pool(n, *page) for n in WINDOW_POOLS)
            rotary = _Rotary("neox", c.window_rotary_dim or c.head_dim,
                             c.window_rope_base, (), True)
        else:
            # one stack of 'llama' blocks rotates the whole head, a stack
            # by kind of layer its "full" layers' first rotary_dim
            pools = (_Pool("k", *page), _Pool("v", *page))
            rotary = _Rotary(
                "gptj" if gptj else "neox",
                c.rotary_dim if gptj or by_kind else c.head_dim,
                c.rope_base, c.rope_yarn, by_kind)
        if c.index_topk:
            pools += (_Pool("ki", 1, c.index_dim),)
        return _LayerKind(
            **common, mixer="latent" if latent else "paged",
            heads=c.kind_heads(name),
            window=c.sliding_window if window else 0, pools=pools,
            table="window" if window else "main",
            scope=name if by_kind else None, rotary=rotary,
            # over per-head K/V the indexer rotates all of index_dim,
            # plain, by a table; over latent rows by the layer's own
            index_rotary=_Rotary("neox", c.index_dim, c.rope_base, (),
                                 False)
            if c.index_topk and not latent else None)

    # a stack with window layers has both kinds of pool, even at a depth
    # that holds no layer of one of them
    kinds = {name: kind(name) for name in
             ("full",) + (("window",) if c.sliding_window else ())
             + (("mamba",) if c.ssm_heads else ())
             + (("delta",) if c.delta_heads else ())
             + (("ffn",) if "ffn" in pattern else ())}
    runs, seen, ordinal = [], {}, dict.fromkeys(kinds, 0)
    for l in range(c.n_layers):
        k, lead = kinds[c.layer_kind(l)], l < c.n_dense_layers
        stack = "dense_layers" if lead else KIND_STACKS[k.name]
        at = seen.get(stack, 0)
        if runs and runs[-1].stack == stack:
            runs[-1] = runs[-1]._replace(n=runs[-1].n + 1)
        else:
            runs.append(_Run(stack, k, bool(c.experts_per_token) and k.mlp
                             and not lead, at, 1, ordinal[k.name]))
        seen[stack] = at + 1
        ordinal[k.name] += 1
    return _LayerPlan(tree, tuple(kinds.values()), tuple(runs))


def _kind_layer_shapes(c: TransformerConfig, kind: str, dense: bool
                       ) -> Dict[str, tuple]:
    """One layer's matmul leaves of a stack by kind: name -> (shape,
    logical axes without the layers axis). ``kind`` sets the mixer, its
    query heads and which sublayers there are (:func:`_kind_halves`),
    ``dense`` a SwiGLU MLP of d_ff in place of the experts."""
    from ray_tpu.models.moe import (topk_moe_logical_axes,
                                    topk_moe_param_shapes)
    e, h = c.d_model, c.kind_heads(kind) * c.head_dim
    kvh = c.kv_heads * c.head_dim
    mixer, mlp = _kind_halves(c, kind)
    if not mixer:
        out = {}
    elif kind == "mamba":
        # w_in's columns as published: gate z | x, each group's B, each
        # group's C (through the convolution) | dt
        di, cw = c.ssm_inner, c.ssm_conv_width
        out = {"w_in": ((e, di + cw + c.ssm_heads), ("embed", "mlp")),
               "conv_w": ((cw, c.ssm_conv), ("mlp", None)),
               "w_out": ((di, e), ("mlp", "embed"))}
    elif kind == "delta":
        # w_qkv's columns: every head's q | k | v, through the
        # convolution; w_g the output's gate; w_ab the decay's and the
        # write strength's inputs, a head each
        di, cw = c.delta_inner, c.delta_conv_width
        out = {"w_qkv": ((e, cw), ("embed", "mlp")),
               "w_g": ((e, di), ("embed", "mlp")),
               "w_ab": ((e, 2 * c.delta_heads), ("embed", None)),
               "conv_w": ((cw, c.delta_conv), ("mlp", None)),
               "w_out": ((di, e), ("mlp", "embed"))}
    else:
        out = {"wq": ((e, h), ("embed", "heads")),
               "wk": ((e, kvh), ("embed", "kv")),
               "wv": ((e, kvh), ("embed", "kv")),
               "wo": ((h, e), ("heads", "embed"))}
        if c.head_gate:
            out["wg"] = ((e, c.kind_heads(kind)), ("embed", None))
    if not mlp:
        return out
    if dense or not c.experts_per_token:
        out.update({"w_gate": ((e, c.d_ff), ("embed", "mlp")),
                    "w_up": ((e, c.d_ff), ("embed", "mlp")),
                    "w_down": ((c.d_ff, e), ("mlp", "embed"))})
    else:
        axes = topk_moe_logical_axes(c)
        out.update({name: (shape, axes[name][1:]) for name, shape
                    in topk_moe_param_shapes(c).items()})
    return out


def _kind_stacks(c: TransformerConfig):
    """(name in the tree, kind, dense, layers) of each stack a
    configuration by kind of layer has, in the tree's order."""
    out = []
    if c.n_dense_layers:
        out.append(("dense_layers", c.layer_kind(0), True,
                    c.n_dense_layers))
    for kind, name in KIND_STACKS.items():
        n = sum(c.layer_kind(l) == kind
                for l in range(c.n_dense_layers, c.n_layers))
        if n:
            out.append((name, kind, False, n))
    return out


#: the convolution's taps are drawn at this and not at 0.02: their fan-in
#: is ``ssm_conv``, not d_model
_CONV_TAP_SD = 0.3


def _mamba_vector_init(c, key, n) -> Dict[str, jnp.ndarray]:
    """The float32 and bias leaves of ``n`` "mamba" layers, as Mamba-2
    starts them: ``A = -exp(A_log)`` uniform in -16..-1, ``dt_bias`` the
    inverse softplus of a step log-uniform in 1e-3..1e-1, ``D`` and the
    gated norm at one; the convolution's bias drawn (at zero a program
    that left it out could not be told apart)."""
    ka, kd, kb = jax.random.split(key, 3)
    step = jnp.exp(jax.random.uniform(
        kd, (n, c.ssm_heads), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        "A_log": jnp.log(jax.random.uniform(ka, (n, c.ssm_heads),
                                            jnp.float32, 1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "D": jnp.ones((n, c.ssm_heads), jnp.float32),
        "ssm_norm": jnp.ones((n, c.ssm_inner), jnp.float32),
        "conv_b": 0.02 * jax.random.normal(kb, (n, c.ssm_conv_width),
                                           jnp.float32)}


def _delta_vector_init(c, key, n) -> Dict[str, jnp.ndarray]:
    """The float32 leaves of ``n`` "delta" layers, as Gated DeltaNet
    starts them: ``A = exp(A_log)`` uniform in (0, 16), ``dt_bias`` the
    inverse softplus of a step log-uniform in 1e-3..1e-1, the output norm
    (a head wide, shared by the heads) at one."""
    ka, kd = jax.random.split(key)
    step = jnp.exp(jax.random.uniform(
        kd, (n, c.delta_heads), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        "A_log": jnp.log(jax.random.uniform(ka, (n, c.delta_heads),
                                            jnp.float32, 1e-4, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "delta_norm": jnp.ones((n, c.delta_value_dim), jnp.float32)}


def _kind_vector_shapes(c, kind: str, dense: bool = False
                        ) -> Dict[str, tuple]:
    """One layer's float32 vector leaves of a stack by kind: name ->
    (width, logical axis). The norm of each sublayer the kind has, ahead
    of it or (``output_norm``) on its output, and the whole-width
    QK-norm's weights of a layer with attention, all drawn at one; the
    router's bias of a layer of experts, drawn."""
    e = c.d_model
    mixer, mlp = _kind_halves(c, kind)
    names = (("attn",) if mixer else ()) + (("mlp",) if mlp else ())
    out = {(f"post_{n}_norm" if c.output_norm else f"{n}_norm"): (e, "embed")
           for n in names}
    if c.qk_norm_whole and kind in ("full", "window"):
        out.update({"q_norm": (c.kind_heads(kind) * c.head_dim, "heads"),
                    "k_norm": (c.kv_heads * c.head_dim, "kv")})
    if c.router_bias and mlp and not dense:
        out["router_bias"] = (c.n_experts, None)
    return out


def _init_kind_params(c, key, dtype, out_scale) -> Dict:
    """The tree of a stack by kind of layer: ``dense_layers`` (the
    leading ones), ``layers`` (the "full" layers behind them),
    ``window_layers``, ``mamba_layers``, ``delta_layers`` and
    ``ffn_layers``, each stacked, every matmul
    leaf drawn a layer at a time into ``dtype``. ``tie_embeddings``: no
    ``lm_head``."""
    keys = list(jax.random.split(jax.random.fold_in(key, 104), 5))
    # a fourth stack draws from a key of its own, and a fifth: the five
    # above stay what they were
    keys += [jax.random.fold_in(key, 105 + i) for i in range(3)]
    params = {
        "embed": _dense_init(keys[0], (c.vocab_size, c.d_model),
                             c.embed_init_std, dtype=dtype),
        "final_norm": {"scale": jnp.ones((c.d_model,), jnp.float32)},
    }
    if not c.tie_embeddings:
        params["lm_head"] = {"w": _dense_init(
            keys[1], (c.d_model, c.vocab_size), dtype=dtype)}
    scales = dict.fromkeys(("wo", "w_down", "we_down", "ws_down", "w_out"),
                           out_scale)
    scales["conv_w"] = _CONV_TAP_SD
    for j, (name, kind, dense, n) in enumerate(_kind_stacks(c)):
        stack: Dict[str, jnp.ndarray] = {}
        for i, (leaf, (shape, _)) in enumerate(
                sorted(_kind_layer_shapes(c, kind, dense).items())):
            stack[leaf] = _layered_init(
                jax.random.fold_in(keys[2 + j], i),
                scales.get(leaf, 0.02), n, shape, dtype)
        stack.update({norm: jnp.ones((n, width), jnp.float32)
                      for norm, (width, _)
                      in _kind_vector_shapes(c, kind, dense).items()})
        if "router_bias" in stack:
            stack["router_bias"] = _ROUTER_BIAS_SD * jax.random.normal(
                jax.random.fold_in(keys[2 + j], 1001),
                stack["router_bias"].shape, jnp.float32)
        if kind == "mamba":
            stack.update(_mamba_vector_init(
                c, jax.random.fold_in(keys[2 + j], 1000), n))
        if kind == "delta":
            stack.update(_delta_vector_init(
                c, jax.random.fold_in(keys[2 + j], 1000), n))
        params[name] = stack
    return params


def _kind_logical_axes(c) -> Dict:
    axes = {"embed": ("vocab", "embed"),
            "final_norm": {"scale": ("embed",)}}
    if not c.tie_embeddings:
        axes["lm_head"] = {"w": ("embed", "vocab")}
    for name, kind, dense, _ in _kind_stacks(c):
        axes[name] = {leaf: ("layers",) + ax for leaf, (_, ax)
                      in _kind_layer_shapes(c, kind, dense).items()}
        axes[name].update({norm: ("layers", axis) for norm, (_, axis)
                           in _kind_vector_shapes(c, kind, dense).items()})
        if kind == "delta":
            axes[name].update({
                "A_log": ("layers", None), "dt_bias": ("layers", None),
                "delta_norm": ("layers", None)})
        if kind == "mamba":
            axes[name].update({
                "A_log": ("layers", None), "dt_bias": ("layers", None),
                "D": ("layers", None), "ssm_norm": ("layers", "mlp"),
                "conv_b": ("layers", "mlp")})
    return axes


#: the router's bias is drawn (at zero a program that ignored it could
#: not be told from one that used it)
_ROUTER_BIAS_SD = 0.01


def _latent_vector_shapes(c: TransformerConfig, dense: bool
                          ) -> Dict[str, tuple]:
    """One layer's float32 vector leaves of the latent model: name ->
    (width, what it starts at: a number, or None where it is drawn)."""
    e = c.d_model
    out = {"attn_norm": (e, 1.0), "mlp_norm": (e, 1.0),
           "q_a_norm": (c.q_lora_rank, 1.0),
           "kv_a_norm": (c.kv_lora_rank, 1.0)}
    if c.sandwich_norm:
        out.update({"post_attn_norm": (e, 1.0), "post_mlp_norm": (e, 1.0)})
    if c.index_topk:
        out.update({"k_idx_scale": (c.index_dim, 1.0),
                    "k_idx_bias": (c.index_dim, 0.0)})
    if c.router_bias and not dense:
        out["router_bias"] = (c.n_experts, None)
    return out


def _latent_layer_shapes(c: TransformerConfig, dense: bool
                         ) -> Dict[str, tuple]:
    """One layer's matmul leaves of the latent model, ``dense`` (a
    leading SwiGLU layer of d_ff) or an expert layer: name -> (shape,
    logical axes without the layers axis)."""
    from ray_tpu.models.moe import (topk_moe_logical_axes,
                                    topk_moe_param_shapes)
    e, H = c.d_model, c.n_heads
    out = {
        "wq_a": ((e, c.q_lora_rank), ("embed", None)),
        "wq_b": ((c.q_lora_rank, H * c.head_dim), (None, "heads")),
        "wkv_a": ((e, c.kv_lora_rank + c.qk_rope_dim), ("embed", None)),
        "wkv_b": ((c.kv_lora_rank, H * (c.qk_nope_dim + c.v_head_dim)),
                  (None, "heads")),
        "wo": ((H * c.v_head_dim, e), ("heads", "embed")),
    }
    if c.head_gate:
        out["wg"] = ((e, H), ("embed", None))
    if c.index_topk:
        src = c.q_lora_rank if c.index_q_lora else e
        out.update({
            "wq_idx": ((src, c.index_heads * c.index_dim), (None, None)),
            "wk_idx": ((e, c.index_dim), ("embed", None)),
            "ww_idx": ((e, c.index_heads), ("embed", None))})
    for norm in ("attn", "mlp") if c.gated_norm_rank else ():
        out.update({
            f"{norm}_gn_down": ((e, c.gated_norm_rank), ("embed", None)),
            f"{norm}_gn_up": ((c.gated_norm_rank, e), (None, "embed"))})
    if dense:
        out.update({"w_gate": ((e, c.d_ff), ("embed", "mlp")),
                    "w_up": ((e, c.d_ff), ("embed", "mlp")),
                    "w_down": ((c.d_ff, e), ("mlp", "embed"))})
    else:
        axes = topk_moe_logical_axes(c)
        out.update({name: (shape, axes[name][1:]) for name, shape
                    in topk_moe_param_shapes(c).items()})
    return out


def _init_latent_params(c, key, dtype, out_scale) -> Dict:
    """The latent model's tree: ``dense_layers`` (the leading
    ``n_dense_layers``, stacked) and ``layers`` (the expert layers,
    stacked), every matmul leaf drawn a layer at a time into ``dtype``."""
    def stack(k, n, dense):
        out: Dict[str, jnp.ndarray] = {}
        for i, (name, (shape, _)) in enumerate(
                sorted(_latent_layer_shapes(c, dense).items())):
            out[name] = _layered_init(
                jax.random.fold_in(k, i),
                out_scale if name in ("wo", "w_down", "we_down", "ws_down")
                else 0.02, n, shape, dtype)
        for name, (width, start) in _latent_vector_shapes(c, dense).items():
            out[name] = jnp.full((n, width), start, jnp.float32) \
                if start is not None else _ROUTER_BIAS_SD * jax.random.normal(
                    jax.random.fold_in(k, 1000), (n, width), jnp.float32)
        return out
    keys = jax.random.split(jax.random.fold_in(key, 103), 4)
    n_moe = c.n_layers - c.n_dense_layers
    params = {
        "embed": _dense_init(keys[0], (c.vocab_size, c.d_model),
                             c.embed_init_std, dtype=dtype),
        "layers": stack(keys[1], n_moe, False),
        "final_norm": {"scale": jnp.ones((c.d_model,), jnp.float32)},
        "lm_head": {"w": _dense_init(keys[2], (c.d_model, c.vocab_size),
                                     dtype=dtype)},
    }
    if c.n_dense_layers:
        params["dense_layers"] = stack(keys[3], c.n_dense_layers, True)
    return params


def _latent_logical_axes(c) -> Dict:
    def stack(dense):
        out = {name: ("layers",) + axes for name, (_, axes)
               in _latent_layer_shapes(c, dense).items()}
        out.update({name: ("layers", "embed" if width == c.d_model
                           else None) for name, (width, _)
                    in _latent_vector_shapes(c, dense).items()})
        return out
    axes = {"embed": ("vocab", "embed"), "layers": stack(False),
            "final_norm": {"scale": ("embed",)},
            "lm_head": {"w": ("embed", "vocab")}}
    if c.n_dense_layers:
        axes["dense_layers"] = stack(True)
    return axes


def logical_axes(config: TransformerConfig) -> Dict:
    """Pytree (same treedef as params) of logical-axis tuples."""
    c = config
    tree = _tree_form(c)
    if tree == "latent":
        return _latent_logical_axes(c)
    if tree == "kinds":
        return _kind_logical_axes(c)
    llama = c.block_style == "llama"
    layers = {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv"),
        "wv": ("layers", "embed", "kv"),
        "wo": ("layers", "heads", "embed"),
    }
    if c.qk_norm:
        layers.update({"q_norm": ("layers", None),
                       "k_norm": ("layers", None)})
    if c.index_topk:
        layers.update({"wq_idx": ("layers", "embed", None),
                       "wk_idx": ("layers", "embed", None),
                       "ww_idx": ("layers", "embed", None),
                       "k_idx_scale": ("layers", None),
                       "k_idx_bias": ("layers", None)})
    if c.experts_per_token:
        from ray_tpu.models.moe import topk_moe_logical_axes
        layers.update(topk_moe_logical_axes(c))
    elif c.n_experts:
        from ray_tpu.models.moe import moe_logical_axes
        layers.update(moe_logical_axes())
    elif llama:
        layers.update({"w_gate": ("layers", "embed", "mlp"),
                       "w_up": ("layers", "embed", "mlp"),
                       "w_down": ("layers", "mlp", "embed")})
    else:
        layers.update({"fc_in": ("layers", "embed", "mlp"),
                       "fc_in_b": ("layers", "mlp"),
                       "fc_out": ("layers", "mlp", "embed"),
                       "fc_out_b": ("layers", "embed")})
    final, head = {"scale": ("embed",)}, {"w": ("embed", "vocab")}
    if llama:
        layers.update({"attn_norm": ("layers", "embed"),
                       "mlp_norm": ("layers", "embed")})
    else:
        layers.update({"ln_scale": ("layers", "embed"),
                       "ln_bias": ("layers", "embed")})
        final["bias"], head["b"] = ("embed",), ("vocab",)
    return {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": final,
        "lm_head": head,
    }


#: Leaves a use site reads in float32 (``ops/norms.py`` takes a norm's
#: scale and bias to f32) — these and everything under ``final_norm``.
#: Every other leaf is read through ``.astype(config.dtype)``.
_F32_LEAVES = frozenset(("attn_norm", "mlp_norm", "ln_scale", "ln_bias",
                         "q_norm", "k_norm", "k_idx_scale", "k_idx_bias",
                         "q_a_norm", "kv_a_norm", "post_attn_norm",
                         "post_mlp_norm", "router_bias", "A_log", "dt_bias",
                         "D", "ssm_norm", "delta_norm"))


def inference_params(config: TransformerConfig, params: Dict) -> Dict:
    """``params`` as a serving engine holds them: each leaf that every
    use site reads through ``.astype(config.dtype)`` (embedding,
    projections, MLP / MoE weights and biases, LM head) cast to
    ``config.dtype`` once, here, so that no step program casts it again
    on every call; norm leaves stay f32. The rounding is the one the
    use sites would do, so the programs compute the same bits.

    Where nothing needs casting (``config.dtype`` is f32, or the tree
    has been through here already) the tree handed in is returned, the
    same object. Otherwise leaf by leaf into a new tree; the one handed
    in is the caller's to drop."""
    dt = jnp.dtype(config.dtype)

    def wants_cast(path, leaf) -> bool:
        names = {getattr(k, "key", None) for k in path}
        return "final_norm" not in names and not names & _F32_LEAVES \
            and jnp.issubdtype(leaf.dtype, jnp.floating) \
            and leaf.dtype != dt

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    todo = [wants_cast(path, leaf) for path, leaf in flat]
    if not any(todo):
        return params
    # every leaf ends up a device array (a refresh off the wire is
    # numpy): the programs' arguments then look alike call to call
    return jax.tree_util.tree_unflatten(treedef, [
        jnp.asarray(leaf).astype(dt) if cast else jnp.asarray(leaf)
        for (_, leaf), cast in zip(flat, todo)])


# ---------------------------------------------------------------- remat
def remat_policy_fn(name: str):
    """Map a policy name to a ``jax.checkpoint`` saveable policy.

    Returns ``None`` for "full" (save nothing — recompute everything);
    "none" (don't checkpoint at all) is the caller's branch. "dots" saves
    matmul outputs WITHOUT batch dims (qkv/out projections, MLP matmuls —
    weight-stationary dots worth keeping) plus the named attention output,
    so neither the flash kernel nor the O(s²) reference attention is
    re-run in the backward; the quadratic score matrices (dots WITH batch
    dims) are still recomputed. "dots_all" additionally saves those.
    "offload" parks block inputs in pinned host memory and saves the
    attention output on device.
    """
    cp = jax.checkpoint_policies
    save_attn = cp.save_only_these_names("attn_out")
    if name == "full":
        return None
    if name == "dots":
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, save_attn)
    if name == "dots_all":
        return cp.save_from_both_policies(cp.dots_saveable, save_attn)
    if name == "offload":
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=["attn_out"],
            names_which_can_be_offloaded=["block_in"],
            offload_src="device", offload_dst="pinned_host")
    raise ValueError(
        f"unknown remat policy {name!r}; have {REMAT_POLICIES}")


# --------------------------------------------------------------- forward
def _attention(c: TransformerConfig, q, k, v, mesh, rules, window=0):
    """Dispatch attention: ring over the sp axis when it's nontrivial,
    otherwise the flash/reference dispatcher (ops layer); ``window``: a
    sliding-window layer's keys behind a position (0: all of them).

    Under a mesh the dispatcher runs inside ``shard_map`` over the batch
    and heads axes: attention is independent per (sequence, head), and
    XLA cannot partition a Pallas custom call — left to GSPMD it would
    all-gather the batch and run the kernel replicated on every chip."""
    from jax.sharding import PartitionSpec as P
    sp_axis = rules.get("sequence") if rules else None
    if mesh is not None and sp_axis is not None and sp_axis in mesh.shape \
            and mesh.shape[sp_axis] > 1:
        if window:
            raise NotImplementedError(
                "sliding_window under a split sequence axis (sp > 1): "
                "ring attention knows the causal structure alone")
        batch_axes = rules.get("batch")
        spec = P(batch_axes, sp_axis, None, None)
        fn = jax.shard_map(
            functools.partial(ring_attention, axis_name=sp_axis,
                              causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    fn = functools.partial(
        multihead_attention, causal=True, impl=c.attn_impl,
        block_q=c.attn_block_q, block_k=c.attn_block_k,
        **({"window": window} if window else {}))
    if mesh is not None and rules is not None and mesh.size > 1:
        spec = P(rules.get("batch"), None, rules.get("heads"), None)
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return fn(q, k, v)


@jax.named_scope("attn")
def _attn_sublayer(c, kind: _LayerKind, h, lp, sin, cos, mesh, rules):
    """The training attention sublayer of a layer of ``kind``: qkv
    projection → rotary (the kind's own: layout, width, table) → GQA
    repeat → attention (behind the kind's window, where it has one) →
    output proj. Shared by both block styles and by every kind of layer
    that trains; in a stack by kind of layer under the kind's scope
    (``layer/attn/window``, ``layer/attn/full``), as the cache path."""
    with jax.named_scope(kind.scope) if kind.scope \
            else contextlib.nullcontext():
        e = h.shape[-1]
        dt = c.dtype

        def proj(w, n):
            return jnp.einsum("bse,ehd->bshd", h.astype(dt),
                              w.reshape(e, n, -1).astype(dt))
        q = proj(lp["wq"], kind.heads)
        k = proj(lp["wk"], c.kv_heads)
        v = proj(lp["wv"], c.kv_heads)
        if kind.rotary.dim:
            q = apply_rotary(q, sin, cos, layout=kind.rotary.layout)
            k = apply_rotary(k, sin, cos, layout=kind.rotary.layout)
        if c.kv_heads != kind.heads:
            rep = kind.heads // c.kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        att = _attention(c, q, k, v, mesh, rules, kind.window)
        att = checkpoint_name(att, "attn_out")
        return jnp.einsum(
            "bshd,hde->bse", att,
            lp["wo"].reshape(kind.heads, c.head_dim, e).astype(dt))


def _swiglu(c, h, lp):
    """The dense gated MLP of the 'llama' block on normed input h."""
    dt = c.dtype
    gate = jax.nn.silu(jnp.dot(h, lp["w_gate"].astype(dt)))
    up = jnp.dot(h, lp["w_up"].astype(dt))
    return jnp.dot(gate * up, lp["w_down"].astype(dt))


@jax.named_scope("mlp")
def _mlp_sublayer(c, h, lp, layer=None, stats=False, mesh=None):
    """The MLP the layer's leaves ``lp`` hold, on normed input h: a dense
    SwiGLU (``w_gate``: the 'llama' block, and a leading dense layer
    ahead of experts), experts, or the 'gptj' block's biased GELU MLP;
    returns (out, moe_aux). ``layer``: ``lp``'s expert leaves are whole
    stacks and this is the layer's index in them (``moe.topk_moe_mlp``).
    ``stats`` (training): the dropless experts' ``moe_aux`` is the layer's
    routing counters (``moe.route_stats``), no loss term; ``mesh`` theirs."""
    dt = c.dtype
    if "w_gate" in lp:
        return _swiglu(c, h, lp), 0.0
    if c.experts_per_token:
        from ray_tpu.models.moe import topk_moe_mlp
        if stats:
            return topk_moe_mlp(c, lp, h, layer, stats=True, mesh=mesh)
        return topk_moe_mlp(c, lp, h, layer), 0.0
    if c.n_experts:
        from ray_tpu.models.moe import moe_mlp
        return moe_mlp(c, lp, h.astype(dt))
    mlp = jnp.dot(h.astype(dt), lp["fc_in"].astype(dt)) \
        + lp["fc_in_b"].astype(dt)
    mlp = jax.nn.gelu(mlp)
    return jnp.dot(mlp, lp["fc_out"].astype(dt)) \
        + lp["fc_out_b"].astype(dt), 0.0


def _block(c, kind: _LayerKind, x, lp, attend, mlp):
    """THE transformer block, for training and for the cache path, of
    every kind of layer: norm -> ``attend(h) -> (att, cache)`` -> residual
    -> norm -> ``mlp(h) -> (out, moe_aux)`` -> residual, or with
    ``kind.parallel`` both sublayers off the one norm and one residual.
    ``kind`` says which norms (every RMS norm at ``norm_eps``; LayerNorm
    keeps its own 1e-5, as the final norm does; "none": a sublayer reads
    the stream as it is) and whether one follows each sublayer, ahead of
    the residual; ``lp`` holds the layer's norm leaves. A kind of ONE
    sublayer (``kind.mixer`` None, or ``kind.mlp`` False) skips the
    absent half, its norm with it; a layer with no mixer hands back no
    cache (None). Returns (x, cache, moe_aux)."""
    eps = c.norm_eps

    def pre(x, name):
        if kind.norm == "none":
            return x
        if kind.norm == "layer":
            return layer_norm(x, lp["ln_scale"], lp["ln_bias"])
        if kind.norm == "gated":
            return gated_rms_norm(x, lp[f"{name}_norm"],
                                  lp[f"{name}_gn_down"],
                                  lp[f"{name}_gn_up"], eps=eps)
        return rms_norm(x, lp[f"{name}_norm"], eps=eps)

    def post(y, name):
        if not kind.post_norm:
            return y
        with jax.named_scope("post_norm"):
            return rms_norm(y, lp[name], eps=eps)
    # a name for the remat policies ("offload"); no op of the program
    x = checkpoint_name(x, "block_in")
    def scaled(y):               # ``residual_scale`` ahead of the residual
        return y if c.residual_scale == 1.0 else y * c.residual_scale
    cache, aux = None, 0.0
    if kind.mixer is not None:
        h = pre(x, "attn")
        att, cache = attend(h)
        if kind.parallel:
            out, aux = mlp(h)
            return x + scaled(att + out).astype(x.dtype), cache, aux
        x = x + scaled(post(att, "post_attn_norm")).astype(x.dtype)
    if kind.mlp:
        out, aux = mlp(pre(x, "mlp").astype(c.dtype))
        x = x + scaled(post(out, "post_mlp_norm")).astype(x.dtype)
    return x, cache, aux


#: the served keys whose forms ``run_layers`` gives a training mixer and
#: a differentiated feed-forward for: dropless top-k experts (softmax
#: routed, a held share of them or all), and a stack of "window" and
#: "full" attention layers, plain RoPE or YaRN by kind
TRAINED_KEYS = ("experts_per_token", "layer_pattern", "sliding_window",
                "rope_yarn")


def untrained_keys(c: TransformerConfig) -> Tuple[str, ...]:
    """The keys ``c`` sets whose form only the cache path implements:
    every served key but ``TRAINED_KEYS``, ``layer_pattern`` where it
    names a kind other than "window" and "full", and the forms of the
    dropless experts that no test holds a gradient of (a shared expert,
    a sigmoid router, a scale on the weights)."""
    out = [k for k in c.served_keys if k not in TRAINED_KEYS]
    if set(c.layer_pattern) - {"window", "full"}:
        out.append("layer_pattern")
    fields = c.__dataclass_fields__
    out += [k for k in ("shared_expert_width", "router_score",
                        "routed_scale")
            if getattr(c, k) != fields[k].default]
    return tuple(out)


def refuse_training(c: TransformerConfig) -> None:
    """Raise, naming the keys at fault, for a configuration with a form
    that only :func:`_forward_with_cache` gives :func:`_block` a mixer
    for (``run_layers``, ``make_train_step`` and ``ParallelPlan.build``
    ask)."""
    at_fault = untrained_keys(c)
    if at_fault:
        raise NotImplementedError(
            f"{', '.join(at_fault)}: set here, and served through "
            f"prefill / decode_step only. Of the served forms training "
            f"takes {', '.join(TRAINED_KEYS)} (softmax-routed dropless "
            f"experts, all or a held share; a stack of 'window' and "
            f"'full' attention layers, plain RoPE or YaRN by kind), "
            f"beside Switch top-1 experts and one kind of dense layer")


def layer_stacks(config: TransformerConfig, params: Dict) -> Dict:
    """What :func:`run_layers` scans, out of a parameter tree: the one
    stack of a plain tree (``params["layers"]``), or of a tree by kind of
    layer its stacks by name (``layers``, ``window_layers``)."""
    if _tree_form(config) == "plain":
        return params["layers"]
    return {run.stack: params[run.stack]
            for run in _layer_plan(config).runs}


def run_layers(config: TransformerConfig, layer_params: Dict,
               x: jnp.ndarray, mesh=None, rules=None):
    """Scan the transformer blocks in ``layer_params`` over hidden states
    ``x``: (b, s, e) -> ((b, s, e), moe_aux). ``layer_params``
    (:func:`layer_stacks`): one stack, its leaves ``[n, ...]``, or for a
    stack by kind of layer the stacks by name. The trunk shared by
    :func:`hidden_states` and the pipeline-stage forward (a stage's trunk
    is a contiguous slice of the stacked layer leaves — same scan, fewer
    layers).

    The layer plan's runs are walked in order, one scan a run of
    consecutive layers of one kind; each kind has its own rotary (a
    table, or with YaRN rows at the positions), its window and its stack
    of leaves, and is compiled once a run. A run shorter than its stack
    scans a slice of it. ``moe_aux``: the Switch experts' summed loss
    term, or for the dropless experts their routing counters
    (``moe.route_stats``: assignments summed, the two ratios a mean over
    the expert layers), or 0.0."""
    c = config
    refuse_training(c)
    plan = _layer_plan(c)
    one_stack = plan.tree == "plain"
    stats = bool(c.experts_per_token)
    positions = None if one_stack else \
        jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    policy = c.resolved_remat_policy
    auxes = []
    for run in plan.runs:
        kind = run.kind
        sin, cos = _rotary(kind.rotary, positions, x.shape[1])
        # the one stack is scanned as handed in (a pipeline stage hands
        # in its slice); of a stack by kind, the run's layers
        stack = layer_params
        if not one_stack:
            stack = layer_params[run.stack]
            if any((run.at, run.n) != (0, v.shape[0])
                   for v in jax.tree.leaves(stack)):
                stack = jax.tree.map(
                    lambda v, run=run: v[run.at:run.at + run.n], stack)

        def body(x, lp, kind=kind, sin=sin, cos=cos):
            out, _, aux = _block(
                c, kind, x, lp,
                lambda h: (_attn_sublayer(c, kind, h, lp, sin, cos,
                                          mesh, rules), None),
                lambda h: _mlp_sublayer(c, h, lp, stats=stats, mesh=mesh))
            return out, aux
        if policy != "none":
            body = jax.checkpoint(body, policy=remat_policy_fn(policy))

        def scan_fn(carry, lp, body=body):
            with jax.named_scope("layer"):
                out, aux = body(carry, lp)
            if mesh is not None and rules is not None:
                from ray_tpu.parallel.sharding import constrain
                out = constrain(out, mesh, rules,
                                ("batch", "sequence", None))
            return out, aux

        x, layer_aux = jax.lax.scan(scan_fn, x, stack)
        auxes.append(layer_aux)
    if stats:
        from ray_tpu.models.moe import sum_route_stats
        return x, sum_route_stats(auxes)
    return x, (sum(jnp.sum(a) for a in auxes) if c.n_experts else 0.0)


@jax.named_scope("final_norm")
def _final_norm(config: TransformerConfig, params: Dict, x: jnp.ndarray):
    fn = params["final_norm"]
    if "bias" in fn:
        return layer_norm(x, fn["scale"], fn["bias"])
    return rms_norm(x, fn["scale"], eps=config.norm_eps)


def hidden_states(config: TransformerConfig, params: Dict,
                  input_ids: jnp.ndarray, mesh=None, rules=None):
    """Embed -> blocks -> final norm: (b, s) int32 -> ((b, s, e), moe_aux).

    The shared trunk under both :func:`apply` (which adds the LM-head
    projection) and :func:`lm_loss` (which fuses the projection into the
    chunked loss so full logits never materialize).
    """
    c = config
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], input_ids, axis=0).astype(c.dtype)
    x, moe_aux = run_layers(c, layer_stacks(c, params), x, mesh=mesh,
                            rules=rules)
    return _final_norm(c, params, x), moe_aux


@jax.named_scope("lm_head")
def _lm_head(c: TransformerConfig, params: Dict, x: jnp.ndarray):
    if c.tie_embeddings:         # the embedding's rows are the head's columns
        logits = jnp.einsum("...e,ve->...v", x.astype(c.dtype),
                            params["embed"].astype(c.dtype))
    else:
        logits = jnp.dot(x.astype(c.dtype),
                         params["lm_head"]["w"].astype(c.dtype))
        if "b" in params["lm_head"]:
            logits = logits + params["lm_head"]["b"].astype(c.dtype)
    return logits if c.logit_scale == 1.0 else logits * c.logit_scale


def apply(config: TransformerConfig, params: Dict, input_ids: jnp.ndarray,
          mesh=None, rules=None, return_moe_aux: bool = False):
    """Forward pass: (batch, seq) int32 -> (batch, seq, vocab) logits.

    Always returns logits; with ``return_moe_aux=True`` returns
    ``(logits, moe_aux_loss)`` (0.0 for dense configs). ``mesh``/``rules``
    enable in-graph sharding constraints and ring attention; both
    optional (single-device path needs neither).
    """
    c = config
    x, moe_aux = hidden_states(c, params, input_ids, mesh=mesh, rules=rules)
    logits = _lm_head(c, params, x)
    if return_moe_aux:
        return logits, moe_aux
    return logits


def _mesh_axes(mesh, rules, logical: str) -> Tuple[str, ...]:
    """The mesh axes of size > 1 that ``rules`` split ``logical`` over."""
    target = rules.get(logical) if mesh is not None and rules else None
    target = (target,) if isinstance(target, str) else tuple(target or ())
    return tuple(a for a in target if mesh.shape.get(a, 1) > 1)


def head_loss_form(config: TransformerConfig, mesh, rules
                   ) -> Tuple[str, Optional[PerChip]]:
    """The form :func:`lm_loss` gives the LM-head loss, read off the config,
    the mesh and the rules alone (no option selects it):

    - ``"logits"``: ``ce_chunk_size == 0``, or the sequence axis is split
      (``sp > 1``: chunking would regather every chunk) — materialized
      logits, partitioned by GSPMD;
    - ``"per_chip"``: fused, and the batch is split over more than one chip
      while the vocabulary is not (a split one wants a cross-chip
      logsumexp) — the scan runs per chip (:class:`PerChip`);
    - ``"gspmd"``: fused, whole arrays, any partitioning GSPMD's: no mesh,
      one device, ``tp > 1``.
    """
    if not config.ce_chunk_size or _mesh_axes(mesh, rules, "sequence"):
        return "logits", None
    batch = _mesh_axes(mesh, rules, "batch")
    if not batch or _mesh_axes(mesh, rules, "vocab"):
        return "gspmd", None
    rows = tuple(a for a in _mesh_axes(mesh, rules, "embed") if a in batch)
    return "per_chip", PerChip(mesh, batch, rows)


def lm_loss(config: TransformerConfig, params: Dict, batch: Dict,
            mesh=None, rules=None) -> Tuple[jnp.ndarray, Dict]:
    """Next-token LM loss. batch: {"input_ids": (b,s) int32,
    "loss_mask": optional (b,s)}. Returns (loss, aux).

    With ``config.ce_chunk_size > 0`` (default) the LM-head projection is
    fused into the chunked cross entropy (``ops.fused_lm_head_loss``) —
    the full float32 logits tensor is never resident. ``ce_chunk_size=0``
    restores the materialized-logits reference path.

    Under a mesh the fused loss takes one of two forms, decided by
    :func:`head_loss_form` from ``mesh`` and ``rules`` (no option selects
    it). Where the data axes split the batch and neither the vocabulary
    nor the sequence is split (``fsdp``, ``dp``, both), it runs per chip:
    the head is gathered once a step, each chip sums its own rows' dW
    through the scan, and the sums over chips (token count, loss, dW in
    float32, db) happen once, after the scan. Otherwise (no mesh, one
    device, ``tp > 1``) the whole-array call is left to GSPMD, which
    reduces every chunk's dW onto the head's sharding; with ``sp > 1`` the
    logits are materialized.
    """
    c = config
    ids = batch["input_ids"]
    labels = ids[:, 1:]
    mask = batch.get("loss_mask")
    mask = mask[:, 1:] if mask is not None else None
    form, per_chip = head_loss_form(c, mesh, rules)
    if form != "logits":
        x, moe_aux = hidden_states(c, params, ids, mesh=mesh, rules=rules)
        head = params["lm_head"]
        with jax.named_scope("lm_head_loss"):
            loss, n = fused_lm_head_loss(
                x.astype(c.dtype)[:, :-1], head["w"], labels,
                head_bias=head.get("b"), mask=mask,
                chunk_size=c.ce_chunk_size, per_chip=per_chip)
    else:
        logits, moe_aux = apply(c, params, ids, mesh=mesh, rules=rules,
                                return_moe_aux=True)
        with jax.named_scope("lm_head_loss"):
            loss, n = cross_entropy_loss(logits[:, :-1], labels,
                                         mask=mask)
    aux = {"n_tokens": n}
    if c.experts_per_token:
        # counters, no term of the loss: the dropless experts' published
        # configurations give no coefficient for one
        aux["moe"] = moe_aux
    elif c.n_experts:
        loss = loss + c.moe_aux_weight * moe_aux
        aux["moe_aux"] = moe_aux
    return loss, aux


# --------------------------------------------------- pipeline stages
# MPMD pipeline parallelism (parallel/mpmd_pipeline.py) splits the model
# into S separately-compiled stage programs: stage 0 owns the embedding
# plus the first trunk slice, middle stages own trunk slices, the last
# stage owns its slice plus final norm and LM head (fused into the loss,
# like lm_loss). Because per-layer weights are STACKED on the leading
# ``layers`` axis, a stage's parameters are literally ``leaf[lo:hi]`` —
# no re-initialization, and a stage slice of ``init_params(key)`` is
# bit-identical to the single-program model's weights.

def stage_layer_ranges(n_layers: int, n_stages: int):
    """Near-even contiguous ``[lo, hi)`` layer ranges, earlier stages
    taking the remainder (they also carry the embedding)."""
    if not 1 <= n_stages <= n_layers:
        raise ValueError(
            f"n_stages must be in [1, {n_layers}], got {n_stages}")
    base, rem = divmod(n_layers, n_stages)
    ranges, lo = [], 0
    for s in range(n_stages):
        hi = lo + base + (1 if s < rem else 0)
        ranges.append((lo, hi))
        lo = hi
    return ranges


def stage_slice_params(config: TransformerConfig, params: Dict,
                       stage: int, n_stages: int) -> Dict:
    """Slice a full parameter pytree down to one pipeline stage's
    weights: trunk-range of the stacked layer leaves, plus the
    embedding (stage 0) / final norm + LM head (last stage)."""
    if config.n_experts:
        raise NotImplementedError(
            "pipeline stage splitting does not support MoE configs "
            "(the aux loss would need cross-stage wiring)")
    if _tree_form(config) != "plain":
        raise NotImplementedError(
            "pipeline stage splitting takes one stack of layers, not "
            "stacks by kind of layer (layer_pattern, sliding_window, "
            "rope_yarn)")
    lo, hi = stage_layer_ranges(config.n_layers, n_stages)[stage]
    out: Dict = {"layers": jax.tree.map(lambda a: a[lo:hi],
                                        params["layers"])}
    if stage == 0:
        out["embed"] = params["embed"]
    if stage == n_stages - 1:
        out["final_norm"] = params["final_norm"]
        out["lm_head"] = params["lm_head"]
    return out


def merge_stage_params(config: TransformerConfig,
                       chunk_params: Dict[int, Dict]) -> Dict:
    """Inverse of :func:`stage_slice_params`: reassemble the canonical
    single-program parameter pytree from per-chunk slices keyed by
    global chunk index ``0..K-1`` (``K = len(chunk_params)``). Works on
    any param-SHAPED tree (Adam moments included), so the pipeline
    checkpoint merge reuses it for optimizer state."""
    if not chunk_params:
        raise ValueError("missing chunks: got an empty chunk set")
    K = max(chunk_params) + 1
    missing = [c for c in range(K) if c not in chunk_params]
    if missing or "final_norm" not in chunk_params[K - 1]:
        raise ValueError(
            f"missing chunks: have {sorted(chunk_params)}, need a "
            f"contiguous 0..K-1 set ending in the final-norm/LM-head "
            f"chunk")
    layer_trees = [chunk_params[c]["layers"] for c in range(K)]
    out: Dict = {"layers": jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0), *layer_trees)}
    out["embed"] = chunk_params[0]["embed"]
    out["final_norm"] = chunk_params[K - 1]["final_norm"]
    out["lm_head"] = chunk_params[K - 1]["lm_head"]
    return out


def stage_forward(config: TransformerConfig, stage: int, n_stages: int,
                  stage_params: Dict, inp: jnp.ndarray,
                  mesh=None, rules=None) -> jnp.ndarray:
    """One stage's forward: stage 0 takes (b, s) int32 token ids and
    embeds them; later stages take the upstream (b, s, e) activation.
    The last stage applies the final norm, so its output feeds
    :func:`stage_loss` (or an LM-head projection) directly."""
    c = config
    if stage == 0:
        x = jnp.take(stage_params["embed"], inp, axis=0).astype(c.dtype)
    else:
        x = inp.astype(c.dtype)
    x, _ = run_layers(c, stage_params["layers"], x, mesh=mesh, rules=rules)
    if stage == n_stages - 1:
        x = _final_norm(c, stage_params, x)
    return x


def stage_loss(config: TransformerConfig, stage_params: Dict,
               h: jnp.ndarray, input_ids: jnp.ndarray,
               loss_mask: Optional[jnp.ndarray] = None):
    """Last-stage LM loss from final-norm'd hidden states ``h``: the
    same fused-projection tail as :func:`lm_loss` (ce_chunk_size > 0)
    or the materialized-logits reference path. Returns (loss, n)."""
    c = config
    labels = input_ids[:, 1:]
    mask = loss_mask[:, 1:] if loss_mask is not None else None
    head = stage_params["lm_head"]
    if c.ce_chunk_size:
        return fused_lm_head_loss(
            h.astype(c.dtype)[:, :-1], head["w"], labels,
            head_bias=head.get("b"), mask=mask,
            chunk_size=c.ce_chunk_size)
    logits = jnp.dot(h.astype(c.dtype), head["w"].astype(c.dtype))
    if "b" in head:
        logits = logits + head["b"].astype(c.dtype)
    return cross_entropy_loss(logits[:, :-1], labels, mask=mask)


# ------------------------------------------------------- inference (KV)
# The serving decode path: a paged KV cache (one pool
# [n_layers, num_blocks, kv_heads, block_size, head_dim] for k and one
# for v, with an indexer a third for its keys; with latent attention ONE
# pool of rows that are every head's key and value, and with an indexer
# its keys beside them; block table per sequence) written by chunked prefill and batched single-token decode
# steps. Both entry points are shape-stable
# — jit them once at the engine's fixed (batch, chunk, table) shapes
# and admission never recompiles — and neither slices, stacks or copies
# the pool: the layer scan carries it whole, each layer scatters its new
# rows into it and attends it by (layer, block). Jitted with the cache
# donated, a step updates the caller's buffer in place.

def init_kv_cache(config: TransformerConfig, num_blocks: int,
                  block_size: int, window_blocks: Optional[int] = None,
                  state_slots: Optional[int] = None,
                  state_snapshots: int = 0) -> Dict[str, jnp.ndarray]:
    """Allocate the paged KV cache: ``{"k", "v"}`` of shape
    ``[n_layers, num_blocks, kv_heads, block_size, head_dim]`` in the
    compute dtype — ``kv_heads`` ahead of ``block_size`` so one head's
    page is a contiguous ``(block_size, head_dim)`` tile, which is what
    the Pallas kernel DMAs. With an indexer (``index_topk``) a page
    carries a third kind of state under the same block id: ``"ki"``,
    ``[n_layers, num_blocks, 1, block_size, index_dim]``, the one shared
    head of indexer keys. Every pool has blocks on axis 1: what copies,
    ships or adopts a page moves that index of every pool.
    :func:`prefill` and :func:`decode_step` pass each pool WHOLE, with a
    layer index, to the write and to the attention; nothing takes a
    layer's slice of it. Zero-filled; a zero key scores 0 pre-softmax,
    so reserved/trash blocks are numerically harmless.

    With latent attention (``kv_lora_rank``) the cache is
    ``{"latent"}``, ``[n_layers, num_blocks, 1, block_size, row]``,
    alone or, with an indexer, beside its keys ``"ki"`` under the same
    block ids: what the engine copies, ships, counts and sizes
    (``kv_bytes_per_token``) it takes from the pools returned here.

    A stack with "window" layers (``layer_pattern``) has TWO KINDS OF
    PAGE: ``k`` / ``v`` hold the "full" layers alone, ``[full layers,
    num_blocks, ...]``, and ``k_window`` / ``v_window`` the window
    layers, ``[window layers, window_blocks, ...]``, with block ids, a
    table and a lifetime of their own (a window layer never reads
    behind ``sliding_window``, so its pages are given back as the
    sequence passes them). ``window_blocks=None``: as many as
    ``num_blocks``, for a caller that reads both kinds through one
    table.

    A stack with "mamba" layers keeps, beside the pools, what is NOT
    paged (``STATE_ARRAYS``; :func:`cache_pools` leaves them out):
    ``ssm`` ``[mamba layers, state_slots, ssm_state, ssm_heads *
    ssm_head_dim]`` float32, a sequence's recurrent state (the state's
    width ahead of the channels: ``ops/ssm.py``), and ``conv``
    ``[mamba layers, state_slots, ssm_conv - 1, ssm_conv_width]`` in the
    compute dtype, the convolution's last inputs: a row a SLOT, the same
    size however long the sequence. ``state_slots=None`` is ONE slot:
    enough for a caller that runs one sequence (a batch row b uses slot
    b unless ``state_rows`` says otherwise); an engine asks for its
    ``decode_slots``. A stack with "delta" layers keeps ``delta``
    ``[delta layers, state_slots, delta_key_dim, delta_heads *
    delta_value_dim]`` float32 and ``delta_conv`` ``[delta layers,
    state_slots, delta_conv - 1, delta_conv_width]`` the same way, and
    with ``state_snapshots`` > 0 a second array of each,
    ``delta_snap`` / ``delta_conv_snap`` ``[delta layers, 1 +
    state_snapshots, ...]``: the state as of a boundary inside a prompt,
    which :func:`prefill` writes where ``snap_rows`` says (row 0 is the
    trash row, as page 0 is) and a prefix hit copies into the slot.

    Which pools, how wide and of how many layers is the layer
    description's to say (``_layer_plan``: each kind's ``pools``, its
    ``table``, its ``state``, and the layers the runs count)."""
    plan = _layer_plan(config)
    cache = {}
    for kind in plan.kinds:
        layers = sum(run.n for run in plan.runs if run.kind is kind)
        blocks = num_blocks if kind.table == "main" \
            or window_blocks is None else window_blocks
        for pool in kind.pools:
            cache[pool.name] = jnp.zeros(
                (layers, blocks, pool.heads, block_size, pool.width),
                config.dtype)
        for state in kind.state:
            cache[state.name] = jnp.zeros(
                (layers, state_slots or 1) + state.shape,
                state.dtype or config.dtype)
            if state_snapshots and state.snap:
                cache[state.snap] = jnp.zeros(
                    (layers, 1 + state_snapshots) + state.shape,
                    state.dtype or config.dtype)
    return cache


@jax.named_scope("indexer")
def _indexer(c, h, lp, isin, icos, positions, q_from=None):
    """The indexer's side of a layer for the new tokens: queries ``[B,
    C, Hi, Di]`` and the one key head ``[B, C, 1, Di]`` (LayerNorm, then
    rotary over as much of ``index_dim`` as the tables cover, both), and
    the head weights ``[B, C, Hi]`` in float32, scaled by ``Hi^-1/2 *
    Di^-1/2``. The queries are projected from ``q_from`` (the latent
    model's normed query bottleneck) where given, else from ``h``."""
    dt = c.dtype
    hd = h.astype(dt)
    src = hd if q_from is None else q_from.astype(dt)
    e = src.shape[-1]
    qi = jnp.einsum("bse,ehd->bshd", src, lp["wq_idx"].reshape(
        e, c.index_heads, c.index_dim).astype(dt))
    ki = layer_norm(jnp.dot(hd, lp["wk_idx"].astype(dt)),
                    lp["k_idx_scale"], lp["k_idx_bias"], eps=1e-6)
    wi = jnp.dot(hd, lp["ww_idx"].astype(dt),
                 preferred_element_type=jnp.float32) \
        * (c.index_heads ** -0.5 * c.index_dim ** -0.5)
    qi = apply_rotary(qi, isin, icos, positions=positions, layout="neox")
    ki = apply_rotary(ki[:, :, None], isin, icos, positions=positions,
                      layout="neox")
    return qi, ki, wi


def _write_rows(cache, new, layer, block_tables, positions, write_mask):
    """Scatter the new tokens' rows ``new[name] [B, C, heads, D]`` into
    layer ``layer``'s pages of the whole pools, in place."""
    pool = next(iter(cache.values()))
    n_blocks, bs = pool.shape[1], pool.shape[3]
    with jax.named_scope("kv_write"):
        bid = jnp.take_along_axis(block_tables, positions // bs, axis=1)
        # invalid (padded) chunk positions scatter out of bounds ->
        # dropped
        bid = jnp.where(write_mask, bid, n_blocks)[..., None]
        slot = (positions % bs)[..., None]
        # [L, N, KVH, bs, D] indexed (layer, bid, head, slot): the
        # index arrays broadcast to the rows' own (B, C, KVH) and each
        # names one D-long row, the pool's minor-most dim. A window over
        # (KVH, D) — at[layer, bid, :, slot] — writes the same rows but
        # is not contiguous in this layout: the TPU compiler then keeps
        # the carried pool with block_size ahead of kv_heads for the
        # scatter and copies ALL of it into the kernel's layout in every
        # layer (tests/ops/test_tpu_lowering.py compiles and looks)
        return {
            name: pool.at[
                layer, bid, jnp.arange(pool.shape[2], dtype=jnp.int32),
                slot].set(new[name].astype(pool.dtype), mode="drop")
            for name, pool in cache.items()}


def _rotary(rot: _Rotary, positions, table_len: int):
    """(sin, cos) of a kind's rotary, in the form its program has: rows
    at ``positions`` (``rot.at_positions``), or a table of ``table_len``
    rows for :func:`apply_rotary` to gather at them; (None, None) where
    the kind rotates nothing."""
    if not rot.dim:
        return None, None
    if not rot.at_positions:
        return rotary_table(table_len, rot.dim, rot.base)
    if rot.yarn:
        factor, original, fast, slow, scale = rot.yarn
        return rotary_at(positions, yarn_inv_freq(
            rot.dim, rot.base, factor, int(original), fast, slow),
            float(scale))
    return rotary_at(positions, (1.0 / rot.base ** (
        np.arange(0, rot.dim, 2, dtype=np.float64) / rot.dim)
    ).astype(np.float32))


def _head_gate(c, hd, lp, att):
    """``head_gate``: each head's output ``att [B, C, H, D]`` times a
    sigmoid of the sublayer's input ``hd`` through ``wg [e, H]``."""
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(jnp.dot(
            hd, lp["wg"].astype(c.dtype),
            preferred_element_type=jnp.float32))
        return att * gate[..., None].astype(att.dtype)


@jax.named_scope("attn")
def _paged_attn_sublayer(c, kind: _LayerKind, h, lp, rot, layer, cache,
                         tables, first, positions, write_mask, lens):
    """The attention sublayer over per-head K/V of a layer of ``kind``,
    cache layer ``layer`` (an int32 scalar, traced by the layer scan) of
    the kind's pools: project qkv for the new tokens (the kind's head
    count), RMSNorm q and k (``kind.qk_norm``: a weight a head;
    ``kind.qk_norm_whole``: one over the whole projected width), rotate
    at their absolute
    positions, scatter k/v into that layer's pages of the WHOLE 5-D pools
    through the kind's table, then attend against the (now-updated) pages
    by ``(layer, block)``, a window layer's keys masked behind its window;
    a sigmoid gate a head (``head_gate``) on the heads' outputs ahead of
    ``wo``. The pools (``cache``, as :func:`init_kv_cache` made it) come
    in and go out whole — the scan's carry — so the write is one in-place
    scatter of the new rows, not a copy of the layer. ``lens`` is the
    per-sequence live token count after this call's writes — the Pallas
    kernel skips whole cache blocks past it. With an indexer the new
    tokens' ``kI`` goes into the same pages, and attention reads the keys
    the indexer selects. ``tables`` / ``first``: the kind's block table
    and the absolute position of its entry 0 (None: position 0; a window
    layer's short table starts behind the window); ``rot``: the (sin,
    cos) pairs, the head's and the indexer's. Returns (attn_out, cache)."""
    with jax.named_scope(kind.scope) if kind.scope \
            else contextlib.nullcontext():
        e = h.shape[-1]
        dt = c.dtype
        hd = h.astype(dt)
        (sin, cos), (isin, icos) = rot

        def proj(w, n):
            return jnp.einsum("bse,ehd->bshd", hd,
                              w.reshape(e, n, -1).astype(dt))
        q = proj(lp["wq"], kind.heads)
        k = proj(lp["wk"], c.kv_heads)
        v = proj(lp["wv"], c.kv_heads)
        if kind.qk_norm:
            q = rms_norm(q, lp["q_norm"])
            k = rms_norm(k, lp["k_norm"])
        elif kind.qk_norm_whole:
            # one weight a channel over ALL of q's and of k's, before
            # the heads are split
            q, k = (rms_norm(a.reshape(a.shape[:2] + (-1,)), lp[w],
                             eps=c.norm_eps).reshape(a.shape)
                    for a, w in ((q, "q_norm"), (k, "k_norm")))
        if kind.rotary.dim:
            q = apply_rotary(q, sin, cos, positions=positions,
                             layout=kind.rotary.layout)
            k = apply_rotary(k, sin, cos, positions=positions,
                             layout=kind.rotary.layout)
        names = [pool.name for pool in kind.pools]
        new = dict(zip(names, (k, v)))
        if kind.index_topk:
            qi, new["ki"], wi = _indexer(c, h, lp, isin, icos, positions)
        if first is not None:
            # positions as the kind's table counts them
            positions = positions - first[:, None]

        def live():                     # and the live rows
            return lens if first is None else lens - first
        pools = _write_rows({n: p for n, p in cache.items() if n in names},
                            new, layer, tables, positions, write_mask)
        keys, values = pools[names[0]], pools[names[1]]
        if 0 < kind.index_topk < tables.shape[1] * keys.shape[3]:
            from ray_tpu.ops.sparse_attention import sparse_paged_attention
            att = sparse_paged_attention(
                q, qi, wi, keys, values, pools["ki"], tables, positions,
                live(), layer=layer, topk=kind.index_topk,
                impl=c.paged_impl, block_r=c.paged_row_block(h.shape[1]))
        else:
            # no indexer: every dense model's path. An indexer whose
            # window holds no more than index_topk tokens lands here too
            # (the selection is the identity): no cell runs that, a test
            # holds it to the dense path bit for bit
            with jax.named_scope("paged_attn"):
                att = paged_attention(
                    q, keys, values, tables, positions, layer=layer,
                    lens=live(), impl=c.paged_impl,
                    block_r=c.paged_row_block(h.shape[1]),
                    window=kind.window, sm_scale=c.attn_scale or None)
        if kind.head_gate:
            att = _head_gate(c, hd, lp, att)
        out = jnp.einsum(
            "bshd,hde->bse", att,
            lp["wo"].reshape(kind.heads, c.head_dim, e).astype(dt))
        return out, {**cache, **pools}


@jax.named_scope("attn")
def _latent_attn_sublayer(c, kind: _LayerKind, h, lp, rot, layer, cache,
                          block_tables, first, positions, write_mask,
                          lens):
    """The latent (MLA) attention sublayer of cache layer ``layer``:
    queries through the ``q_lora_rank`` bottleneck, one latent row a
    new token written to the pool (normed latent | rotated shared key),
    then every head attends the pool's rows themselves
    (``ops/latent_attention.py``): ``wkv_b``'s key half goes into the
    query and its value half comes after the softmax, so no per-head K
    or V of the context exists. With an indexer (``index_topk``) the new
    tokens' index keys go into the same pages' ``ki`` and the heads
    attend the latent rows it selects. ``head_gate``: a sigmoid gate a
    head on the heads' outputs ahead of ``wo``. The arguments are
    :func:`_paged_attn_sublayer`'s; latent rows are read through the main
    table (``first`` None). Returns (attn_out, cache)."""
    from ray_tpu.ops.latent_attention import latent_attention
    assert first is None
    dt = c.dtype
    b, n, e = h.shape
    H, dn, dr, dv = kind.heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
    r = c.kv_lora_rank
    (sin, cos), (isin, icos) = rot
    hd = h.astype(dt)
    with jax.named_scope("mla_q"):
        cq = rms_norm(jnp.dot(hd, lp["wq_a"].astype(dt)), lp["q_a_norm"],
                      eps=c.norm_eps)
        q = jnp.dot(cq, lp["wq_b"].astype(dt)).reshape(b, n, H, dn + dr)
        q_nope = q[..., :dn]
        q_rope = apply_rotary(q[..., dn:], sin, cos, positions=positions,
                              layout=kind.rotary.layout)
    with jax.named_scope("mla_latent"):
        ckv = jnp.dot(hd, lp["wkv_a"].astype(dt))
        lat = rms_norm(ckv[..., :r], lp["kv_a_norm"], eps=c.norm_eps)
        k_rope = apply_rotary(ckv[..., None, r:], sin, cos,
                              positions=positions,
                              layout=kind.rotary.layout)
        width = cache["latent"].shape[-1]
        row = jnp.concatenate(
            [lat[:, :, None], k_rope,
             jnp.zeros((b, n, 1, width - r - dr), dt)], axis=-1)
    new, select = {"latent": row}, None
    if kind.index_topk:
        qi, new["ki"], wi = _indexer(
            c, h, lp, isin, icos, positions,
            q_from=cq if c.index_q_lora else None)
    cache = _write_rows(cache, new, layer, block_tables, positions,
                        write_mask)
    if 0 < kind.index_topk \
            < block_tables.shape[1] * cache["latent"].shape[3]:
        # a window of no more than index_topk tokens selects every key:
        # the dense path, bit for bit (a test holds it)
        select = (qi, wi, cache["ki"], kind.index_topk)
    wkv_b = lp["wkv_b"].reshape(r, H, dn + dv)
    att = latent_attention(
        q_nope, q_rope, wkv_b[..., :dn], wkv_b[..., dn:], cache["latent"],
        block_tables, positions, layer=layer, lens=lens,
        sm_scale=(dn + dr) ** -0.5 * c.rope_softmax_scale,
        impl=c.paged_impl, block_r=c.paged_row_block(n), select=select)
    if kind.head_gate:
        att = _head_gate(c, hd, lp, att)
    with jax.named_scope("mla_out"):
        out = jnp.einsum("bshd,hde->bse", att,
                         lp["wo"].reshape(H, dv, e).astype(dt))
    return out, cache


@jax.named_scope("ssm")
def _scan_sublayer(c, kind: _LayerKind, h, lp, rot, layer, cache,
                   state_rows, first, positions, write_mask, lens):
    """The Mamba-2 mixer of a "mamba" layer, state layer ``layer`` of the
    kind's per-slot arrays (``ops/ssm.py`` has the recurrence): project
    ``h`` to gate ``z``, the convolution's inputs ``x | B | C`` and the
    heads' steps ``dt``; a causal depthwise convolution over the new
    inputs behind the slot's last ``ssm_conv - 1``; the recurrence from
    the slot's state, blocked for a chunk or elementwise for one token;
    ``y * silu(z)`` through an RMSNorm over each group's channels (one
    over all of them at ``ssm_groups`` 1) and ``w_out``.
    The arguments are :func:`_paged_attn_sublayer`'s, with the slot of
    each row (``state_rows [B]``; None: row b is slot b) where that has
    a block table. A row's state is read at ``(layer, slot)`` and written
    back there, in place in the scan's carry: a row whose call starts at
    position 0 reads zeros whatever the slot held (a new sequence), and
    a token that is not live (``write_mask``, or a row with ``lens`` 0:
    a decode slot with no sequence) changes neither state nor tail.
    Returns (out, cache)."""
    from ray_tpu.ops.ssm import (causal_conv, put_slot_rows, slot_rows,
                                 ssd_chunk_scan, ssd_step_slots)
    dt_ = c.dtype
    b, n, _ = h.shape
    H, P, N, G = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    di, cw = c.ssm_inner, c.ssm_conv_width
    live = write_mask & (lens > 0)[:, None]
    n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
    fresh = positions[:, 0] == 0

    def read(name):
        return slot_rows(cache[name], layer, state_rows, b, fresh)

    def write(name, rows):
        return put_slot_rows(cache[name], layer, state_rows, rows)

    with jax.named_scope("ssm_in_proj"):
        proj = jnp.dot(h.astype(dt_), lp["w_in"].astype(dt_))
        z, xbc, dt = proj[..., :di], proj[..., di:di + cw], \
            proj[..., di + cw:]
    with jax.named_scope("ssm_conv"):
        xbc, tail = causal_conv(xbc, read("conv"), lp["conv_w"],
                                lp["conv_b"], n_live)
        conv = write("conv", tail)
    with jax.named_scope("ssm_scan"):
        x = xbc[..., :di].reshape(b, n, H, P)
        Bm = xbc[..., di:di + G * N].reshape(b, n, G, N)
        Cm = xbc[..., di + G * N:].reshape(b, n, G, N)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        A = -jnp.exp(lp["A_log"].astype(jnp.float32))
        if n == 1:
            # the whole array and the layer's index, never a slice of it
            y, ssm = ssd_step_slots(
                x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], lp["D"],
                cache["ssm"], layer, state_rows, live[:, 0], fresh,
                impl=c.paged_impl)
            y = y[:, None]
        else:
            y, state = ssd_chunk_scan(x, dt, A, Bm, Cm, lp["D"],
                                      read("ssm"), live, block=c.ssm_chunk)
            ssm = write("ssm", state)
    with jax.named_scope("ssm_out"):
        y = y.reshape(b, n, di) * jax.nn.silu(z.astype(jnp.float32))
        # each group's channels apart
        y = rms_norm(y.reshape(b, n, G, di // G),
                     lp["ssm_norm"].reshape(G, di // G),
                     eps=c.norm_eps).reshape(b, n, di)
        out = jnp.dot(y.astype(dt_), lp["w_out"].astype(dt_))
    return out, {**cache, "conv": conv, "ssm": ssm}


def _unit(x, eps: float = 1e-6):
    """``x`` over its length along the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@jax.named_scope("delta")
def _delta_sublayer(c, kind: _LayerKind, h, lp, rot, layer, cache,
                    state_rows, first, positions, write_mask, lens,
                    snap_rows=None):
    """The gated-delta-rule mixer of a "delta" layer, state layer
    ``layer`` of the kind's per-slot arrays (``ops/delta.py`` has the
    recurrence): project ``h`` to the convolution's inputs ``q | k | v``,
    the output's gate and a decay and a write strength a head; a causal
    depthwise convolution (no bias, SiLU) over the new inputs behind the
    slot's last ``delta_conv - 1``; q and k to unit length a head, q over
    ``sqrt(delta_key_dim)``; the recurrence from the slot's state, blocked
    for a chunk or elementwise for one token; an RMSNorm a head wide on
    each head's output, times ``silu(gate)``, through ``w_out``. The
    arguments are :func:`_scan_sublayer`'s, and the slot's rows are read
    and written as there; ``snap_rows [B, n_snaps]`` (None: no snapshot)
    is the row of the snapshot arrays the state and the tail after each
    ``C / n_snaps`` tokens of this call are written to, 0 (the trash row)
    for none. Returns (out, cache)."""
    from ray_tpu.ops.delta import (BLOCK, conv_tails_at,
                                   gated_delta_chunk_scan,
                                   gated_delta_step_slots)
    from ray_tpu.ops.ssm import causal_conv, put_slot_rows, slot_rows
    dt_ = c.dtype
    b, n, _ = h.shape
    H, dk, dv = c.delta_heads, c.delta_key_dim, c.delta_value_dim
    live = write_mask & (lens > 0)[:, None]
    n_live = jnp.sum(live, axis=1, dtype=jnp.int32)
    fresh = positions[:, 0] == 0
    snaps = {}
    every = n // snap_rows.shape[1] if snap_rows is not None else None

    def keep(name, rows):            # [n_snaps, B, ...] -> the rows named
        return cache[name].at[layer, snap_rows.T].set(
            rows.astype(cache[name].dtype))

    with jax.named_scope("delta_in_proj"):
        hd = h.astype(dt_)
        raw = jnp.dot(hd, lp["w_qkv"].astype(dt_))
        gate = jnp.dot(hd, lp["w_g"].astype(dt_))
        ab = jnp.dot(hd, lp["w_ab"].astype(dt_),
                     preferred_element_type=jnp.float32)
    with jax.named_scope("delta_conv"):
        tail_in = slot_rows(cache["delta_conv"], layer, state_rows, b, fresh)
        qkv, tail = causal_conv(raw, tail_in, lp["conv_w"], None, n_live)
        conv = put_slot_rows(cache["delta_conv"], layer, state_rows, tail)
        if every:
            snaps["delta_conv_snap"] = keep(
                "delta_conv_snap", conv_tails_at(raw, tail_in, every))
    with jax.named_scope("delta_scan"):
        q = _unit(qkv[..., :H * dk].reshape(b, n, H, dk)) * dk ** -0.5
        k = _unit(qkv[..., H * dk:2 * H * dk].reshape(b, n, H, dk))
        v = qkv[..., 2 * H * dk:].reshape(b, n, H, dv)
        g = -jnp.exp(lp["A_log"].astype(jnp.float32)) \
            * jax.nn.softplus(ab[..., :H] + lp["dt_bias"])
        beta = jax.nn.sigmoid(ab[..., H:])
        if c.delta_neg_eigval:
            beta = 2.0 * beta
        if n == 1:
            # the whole array and the layer's index, never a slice of it
            o, state = gated_delta_step_slots(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                cache["delta"], layer, state_rows, live[:, 0], fresh,
                impl=c.paged_impl)
            o = o[:, None]
        else:
            o, rows, at = gated_delta_chunk_scan(
                q, k, v, g, beta,
                slot_rows(cache["delta"], layer, state_rows, b, fresh),
                live, block=math.gcd(BLOCK, every) if every else BLOCK,
                snap_every=every)
            state = put_slot_rows(cache["delta"], layer, state_rows, rows)
            if every:
                snaps["delta_snap"] = keep("delta_snap", at)
    with jax.named_scope("delta_out"):
        o = rms_norm(o, lp["delta_norm"], eps=c.norm_eps) \
            * jax.nn.silu(gate.astype(jnp.float32)).reshape(b, n, H, dv)
        out = jnp.dot(o.reshape(b, n, H * dv).astype(dt_),
                      lp["w_out"].astype(dt_))
    return out, {**cache, "delta_conv": conv, "delta": state, **snaps}


_MIXERS = {"paged": _paged_attn_sublayer, "latent": _latent_attn_sublayer,
           "scan": _scan_sublayer, "delta": _delta_sublayer}


def _forward_with_cache(c: TransformerConfig, params: Dict,
                        ids: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                        block_tables: jnp.ndarray,
                        positions: jnp.ndarray,
                        write_mask: jnp.ndarray,
                        lens: jnp.ndarray,
                        window_tables: Optional[jnp.ndarray] = None,
                        window_first: Optional[jnp.ndarray] = None,
                        state_rows: Optional[jnp.ndarray] = None,
                        snap_rows: Optional[jnp.ndarray] = None):
    """Shared trunk of :func:`prefill` and :func:`decode_step`:
    (B, C) token ids at absolute ``positions`` -> (B, C, vocab) logits,
    writing each layer's k/v into the paged cache as it goes. ``lens``
    (B,) is each sequence's live token count including this call's
    writes — the attention kernel's length-skipping bound.

    Each run of the plan (consecutive layers of one kind in one stack of
    the tree: a leading dense layer, the expert layers behind it, a
    stretch of window layers) is one scan over ``(layers, layer index)``
    that carries ``(x, cache)`` with every pool whole: the pools are
    never among the scanned inputs or outputs (those are sliced per layer
    and stacked into a new buffer — a copy of the whole pool every
    step). Each kind of layer is compiled once a run. A run that is a
    whole stack scans it; a shorter one scans the layer index alone and
    reads the layer's leaves out of the whole stack at it. With no
    window table given the window layers read ``block_tables`` through
    the window mask."""
    from ray_tpu.models.moe import expert_leaves
    if c.n_experts and not c.experts_per_token:
        raise NotImplementedError(
            "paged decode serves dropless top-k experts "
            "(experts_per_token > 0); Switch top-1 with capacity drops "
            "tokens by the batch they arrive in")
    plan = _layer_plan(c)
    if snap_rows is not None and not any(
            state.snap in cache for kind in plan.kinds
            for state in kind.state):
        raise ValueError("snap_rows: this cache has no snapshot rows "
                         "(init_kv_cache(state_snapshots=...), for a "
                         "model whose recurrent layers hand them out)")
    pools = cache_pools(cache)
    bs = next(iter(pools.values())).shape[3] if pools else 1
    table_len = block_tables.shape[1] * bs
    # a kind's block table and the absolute position of its entry 0; a
    # kind with per-slot state has its rows' slots where a table would be
    tables = {"main": (block_tables, None),
              "window": (block_tables, None) if window_tables is None
              else (window_tables, window_first),
              "state": (state_rows, None)}
    rot = {}
    for kind in plan.kinds:
        if kind.mixer is None:
            continue
        own = _rotary(kind.rotary, positions, table_len)
        rot[kind] = (own, own if kind.index_rotary is None else
                     _rotary(kind.index_rotary, positions, table_len))
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], ids, axis=0).astype(c.dtype)
        if c.embed_scale != 1.0:
            x = x * c.embed_scale
    carry = (x, dict(cache))
    for run in plan.runs:
        stack = params[run.stack]
        # the dropless experts stay out of the scanned leaves: the grouped
        # product reads layer ``place`` of the whole stack in place
        whole = {k: stack[k] for k in expert_leaves(c)} \
            if run.experts else {}
        scanned = {k: v for k, v in stack.items() if k not in whole}
        # a run shorter than its stack reads each layer's leaves out of
        # the whole stack at the layer's place, as a scan reads its own
        # inputs: a static slice of the stack to scan over would be a
        # copy of those layers' weights in every call
        indexed = {}
        if any((run.at, run.n) != (0, v.shape[0]) for v in scanned.values()):
            scanned, indexed = {}, scanned

        def step(carry, per_layer, run=run, whole=whole, indexed=indexed):
            x, cache = carry
            lp, layer = per_layer
            kind, behind = run.kind, run.cache_layer - run.at
            # the layer's place in its stack: behind its place in the
            # kind's pools by the layers of that kind in the stacks ahead
            place = layer - behind if behind else layer
            if indexed:
                lp = {k: jax.lax.dynamic_index_in_dim(v, place, 0, False)
                      for k, v in indexed.items()}

            def attend(h):
                # a kind whose state has snapshot rows is told where the
                # call's boundaries go
                snap = {"snap_rows": snap_rows} if any(
                    state.snap for state in kind.state) else {}
                return _MIXERS[kind.mixer](
                    c, kind, h, lp, rot[kind], layer, cache,
                    *tables[kind.table], positions, write_mask, lens,
                    **snap)

            def mlp(h):
                return _mlp_sublayer(c, h, {**lp, **whole}, place)
            with jax.named_scope("layer"):
                x, new, _ = _block(c, kind, x, lp, attend, mlp)
            # a layer with no mixer touched no pool and no state
            return (x, cache if new is None else new), None

        carry, _ = jax.lax.scan(step, carry, (scanned, jnp.arange(
            run.cache_layer, run.cache_layer + run.n, dtype=jnp.int32)))
    x, cache = carry
    x = _final_norm(c, params, x)
    return _lm_head(c, params, x), cache


def prefill(config: TransformerConfig, params: Dict, tokens: jnp.ndarray,
            cache: Dict[str, jnp.ndarray], block_tables: jnp.ndarray,
            start_pos: jnp.ndarray, lens: jnp.ndarray,
            window_tables: Optional[jnp.ndarray] = None,
            window_first: Optional[jnp.ndarray] = None,
            state_rows: Optional[jnp.ndarray] = None,
            snap_rows: Optional[jnp.ndarray] = None):
    """Process one prompt chunk per sequence, writing cache blocks.

    ``tokens``: (B, C) int32 — chunk ``start_pos[b] .. start_pos[b]+
    lens[b]-1`` of each prompt, zero-padded past ``lens[b]`` (chunked
    prefill feeds a fixed C per call so the engine never recompiles).
    Chunk token i attends every cached position ``<= start_pos + i`` —
    earlier chunks of the same prompt plus the chunk's own causal
    prefix. Returns ``(logits (B, C, vocab), cache)``; the first
    generated token comes from ``logits[b, lens[b]-1]`` of the FINAL
    chunk.

    ``window_tables`` ``(B, Tw)`` / ``window_first`` ``(B,)``, for a
    stack with "window" layers: the window pools' block table of each
    sequence and the absolute position its entry 0 starts at (a multiple
    of the page), so the table holds the pages from behind the window to
    the chunk's end and none before. Left out, the window layers read
    ``block_tables`` (the window pools then have its pages).

    ``state_rows`` ``(B,)``, for a stack with "mamba" layers: the slot of
    the per-slot state arrays each sequence's recurrent state lives in
    (left out: sequence b's is slot b). A chunk at ``start_pos == 0``
    starts from a zero state whatever its slot held; a later chunk goes
    on from what the chunk before left there.

    ``snap_rows`` ``(B, C // stride)`` int32, for a cache with snapshot
    rows (``init_kv_cache(state_snapshots=...)``): the snapshot row the
    recurrent state after each ``stride`` tokens of THIS call is written
    to, 0 (the trash row) for a boundary that wants none or lies past the
    row's live tokens. ``start_pos`` is then a multiple of the stride, so
    that the boundaries are the prompt's own. Left out, the program is
    the one it would be without snapshots.
    """
    b, chunk = tokens.shape
    positions = start_pos[:, None] + jnp.arange(chunk, dtype=jnp.int32)
    write_mask = jnp.arange(chunk, dtype=jnp.int32)[None, :] \
        < lens[:, None]
    # live tokens after this chunk's writes: earlier chunks + this one
    live = (start_pos + lens).astype(jnp.int32)
    return _forward_with_cache(config, params, tokens, cache,
                               block_tables, positions, write_mask,
                               live, window_tables, window_first,
                               state_rows, snap_rows)


def decode_step(config: TransformerConfig, params: Dict,
                token_ids: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                block_tables: jnp.ndarray, seq_lens: jnp.ndarray,
                window_tables: Optional[jnp.ndarray] = None,
                window_first: Optional[jnp.ndarray] = None,
                state_rows: Optional[jnp.ndarray] = None):
    """One batched decode step: each sequence's newest token
    (``token_ids``: (B,) int32, sitting at absolute position
    ``seq_lens[b]``) is written to its cache block and attends every
    earlier position — causal by construction. Returns
    ``(logits (B, vocab), cache)``. A row with ``seq_lens < 0`` holds no
    sequence: a "mamba" layer leaves its slot's state as it was.
    """
    positions = seq_lens[:, None].astype(jnp.int32)
    write_mask = jnp.ones_like(positions, dtype=bool)
    logits, cache = _forward_with_cache(
        config, params, token_ids[:, None], cache,
        block_tables, positions, write_mask,
        seq_lens.astype(jnp.int32) + 1, window_tables, window_first,
        state_rows)
    return logits[:, 0], cache


class Transformer:
    """Convenience OO wrapper binding a config: ``init``/``apply``/``loss``
    plus the sharding-annotation tree."""

    def __init__(self, config: TransformerConfig):
        self.config = config

    def init(self, key) -> Dict:
        return init_params(self.config, key)

    def logical_axes(self) -> Dict:
        return logical_axes(self.config)

    def apply(self, params, input_ids, mesh=None, rules=None):
        return apply(self.config, params, input_ids, mesh=mesh, rules=rules)

    def loss(self, params, batch, mesh=None, rules=None):
        return lm_loss(self.config, params, batch, mesh=mesh, rules=rules)
