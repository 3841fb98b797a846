"""Continuous-batching LLM inference engine (the "millions of users"
serving path, ROADMAP item 1).

vLLM-style serving on the repo's own model stack: a paged KV cache in
device memory (``models.transformer.init_kv_cache``), a fixed array of
**decode slots** stepped as ONE batched ``decode_step`` call, and
**chunked prefill** interleaved between decode steps so a new arrival's
time-to-first-token never stalls in-flight streams for more than one
``prefill_chunk``'s worth of compute. New requests are admitted into the
in-flight batch between steps — continuous batching, not static batching:
a finishing stream frees its slot and blocks for the next queued prompt
immediately, so the MXU stays at high occupancy under ragged request
lengths.

Shapes are FIXED at engine construction (``decode_slots`` sequences per
decode call, ``prefill_chunk`` tokens per prefill call, one block table
of ``blocks_per_seq`` entries per slot) and both model functions are
jitted once with donated caches — admission, EOS, and cancellation are
pure host-side bookkeeping and never recompile.

Memory accounting: one KV block holds ``block_size`` tokens ×
``2 (k+v) × n_layers × kv_heads × head_dim × dtype_bytes`` bytes; the
pool is ``num_kv_blocks`` blocks (default: full occupancy — every slot
can hold ``max_seq_len`` tokens — plus one reserved trash block that
idle slots' writes land in). Blocks are **refcounted**
(:mod:`ray_tpu.serve.prefix_cache`): EOS/cancel/error decref instead
of free, full prompt chunks are indexed in a radix trie so a new
request whose prompt shares a prefix (the high-traffic common
system-prompt case) skips prefilling the matched blocks entirely —
copy-on-write covers the fully-matched tail block — and ref-0 blocks
stay warm in the trie until pool pressure evicts them LRU.

Speculative multi-token decode (``spec_tokens > 0``): each decode step
drafts up to k tokens per slot by **prompt lookup** (the sequence's
own history's most recent matching n-gram — no draft model), verifies
them in ONE batched (slots, k+1)-token call jitted once at fixed
shape, and accepts the longest prefix that matches the model's own
greedy argmax — per-token output is bit-identical to one-token-at-a-
time decode by construction. A per-slot acceptance EWMA disables
drafting for sequences it doesn't pay for.

Integration: :class:`LLMServer` is the deployment-facing wrapper —
``generate`` is an async generator, so a Serve replica streams tokens
through the core ``num_returns="streaming"`` machinery and
``handle.options(stream=True)`` / the HTTP proxy work unchanged;
consumer ``close()`` lands in :meth:`LLMEngine.cancel`, which frees the
slot and blocks at the next step boundary.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import functools
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu.exceptions import RayTpuError
from ray_tpu.serve import request_trace as RT


class EngineDeadError(RayTpuError):
    """The engine's step loop died; every queued/in-flight request is
    failed with this (typed — consumers never hang on a dead engine)."""


class RequestTooLargeError(RayTpuError):
    """prompt_len + 1 exceeds the engine's per-request window
    (``max_seq_len``) — the request can never be admitted."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs of the serving engine (see README "Serving").

    - ``decode_slots``: sequences decoded per batched step — the
      continuous-batching width and the unit of batch occupancy.
    - ``kv_block_size``: tokens per KV-cache block (paging granularity;
      smaller = less internal fragmentation, more gather indices).
    - ``max_seq_len``: per-request window (prompt + generated tokens);
      sets ``blocks_per_seq`` and the attention gather width.
    - ``prefill_chunk``: prompt tokens processed per engine step — the
      TTFT-vs-inter-token-latency tradeoff knob.
    - ``num_kv_blocks``: KV pool size; 0 = auto (full occupancy + the
      reserved trash block idle slots write into).
    - ``num_window_blocks``: size of the SECOND pool a model with
      sliding-window layers has (``TransformerConfig.layer_pattern``),
      the window layers' pages; 0 = auto, ``decode_slots`` sequences of
      ``ceil((sliding_window + prefill_chunk) / kv_block_size) + 1``
      pages (the most one can pin: the window behind a chunk, the
      chunk, a ragged edge) and a trash page. A window layer's page
      lives while a row of it lies inside ``[next position -
      sliding_window, next position)`` of its sequence and is given
      back in the tick that passes it (to the prefix trie, as evictable
      cache, where the trie indexes it); the full layers' pages live as
      long as the sequence. More than the auto size is room for cached
      tails, which is what a prefix hit needs of the window layers.
    - ``enable_prefix_sharing``: refcounted radix-trie sharing of full
      prompt KV blocks (prefill skips matched prefixes).
    - ``state_snapshot_stride`` / ``num_state_snapshots``: for a model
      with recurrent state (a row a decode slot, no page), what lets
      the trie serve it: the state as of every ``state_snapshot_stride``
      tokens of a prompt (a multiple of ``kv_block_size`` that divides
      ``prefill_chunk``) is kept in one of ``num_state_snapshots``
      snapshot rows, named by the trie node whose page ends there, and a
      prefix hit is cut back to the deepest such node and copies its row
      into the request's slot. Stride 0 = none, and then
      ``enable_prefix_sharing`` is refused for such a model by name;
      ``num_state_snapshots`` 0 = one row a stride of the page pool's
      tokens (what the pages can hold a snapshot for). A property of the
      cache, not of the model.
    - ``spec_tokens``: draft tokens per slot per decode step via
      prompt-lookup speculation (0 = classic one-token decode).
    - ``spec_ngram``: longest history n-gram tried by the draft lookup.
    - ``spec_min_acceptance``: per-slot acceptance-EWMA floor below
      which drafting is disabled for that sequence.
    - ``capture_logprobs``: the jitted prefill/decode programs also
      return the log-probability of each selected token, so ``detailed``
      streams carry ``(token, policy_version, logprob)`` — the RLHF
      rollout payload. Mutually exclusive with ``spec_tokens > 0``
      (the verify path re-scores positions out of emission order).
    """
    decode_slots: int = 8
    kv_block_size: int = 16
    max_seq_len: int = 256
    prefill_chunk: int = 32
    num_kv_blocks: int = 0
    num_window_blocks: int = 0
    state_snapshot_stride: int = 0
    num_state_snapshots: int = 0
    max_new_tokens: int = 64          # default per-request cap
    eos_token_id: Optional[int] = None
    enable_prefix_sharing: bool = True
    spec_tokens: int = 0
    spec_ngram: int = 3
    spec_min_acceptance: float = 0.1
    capture_logprobs: bool = False
    #: Per-request tracing (serve/request_trace.py): None follows the
    #: runtime config's enable_request_trace; True/False force it for
    #: this engine (a benchmark cell's engines turn it off).
    enable_trace: Optional[bool] = None
    #: Tokens per DECODE trace span — bounds span count for long
    #: generations (a 4k-token decode is ~256 spans at 16, not 4k).
    trace_decode_tick: int = 16
    #: Wire format for disaggregated KV hand-offs (serve/disagg.py):
    #: "bf16" ships blocks raw in the cache's native dtype (bit-exact
    #: adoption — an f32 cache ships f32); "int8" ships blockwise-
    #: quantized values + f32 scales (~4x smaller, quant tolerance).
    kv_wire: str = "bf16"
    #: Part label this engine's trace span batches ship under. The
    #: controller store dedups by (part, seq) per request — a disagg
    #: pair (prefill engine + decode engine) sharing one request_id
    #: MUST ship under distinct parts or one side's spans vanish.
    trace_part: str = "engine"

    @property
    def blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.kv_block_size)

    @property
    def resolved_num_blocks(self) -> int:
        if self.num_kv_blocks:
            return self.num_kv_blocks
        return 1 + self.decode_slots * self.blocks_per_seq

    def window_blocks_per_seq(self, model_config) -> int:
        """Window-pool pages one sequence can pin (0: no window layers):
        the window behind a chunk, the chunk, and a ragged edge."""
        if not getattr(model_config, "sliding_window", 0):
            return 0
        return -(-(model_config.sliding_window + self.prefill_chunk)
                 // self.kv_block_size) + 1

    def resolved_window_blocks(self, model_config) -> int:
        per_seq = self.window_blocks_per_seq(model_config)
        if not per_seq:
            return 0
        return self.num_window_blocks or 1 + self.decode_slots * per_seq

    def kv_bytes_per_token(self, model_config, kind: str = "full") -> int:
        """KV bytes/token — the HBM-budget side of the block math, BY
        KIND of page: ``"full"`` what a token takes for as long as its
        sequence lives (every pool of a model with one kind of layer: k
        and v, an indexer's keys, or one latent row), ``"window"`` what
        it takes of the window layers' pools, and only while it lies
        inside the window (0 without such layers). A layer with recurrent
        state holds no page and adds nothing here: its bytes are a slot's,
        whatever the sequence's length (``state_bytes_per_slot``)."""
        import jax
        from ray_tpu.models import init_kv_cache
        from ray_tpu.models.transformer import WINDOW_POOLS, cache_pools
        pools = cache_pools(jax.eval_shape(
            lambda: init_kv_cache(model_config, 1, 1)))
        return sum(p.size * p.dtype.itemsize for name, p in pools.items()
                   if (name in WINDOW_POOLS) == (kind == "window"))

    @property
    def resolved_state_snapshots(self) -> int:
        """Snapshot rows: as given, or one a stride of the pool's tokens."""
        if not self.state_snapshot_stride:
            return 0
        return self.num_state_snapshots or (
            (self.resolved_num_blocks - 1) * self.kv_block_size
            // self.state_snapshot_stride)

    @property
    def snapshots_per_chunk(self) -> int:
        """Boundaries of the snapshot stride inside one chunk's call."""
        return self.prefill_chunk // self.state_snapshot_stride \
            if self.state_snapshot_stride else 0

    @staticmethod
    def state_bytes_per_slot(model_config) -> int:
        """Bytes one decode slot's recurrent state takes, over all layers
        that have one (0: the model keeps pages and nothing else)."""
        import jax
        from ray_tpu.models import init_kv_cache
        from ray_tpu.models.transformer import STATE_ARRAYS
        cache = jax.eval_shape(lambda: init_kv_cache(model_config, 1, 1))
        return sum(a.size * a.dtype.itemsize for name, a in cache.items()
                   if name in STATE_ARRAYS)


def _unpack(rows, width: int, scalars: int):
    """A step program's integer inputs out of the one int32 array it is
    fed by, by static slices: every row is ``[width tokens | scalars |
    block-table row]``. Returns ``(tokens, *scalar columns, table)``."""
    cols = [rows[:, width + i] for i in range(scalars)]
    return (rows[:, :width], *cols, rows[:, width + scalars:])


def _step_fns(model_config, ec: EngineConfig):
    """The engine's step programs, unjitted: ``fn(params, rows, cache)``
    with ``rows`` the staged int32 array — prefill ``[1, prefill_chunk +
    2 + blocks_per_seq]`` (chunk tokens, start, n, the slot's table
    row), decode ``[decode_slots, 2 + blocks_per_seq]`` (token, length,
    table row of every slot), verify ``[decode_slots, spec_tokens + 1 +
    2 + blocks_per_seq]`` (prefill's layout over all slots). A slot
    with no decoding sequence (free, or its prompt still prefilling) is
    staged as ``[token 0, length -1 (``_NO_SEQUENCE``), a table of
    trash blocks]``: the decode program makes of it position -1 and
    ``lens = 0``, a sequence that holds nothing, for which the paged
    kernel fetches no page and returns zeros, and whose one K/V row
    lands in the trash block. Length 0 is a live sequence's first token
    (``decode_step`` at ``seq_lens == 0``), so the program cannot guess:
    the engine says. A verify row of such a slot is ``start 0, n 0``,
    ``lens = 0`` as well, and writes nothing. With
    ``capture_logprobs`` prefill and decode also return the selected
    token's logprob (greedy argmax is unchanged — the extra output is
    the RLHF rollout payload, not a sampling change). A model with
    window layers has ``1 + window_blocks_per_seq`` columns more behind
    each table row: the absolute position the window layers' short table
    starts at, and that table (ids of the window pool). A model with
    recurrent state (``state_bytes_per_slot`` > 0) has one column more at
    the end of a PREFILL row: the request's decode slot, where its state
    lives; a decode row needs none (row i is slot i), and a row staged
    with no sequence leaves its slot's state as it was. With
    ``state_snapshot_stride`` a PREFILL row has ``prefill_chunk //
    stride`` columns more ahead of the slot: the snapshot row each
    boundary of the call is written to (0: none)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import decode_step, prefill
    capture = ec.capture_logprobs
    T = ec.blocks_per_seq
    slotted = bool(ec.state_bytes_per_slot(model_config))
    n_snap = ec.snapshots_per_chunk if slotted else 0

    def tables(bt, chunk=False):
        """(table, the window layers' arguments, the state's) out of a
        row's table part: with window layers it is ``[table | the window
        table's first position | window table]``, two arguments more out
        of the same one transfer; a chunk's row of a model with recurrent
        state ends in its slot."""
        state = {}
        if slotted and chunk:
            bt, state = bt[:, :-1], {"state_rows": bt[:, -1]}
            if n_snap:
                bt, state["snap_rows"] = bt[:, :-n_snap], bt[:, -n_snap:]
        if not ec.window_blocks_per_seq(model_config):
            return bt, (), state
        return bt[:, :T], (bt[:, T + 1:], bt[:, T]), state

    def _prefill_fn(params, rows, cache):
        tokens, start, lens, bt = _unpack(rows, ec.prefill_chunk, 2)
        bt, window, state = tables(bt, chunk=True)
        logits, cache = prefill(model_config, params, tokens, cache,
                                bt, start, lens, *window, **state)
        with jax.named_scope("sample"):
            last = jnp.take_along_axis(
                logits, (lens - 1)[:, None, None], axis=1)[:, 0]
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            if capture:
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(last, axis=-1), tok[:, None],
                    axis=-1)[:, 0]
                return tok, lp, cache
        return tok, cache

    def _decode_fn(params, rows, cache):
        toks, seq_lens, bt = _unpack(rows, 1, 1)
        bt, window, _ = tables(bt)
        logits, cache = decode_step(model_config, params, toks[:, 0],
                                    cache, bt, seq_lens, *window)
        with jax.named_scope("sample"):
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if capture:
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(logits, axis=-1),
                    tok[:, None], axis=-1)[:, 0]
                return tok, lp, cache
        return tok, cache

    # speculative verify: the whole slot array steps k+1 tokens per
    # call through the chunked-prefill trunk (positions/write-masks
    # already handle ragged per-slot lengths); per-position argmax
    # comes back for host-side longest-prefix acceptance
    def _verify_fn(params, rows, cache):
        toks, start, lens, bt = _unpack(rows, ec.spec_tokens + 1, 2)
        logits, cache = prefill(model_config, params, toks, cache,
                                bt, start, lens)
        with jax.named_scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return _prefill_fn, _decode_fn, _verify_fn


_DONE = object()          # stream-end sentinel on the request queue

#: the staged length of a decode slot with no decoding sequence
#: (_step_fns): position -1, no live key
_NO_SEQUENCE = -1

# request lifecycle states
_QUEUED, _PREFILL, _DECODE, _FINISHED = range(4)


class _Request:
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "out", "state", "slot", "blocks", "prefill_pos",
                 "seq_len", "generated", "cancelled", "t_submit",
                 "t_first_token", "history", "hit_blocks", "trie_node",
                 "trie_cursor", "spec_ewma", "spec_disabled", "warmup",
                 "detailed", "trace", "t_enqueue_wall", "queue_wait_s",
                 "last_tok_wall", "tick_t0", "tick_toks", "export",
                 "adopt", "t_slot", "t_first_chunk", "tick_first_chunk",
                 "n_chunks", "wpages", "wspan")

    def __init__(self, rid: int, prompt: List[int], max_new_tokens: int,
                 eos_token_id: Optional[int]):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.out: "queue.Queue" = queue.Queue()
        self.state = _QUEUED
        self.slot: Optional[int] = None
        self.blocks: List[int] = []
        # window layers' pages it pins, page index -> window-pool id,
        # and the (first, last) page its staged window table holds
        self.wpages: Dict[int, int] = {}
        self.wspan: Optional[tuple] = None
        self.prefill_pos = 0          # prompt tokens already in cache
        self.seq_len = 0              # cache positions written
        self.generated = 0            # tokens emitted
        self.cancelled = False
        self.warmup = False       # compile-only request: no telemetry
        self.detailed = False     # stream (tok, version, logprob) tuples
        # -- disaggregated hand-off (serve/disagg.py)
        self.export = False       # terminate at prompt end: ship KV
        self.adopt: Optional[dict] = None   # shipped payload to adopt
        self.t_submit = time.monotonic()
        self.t_first_token: Optional[float] = None
        # -- the TTFT split (always stamped; time.monotonic)
        self.t_slot: Optional[float] = None         # a decode slot won
        self.t_first_chunk: Optional[float] = None  # first chunk staged
        self.tick_first_chunk = 0     # engine tick of that chunk
        self.n_chunks = 0             # prefill chunks it has run
        self.t_enqueue_wall = 0.0     # router (or submit) wall clock
        # -- per-request tracing (serve/request_trace.py)
        self.trace = None             # RequestTrace or None
        self.queue_wait_s = 0.0       # enqueue -> engine admission
        self.last_tok_wall: Optional[float] = None
        self.tick_t0: Optional[float] = None   # open DECODE tick start
        self.tick_toks = 0            # tokens in the open DECODE tick
        # -- prefix sharing (prefix_cache.PrefixBlockPool)
        self.hit_blocks = 0           # prompt blocks prefill skipped
        self.trie_node = None         # deepest trie node of this prompt
        self.trie_cursor = 0          # next full prompt block to index
        # -- speculative decode
        self.history: List[int] = list(prompt)   # tokens 0..seq_len
        self.spec_ewma: Optional[float] = None   # acceptance EWMA
        self.spec_disabled = False


class LLMEngine:
    """Continuous-batching scheduler over the paged decode path.

    Thread model: one background step thread owns the device state
    (caches + slot arrays); ``submit``/``cancel`` only touch the queue
    under a lock and are safe from any thread or event loop. Consumers
    read per-request ``queue.Queue``s fed by the step thread.
    """

    def __init__(self, model_config, engine_config: Optional[EngineConfig]
                 = None, params=None, seed: int = 0,
                 replica_tag: str = ""):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from ray_tpu.models import (inference_params, init_kv_cache,
                                    init_params)

        self.model_config = model_config
        self.config = engine_config or EngineConfig()
        self.replica_tag = replica_tag
        ec = self.config
        if ec.prefill_chunk < 1 or ec.decode_slots < 1:
            raise ValueError("prefill_chunk and decode_slots must be >= 1")
        if ec.spec_tokens < 0 or ec.spec_ngram < 1:
            raise ValueError("spec_tokens must be >= 0 and spec_ngram "
                             ">= 1")
        if ec.capture_logprobs and ec.spec_tokens > 0:
            raise ValueError(
                "capture_logprobs is incompatible with speculative "
                "decode (spec_tokens > 0): the verify path scores "
                "positions out of emission order")
        if ec.kv_wire not in ("bf16", "int8"):
            raise ValueError(
                f"kv_wire must be 'bf16' or 'int8', got {ec.kv_wire!r}")
        # window layers: a second pool, a second table a slot
        self._window = int(getattr(model_config, "sliding_window", 0))
        Tw = self._window_table = ec.window_blocks_per_seq(model_config)
        if Tw:
            if ec.spec_tokens > 0:
                raise ValueError(
                    "spec_tokens > 0 is not served with window layers "
                    "(sliding_window): a rejected draft's rows would "
                    "have to be unwound from pages the window has given "
                    "back")
            if ec.resolved_window_blocks(model_config) \
                    < 1 + ec.decode_slots * Tw:
                raise ValueError(
                    f"num_window_blocks {ec.num_window_blocks} is under "
                    f"what {ec.decode_slots} sequences can pin at once, "
                    f"{ec.decode_slots} x {Tw} pages and the trash page")

        # recurrent state: a row a decode slot, no page (models/
        # transformer.py init_kv_cache). What shares, ships or rolls back
        # a prefix would have to move a state with it, and nothing does
        self._state_bytes = ec.state_bytes_per_slot(model_config)
        # ... but for snapshots at a fixed stride, which let the trie
        # share a prefix: (state array, its snapshot rows) by name
        from ray_tpu.models.transformer import (state_counters,
                                                state_snapshot_arrays)
        self._state_counters = state_counters(model_config)
        self._snap_arrays = state_snapshot_arrays(model_config) \
            if ec.state_snapshot_stride else {}
        self._snap_stride = ec.state_snapshot_stride
        if self._snap_stride:
            if not self._snap_arrays:
                raise NotImplementedError(
                    f"state_snapshot_stride {self._snap_stride}: no layer "
                    f"of this model hands out its recurrent state at a "
                    f"chunk's boundaries (a 'delta' layer does; a 'mamba' "
                    f"layer's scan does not yet, and a model of pages "
                    f"alone has no state)")
            if Tw:
                raise NotImplementedError(
                    f"state_snapshot_stride {self._snap_stride} is not "
                    f"served with window layers (sliding_window="
                    f"{self._window}): a hit would have to bring a "
                    f"snapshot AND a window tail, and no match cuts to "
                    f"both")
            if self._snap_stride % ec.kv_block_size \
                    or ec.prefill_chunk % self._snap_stride \
                    or ec.resolved_state_snapshots \
                    < ec.snapshots_per_chunk:
                raise ValueError(
                    f"state_snapshot_stride {self._snap_stride}: a "
                    f"multiple of kv_block_size {ec.kv_block_size} that "
                    f"divides prefill_chunk {ec.prefill_chunk}, with "
                    f"{ec.resolved_state_snapshots} snapshot rows >= the "
                    f"{ec.snapshots_per_chunk} boundaries of one chunk")
        self._state_step_impl = None
        if self._state_bytes:
            # what a decode step's state update runs as (ops/ssm.py,
            # ops/delta.py): the kernel, or the plain form where the
            # shapes or the platform rule it out
            if model_config.ssm_heads:
                from ray_tpu.ops.ssm import step_choice
                self._state_step_impl = step_choice(
                    model_config.paged_impl, model_config.ssm_state,
                    model_config.ssm_inner, groups=model_config.ssm_groups)
            elif model_config.delta_heads:
                from ray_tpu.ops.delta import step_choice
                self._state_step_impl = step_choice(
                    model_config.paged_impl, model_config.delta_key_dim,
                    model_config.delta_heads, model_config.delta_value_dim)
            for name, on in (("enable_prefix_sharing",
                              ec.enable_prefix_sharing
                              and not self._snap_stride),
                             ("spec_tokens > 0", ec.spec_tokens > 0)):
                if on:
                    raise NotImplementedError(self._state_refusal(name))

        # Carried-over paged-kernel follow-on: at long table windows
        # (>= 4k tokens per sequence) the chunked-prefill side of the
        # paged kernel may win with row blocks > 128 — autotune once
        # (winner persists in the flash autotune cache under paged|
        # keys; off-TPU without an injected timer this is the chip
        # default and the config is left alone).
        from ray_tpu.util import compile_cache
        compile_cache.enable()
        compile_cache.stats()     # count loads vs compiles from here on
        window = ec.blocks_per_seq * ec.kv_block_size
        if (model_config.paged_block_r_prefill == 0
                and not model_config.kv_lora_rank
                and window >= 4096 and ec.prefill_chunk > 1):
            from ray_tpu.ops.paged_flash import autotune_paged_block_r
            rows = ec.prefill_chunk * (model_config.n_heads
                                       // model_config.kv_heads)
            br = autotune_paged_block_r(
                ec.kv_block_size, ec.blocks_per_seq, rows,
                model_config.head_dim,
                candidates=(32, 64, 128, 256, 512))
            model_config = dataclasses.replace(
                model_config, paged_block_r_prefill=int(br))
            self.model_config = model_config

        # The programs take their weights in the compute dtype: cast
        # once here (and in stage_weights), never inside a step program,
        # where XLA would redo the cast of every stacked weight in every
        # call. A tree of the engine's own making is rounded leaf by
        # leaf as it is drawn and is never resident in f32; a caller's
        # f32 tree is cast before the pool exists, while there is room.
        self._inference_params = functools.partial(inference_params,
                                                   model_config)
        self._params = self._inference_params(
            params if params is not None else init_params(
                model_config, jax.random.PRNGKey(seed),
                dtype=model_config.dtype))
        self._cache = init_kv_cache(
            model_config, ec.resolved_num_blocks, ec.kv_block_size,
            *([ec.resolved_window_blocks(model_config)] if Tw else []),
            **({"state_slots": ec.decode_slots} if self._state_bytes
               else {}),
            **({"state_snapshots": ec.resolved_state_snapshots}
               if self._snap_stride else {}))

        S, T = ec.decode_slots, ec.blocks_per_seq
        self._np = np
        self._jnp = jnp
        # Host-side slot arrays: views of the one array a decode step is
        # fed by, [token, length, block-table row] a slot. Block-table
        # row 0s point idle slots at the reserved trash block, so their
        # (masked-garbage) decode writes never touch a live sequence's
        # blocks, and their length _NO_SEQUENCE tells the kernel to
        # read nothing for them. A slot whose prompt is still
        # prefilling is such a slot: its row stays so until the prompt
        # ends (a decode step writes every slot's position `length`,
        # and position 0 of its first block is its first token's, or a
        # shared prefix's).
        # With window layers a row goes on: the absolute position the
        # slot's window table starts at, and that table (the window
        # pool's ids, trash where it holds nothing)
        self._slot_rows = np.zeros((S, 2 + T + (1 + Tw if Tw else 0)),
                                   np.int32)
        self._last_tok = self._slot_rows[:, 0]
        self._seq_lens = self._slot_rows[:, 1]
        self._seq_lens[:] = _NO_SEQUENCE
        self._block_tables = self._slot_rows[:, 2:2 + T]
        self._window_rows = self._slot_rows[:, 2 + T:]
        self._slots: List[Optional[_Request]] = [None] * S
        self._free_slots = list(range(S))
        # refcounted block pool + radix prefix index (block 0 = trash,
        # reserved); sharing off still routes through the pool — match/
        # insert are simply skipped, so the free-list path is one code
        # path either way
        from ray_tpu.serve.prefix_cache import (PrefixBlockPool,
                                                WindowPagePool)
        self._wpool = WindowPagePool(
            ec.resolved_window_blocks(model_config)) if Tw else None
        self._pool = PrefixBlockPool(
            ec.resolved_num_blocks, ec.kv_block_size, reserved=(0,),
            window_pool=self._wpool, window=self._window,
            snapshot_stride=self._snap_stride,
            num_snapshots=ec.resolved_state_snapshots)

        # jit once at the fixed shapes; caches are donated so XLA
        # updates them in place step over step: the trunk carries the
        # pool whole through its layer scan, scatters the new rows into
        # it and hands it whole to the paged kernel, so the donated
        # buffer is the output buffer and nothing copies a layer of it
        # (tests/ops/test_tpu_lowering.py reads that off the programs
        # the TPU compiler builds).
        prefill_fn, decode_fn, verify_fn = _step_fns(model_config, ec)
        self._jit_prefill = jax.jit(prefill_fn, donate_argnums=(2,))
        self._jit_decode = jax.jit(decode_fn, donate_argnums=(2,))
        # speculative verify is jitted once at (S, k+1) — drafting never
        # recompiles
        self._jit_verify = jax.jit(verify_fn, donate_argnums=(2,)) \
            if ec.spec_tokens > 0 else None

        # every pool of the cache (k, v, and the indexer's keys where
        # the model has them) has blocks on axis 1: a page is that index
        # of each, and whatever moves a page moves all of them.
        # copy-on-write block copy (fully-matched prompt tail): one
        # block copied src -> dst across all layers; indices are
        # traced scalars, so every CoW reuses the same compiled program
        # With window layers there are two kinds of page and two pairs of
        # ids: ``window`` = (src, dst) in the window pools
        # The per-slot state arrays a model with recurrent state keeps
        # beside its pools are no pages: the movers go over the pools
        # (``cache_pools``) and hand the rest on as it came
        from ray_tpu.models.transformer import WINDOW_POOLS, cache_pools

        @jax.named_scope("kv_copy")
        def _copy_fn(cache, src, dst, *window):
            def one(name, pool):
                a, b = window if name in WINDOW_POOLS else (src, dst)
                return jax.lax.dynamic_update_slice_in_dim(
                    pool, jax.lax.dynamic_slice_in_dim(pool, a, 1, axis=1),
                    b, axis=1)
            return {**cache, **{name: one(name, pool) for name, pool
                                in cache_pools(cache).items()}}

        self._jit_copy = jax.jit(_copy_fn, donate_argnums=(0,))

        # a prefix hit of a model with recurrent state: snapshot row
        # ``row`` of every state array into slot ``slot``, all layers;
        # traced scalars, one compiled program
        snap_arrays = self._snap_arrays

        @jax.named_scope("state_copy")
        def _snap_copy_fn(cache, row, slot):
            return {**cache, **{
                name: jax.lax.dynamic_update_slice_in_dim(
                    cache[name], jax.lax.dynamic_slice_in_dim(
                        cache[snap], row, 1, axis=1), slot, axis=1)
                for name, snap in snap_arrays.items()}}

        self._jit_snap_copy = jax.jit(_snap_copy_fn, donate_argnums=(0,)) \
            if snap_arrays else None

        # disaggregated hand-off block I/O (serve/disagg.py): gather
        # pulls a request's blocks into one contiguous slab a pool for
        # the wire; scatter adopts shipped slabs into this pool. Both
        # run at the FIXED padded shape (blocks_per_seq ids) so adoption
        # never recompiles — pad ids point at the reserved trash block
        # and pad data is zeros, so the duplicate block-0 writes all
        # write zeros and scatter order cannot matter.
        @jax.named_scope("kv_gather")
        def _gather_fn(cache, ids):
            return {name: jnp.take(pool, ids, axis=1)
                    for name, pool in cache_pools(cache).items()}

        @jax.named_scope("kv_scatter")
        def _scatter_fn(cache, ids, slabs):
            return {**cache, **{name: pool.at[:, ids].set(slabs[name])
                                for name, pool
                                in cache_pools(cache).items()}}

        self._jit_gather = jax.jit(_gather_fn)
        self._jit_scatter = jax.jit(_scatter_fn, donate_argnums=(0,))

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._prefilling: "collections.deque[_Request]" = \
            collections.deque()
        #: step-thread op queue: device work posted from actor-call
        #: threads (warm-prefix export/import) runs at the top of the
        #: next step, where the step thread exclusively owns the
        #: donated caches — no cross-thread device races by design
        self._ops: "collections.deque[dict]" = collections.deque()
        self._rid = 0
        self._stop = False
        self._dead: Optional[BaseException] = None

        self._jax = jax

        # -- in-flight weight refresh (MindSpeed-RL style): the learner
        # stages a fresh param tree + version; the step thread swaps the
        # pointer between decode steps. Slots are NEVER drained, so the
        # sync stall is structurally zero — _sync_stall_s exists to
        # PROVE that (any drain path would have to charge it).
        self._staged_weights: Optional[tuple] = None
        self._weight_version = 0
        self._weight_swaps = 0
        self._weight_casts = 0
        self._weight_swap_wall_s = 0.0
        self._sync_stall_s = 0.0

        # -- stats / metrics -------------------------------------------
        self._tokens_total = 0
        self._decode_steps = 0
        self._prefill_chunks = 0
        # The newest step program's results. A result's device buffer is
        # let go where the next program's results take this place, inside
        # that program's *.dispatch and so while the device runs it; let
        # go when its fetch returns, it is freed with the device idle
        self._spent = None
        # One program ahead: the chunk launched under the last tick's
        # decode step and not fetched yet (_launch_chunk's record), the
        # count of programs launched while another was still out, and
        # the launches that had to wait for a fetch, by reason
        self._ahead: Optional[tuple] = None
        self._programs_ahead = 0
        self._ahead_blocked = dict.fromkeys(
            ("last_chunk", "no_backlog", "op_or_swap", "speculative"), 0)
        # host-to-device transfers the step thread staged: one a step
        # program (its integer inputs as one numpy array, uploaded by
        # the jitted call itself: on the chip that was 0.2 ms a program
        # cheaper than an explicit device_put ahead of the call), so it
        # equals prefill_chunks + decode_steps
        self._h2d_transfers = 0
        # device-wall split:
        # a program's wall runs from its staging, or from the fetch of
        # the program before it where that came later (it was launched
        # ahead and queued behind that one), to its own fetch, so the
        # two walls never overlap and add up to no more than the ticks
        self._decode_wall_s = 0.0
        self._prefill_wall_s = 0.0
        self._fetched_at = 0.0        # time.monotonic of the last fetch
        # the same walls by what they hold (_program_wall): a fetch that
        # blocked ends at its program's completion as the host can first
        # know it, one that found its result ready (is_ready(), asked
        # once before the fetch) says only that the host came late. Two
        # programs that ran back to back finish one device time apart,
        # so a program launched with another out, both fetches blocking,
        # has its time on the DEVICE for a wall; one launched with none
        # out is SERIAL, its wall launch + wait; (class, kind) ->
        # [programs, seconds], a program with a ready fetch on either
        # side in neither class
        self._fetch_blocked = False   # the last fetch had to wait
        self._found_ready = {"prefill": 0, "decode": 0}
        self._class_walls = {(cls, kind): [0, 0.0]
                             for cls in ("device", "serial")
                             for kind in ("prefill", "decode")}
        # length-aware work accounting: pages a lens-skipping kernel
        # touches, summed over decode steps (FLOPs are proportional to
        # pages)
        self._decode_pages_live = 0
        self._decode_slots_skipped = 0
        # by kind of layer: pages ONE layer of the kind reads in the
        # decode programs and in the chunks (full: the sequence's live
        # pages; window: those with a key inside the window), the hits a
        # missing window tail shortened or refused, and the most window
        # pages one sequence has pinned
        self._pages_live = dict.fromkeys(
            ("decode_full", "decode_window", "prefill_full",
             "prefill_window", "prefill_keys_full",
             "prefill_keys_window"), 0)
        self._prefix_hits = self._prefix_hits_cut = 0
        self._window_pinned_max = 0
        # recurrent state: live rows the decode steps updated, live
        # tokens and sequence-calls through the chunk programs
        self._ssm = dict.fromkeys(("decode_rows", "prefill_tokens",
                                   "prefill_calls"), 0)
        # admissions resumed from a snapshot, the pages the trie matched
        # for a model with state, and those of them recomputed because
        # no snapshot stood at their end
        self._snap_hits = dict.fromkeys(("hits", "matched_blocks",
                                         "cut_blocks"), 0)
        # the same for the paged kernel's innermost grid axis: it folds
        # P pages of a sequence a grid step, so a decode call takes
        # slots x ceil(T/P) steps of which sum(ceil(pages/P)) have a
        # live page; P is the kernel's own choice for the decode (or
        # verify) call's shape
        from ray_tpu.ops.paged_flash import paged_pages_per_step
        # a page's heads and row width as the pools have them (a latent
        # cache: one pool, one "head")
        pool = next(iter(cache_pools(self._cache).values()))
        page_heads, row = pool.shape[2], pool.shape[4]
        self._decode_pages_per_step = paged_pages_per_step(
            (ec.spec_tokens + 1) * (model_config.n_heads // page_heads),
            page_heads, ec.kv_block_size, row,
            model_config.dtype, ec.blocks_per_seq,
            block_r=model_config.paged_block_r,
            chip="cpu" if model_config.paged_impl == "interpret" else None,
            pools=2 if "v" in self._cache else 1)
        self._decode_grid_steps = 0
        self._decode_grid_steps_live = 0
        # and for its row-block axis in a chunk's call: row blocks one
        # layer of a kind takes a kv head group and those with a live
        # row (the others, a short prompt's padding, hold no page),
        # summed over chunks; a kind has its own heads a kv head. A
        # model that selects its keys books none: its chunks reach the
        # kernel a run of tokens at a time with the rows head-major
        # (ops/sparse_attention.py), another count.
        selecting = 0 < model_config.index_topk \
            < ec.blocks_per_seq * ec.kv_block_size
        kinds = () if selecting else ("full", "window") if Tw else ("full",)
        self._row_blocks = {kind: [0, 0] for kind in kinds}  # all, live
        self._chunk_rep = {kind: model_config.kind_heads(kind) // page_heads
                           for kind in kinds}
        self._chunk_row_block = dict(
            head_dim=row, dtype=model_config.dtype,
            block_r=model_config.paged_row_block(ec.prefill_chunk),
            chip="cpu" if model_config.paged_impl == "interpret" else None)
        # what a selecting, routing model did, from positions alone (no
        # device work): keys a query could see and keys it attended
        # (min(visible, index_topk)), summed over queries; keys the
        # indexer scored and (token, expert) assignments, over layers
        self._sparse = collections.Counter()
        # layers whose feed-forward is the dropless experts (a layer of
        # another kind may have none), and layers that are a feed-forward
        # alone
        self._expert_layers = model_config.expert_layers
        self._ffn_layers = sum(
            model_config.layer_kind(l) == "ffn"
            for l in range(model_config.n_layers))
        # what the step programs' selected attention was built as
        # (ops/sparse_attention.py), the decode step's and a chunk's:
        # the paged kernel with the selection as a mask, or the plain
        # form ("reference": off the TPU, where the shapes rule the
        # kernel out, and every latent cache). None: the model selects
        # nothing.
        self._sparse_impl = None
        if selecting and model_config.kv_lora_rank:
            self._sparse_impl = {"decode": "reference",
                                 "prefill": "reference"}
        elif selecting:
            from ray_tpu.ops.sparse_attention import (chunk_choice,
                                                      decode_choice)
            self._sparse_impl = {
                "decode": decode_choice(
                    model_config.paged_impl, model_config.index_topk,
                    pool, ec.blocks_per_seq),
                "prefill": chunk_choice(model_config.paged_impl, pool)}
        self._prompt_blocks_total = 0   # full prompt blocks seen
        self._cow_copies = 0
        # disagg hand-off accounting (exports count on the prefill
        # fleet, adopts on the decode fleet)
        self._kv_exports = 0
        self._kv_adopts = 0
        self._kv_adopt_bytes = 0
        self._kv_adopt_blocks = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_disables = 0
        self._occupancy: Dict[int, int] = collections.defaultdict(int)
        self._t_start = time.monotonic()
        self._last_stats_emit = 0.0
        # EWMA of recent TTFTs: the autoscaler's latency signal (a
        # histogram is right for dashboards, wrong for a scale-up
        # decision that wants "what are users seeing RIGHT NOW")
        self._ttft_ewma: Optional[float] = None
        # where first-token time went, summed over ttft_requests (the
        # three parts add up to ttft_s): submit -> slot, slot -> first
        # chunk (behind other requests' chunks), first chunk -> first
        # token, with the ticks and chunks that took; stats() keys
        self._ttft = {
            "ttft_requests": 0, "ttft_s": 0.0, "ttft_queue_s": 0.0,
            "ttft_prefill_wait_s": 0.0, "ttft_prefill_s": 0.0,
            "ttft_prefill_ticks": 0, "ttft_prefill_chunks": 0}
        # the tick's own clock (step thread only): phase totals, the
        # newest spans, profiler annotations, and the tick's books by
        # kind (util/tracing.PhaseClock). A tick's kind is what it
        # FETCHED (_fetched): idle, decode, full, part, full_decode,
        # part_decode, and the _verify forms with speculation on. A part
        # chunk is anything up to a full one: no median bounds it
        from ray_tpu.util.tracing import PhaseClock
        self._clock = PhaseClock(
            f"engine:{replica_tag}" if replica_tag else "engine",
            kind="idle", unbounded=("part",), on_slow=self._tick_slow)
        self._metrics = self._recorder = None
        try:
            from ray_tpu.core.metric_defs import runtime_metrics
            self._metrics = runtime_metrics()
        except Exception:
            pass
        try:
            from ray_tpu.core.global_state import try_global_worker
            w = try_global_worker()
            self._recorder = getattr(w, "recorder", None)
        except Exception:
            pass
        # -- per-request tracing + SLO watchdog --------------------------
        # (serve/request_trace.py, serve/slo.py): the engine is the
        # waterfall's single shipper — router annotations arrive in the
        # call context, every phase span is materialised here, and ONE
        # REQUEST_SPANS batch ships at request end iff sampled /
        # SLO-tripped / failed.
        self._tracer = self._slo = None
        try:
            from ray_tpu.serve.request_trace import RequestTracer
            from ray_tpu.serve.slo import SLOBudget, SLOWatchdog
            cfg = None
            try:
                from ray_tpu.core.global_state import try_global_worker
                cfg = getattr(try_global_worker(), "config", None)
            except Exception:
                pass
            self._tracer = RequestTracer(cfg, part=ec.trace_part)
            if ec.enable_trace is not None:
                self._tracer.enabled = bool(ec.enable_trace)
            self._slo = SLOWatchdog(SLOBudget.from_config(cfg))
        except Exception:
            pass

        # Engine-owned executor for consumer-side queue polls: sharing
        # the actor event loop's default executor would let stream
        # polls and whole actor calls starve each other under load.
        from concurrent.futures import ThreadPoolExecutor
        self._poll_pool = ThreadPoolExecutor(
            2 * ec.decode_slots + 4, thread_name_prefix="llm-engine-poll")

        self._thread = threading.Thread(
            target=self._run, name="llm-engine-step", daemon=True)
        self._thread.start()

    # ------------------------------------------------------- public API
    def stage_weights(self, params, version: int) -> None:
        """Stage a fresh parameter tree for an in-flight refresh. The
        step thread swaps it in at the next step boundary (between
        decode calls) — in-flight sequences finish their current step
        on the old policy and continue on the new one, with every
        emitted token stamped by the version that actually produced it.
        Staging twice before a swap keeps only the newest tree (the
        double buffer holds one pending refresh). Safe from any thread;
        dequantize on the caller's thread, not here. A tree that is not
        yet in the compute dtype (a learner's f32 masters) is cast here,
        on the caller's thread, so the programs always see the avals
        they were compiled for and a refresh compiles nothing."""
        cast = self._inference_params(params)
        with self._work:
            if self._dead is not None:
                raise EngineDeadError(
                    f"engine step loop died: {self._dead!r}")
            self._weight_casts += cast is not params
            self._staged_weights = (cast, int(version))
            self._work.notify_all()

    @property
    def weight_version(self) -> int:
        """Version of the policy the NEXT decode step will run."""
        with self._lock:
            staged = self._staged_weights
            return staged[1] if staged is not None \
                else self._weight_version

    def submit(self, prompt_ids: Sequence[int],
               max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               detailed: bool = False,
               trace_ctx: Optional[Dict[str, Any]] = None,
               _warmup: bool = False, _export: bool = False,
               _adopt: Optional[Dict[str, Any]] = None) -> _Request:
        prompt = [int(t) for t in prompt_ids]
        if not prompt:
            raise ValueError("empty prompt")
        ec = self.config
        if len(prompt) + 1 > ec.max_seq_len:
            raise RequestTooLargeError(
                f"prompt of {len(prompt)} tokens + 1 exceeds the engine "
                f"window max_seq_len={ec.max_seq_len}")
        mnt = max_new_tokens if max_new_tokens is not None \
            else ec.max_new_tokens
        eos = eos_token_id if eos_token_id is not None else ec.eos_token_id
        with self._work:
            if self._dead is not None:
                raise EngineDeadError(
                    f"engine step loop died: {self._dead!r}")
            self._rid += 1
            req = _Request(self._rid, prompt, max(1, int(mnt)), eos)
            req.warmup = _warmup
            req.detailed = detailed
            req.export = _export
            req.adopt = _adopt
            # clamp a skewed cross-process enqueue stamp: the queue
            # wait must never start in this process's future
            now = time.time()
            req.t_enqueue_wall = min(
                float((trace_ctx or {}).get("enqueue_ts") or now), now)
            if not _warmup:
                self._attach_trace(req, trace_ctx)
            self._pending.append(req)
            self._work.notify_all()
        return req

    def _attach_trace(self, req: _Request,
                      trace_ctx: Optional[Dict[str, Any]]) -> None:
        """Open this request's trace. ``trace_ctx`` is the router's
        stamp (request_id, sampled verdict, enqueue timestamp, routing
        annotations) flattened out of the replica call context; a
        direct ``submit`` (RLHF rollouts, tests) gets a locally-minted
        request_id and the tracer's own 1-in-N sampling decision."""
        tr = self._tracer
        if tr is None or not tr.enabled:
            return
        ctx = trace_ctx or {}
        rid = ctx.get("request_id")
        # a caller-pinned id with no explicit sampling verdict (RLHF
        # rollouts stamping ids) keeps the tracer's own 1-in-N; the
        # router always stamps its verdict explicitly
        sampled = ctx.get("sampled") if rid else None
        if sampled is not None:
            sampled = bool(sampled)
        meta = {k: ctx[k] for k in ("policy", "score", "admission")
                if ctx.get(k) is not None}
        trace = tr.begin(request_id=rid, sampled=sampled,
                         meta=meta or None)
        if trace is None:
            return
        req.trace = trace

    def cancel(self, req: _Request) -> None:
        """Mark a request cancelled; the step thread frees its slot and
        blocks at the next step boundary (the generator ``close()``
        path lands here)."""
        with self._work:
            req.cancelled = True
            self._work.notify_all()

    async def generate(self, prompt_ids: Sequence[int],
                       max_new_tokens: Optional[int] = None,
                       eos_token_id: Optional[int] = None,
                       trace_ctx: Optional[Dict[str, Any]] = None):
        """Async token stream for one request. Raises typed errors
        (``EngineDeadError`` / ``RequestTooLargeError``) instead of
        hanging; early ``aclose()`` cancels the request and frees its
        slot + blocks."""
        req = self.submit(prompt_ids, max_new_tokens, eos_token_id,
                          trace_ctx=trace_ctx)
        loop = asyncio.get_running_loop()
        get = functools.partial(req.out.get, timeout=0.2)
        try:
            while True:
                try:
                    item = await loop.run_in_executor(self._poll_pool, get)
                except queue.Empty:
                    if self._dead is not None:
                        raise EngineDeadError(
                            f"engine step loop died: {self._dead!r}")
                    continue
                if item is _DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            self.cancel(req)

    def generate_sync(self, prompt_ids: Sequence[int],
                      max_new_tokens: Optional[int] = None,
                      eos_token_id: Optional[int] = None,
                      timeout_s: float = 120.0,
                      detailed: bool = False,
                      trace_ctx: Optional[Dict[str, Any]] = None):
        """Blocking token stream (tests / direct embedding)."""
        req = self.submit(prompt_ids, max_new_tokens, eos_token_id,
                          detailed=detailed, trace_ctx=trace_ctx)
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                try:
                    item = req.out.get(timeout=0.2)
                except queue.Empty:
                    if self._dead is not None:
                        raise EngineDeadError(
                            f"engine step loop died: {self._dead!r}")
                    if time.monotonic() > deadline:
                        raise TimeoutError("generate_sync timed out")
                    continue
                if item is _DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            self.cancel(req)

    # ------------------------------------------- disagg hand-off API
    def prefill_export(self, prompt_ids: Sequence[int],
                       trace_ctx: Optional[Dict[str, Any]] = None,
                       timeout_s: float = 120.0) -> Dict[str, Any]:
        """Run a prompt through chunked prefill and return the hand-off
        payload (prompt + first token + packed KV slab) instead of
        decoding — the prefill half of the disaggregated pipeline.
        Blocking; see :class:`LLMServer.prefill_export` for the actor
        wrapper."""
        self._refuse_with_window("prefill_export")
        self._refuse_with_state("prefill_export")
        req = self.submit(prompt_ids, max_new_tokens=1,
                          trace_ctx=trace_ctx, _export=True)
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                try:
                    item = req.out.get(timeout=0.2)
                except queue.Empty:
                    if self._dead is not None:
                        raise EngineDeadError(
                            f"engine step loop died: {self._dead!r}")
                    if time.monotonic() > deadline:
                        raise TimeoutError("prefill_export timed out")
                    continue
                if isinstance(item, BaseException):
                    raise item
                if isinstance(item, dict):
                    return item
                if item is _DONE:
                    raise EngineDeadError(
                        "prefill_export stream ended without a payload")
        finally:
            self.cancel(req)

    def submit_adopt(self, payload: Dict[str, Any],
                     max_new_tokens: Optional[int] = None,
                     eos_token_id: Optional[int] = None,
                     detailed: bool = False,
                     trace_ctx: Optional[Dict[str, Any]] = None
                     ) -> _Request:
        """Enqueue a shipped prefill payload for adoption + decode —
        the decode half of the disaggregated pipeline. The returned
        request streams exactly what a colocated ``submit`` of the same
        prompt would have streamed (first token included)."""
        self._refuse_with_window("submit_adopt")
        self._refuse_with_state("submit_adopt")
        if int(payload.get("block_size", 0)) != self.config.kv_block_size:
            raise ValueError(
                f"shipped block_size {payload.get('block_size')} != "
                f"engine kv_block_size {self.config.kv_block_size}")
        return self.submit(payload["prompt"], max_new_tokens,
                           eos_token_id, detailed=detailed,
                           trace_ctx=trace_ctx, _adopt=payload)

    # --------------------------------------- warm-prefix migration API
    def export_warm_prefixes(self, min_hits: int = 1,
                             max_blocks: int = 0
                             ) -> Optional[Dict[str, Any]]:
        """Package this engine's warm ref-0 radix-trie chains (hits >=
        ``min_hits``) for migration to a surviving replica — the
        drain-path rescue of a trie that would otherwise die with this
        process. Runs on the step thread. Returns None when there is
        nothing worth shipping."""
        self._refuse_with_window("export_warm_prefixes")
        self._refuse_with_state("export_warm_prefixes")
        ec = self.config
        bs = ec.kv_block_size
        np, jnp = self._np, self._jnp

        def _do():
            with self._lock:
                chains = self._pool.export_chains(
                    min_hits, max_blocks) \
                    if ec.enable_prefix_sharing else []
                # chains share root prefixes: ship each block once
                slab_idx: Dict[int, int] = {}
                entries: List[tuple] = []   # (chunk tokens, block id)
                for chain in chains:
                    for key, blk in chain:
                        if blk not in slab_idx:
                            slab_idx[blk] = len(entries)
                            entries.append((key, blk))
            if not entries:
                return None
            kv = self._ship_blocks([b for _, b in entries])
            payload = {
                "chains": [[(list(key), slab_idx[blk])
                            for key, blk in chain] for chain in chains],
                "kv": kv,
                "n_blocks": len(entries),
                "block_size": bs,
                "wire": ec.kv_wire,
                "wire_bytes": kv["wire_bytes"],
                "src": self.replica_tag,
            }
            if self._metrics is not None:
                try:
                    self._metrics.serve_prefix_migrated.inc(
                        len(entries), tags={"dir": "export"})
                except Exception:
                    pass
            if self._recorder is not None:
                try:
                    self._recorder.record(
                        "PREFIX_MIGRATE", replica=self.replica_tag,
                        dir="export", blocks=len(entries),
                        chains=len(chains))
                except Exception:
                    pass
            return payload

        return self._run_on_step_thread(_do)

    def import_warm_prefixes(self, payload: Dict[str, Any]) -> int:
        """Adopt a migrated warm-prefix payload into this engine's pool
        + radix trie (ref-0 cached blocks, evictable like any local
        cache). Opportunistic by design: chunks already held locally
        are skipped, and import stops at pool pressure rather than
        evicting this replica's own warm cache — migrated cold blocks
        must never displace proven-hot local ones. Runs on the step
        thread; returns the number of blocks adopted."""
        if payload is None:
            return 0
        self._refuse_with_window("import_warm_prefixes")
        self._refuse_with_state("import_warm_prefixes")
        if int(payload.get("block_size", 0)) != self.config.kv_block_size:
            raise ValueError(
                f"migrated block_size {payload.get('block_size')} != "
                f"engine kv_block_size {self.config.kv_block_size}")
        ec = self.config
        np, jnp = self._np, self._jnp

        def _do():
            plan: List[tuple] = []     # (slab index, local block id)
            with self._lock:
                if not ec.enable_prefix_sharing:
                    return 0
                pool = self._pool
                for chain in payload["chains"]:
                    node = pool._root
                    for key, idx in chain:
                        key = tuple(int(t) for t in key)
                        child = node.children.get(key)
                        if child is not None and not child.detached:
                            node = child
                            continue
                        # pressure guard: free-list only — migration
                        # never evicts local warm cache, and never
                        # recycles a block another import just planned
                        if not pool._free:
                            node = None
                            break
                        blk = pool.allocate(1)[0]
                        nnode, inserted = pool.insert_child(
                            node, key, blk)
                        if not inserted:
                            pool.release([blk])
                            node = nnode
                            if node is None:
                                break
                            continue
                        plan.append((idx, blk))
                        pool.decref(blk)   # ref-0, trie-resident
                        node = nnode
                    # chain truncated: deeper chunks need their parent
            if not plan:
                return 0
            self._adopt_blocks(payload["kv"], plan)
            if self._metrics is not None:
                try:
                    self._metrics.serve_prefix_migrated.inc(
                        len(plan), tags={"dir": "import"})
                except Exception:
                    pass
            if self._recorder is not None:
                try:
                    self._recorder.record(
                        "PREFIX_MIGRATE", replica=self.replica_tag,
                        dir="import", blocks=len(plan),
                        chains=len(payload["chains"]))
                except Exception:
                    pass
            return len(plan)

        return self._run_on_step_thread(_do)

    def _refuse_with_window(self, what: str) -> None:
        """The hand-off and the warm-prefix migration move ONE kind of
        page under one list of ids: with window layers a shipped prefix
        would also need its window tail, which they do not move."""
        if self._window_table:
            raise NotImplementedError(
                f"{what} is not served with window layers "
                f"(sliding_window={self._window}): it ships the full "
                f"layers' pages alone, and a prefix resumed without the "
                f"window layers' rows behind it would not be exact")

    def _state_refusal(self, what: str) -> str:
        kept = "nothing keeps" if not self._snap_stride else \
            "the snapshot rows keep for the trie alone"
        return (f"{what} is not served with recurrent state (a model "
                f"with 'mamba' or 'delta' layers: {self._state_bytes} B a "
                f"decode slot): a sequence's state is one row of its "
                f"slot, as of its last token; a shared, shipped or "
                f"rolled-back prefix would need the state as of the "
                f"prefix's end, which {kept} (state_snapshot_stride "
                f"{self._snap_stride})")

    def _refuse_with_state(self, what: str) -> None:
        """The hand-off and the warm-prefix migration move pages: a
        model with recurrent state would need the state moved too."""
        if self._state_bytes:
            raise NotImplementedError(self._state_refusal(what))

    def _ship_blocks(self, blocks: List[int]) -> Dict[str, Any]:
        """These pages of every pool, packed for the wire (step thread):
        gathered ``blocks_per_seq`` ids at a time, the compiled shape."""
        from ray_tpu.serve.disagg import pack_kv_blocks
        np, T = self._np, self.config.blocks_per_seq
        parts: List[Dict[str, Any]] = []
        for i0 in range(0, len(blocks), T):
            grp = blocks[i0:i0 + T]
            ids = np.zeros((T,), np.int32)
            ids[:len(grp)] = grp
            slabs = self._jit_gather(self._cache, self._jnp.asarray(ids))
            parts.append({name: np.asarray(a)[:, :len(grp)]
                          for name, a in slabs.items()})
        slabs = {name: np.concatenate([p[name] for p in parts], axis=1)
                 for name in parts[0]}
        return pack_kv_blocks(
            slabs.pop("k", None), slabs.pop("v", None),
            self.config.kv_wire, extra=slabs)

    def _adopt_blocks(self, kv: Dict[str, Any], plan: List[tuple]) -> None:
        """Write shipped pages into this pool (step thread): ``plan``
        pairs a slab index of the packed ``kv`` with a local block id."""
        from ray_tpu.serve.disagg import unpack_kv_blocks, unpack_kv_extra
        np, T = self._np, self.config.blocks_per_seq
        slabs = dict(unpack_kv_extra(kv))
        if "k" in kv:
            slabs["k"], slabs["v"] = unpack_kv_blocks(
                kv, dtype=self._cache["k"].dtype)
        if set(slabs) != set(self._cache):
            raise ValueError(
                f"shipped pools {sorted(slabs)} are not this engine's "
                f"{sorted(self._cache)}")
        for i0 in range(0, len(plan), T):
            grp = plan[i0:i0 + T]
            ids = np.zeros((T,), np.int32)
            ids[:len(grp)] = [b for _, b in grp]
            pads = {}
            for name, slab in slabs.items():
                shp = self._cache[name].shape
                pads[name] = np.zeros((shp[0], T) + shp[2:], slab.dtype)
                pads[name][:, :len(grp)] = slab[:, [i for i, _ in grp]]
            self._cache = self._jit_scatter(
                self._cache, self._jnp.asarray(ids),
                {name: self._jnp.asarray(a) for name, a in pads.items()})
        self._jax.block_until_ready(self._cache)

    def _programs(self) -> Dict[str, Any]:
        """The jitted programs this engine can run, by name. With
        speculation on, decode steps go through ``verify`` and the
        plain ``decode`` program is never called."""
        progs = {"prefill": self._jit_prefill, "copy": self._jit_copy}
        if self._jit_snap_copy is not None:
            progs["state_copy"] = self._jit_snap_copy
        # the hand-off's, refused with window layers and with state
        if not (self._window_table or self._state_bytes):
            progs.update(gather=self._jit_gather,
                         scatter=self._jit_scatter)
        if self._jit_verify is not None:
            progs["verify"] = self._jit_verify
        else:
            progs["decode"] = self._jit_decode
        return progs

    def _warm_block_io(self) -> None:
        """Compile the CoW copy and the hand-off gather/scatter (step
        thread). Every index is the reserved trash block and the slab
        written back is zeros, so no live block changes."""
        np, jnp = self._np, self._jnp
        zero = np.int32(0)
        if self._jit_snap_copy is not None:
            # the trash row into slot 0, which holds no sequence yet
            self._cache = self._jit_snap_copy(self._cache, zero, zero)
        if self._window_table or self._state_bytes:
            # no hand-off: the copy alone
            self._cache = self._jit_copy(
                self._cache, *[zero] * (4 if self._window_table else 2))
            self._jax.block_until_ready(self._cache)
            return
        self._cache = self._jit_copy(self._cache, zero, zero)
        ids = jnp.zeros((self.config.blocks_per_seq,), jnp.int32)
        slabs = self._jit_gather(self._cache, ids)
        self._cache = self._jit_scatter(
            self._cache, ids, self._jax.tree.map(jnp.zeros_like, slabs))
        self._jax.block_until_ready(self._cache)

    def warmup(self, timeout_s: float = 600.0) -> None:
        """Compile every jitted program the engine can run — prefill
        and decode (or verify) through one tiny end-to-end generate,
        then the CoW copy and the KV gather/scatter — so nothing
        compiles under traffic, and reset the session counters the
        generate skewed: the TTFT EWMA would otherwise carry the compile
        wall into the gauge router's scoring and starve a
        freshly-scaled-up replica of traffic. Raises if any program
        fails to compile or run."""
        self._run_on_step_thread(self._warm_block_io, timeout_s)
        req = self.submit([2, 3], 2, _warmup=True)
        deadline = time.monotonic() + timeout_s
        try:
            while True:
                try:
                    item = req.out.get(timeout=0.2)
                except queue.Empty:
                    if self._dead is not None:
                        raise EngineDeadError(
                            f"engine step loop died: {self._dead!r}")
                    if time.monotonic() > deadline:
                        raise TimeoutError("warmup timed out")
                    continue
                if item is _DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
        finally:
            self.cancel(req)
        with self._lock:
            self._ttft_ewma = None
            self._t_start = time.monotonic()
            self._tokens_total = 0
            self._decode_steps = 0
            self._prefill_chunks = 0
            self._h2d_transfers = 0
            self._programs_ahead = 0
            self._ahead_blocked = dict.fromkeys(self._ahead_blocked, 0)
            self._decode_wall_s = self._prefill_wall_s = 0.0
            for booked in self._class_walls.values():
                booked[:] = 0, 0.0
            self._found_ready = dict.fromkeys(self._found_ready, 0)
            self._decode_pages_live = 0
            self._decode_slots_skipped = 0
            self._decode_grid_steps = self._decode_grid_steps_live = 0
            for booked in self._row_blocks.values():
                booked[:] = 0, 0
            self._pages_live = dict.fromkeys(self._pages_live, 0)
            self._window_pinned_max = 0
            self._ssm = dict.fromkeys(self._ssm, 0)
            self._snap_hits = dict.fromkeys(self._snap_hits, 0)
            self._sparse.clear()
            self._prompt_blocks_total = 0
            self._occupancy.clear()
            self._clock.reset()

    def stats(self) -> Dict[str, Any]:
        """Scheduler counters (the autoscaling signal surface): queue
        depth, batch occupancy histogram, tokens/s, leak-check views of
        the slot/block free lists."""
        from ray_tpu.ops.attention import dispatch_log
        with self._lock:
            elapsed = max(time.monotonic() - self._t_start, 1e-9)
            ps = self._pool.stats()
            hit_rate = (round(ps["hits_total"]
                              / self._prompt_blocks_total, 4)
                        if self._prompt_blocks_total else None)
            books = self._clock.books()
            phases = books.pop("phases")
            out = {
                "queue_depth": len(self._pending),
                "prefilling": len(self._prefilling),
                "active_slots": sum(1 for r in self._slots
                                    if r is not None),
                "free_slots": len(self._free_slots),
                # reclaimable = free list + ref-0 trie-cached blocks:
                # the leak-check view (cached blocks are warm cache,
                # not leaks — eviction reclaims them on demand)
                "free_blocks": ps["reclaimable"],
                "blocks_cached": ps["cached"],
                "blocks_shared": ps["shared"],
                "total_blocks": self.config.resolved_num_blocks - 1,
                "prefix_hit_blocks_total": ps["hits_total"],
                "prompt_blocks_total": self._prompt_blocks_total,
                "prefix_hit_rate": hit_rate,
                "prefix_evictions_total": ps["evictions_total"],
                "cow_copies_total": self._cow_copies,
                "tokens_total": self._tokens_total,
                "tokens_per_s": round(self._tokens_total / elapsed, 2),
                "decode_steps": self._decode_steps,
                "prefill_chunks": self._prefill_chunks,
                "h2d_transfers_total": self._h2d_transfers,
                # of those programs, the ones launched while the one
                # before them was still out (its fetch came after), and
                # the launches that waited for the fetch, by reason
                "programs_ahead_total": self._programs_ahead,
                "ahead_blocked_total": dict(self._ahead_blocked),
                # device-wall split and length-aware page accounting
                "decode_wall_s": round(self._decode_wall_s, 4),
                "prefill_wall_s": round(self._prefill_wall_s, 4),
                # the walls by class: *_device_* of programs launched
                # behind a running one (fetch to fetch, both blocking:
                # their time on the device), *_serial_* of programs
                # launched with none out (launch + wait); and the
                # fetches that found their result ready, whose programs
                # are in neither
                **self._class_wall_stats(),
                "fetch_found_ready_total": dict(self._found_ready),
                "decode_pages_live": self._decode_pages_live,
                # slots a decode step staged with no sequence (the
                # kernel reads nothing for them), summed over steps:
                # decode_steps x decode_slots less the occupancy
                "decode_slots_skipped_total": self._decode_slots_skipped,
                **self._window_stats(),
                **self._state_stats(),
                # how often the kernel's page groups engage: grid steps
                # a decode call took on its innermost axis, those with
                # a live page to fold, and their ratio
                "decode_pages_per_step": self._decode_pages_per_step,
                "decode_grid_steps": self._decode_grid_steps,
                "decode_grid_steps_live": self._decode_grid_steps_live,
                "decode_grid_live_frac": (
                    round(self._decode_grid_steps_live
                          / self._decode_grid_steps, 4)
                    if self._decode_grid_steps else None),
                # how often a row block's own bound engages: row blocks
                # a chunk's call takes on one layer of a kind, a kv
                # head group, and those with a live row (the rest fold
                # nothing), by kind of layer
                "prefill_row_blocks": {
                    kind: n for kind, (n, _) in self._row_blocks.items()},
                "prefill_row_blocks_live": {
                    kind: n for kind, (_, n) in self._row_blocks.items()},
                "keys_visible_total": self._sparse["visible"],
                "keys_attended_total": self._sparse["attended"],
                "indexer_keys_scored_total": self._sparse["scored"],
                # the same two counts by the kind of program that booked
                # them (they add up to the totals)
                **{f"{name}_{kind}_total": self._sparse[key, kind]
                   for name, key in (("keys_attended", "attended"),
                                     ("indexer_keys_scored", "scored"))
                   for kind in ("decode", "prefill")},
                "moe_assignments_total": self._sparse["assigned"],
                **{f"moe_{kind}_assignments_total":
                   self._sparse["assigned", kind]
                   for kind in ("decode", "prefill")},
                "ffn_layers": self._ffn_layers,
                **self._sparse_stats(),
                "kv_block_size": self.config.kv_block_size,
                "paged_impl": self.model_config.paged_impl,
                # what each traced attention call resolved to and why
                # (ops.attention.dispatch_log) — a reference entry that
                # was not requested is a kernel the shapes ruled out
                "attention_dispatch": dispatch_log(),
                # compiled variants per jitted program: 1 each after
                # warmup, and still 1 under any traffic (shapes are
                # fixed) — more means a compile happened mid-serving
                "compiled_programs": {
                    name: fn._cache_size()
                    for name, fn in self._programs().items()},
                # trie-root fingerprints: the router's prefix-aware
                # COLD-session placement signal (first-turn requests
                # land where their system prompt's KV already lives)
                "prefix_fingerprints": (
                    self._pool.root_fingerprints()
                    if self.config.enable_prefix_sharing else []),
                "occupancy_hist": dict(self._occupancy),
                "ttft_ewma_s": (round(self._ttft_ewma, 6)
                                if self._ttft_ewma is not None else None),
                # the tick's phases, name -> [count, seconds], and two
                # sums of them: all tick time, and the part of it in
                # which the device had nothing queued (from the end of
                # the *.wait that fetched the last program out to the
                # start of the next *.dispatch)
                "phases": phases,
                "tick_wall_s": phases.get("engine.tick", (0, 0.0))[1],
                "host_gap_s": books.pop("gap_s"),
                # the ticks by kind (what each fetched), from the same
                # reading, so they add up to the three above:
                # tick_kind_total / _s / _wait_s / _gap_s, tick_hist_<kind>
                # (a bucket's lower edge in seconds -> ticks),
                # tick_slow_total by kind, tick_slow_s by the phase that
                # held the overrun, and slow_ticks, the newest whole
                **books,
                # first-token time by where it went (ttft_* keys)
                **self._ttft,
                # in-flight weight refresh accounting (RLHF rollout
                # backend): swaps are pointer flips between decode
                # steps, so sync_stall_s — decode time lost waiting on
                # a refresh — must stay 0.0
                # disagg hand-off accounting: exports tick on the
                # prefill fleet, adopts on the decode fleet
                "kv_exports": self._kv_exports,
                "kv_adopts": self._kv_adopts,
                "kv_adopt_bytes": self._kv_adopt_bytes,
                "kv_adopt_blocks": self._kv_adopt_blocks,
                "weight_version": self._weight_version,
                "weight_swaps": self._weight_swaps,
                # bytes of the tree the programs take, and how many
                # refreshes arrived outside the compute dtype and were
                # cast on the stager's thread
                "weight_bytes": sum(
                    x.nbytes for x in
                    self._jax.tree.leaves(self._params)),
                "weight_casts_total": self._weight_casts,
                "weight_swap_wall_s": round(self._weight_swap_wall_s,
                                            6),
                "sync_stall_s": round(self._sync_stall_s, 6),
                "dead": repr(self._dead) if self._dead else None,
            }
            if self.config.spec_tokens > 0:
                out["spec"] = {
                    "drafted": self._spec_drafted,
                    "accepted": self._spec_accepted,
                    "acceptance_rate": (
                        round(self._spec_accepted / self._spec_drafted,
                              4) if self._spec_drafted else None),
                    "disables": self._spec_disables,
                }
            return out

    def _window_stats(self) -> Dict[str, Any]:
        """``stats()``' keys of the second kind of page (call with the
        lock held); none for a model without window layers."""
        if self._wpool is None:
            return {}
        ws = self._wpool.stats()
        live = self._pages_live
        return {
            # gauges: the window pool's pages, those free or cached,
            # those some sequence pins now, and the most one has pinned
            # (the bound window_blocks_per_seq, which the tests hold)
            "window_total_blocks": self._wpool.total_managed,
            "window_free_blocks": ws["reclaimable"],
            "window_pages_pinned": ws["active"],
            "window_pages_pinned_max": self._window_pinned_max,
            # pages given back behind the window, and cached ones evicted
            # (tails lost to pressure: what num_window_blocks is sized by)
            "window_pages_released": ws["released_total"],
            "window_evictions_total": ws["evictions_total"],
            # pages one layer of a kind reads, summed over the programs
            # (decode_pages_live is the full layers' count)
            "decode_pages_live_full": live["decode_full"],
            "decode_pages_live_window": live["decode_window"],
            "prefill_pages_live_full": live["prefill_full"],
            "prefill_pages_live_window": live["prefill_window"],
            # keys the chunks' query rows attend in one layer of a kind,
            # summed (position p: p + 1, on a window layer the window's
            # at most)
            "prefill_keys_live_full": live["prefill_keys_full"],
            "prefill_keys_live_window": live["prefill_keys_window"],
            # requests admitted on a trie hit, and those of them whose
            # hit a missing window tail shortened or refused
            "prefix_hits": self._prefix_hits,
            "prefix_hits_cut": self._prefix_hits_cut,
        }

    def _sparse_stats(self) -> Dict[str, Any]:
        """``stats()``' keys of a selecting model's step programs (call
        with the lock held): the form the decode step's and a chunk's
        selected attention were built as, and the decode steps that ran
        through the kernel (all, or none where the gather runs; a
        verify step is a chunk's call)."""
        impl = self._sparse_impl
        if impl is None:
            return {}
        through = impl["decode"] != "reference" \
            and not self.config.spec_tokens
        return {"sparse_decode_impl": impl["decode"],
                "sparse_prefill_impl": impl["prefill"],
                "sparse_decode_kernel_steps_total":
                    self._decode_steps if through else 0}

    def _state_stats(self) -> Dict[str, Any]:
        """``stats()``' keys of the recurrent state (call with the lock
        held); none for a model that keeps pages and nothing else."""
        if not self._state_bytes:
            return {}
        out = {
            # a slot's state is its decode slot's row: as many as slots,
            # each this many bytes over all layers, whatever the length
            "state_slots_total": self.config.decode_slots,
            "state_bytes_per_slot": self._state_bytes,
        }
        # rows with a sequence the decode steps updated (one layer's
        # count), summed over steps; live tokens and sequence-calls
        # through the chunk programs: under the name the layer plan gives
        # the recurrence
        kind = self._state_counters
        out.update({f"{kind}_decode_rows_total": self._ssm["decode_rows"],
                    f"{kind}_prefill_tokens_total":
                        self._ssm["prefill_tokens"],
                    f"{kind}_prefill_calls_total":
                        self._ssm["prefill_calls"]})
        if self._state_step_impl is not None:
            # ... and how many of them the kernel updated: all, or none
            # where the step fell back to XLA's plain form
            out.update({
                f"{kind}_step_impl": self._state_step_impl,
                f"{kind}_kernel_rows_total": self._ssm["decode_rows"]
                if self._state_step_impl != "reference" else 0})
        if self._snap_stride:
            ps = self._pool.stats()
            out.update({
                # gauges: the snapshot rows and those a trie node names;
                # counters: rows attached to a node, rows taken from one
                # (for another's call, or with the node's eviction)
                "state_snapshots_total": ps["snapshots_total"],
                "state_snapshots_live": ps["snapshots_live"],
                "state_snapshots_taken_total": ps["snapshots_taken_total"],
                "state_snapshots_evicted_total":
                    ps["snapshots_evicted_total"],
                "state_hits_total": self._snap_hits["hits"],
                "state_matched_blocks_total":
                    self._snap_hits["matched_blocks"],
                "state_cut_blocks_total": self._snap_hits["cut_blocks"]})
        return out

    def pool_audit(self) -> List[str]:
        """Block-accounting integrity check (leak regression tests):
        empty list = every refcounted block is exactly one of
        free/active/cached and the trie holds no dangling entries."""
        with self._lock:
            return self._pool.audit()

    def shutdown(self) -> None:
        with self._work:
            self._stop = True
            self._work.notify_all()
        self._thread.join(timeout=10)
        self._poll_pool.shutdown(wait=False)

    # -------------------------------------------------------- step loop
    def _run(self) -> None:
        try:
            while True:
                with self._work:
                    while not self._stop and not self._has_work_locked():
                        self._work.wait(timeout=0.5)
                    if self._stop:
                        break
                self._step()
        except BaseException as e:  # noqa: BLE001 — fail typed, never hang
            self._on_dead(e)

    def _has_work_locked(self) -> bool:
        return bool(self._pending) or bool(self._prefilling) \
            or self._op_or_swap_pending_locked() \
            or any(r is not None for r in self._slots)

    def _on_dead(self, e: BaseException) -> None:
        with self._work:
            self._dead = e
            reqs = [r for r in self._slots if r is not None]
            reqs += list(self._prefilling) + list(self._pending)
            self._pending.clear()
            self._prefilling.clear()
            ops = list(self._ops)
            self._ops.clear()
        err = EngineDeadError(f"engine step loop died: {e!r}")
        err.__cause__ = e
        for r in set(reqs):
            self._close_trace(r, err)
            r.out.put(err)
        for op in ops:                 # never strand an op waiter
            op["box"]["e"] = err
            op["done"].set()

    def _run_on_step_thread(self, fn, timeout_s: float = 30.0):
        """Run ``fn`` on the step thread (the donated caches' only
        owner) at the next step boundary and return its result. The
        warm-prefix migration paths use this so their gathers/scatters
        can never interleave with an in-flight donated-cache update."""
        op = {"fn": fn, "done": threading.Event(), "box": {}}
        with self._work:
            if self._dead is not None:
                raise EngineDeadError(
                    f"engine step loop died: {self._dead!r}")
            self._ops.append(op)
            self._work.notify_all()
        if not op["done"].wait(timeout_s):
            raise TimeoutError("engine step-thread op timed out")
        if "e" in op["box"]:
            raise op["box"]["e"]
        return op["box"].get("r")

    def _drain_ops(self) -> None:
        while True:
            with self._lock:
                op = self._ops.popleft() if self._ops else None
            if op is None:
                return
            try:
                op["box"]["r"] = op["fn"]()
            except BaseException as e:  # noqa: BLE001 — typed to waiter
                op["box"]["e"] = e
            finally:
                op["done"].set()

    # one engine step: drain posted ops -> swap staged weights -> reap
    # -> admit -> one prefill chunk -> one decode. The two programs run
    # on the device in that order, and the step thread stays ONE PROGRAM
    # AHEAD of it where it holds the next program's inputs in full: the
    # decode step is launched before the chunk is fetched (unless the
    # chunk ends its prompt: its token is a decode input), and the
    # backlog's next chunk before the decode step is fetched. Each
    # launch and each fetch-book-emit then passes under the other
    # program's device time; at most one program is queued behind the
    # running one, and what runs, and in which order, is the serial
    # tick's.
    def _step(self) -> None:
        clock = self._clock
        clock.tick()
        with clock.phase("engine.tick"):
            # the chunk launched under the last tick's decode step is
            # this tick's chunk
            chunk, self._ahead = self._ahead, None
            had_chunk = chunk is not None
            if had_chunk:
                with self._lock:
                    pending = self._op_or_swap_pending_locked()
                if pending:
                    # a posted op or a staged swap finds nothing out
                    self._finish_chunk(chunk)
                    chunk = None
            with clock.phase("engine.ops"):
                if chunk is None:
                    self._drain_ops()
                    self._maybe_swap_weights()
                self._reap_cancelled(chunk)
            with clock.phase("engine.admit"):
                self._admit()
            if not had_chunk:
                chunk = self._launch_chunk()
            if self.config.spec_tokens > 0:
                # drafting reads the host's history: serial throughout
                if chunk is not None:
                    self._finish_chunk(chunk)
                self._decode_speculative()
            else:
                self._chunk_then_decode(chunk)
            with clock.phase("engine.report"):
                self._emit_stats()

    def _chunk_then_decode(self, chunk: Optional[tuple]) -> None:
        """Fetch this tick's chunk (launched, or None) and run its
        decode step, each launch ahead of the other's fetch where the
        inputs allow."""
        decode = None
        if chunk is not None:
            req, start, n = chunk[:3]
            active = self._decoding()
            # a decode step's rows are every active slot's last token:
            # the chunk that ends its prompt brings one of them
            if active and self._go_ahead(
                    "last_chunk" if start + n == len(req.prompt)
                    else None):
                decode = self._launch_decode(active)
            self._finish_chunk(chunk)
        if decode is None:
            active = self._decoding()
            if not active:
                return
            decode = self._launch_decode(active)
        # the next chunk's row is prompt tokens, start, n and a table
        # row: all booked by now. The request at the backlog's head
        # stays there until its prompt ends (admission appends; a
        # cancelled head is reaped at the next tick's top, so its next
        # chunk is nobody's)
        with self._lock:
            head = self._prefilling[0] if self._prefilling else None
            reason = "no_backlog" if head is None or head.cancelled \
                else "op_or_swap" if self._op_or_swap_pending_locked() \
                else None
        if self._go_ahead(reason):
            self._ahead = self._launch_chunk()
        self._finish_decode(decode)

    def _go_ahead(self, blocked: Optional[str]) -> bool:
        """Book one launch that could pass ahead of the running
        program's fetch: it does, or waits for the fetch and why."""
        if blocked is None:
            self._programs_ahead += 1
            return True
        self._ahead_blocked[blocked] += 1
        return False

    def _op_or_swap_pending_locked(self) -> bool:
        return bool(self._ops) or self._staged_weights is not None

    def _fetched(self, what: str) -> None:
        """The running tick has fetched a program's result: name the
        tick after it (its kind in the clock's books; a tick fetches at
        most a chunk, ``full`` or ``part``, and then a step)."""
        kind = self._clock.kind
        self._clock.kind = what if kind is None else f"{kind}_{what}"

    def _tick_slow(self, tick: tuple, phase: str) -> None:
        """The clock's ``on_slow``: a slow tick goes once to the flight
        recorder, a slice that ends where the tick did, named after the
        phase that held most of it."""
        if self._recorder is None:
            return
        number, kind, _, seconds, held = tick
        try:
            self._recorder.record(
                "ENGINE_TICK_SLOW", replica=self.replica_tag,
                tick=number, kind=kind, phase=phase,
                dur_s=round(seconds, 6),
                held_s={name: round(s, 6) for name, s in held.items()})
        except Exception:
            pass

    def _program_wall(self, kind: str, t0: float, ready: bool) -> float:
        """A fetch has just returned: the fetched program's wall, from
        its staging at ``t0`` or from the fetch before it, whichever
        came later (``prefill_wall_s`` / ``decode_wall_s``), booked by
        class as well. ``ready``: the result was there before the fetch
        asked."""
        now = time.monotonic()
        ahead = t0 < self._fetched_at   # staged with the one before out
        wall = now - max(t0, self._fetched_at)
        if ready:
            self._found_ready[kind] += 1
        elif not ahead or self._fetch_blocked:
            booked = self._class_walls[
                "device" if ahead else "serial", kind]
            booked[0] += 1
            booked[1] += wall
        self._fetched_at, self._fetch_blocked = now, not ready
        return wall

    def _class_wall_stats(self) -> Dict[str, Any]:
        """``stats()``' eight keys of the walls by class."""
        out = {}
        for (cls, kind), (n, seconds) in self._class_walls.items():
            unit = "chunks" if kind == "prefill" else "steps"
            out[f"{kind}_{cls}_{unit}"] = n
            out[f"{kind}_{cls}_s"] = round(seconds, 6)
        return out

    def _maybe_swap_weights(self) -> None:
        """Apply a staged weight refresh between decode steps: a pure
        pointer swap on the step thread (the only device-state owner),
        so in-flight decode slots are never drained and no request
        waits. The swap wall is the full cost of the refresh as seen by
        decode — booked separately from _sync_stall_s, which stays 0
        because no slot ever blocks on it."""
        with self._lock:
            staged = self._staged_weights
            if staged is None:
                return
            self._staged_weights = None
            active_reqs = [r for r in self._slots if r is not None]
            active = len(active_reqs)
        t0w = time.time()
        t0 = time.monotonic()
        params, version = staged
        self._params = params
        swap_s = time.monotonic() - t0
        now_w = time.time()
        for r in active_reqs:
            # the swap overlapped these requests' decode: annotate each
            # waterfall with the version boundary it decoded across
            if r.trace is not None:
                r.trace.span(RT.WEIGHT_SWAP, t0w, now_w,
                             version=version)
        with self._lock:
            self._weight_version = version
            self._weight_swaps += 1
            self._weight_swap_wall_s += swap_s
        if self._recorder is not None:
            try:
                self._recorder.record(
                    "RLHF_SYNC", replica=self.replica_tag,
                    version=version, swap_s=round(swap_s, 6),
                    active_slots=active)
            except Exception:
                pass

    def _reap_cancelled(self, chunk: Optional[tuple] = None) -> None:
        """Release what was cancelled since the last tick. The request
        whose ``chunk`` is in flight keeps its slot and blocks until the
        chunk is booked: the next tick reaps it, or the booking does
        where the chunk ends its prompt."""
        flying = chunk[0] if chunk is not None else None
        with self._lock:
            for req in list(self._prefilling):
                if req.cancelled and req is not flying:
                    self._prefilling.remove(req)
                    self._release_locked(req)
            for req in list(self._pending):
                if req.cancelled:
                    self._pending.remove(req)
                    self._close_trace(req)
                    req.out.put(_DONE)
            for req in self._slots:
                if req is not None and req.cancelled:
                    self._release_locked(req)

    def _admit(self) -> None:
        ec = self.config
        bs = ec.kv_block_size
        while True:
            with self._lock:
                if not self._pending or not self._free_slots:
                    return
                head_adopt = self._pending[0].adopt is not None
            if head_adopt:
                # disagg adoption: shipped KV blocks, no prefill
                if not self._admit_adopt(self._pending[0]):
                    return          # pool pressure: wait for blocks
                continue
            with self._lock:
                if not self._pending or not self._free_slots:
                    return
                req = self._pending[0]
                plen = len(req.prompt)
                need = -(-min(plen + req.max_new_tokens,
                              ec.max_seq_len) // bs)
                # -- radix prefix match: matched full blocks are shared
                # (incref'd) and skip prefill entirely; a fully-matched
                # block-aligned prompt keeps its LAST matched block as a
                # copy-on-write source so the final token still runs
                # through prefill for its logits
                matched: List[int] = []
                mtok = 0
                cow_src = None
                # the window layers' pages behind the boundary, taken
                # with it, and whether a missing one cut the hit short
                wtail: Dict[int, int] = {}
                cut = False
                # recurrent state: the snapshot row a hit resumes from,
                # and the pages matched beyond it (recomputed)
                snap_row = cut_blocks = 0
                if ec.enable_prefix_sharing:
                    if self._snap_stride:
                        matched, mtok, req.trie_node, snap_row, \
                            cut_blocks = self._pool.match_prefix_state(
                                req.prompt)
                    elif self._wpool is None:
                        matched, mtok, req.trie_node = \
                            self._pool.match_prefix(req.prompt)
                    else:
                        matched, mtok, req.trie_node, wtail, cut = \
                            self._pool.match_prefix_window(req.prompt)
                    if mtok == plen and matched:
                        cow_src = matched.pop()
                        mtok -= bs
                n_priv = need - len(matched) - (1 if cow_src is not None
                                                else 0)
                priv = self._allocate_locked(n_priv)
                if priv is None:
                    # full occupancy: release the match and WAIT for
                    # blocks (shapes are fixed; admission pressure
                    # never grows the compiled batch)
                    self._pool.release(matched)
                    if cow_src is not None:
                        self._pool.release([cow_src])
                    for wblock in wtail.values():
                        self._wpool.decref(wblock)
                    req.trie_node = None
                    return
                cow_dst = None
                wcow = None
                if cow_src is not None:
                    cow_dst = priv[0]
                    priv = priv[1:]
                    self._cow_copies += 1
                    if wtail:
                        # the window layers' page of the same chunk: a
                        # private copy as well (a sequence can always pin
                        # its share of the window pool)
                        page = plen // bs - 1
                        wcow = wtail[page], self._wpool.allocate(1)[0]
                        wtail[page] = wcow[1]
                req.wpages, req.wspan = wtail, None
                req.blocks = matched + \
                    ([cow_dst] if cow_dst is not None else []) + priv
                req.hit_blocks = len(matched) + \
                    (1 if cow_src is not None else 0)
                self._prefix_hits_cut += cut
                self._prefix_hits += bool(cut or req.hit_blocks)
                self._snap_hits["hits"] += bool(snap_row)
                self._snap_hits["matched_blocks"] += len(matched) \
                    + cut_blocks
                self._snap_hits["cut_blocks"] += cut_blocks
                self._pool.count_hits(req.hit_blocks)
                req.trie_cursor = req.hit_blocks
                req.prefill_pos = (plen - 1) if cow_src is not None \
                    else mtok
                self._prompt_blocks_total += -(-plen // bs)
                self._pending.popleft()
                req.slot = self._free_slots.pop()
                self._block_tables[req.slot, :] = 0
                self._seq_lens[req.slot] = _NO_SEQUENCE
                req.state = _PREFILL
                self._slots[req.slot] = req
                self._prefilling.append(req)
                if req.hit_blocks and self._metrics is not None:
                    try:
                        self._metrics.serve_prefix_hits.inc(
                            req.hit_blocks)
                    except Exception:
                        pass
                now = self._stamp_slot(req)
                if req.trace is not None:
                    req.trace.span(RT.QUEUED, req.t_enqueue_wall, now)
                    req.trace.span(RT.ADMITTED, now, None,
                                   slot=req.slot,
                                   hit_blocks=req.hit_blocks,
                                   prefix_tokens=mtok,
                                   cow=cow_src is not None)
                    self._slo.observe_queue(req.trace,
                                            req.queue_wait_s)
            # device-side copies OUTSIDE the lock (the step thread is the
            # only device user; submit/cancel stay responsive). The
            # snapshot goes into the slot ahead of the request's first
            # chunk, and ahead of any later call that could be handed the
            # row: the device runs its programs in the order of dispatch
            if snap_row:
                self._cache = self._jit_snap_copy(
                    self._cache, self._np.int32(snap_row),
                    self._np.int32(req.slot))
            if cow_src is not None:
                ids = [cow_src, cow_dst] + list(wcow or ())
                self._cache = self._jit_copy(
                    self._cache, *(self._np.int32(i) for i in ids))
                with self._lock:
                    self._pool.release([cow_src])
                    if wcow is not None:
                        self._wpool.decref(wcow[0])

    def _allocate_locked(self, n: int) -> Optional[List[int]]:
        """``n`` private blocks for an admission, or None under pool
        pressure (call with self._lock held). What the free list cannot
        cover is evicted from the trie, under a phase of its own."""
        if n <= len(self._pool._free):
            return self._pool.allocate(n)
        with self._clock.phase("engine.admit.evict"):
            return self._pool.allocate(n)

    def _stamp_slot(self, req: _Request) -> float:
        """A decode slot is won: the end of the queue wait, traced or
        not. Returns the wall clock the trace spans use."""
        req.t_slot = time.monotonic()
        now = time.time()
        req.queue_wait_s = max(0.0, now - req.t_enqueue_wall)
        return now

    # --------------------------------------------- disagg adopt / export
    def _admit_adopt(self, req: _Request) -> bool:
        """Admit a disagg hand-off: slot + blocks like a normal request,
        but the prompt's KV arrives in the shipped slab instead of via
        prefill. Blocks the local radix trie already holds are reused
        (their slab copy is skipped — the bytes were shipped but the
        scatter isn't repeated); the rest are scattered into the pool,
        then every full prompt chunk is trie-indexed so the shipped
        prefix is warm here from now on. Never copy-on-write: the first
        token came with the payload, so a fully block-aligned matched
        prompt just starts decode in a fresh private block. Returns
        False — with nothing taken — on pool pressure (admission wait).
        """
        np = self._np
        ec = self.config
        bs = ec.kv_block_size
        payload = req.adopt
        t0w = time.time()
        with self._lock:
            if not self._free_slots:
                return False
            plen = len(req.prompt)
            n_ship = min(int(payload["n_blocks"]), -(-plen // bs))
            need = -(-min(plen + req.max_new_tokens,
                          ec.max_seq_len) // bs)
            matched: List[int] = []
            mtok = 0
            if ec.enable_prefix_sharing:
                matched, mtok, req.trie_node = \
                    self._pool.match_prefix(req.prompt)
            priv = self._allocate_locked(need - len(matched))
            if priv is None:
                self._pool.release(matched)
                req.trie_node = None
                return False
            req.blocks = matched + priv
            req.hit_blocks = len(matched)
            self._pool.count_hits(req.hit_blocks)
            req.trie_cursor = req.hit_blocks
            req.prefill_pos = plen
            self._prompt_blocks_total += -(-plen // bs)
            self._pending.popleft()
            req.slot = self._free_slots.pop()
            self._block_tables[req.slot, :] = 0
            self._block_tables[req.slot, :len(req.blocks)] = req.blocks
            self._seq_lens[req.slot] = _NO_SEQUENCE
            req.state = _PREFILL
            self._slots[req.slot] = req
            if req.hit_blocks and self._metrics is not None:
                try:
                    self._metrics.serve_prefix_hits.inc(req.hit_blocks)
                except Exception:
                    pass
            now = self._stamp_slot(req)
            # no chunk of its own: the shipped slab is its prefill
            req.t_first_chunk = req.t_slot
            req.tick_first_chunk = self._clock.tick_no
            if req.trace is not None:
                req.trace.span(RT.QUEUED, req.t_enqueue_wall, now)
                req.trace.span(RT.ADMITTED, now, None, slot=req.slot,
                               hit_blocks=req.hit_blocks,
                               prefix_tokens=mtok, adopt=True)
                self._slo.observe_queue(req.trace, req.queue_wait_s)
            # physical destinations for the slab blocks the local trie
            # did NOT already hold
            dst = req.blocks[req.hit_blocks:n_ship]
        # scatter OUTSIDE the lock (step thread owns the device)
        if dst:
            self._adopt_blocks(payload["kv"], list(zip(
                range(req.hit_blocks, n_ship), dst)))
        t1w = time.time()
        first = int(payload["first"])
        req.seq_len = plen
        req.t_first_token = time.monotonic()
        ship_ts = min(float(payload.get("ship_ts") or t0w), t0w)
        wire = payload.get("wire", ec.kv_wire)
        if req.trace is not None:
            req.trace.span(RT.KV_SHIP, ship_ts, t0w,
                           bytes=payload.get("wire_bytes"), wire=wire,
                           src=payload.get("src"))
            req.trace.span(RT.KV_ADOPT, t0w, t1w,
                           blocks=len(dst), reused=req.hit_blocks,
                           bytes=payload.get("wire_bytes"), wire=wire)
        self._kv_adopts += 1
        self._kv_adopt_bytes += int(payload.get("wire_bytes") or 0)
        self._kv_adopt_blocks += len(dst)
        if self._metrics is not None:
            try:
                self._metrics.serve_kv_ship_seconds.observe(
                    max(0.0, t1w - ship_ts))
            except Exception:
                pass
        if self._recorder is not None:
            try:
                self._recorder.record(
                    "KV_ADOPT", replica=self.replica_tag,
                    blocks=len(dst), reused=req.hit_blocks,
                    dur_s=round(t1w - t0w, 6))
            except Exception:
                pass
        self._first_token(req)
        with self._lock:
            # trie-index every full prompt chunk: the shipped prefix
            # is warm on THIS replica for later requests
            if req.trie_node is not None:
                while req.trie_node is not None and \
                        req.trie_cursor < plen // bs:
                    i = req.trie_cursor
                    node, _ = self._pool.insert_child(
                        req.trie_node, req.prompt[i * bs:(i + 1) * bs],
                        req.blocks[i])
                    req.trie_node = node
                    req.trie_cursor += 1
            if req.cancelled:
                self._release_locked(req)
                return True
            if req.eos_token_id is not None \
                    and first == req.eos_token_id:
                self._release_locked(req)
                return True
            req.generated = 1
            req.out.put(self._item(req, first,
                                   payload.get("first_lp")))
            req.history.append(first)
            self._tokens_total += 1
            if req.generated >= req.max_new_tokens:
                self._release_locked(req)
                return True
            req.state = _DECODE
            self._last_tok[req.slot] = first
            self._seq_lens[req.slot] = req.seq_len
        return True

    def _finish_export(self, req: _Request, first: int, lp) -> None:
        """Terminal step of a prefill-export request: gather the
        prompt's finished KV blocks into one contiguous slab, pack it
        for the wire, hand the payload to the waiting ``prefill_export``
        call, and free the slot — this engine never decodes it."""
        np = self._np
        ec = self.config
        bs = ec.kv_block_size
        plen = len(req.prompt)
        n_ship = -(-plen // bs)
        t0w = time.time()
        with self._lock:
            self._prefilling.popleft()
            if req.cancelled:
                self._release_locked(req)
                return
            shipped = list(req.blocks[:n_ship])
        kv = self._ship_blocks(shipped)
        payload = {
            "prompt": list(req.prompt),
            "first": int(first),
            "first_lp": None if lp is None else float(lp[0]),
            "kv": kv,
            "n_blocks": n_ship,
            "block_size": bs,
            "wire": ec.kv_wire,
            "wire_bytes": kv["wire_bytes"],
            "ship_ts": time.time(),
            "src": self.replica_tag,
        }
        t1w = time.time()
        self._kv_exports += 1
        if req.trace is not None:
            req.trace.span(RT.KV_SHIP, t0w, t1w,
                           bytes=kv["wire_bytes"], wire=ec.kv_wire,
                           blocks=n_ship, dir="export")
        if self._metrics is not None:
            try:
                self._metrics.serve_kv_ship_bytes.inc(
                    kv["wire_bytes"], tags={"wire": ec.kv_wire})
            except Exception:
                pass
        if self._recorder is not None:
            try:
                self._recorder.record(
                    "KV_SHIP", replica=self.replica_tag,
                    blocks=n_ship, bytes=kv["wire_bytes"],
                    wire=ec.kv_wire)
            except Exception:
                pass
        with self._lock:
            req.out.put(payload)
            self._release_locked(req)

    def _window_row(self, req: _Request, start: int, n: int):
        """The window layers' side of a program over positions ``start
        .. start + n - 1`` of ``req`` (call with the lock held): give
        back the pages wholly behind the window of ``start`` (to the
        trie's cache where it names them), take pages for the rows to be
        written, and return ``[first position, table]`` for the row, or
        None where the staged one still holds (no page came or went)."""
        bs, pool = self.config.kv_block_size, self._wpool
        first = max(0, start - self._window + 1) // bs
        last = (start + n - 1) // bs
        if req.wspan == (first, last):
            return None
        plen = len(req.prompt)
        for page in [p for p in req.wpages if p < first]:
            pool.decref(req.wpages.pop(page),
                        near=self._near_prompt_end(page, plen))
            pool.released_total += 1
        new = [p for p in range(first, last + 1) if p not in req.wpages]
        if new and new[0] < start // bs:
            raise RuntimeError(
                f"window page {new[0]} of a sequence at position {start} "
                f"was given back while a query still sees it")
        got = pool.allocate(len(new))
        if got is None:
            raise RuntimeError(
                f"the window pool cannot cover {len(new)} pages: "
                f"{pool.stats()}")
        req.wpages.update(zip(new, got))
        req.wspan = first, last
        self._window_pinned_max = max(self._window_pinned_max,
                                      len(req.wpages))
        row = self._np.zeros((1 + self._window_table,), self._np.int32)
        row[0] = first * bs
        row[1:2 + last - first] = [req.wpages[p]
                                   for p in range(first, last + 1)]
        return row

    def _near_prompt_end(self, page: int, prompt_len: int) -> bool:
        """The eviction class of a cached window page: within two
        windows of its request's prompt end (a re-ask of the document
        resumes from there) or far behind it (evicted first)."""
        return (page + 1) * self.config.kv_block_size \
            > prompt_len - 2 * self._window

    def _launch_chunk(self) -> Optional[tuple]:
        """Stage and dispatch the next chunk of the request at the
        backlog's head. Returns what :meth:`_finish_chunk` needs, the
        request, ``start`` and ``n`` first, or None with no backlog."""
        with self._lock:
            req = self._prefilling[0] if self._prefilling else None
        if req is None:
            return None
        np = self._np
        ec = self.config
        clock = self._clock
        C, bs = ec.prefill_chunk, ec.kv_block_size
        start = req.prefill_pos
        n = min(C, len(req.prompt) - start)
        with clock.phase("engine.prefill.stage"):
            slotted = bool(self._state_bytes)
            row = np.zeros((1, C + self._slot_rows.shape[1] + slotted
                            + (ec.snapshots_per_chunk if slotted else 0)),
                           np.int32)
            row[0, :n] = req.prompt[start:start + n]
            row[0, C:C + 2] = start, n
            row[0, C + 2:C + 2 + len(req.blocks)] = req.blocks
            snaps: Dict[int, int] = {}    # boundary position -> row
            if slotted:
                row[0, -1] = req.slot     # where its state lives
                if self._snap_stride and req.trie_node is not None:
                    snaps = self._stage_snapshots(req, start, n, row)
            if self._wpool is not None:
                with self._lock, clock.phase("engine.window.release"):
                    row[0, C + 2 + ec.blocks_per_seq:
                        C + self._slot_rows.shape[1]] = \
                        self._window_row(req, start, n)
                    req.wspan = None      # a chunk's table is its own
                live, w = self._pages_live, self._window
                live["prefill_full"] += -(-(start + n) // bs)
                live["prefill_window"] += (start + n - 1) // bs + 1 \
                    - max(0, start - w + 1) // bs
                # keys seen: position p sees p + 1, a window layer w at most
                live["prefill_keys_full"] += n * start + n * (n + 1) // 2
                ramp = max(0, min(start + n, w) - start)   # rows under w
                live["prefill_keys_window"] += ramp * start \
                    + ramp * (ramp + 1) // 2 + (n - ramp) * w
            self._account_row_blocks(n)
            t0w = time.time()
            t0 = time.monotonic()
            if req.t_first_chunk is None:
                req.t_first_chunk = t0
                req.tick_first_chunk = clock.tick_no
                if req.trace is not None:
                    # slot won, waiting behind other requests' chunks
                    req.trace.span(RT.PREFILL_WAIT,
                                   t0w - (t0 - req.t_slot), t0w)
            self._prefill_chunks += 1
            self._h2d_transfers += 1
        with clock.phase("engine.prefill.dispatch"):
            # the call uploads the row, its one transfer, and holds the
            # only reference to the device's copy: nothing of it is left
            # to free on the next program's path; the last program's
            # results go here, under this one (_spent), unless they are
            # still to be fetched (then their record holds them)
            out = self._spent = self._jit_prefill(self._params, row,
                                                  self._cache)
            # the next launch takes the pool this one returns
            if self.config.capture_logprobs:
                tok, lp, self._cache = out
            else:
                tok, self._cache = out
                lp = None
        return req, start, n, t0, t0w, tok, lp, snaps

    def _stage_snapshots(self, req: _Request, start: int, n: int, row
                         ) -> Dict[int, int]:
        """Name, in a chunk's staged ``row``, the snapshot row of each
        boundary of the stride among the call's ``n`` live tokens (the
        columns ahead of the slot's; a boundary past them stays 0, the
        trash row). Returns boundary position -> row, for the booking to
        attach to the trie's nodes."""
        stride, per = self._snap_stride, self.config.snapshots_per_chunk
        ends = [start + (j + 1) * stride for j in range(per)
                if (j + 1) * stride <= n]
        with self._lock:
            got = self._pool.take_snapshot_rows(len(ends))
        snaps = dict(zip(ends, got))
        for pos, r in snaps.items():
            row[0, -1 - per + (pos - start) // stride - 1] = r
        return snaps

    def _finish_chunk(self, chunk: tuple) -> None:
        """Fetch a launched chunk's result and book it."""
        req, start, n, t0, t0w, tok, lp, snaps = chunk
        np = self._np
        clock = self._clock
        ready = tok.is_ready()
        with clock.phase("engine.prefill.wait", ready=int(ready)):
            tok = np.asarray(tok)
            if lp is not None:
                lp = np.asarray(lp)
        self._prefill_wall_s += self._program_wall("prefill", t0, ready)
        self._fetched("full" if n == self.config.prefill_chunk else "part")
        with clock.phase("engine.prefill.book"):
            self._book_prefill(req, start, n, t0w, tok, lp, snaps)

    def _book_prefill(self, req: _Request, start: int, n: int,
                      t0w: float, tok, lp,
                      snaps: Optional[Dict[int, int]] = None) -> None:
        """After a chunk: the trie's new blocks (and the snapshot rows
        the call wrote, ``snaps``: boundary position -> row, each to the
        node whose page ends there) and, at the prompt's end, the first
        token (or the hand-off)."""
        ec = self.config
        req.prefill_pos += n
        req.n_chunks += 1
        self._ssm["prefill_tokens"] += n
        self._ssm["prefill_calls"] += 1
        self._account_queries([start], [n], "prefill")
        if req.trace is not None:
            req.trace.span(RT.PREFILL, t0w, time.time(),
                           pos=start, tokens=n, tick=self._clock.tick_no)
        # index newly-completed FULL prompt blocks in the radix trie so
        # concurrent/later requests with the same prefix share them; a
        # lost insert race (same chunk path already indexed) keeps our
        # block private and just deepens along the existing path
        if req.trie_node is not None:
            with self._lock:
                while req.trie_node is not None and \
                        (req.trie_cursor + 1) * ec.kv_block_size \
                        <= req.prefill_pos:
                    i = req.trie_cursor
                    chunk = req.prompt[i * ec.kv_block_size:
                                       (i + 1) * ec.kv_block_size]
                    node, _ = self._pool.insert_child(
                        req.trie_node, chunk, req.blocks[i],
                        req.wpages.get(i))
                    req.trie_node = node   # None = parent evicted: stop
                    req.trie_cursor += 1
                    if snaps and (i + 1) * ec.kv_block_size in snaps:
                        self._pool.attach_snapshot(
                            node, snaps.pop((i + 1) * ec.kv_block_size))
        if snaps:
            # no node took them (the request stopped indexing)
            with self._lock:
                self._pool.return_snapshot_rows(list(snaps.values()))
        if req.prefill_pos < len(req.prompt):
            return
        # prompt fully cached: the final chunk's last logits give the
        # first generated token — TTFT stops here
        first = int(tok[0])
        if req.export:
            # disagg prefill replica: ship the finished blocks instead
            # of decoding (user-facing TTFT is the decode side's)
            self._finish_export(req, first, lp)
            return
        req.seq_len = len(req.prompt)
        req.t_first_token = time.monotonic()
        self._first_token(req)
        with self._lock:
            self._prefilling.popleft()
            if req.cancelled:
                self._release_locked(req)
                return
            if req.eos_token_id is not None and first == req.eos_token_id:
                self._release_locked(req)
                return
            req.generated = 1
            req.out.put(self._item(req, first,
                                   None if lp is None
                                   else float(lp[0])))
            req.history.append(first)
            self._tokens_total += 1
            if req.generated >= req.max_new_tokens:
                self._release_locked(req)
                return
            req.state = _DECODE
            self._last_tok[req.slot] = first
            self._seq_lens[req.slot] = req.seq_len
            self._block_tables[req.slot, :len(req.blocks)] = req.blocks

    def _account_decode_pages(self, live_lens) -> None:
        """Book one decode step's length-aware work: pages the paged
        kernel reads (``ceil(live/bs)`` per slot — none for a slot with
        no sequence) vs the full table window the XLA reference
        gathers, and the kernel's grid steps with a live page vs all it
        takes. Host-side numpy over the slot arrays the step already
        copied — no device work."""
        from ray_tpu.ops.paged_flash import (paged_grid_steps,
                                             paged_work_pages)
        ec = self.config
        pages = paged_work_pages(
            self._np.asarray(live_lens, self._np.int64),
            ec.kv_block_size)
        live_rows = int(self._np.count_nonzero(pages))
        self._decode_pages_live += int(pages.sum())
        self._ssm["decode_rows"] += live_rows
        self._decode_slots_skipped += len(pages) - live_rows
        steps, live = paged_grid_steps(pages, ec.blocks_per_seq,
                                       self._decode_pages_per_step)
        self._decode_grid_steps += steps
        self._decode_grid_steps_live += live

    def _account_row_blocks(self, n: int) -> None:
        """Book one chunk of ``n`` live tokens: the row blocks the paged
        kernel's call takes on a layer of each kind, and those with a
        live row. Host arithmetic, no device work."""
        from ray_tpu.ops.paged_flash import paged_row_blocks
        for kind, rep in self._chunk_rep.items():
            blocks, live = paged_row_blocks(
                self.config.prefill_chunk * rep, n * rep,
                **self._chunk_row_block)
            booked = self._row_blocks[kind]
            booked[:] = booked[0] + blocks, booked[1] + live

    def _account_queries(self, first, n, kind: str) -> None:
        """Book the queries at positions ``first[i] .. first[i] + n[i] -
        1`` (numpy arrays, a sequence each) of a program of ``kind``
        ("decode" | "prefill"): what each could see, what it attended,
        what the indexer scored and the experts it was sent to. Host
        arithmetic on positions; a model that neither selects nor routes
        books nothing (its counters stay 0)."""
        mc, np = self.model_config, self._np
        if not (mc.index_topk or mc.experts_per_token):
            return
        first, n = np.asarray(first, np.int64), np.asarray(n, np.int64)
        last = first + n                      # query p sees p + 1 keys
        visible = int((last * (last + 1) - first * (first + 1)).sum()) // 2
        attended = visible
        if mc.index_topk:
            k = mc.index_topk                 # positions past k see k
            lo, hi = np.minimum(first, k), np.minimum(last, k)
            attended = int((hi * (hi + 1) - lo * (lo + 1)).sum()) // 2 \
                + int(((last - hi) - (first - lo)).sum()) * k
            self._sparse["scored"] += visible * mc.n_layers
            self._sparse["scored", kind] += visible * mc.n_layers
        self._sparse["visible"] += visible
        self._sparse["attended"] += attended
        self._sparse["attended", kind] += attended
        assigned = int(n.sum()) * mc.experts_per_token * self._expert_layers
        self._sparse["assigned"] += assigned
        self._sparse["assigned", kind] += assigned

    def _decoding(self) -> List[_Request]:
        # only this thread moves a request in or out of a slot
        return [r for r in self._slots
                if r is not None and r.state == _DECODE]

    def _launch_decode(self, active: List[_Request]) -> tuple:
        """Stage and dispatch one decode step over the slot array, for
        the ``active`` requests (``_decoding()``, not empty). Returns
        what :meth:`_finish_decode` needs."""
        clock = self._clock
        with clock.phase("engine.decode.stage"):
            with self._lock:
                self._decode_steps += 1
                self._occupancy[len(active)] += 1
                if self._wpool is not None:
                    self._stage_window_rows(active)
                rows = self._slot_rows.copy()
            self._account_decode_pages(rows[:, 1] + 1)
            self._account_queries([r.seq_len for r in active],
                                  [1] * len(active), "decode")
            t0 = time.monotonic()
            self._h2d_transfers += 1
        with clock.phase("engine.decode.dispatch"):
            res = self._spent = self._jit_decode(self._params, rows,
                                                 self._cache)
            if self.config.capture_logprobs:
                out, lps, self._cache = res
            else:
                out, self._cache = res
                lps = None
        return active, t0, out, lps

    def _stage_window_rows(self, active: List[_Request]) -> None:
        """Before a decode step (lock held): each decoding sequence's
        window table follows its next position, the pages it passed go
        back, and the pages one window layer reads are booked."""
        bs = self.config.kv_block_size
        with self._clock.phase("engine.window.release"):
            for req in active:
                row = self._window_row(req, req.seq_len, 1)
                if row is not None:
                    self._window_rows[req.slot] = row
                first, last = req.wspan
                self._pages_live["decode_window"] += last - first + 1
                self._pages_live["decode_full"] += req.seq_len // bs + 1

    def _finish_decode(self, decode: tuple) -> None:
        """Fetch a launched decode step's tokens and emit them."""
        active, t0, out, lps = decode
        clock = self._clock
        ready = (out if lps is None else lps).is_ready()
        with clock.phase("engine.decode.wait", ready=int(ready)):
            if lps is not None:
                lps = self._np.asarray(lps)
            out = self._np.asarray(out)
        self._decode_wall_s += self._program_wall("decode", t0, ready)
        self._fetched("decode")
        with clock.phase("engine.decode.emit"):
            self._emit_decoded(active, out, lps)

    def _emit_decoded(self, active: List[_Request], out, lps) -> None:
        """One decoded token to each active request's stream."""
        produced = 0
        now_w = time.time()
        with self._lock:
            for req in active:
                if req.cancelled or self._slots[req.slot] is not req:
                    continue
                tok = int(out[req.slot])
                req.seq_len += 1           # the token we just wrote
                self._seq_lens[req.slot] = req.seq_len
                if req.eos_token_id is not None \
                        and tok == req.eos_token_id:
                    self._release_locked(req)
                    continue
                req.generated += 1
                req.out.put(self._item(req, tok,
                                       None if lps is None
                                       else float(lps[req.slot])))
                req.history.append(tok)
                self._tokens_total += 1
                produced += 1
                self._trace_token(req, now_w)
                if req.generated >= req.max_new_tokens \
                        or req.seq_len + 1 >= self.config.max_seq_len:
                    self._release_locked(req)
                else:
                    self._last_tok[req.slot] = tok
        # decode tokens into the fleet counter (the first token per
        # request is counted by _record_ttft), so the plane's
        # rate(serve_engine_tokens_total) IS engine tokens/s
        if produced and self._metrics is not None:
            try:
                self._metrics.serve_tokens.inc(produced)
            except Exception:
                pass

    # ---------------------------------------------- speculative decode
    def _draft(self, req: _Request, n_draft: int) -> List[int]:
        """Prompt-lookup drafting: continuation of the most recent
        earlier occurrence of the sequence's own trailing n-gram
        (longest n first). No draft model, no device work — misses just
        return fewer (or no) drafts."""
        if n_draft <= 0:
            return []
        h = req.history
        for g in range(min(self.config.spec_ngram, len(h) - 1), 0, -1):
            pat = h[-g:]
            for i in range(len(h) - g - 1, -1, -1):
                if h[i:i + g] == pat:
                    return h[i + g:i + g + n_draft]
        return []

    def _decode_speculative(self) -> None:
        """One verify step over the slot array: each active slot
        processes [last_tok, draft_1..draft_d] at its next positions in
        ONE fixed-shape (S, k+1) call, then accepts the longest draft
        prefix matching the model's own argmax chain plus one bonus
        token. d=0 degenerates to exactly the classic decode step, so
        per-token output is bit-identical with speculation on or off.
        Rejected drafts leave stale writes only at positions beyond the
        accepted seq_len — never read (causal masking) and overwritten
        when real tokens reach them."""
        np = self._np
        ec = self.config
        L = ec.spec_tokens + 1
        S = ec.decode_slots
        bs = ec.kv_block_size
        active = self._decoding()
        if not active:
            return
        # neither ahead of its tick's chunk nor with the next under it
        self._go_ahead("speculative")
        clock = self._clock
        with clock.phase("engine.decode.stage"):
            with self._lock:
                self._decode_steps += 1
                self._occupancy[len(active)] += 1
                # a row: [last token, drafts | start | n | table row]
                rows = np.zeros((S, L + 2 + ec.blocks_per_seq), np.int32)
                drafts: Dict[int, List[int]] = {}
                for req in active:
                    s = req.slot
                    # cap drafts to the sequence's allocated block span
                    # so speculative writes NEVER spill into the shared
                    # trash block (concurrent slots' junk could corrupt
                    # verify)
                    span = len(req.blocks) * bs
                    budget = min(L, span - req.seq_len,
                                 req.max_new_tokens - req.generated + 1)
                    d = [] if req.spec_disabled else \
                        self._draft(req, max(0, budget - 1))
                    rows[s, 0] = self._last_tok[s]
                    if d:
                        rows[s, 1:1 + len(d)] = d
                    rows[s, L:L + 2] = req.seq_len, 1 + len(d)
                    drafts[s] = d
                rows[:, L + 2:] = self._block_tables
            self._account_decode_pages(rows[:, L] + rows[:, L + 1])
            t0w = time.time()
            t0 = time.monotonic()
            self._h2d_transfers += 1
        with clock.phase("engine.decode.dispatch"):
            preds, self._cache = self._spent = self._jit_verify(
                self._params, rows, self._cache)
        ready = preds.is_ready()
        with clock.phase("engine.decode.wait", ready=int(ready)):
            preds = np.asarray(preds)
        self._decode_wall_s += self._program_wall("decode", t0, ready)
        self._fetched("verify")
        with clock.phase("engine.decode.emit"):
            self._emit_verified(active, preds, drafts, t0w)

    def _emit_verified(self, active: List[_Request], preds,
                       drafts: Dict[int, List[int]],
                       t0w: float) -> None:
        """Each active request's accepted drafts and its bonus token
        to its stream; the acceptance books."""
        ec = self.config
        produced = 0
        now_w = time.time()
        with self._lock:
            for req in active:
                if req.cancelled or self._slots[req.slot] is not req:
                    continue
                s = req.slot
                d = drafts[s]
                emitted = 0
                for j in range(len(d) + 1):
                    tok = int(preds[s, j])
                    req.seq_len += 1       # position j's token is real
                    self._seq_lens[s] = req.seq_len
                    if req.eos_token_id is not None \
                            and tok == req.eos_token_id:
                        self._release_locked(req)
                        break
                    req.generated += 1
                    req.out.put(self._item(req, tok, None))
                    req.history.append(tok)
                    self._tokens_total += 1
                    produced += 1
                    emitted += 1
                    self._trace_token(req, now_w)
                    if req.generated >= req.max_new_tokens \
                            or req.seq_len + 1 >= ec.max_seq_len:
                        self._release_locked(req)
                        break
                    self._last_tok[s] = tok
                    # continue into draft j+1 only if draft j was what
                    # the model itself predicted (cache entry correct)
                    if j >= len(d) or d[j] != tok:
                        break
                if d:
                    accepted = max(0, emitted - 1)
                    self._spec_drafted += len(d)
                    self._spec_accepted += accepted
                    if req.trace is not None:
                        req.trace.span(RT.SPEC_VERIFY, t0w, now_w,
                                       drafted=len(d),
                                       accepted=accepted,
                                       tick=self._clock.tick_no)
                    ratio = accepted / len(d)
                    req.spec_ewma = ratio if req.spec_ewma is None \
                        else 0.8 * req.spec_ewma + 0.2 * ratio
                    if req.spec_ewma < ec.spec_min_acceptance \
                            and not req.spec_disabled:
                        req.spec_disabled = True
                        self._spec_disables += 1
                    if self._metrics is not None:
                        try:
                            self._metrics.serve_spec_accept.observe(
                                ratio)
                        except Exception:
                            pass
        if produced and self._metrics is not None:
            try:
                self._metrics.serve_tokens.inc(produced)
            except Exception:
                pass

    def _trace_token(self, req: _Request, now_w: float) -> None:
        """Book one emitted decode token into the request's trace:
        inter-token gap to the SLO watchdog, and a DECODE span every
        ``trace_decode_tick`` tokens (bounding span count for long
        generations). Speculative bursts emit several tokens at one
        wall instant — the intra-burst gaps are genuinely ~0, which is
        exactly what the user-perceived stream looks like."""
        tr = req.trace
        if tr is None:
            return
        last = req.last_tok_wall
        req.last_tok_wall = now_w
        if last is not None:
            self._slo.observe_gap(tr, max(0.0, now_w - last))
        if req.tick_t0 is None:
            req.tick_t0 = last if last is not None else now_w
        req.tick_toks += 1
        if req.tick_toks >= self.config.trace_decode_tick:
            tr.span(RT.DECODE, req.tick_t0, now_w,
                    tokens=req.tick_toks, tick=self._clock.tick_no)
            req.tick_t0, req.tick_toks = None, 0

    def _item(self, req: _Request, tok: int, logprob):
        """Shape one stream item: plain int for serving consumers,
        ``(token, policy_version, logprob)`` for ``detailed`` RLHF
        streams. Called from the step thread, where _weight_version is
        constant for the whole step — the stamp is exactly the policy
        that computed this token's logits."""
        if not req.detailed:
            return tok
        return (tok, self._weight_version, logprob)

    def _release_locked(self, req: _Request,
                        err: Optional[BaseException] = None) -> None:
        """Return a request's slot + blocks to the free lists and close
        its stream (call with self._lock held)."""
        if req.slot is not None and self._slots[req.slot] is req:
            self._slots[req.slot] = None
            self._block_tables[req.slot, :] = 0
            self._window_rows[req.slot, :] = 0
            self._seq_lens[req.slot] = _NO_SEQUENCE
            self._last_tok[req.slot] = 0
            self._free_slots.append(req.slot)
            for page, wblock in req.wpages.items():
                self._wpool.decref(wblock, near=self._near_prompt_end(
                    page, len(req.prompt)))
            req.wpages, req.wspan = {}, None
            # decref, not free: trie-indexed blocks stay warm for the
            # next request sharing this prefix (evicted LRU only under
            # pool pressure)
            self._pool.release(req.blocks)
            req.blocks = []
            req.slot = None
            req.trie_node = None
        req.state = _FINISHED
        self._close_trace(req, err)
        req.out.put(err if err is not None else _DONE)
        self._work.notify_all()

    def _close_trace(self, req: _Request,
                     err: Optional[BaseException] = None) -> None:
        """Terminal span + ship decision for one request's trace
        (exactly once — the trace is detached first). FAILED names the
        typed error; DONE carries the token count. Shipping is an
        out-queue put, so holding the engine lock here is fine."""
        tr = req.trace
        if tr is None:
            return
        req.trace = None
        now = time.time()
        if req.tick_toks and req.tick_t0 is not None:
            tr.span(RT.DECODE, req.tick_t0, now, tokens=req.tick_toks,
                    tick=self._clock.tick_no)
            req.tick_t0, req.tick_toks = None, 0
        if err is not None:
            tr.span(RT.FAILED, now, None,
                    error=type(err).__name__, detail=str(err)[:200])
        else:
            tr.span(RT.DONE, now, None, tokens=req.generated,
                    cancelled=bool(req.cancelled))
        if self._tracer is not None:
            self._tracer.finish(tr)

    # ------------------------------------------------ metrics / events
    def _first_token(self, req: _Request) -> None:
        """The first token is out (``t_first_token`` stamped): book
        where its time went (the ttft_* counters), then tell the gauges
        and the trace."""
        if not req.warmup:
            split = self._ttft
            split["ttft_requests"] += 1
            split["ttft_s"] += req.t_first_token - req.t_submit
            split["ttft_queue_s"] += req.t_slot - req.t_submit
            split["ttft_prefill_wait_s"] += req.t_first_chunk - req.t_slot
            split["ttft_prefill_s"] += \
                req.t_first_token - req.t_first_chunk
            split["ttft_prefill_ticks"] += \
                self._clock.tick_no - req.tick_first_chunk + 1
            split["ttft_prefill_chunks"] += req.n_chunks
        self._record_ttft(req)

    def _record_ttft(self, req: _Request) -> None:
        if getattr(req, "warmup", False):
            # compile-only traffic: its TTFT is the jit wall, noise for
            # both the router's EWMA and the flight recorder
            return
        ttft = req.t_first_token - req.t_submit
        # full TTFT = router-enqueue -> first token: queue_wait_s is
        # the router-stamped component the engine never used to see.
        # The fleet histogram observes the FULL number so its quantiles
        # agree with the request waterfalls on what TTFT means; the
        # EWMA stays engine-scoped (it is the router's own-capacity
        # gauge — charging it the router's queueing would feed back).
        qw = getattr(req, "queue_wait_s", 0.0)
        t_enq = getattr(req, "t_enqueue_wall", 0.0)
        full = max(ttft, time.time() - t_enq) if t_enq else ttft
        self._ttft_ewma = ttft if self._ttft_ewma is None \
            else 0.8 * self._ttft_ewma + 0.2 * ttft
        if self._metrics is not None:
            try:
                self._metrics.serve_ttft.observe(full)
                self._metrics.serve_tokens.inc()
            except Exception:
                pass
        trace = getattr(req, "trace", None)
        if self._recorder is not None:
            try:
                self._recorder.record(
                    "ENGINE_TTFT", replica=self.replica_tag,
                    rid=req.rid, ttft_s=round(ttft, 6),
                    queue_wait_s=round(qw, 6),
                    prompt_len=len(req.prompt),
                    request_id=(trace.request_id
                                if trace is not None else None))
            except Exception:
                pass
        if trace is not None:
            now = time.time()
            req.last_tok_wall = now     # inter-token gap baseline
            trace.event(RT.FIRST_TOKEN, now,
                        ttft_s=round(full, 6),
                        engine_ttft_s=round(ttft, 6),
                        queue_wait_s=round(qw, 6))
            self._slo.observe_ttft(trace, full)

    def _emit_stats(self, interval_s: float = 0.5) -> None:
        now = time.monotonic()
        if now - self._last_stats_emit < interval_s:
            return
        self._last_stats_emit = now
        s = self.stats()
        if self._metrics is not None:
            try:
                self._metrics.serve_queue_depth.set(s["queue_depth"])
                self._metrics.serve_tokens_per_s.set(s["tokens_per_s"])
                self._metrics.serve_blocks_shared.set(
                    s["blocks_shared"])
            except Exception:
                pass
        if self._recorder is not None:
            try:
                self._recorder.record(
                    "ENGINE_STATS", replica=self.replica_tag,
                    queue_depth=s["queue_depth"],
                    active=s["active_slots"],
                    tokens_per_s=s["tokens_per_s"],
                    free_blocks=s["free_blocks"])
                self._recorder.maybe_flush()
            except Exception:
                pass
        # a replica decoding flat-out may never hit the worker idle
        # loop: the stats cadence doubles as the fleet-report heartbeat
        try:
            from ray_tpu.core.global_state import try_global_worker
            w = try_global_worker()
            if w is not None and getattr(w, "metrics_reporter",
                                         None) is not None:
                w.metrics_reporter.maybe_report()
        except Exception:
            pass


def _resolve_dtype(name):
    import jax.numpy as jnp
    if not isinstance(name, str):
        return name
    return {"float32": jnp.float32, "f32": jnp.float32,
            "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
            "float16": jnp.float16}[name]


class LLMServer:
    """Deployment-facing engine wrapper. Construct with plain dicts so
    the deployment graph ships cheaply to the replica actor::

        app = serve.deployment(LLMServer).bind(
            model={"d_model": 256, "n_layers": 4, ...},
            engine={"decode_slots": 8, "kv_block_size": 16})
        h = serve.run(app)
        for tok in h.options(stream=True).generate.remote([1, 2, 3]):
            ...

    ``generate`` is an async generator, so each token rides the core
    streaming-generator machinery (per-item objects, backpressure,
    typed failure on replica death).
    """

    def __init__(self, model: Optional[Dict[str, Any]] = None,
                 engine: Optional[Dict[str, Any]] = None,
                 seed: int = 0, warmup: bool = True):
        from ray_tpu.models import TransformerConfig
        model = dict(model or {})
        if "dtype" in model:
            model["dtype"] = _resolve_dtype(model["dtype"])
        model.setdefault("dtype", _resolve_dtype("float32"))
        self.model_config = TransformerConfig(**model)
        self.engine_config = EngineConfig(**(engine or {}))
        self.engine = LLMEngine(self.model_config, self.engine_config,
                                seed=seed,
                                replica_tag=f"pid:{os.getpid()}")
        if warmup:
            # compile every program BEFORE the replica enters
            # rotation: actor calls queue behind __init__, so a
            # replica the autoscaler adds mid-load serves its first
            # request hot instead of charging users the jit wall. A
            # program that does not compile fails the constructor, and
            # with it the replica — it must never enter rotation.
            self.engine.warmup()

    @staticmethod
    def _trace_ctx() -> Optional[Dict[str, Any]]:
        """Flatten the router's trace stamp out of the replica call
        context (request_id + sampling verdict + routing annotations),
        so the engine opens the request's trace under the id the
        client/proxy already knows."""
        try:
            from ray_tpu.serve._private.replica import \
                get_request_context
            ctx = get_request_context()
        except Exception:
            return None
        rid = ctx.get("request_id")
        if not rid:
            return None
        return dict(ctx.get("trace") or {}, request_id=rid)

    async def generate(self, prompt_ids: Sequence[int],
                       max_new_tokens: Optional[int] = None,
                       eos_token_id: Optional[int] = None):
        async for tok in self.engine.generate(
                prompt_ids, max_new_tokens, eos_token_id,
                trace_ctx=self._trace_ctx()):
            yield tok

    async def __call__(self, prompt_ids: Sequence[int],
                       max_new_tokens: Optional[int] = None):
        async for tok in self.engine.generate(
                prompt_ids, max_new_tokens,
                trace_ctx=self._trace_ctx()):
            yield tok

    # ------------------------------------------ disagg replica surface
    async def prefill_export(self, prompt_ids: Sequence[int]
                             ) -> Dict[str, Any]:
        """Prefill-fleet actor method: chunked-prefill the prompt and
        return the KV hand-off payload. The payload's device slabs ride
        the out-of-band zero-copy serializer; the decode replica pulls
        them peer-to-peer when the router chains this call's ObjectRef
        into ``adopt_generate``."""
        eng = self.engine
        req = eng.submit(prompt_ids, max_new_tokens=1,
                         trace_ctx=self._trace_ctx(), _export=True)
        loop = asyncio.get_running_loop()
        get = functools.partial(req.out.get, timeout=0.2)
        try:
            while True:
                try:
                    item = await loop.run_in_executor(
                        eng._poll_pool, get)
                except queue.Empty:
                    if eng._dead is not None:
                        raise EngineDeadError(
                            f"engine step loop died: {eng._dead!r}")
                    continue
                if isinstance(item, BaseException):
                    raise item
                if isinstance(item, dict):
                    return item
                if item is _DONE:
                    raise EngineDeadError(
                        "prefill_export ended without a payload")
        finally:
            eng.cancel(req)

    async def adopt_generate(self, payload: Dict[str, Any],
                             max_new_tokens: Optional[int] = None,
                             eos_token_id: Optional[int] = None):
        """Decode-fleet actor method: adopt a shipped prefill payload
        and stream tokens — the first token (computed by the prefill
        replica) included, so the stream is exactly what a colocated
        ``generate`` would produce."""
        eng = self.engine
        req = eng.submit_adopt(payload, max_new_tokens, eos_token_id,
                               trace_ctx=self._trace_ctx())
        loop = asyncio.get_running_loop()
        get = functools.partial(req.out.get, timeout=0.2)
        try:
            while True:
                try:
                    item = await loop.run_in_executor(
                        eng._poll_pool, get)
                except queue.Empty:
                    if eng._dead is not None:
                        raise EngineDeadError(
                            f"engine step loop died: {eng._dead!r}")
                    continue
                if item is _DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            eng.cancel(req)

    async def export_warm_prefixes(self, min_hits: int = 1,
                                   max_blocks: int = 0
                                   ) -> Optional[Dict[str, Any]]:
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self.engine._poll_pool,
            functools.partial(self.engine.export_warm_prefixes,
                              min_hits, max_blocks))

    async def import_warm_prefixes(self,
                                   payload: Optional[Dict[str, Any]]
                                   ) -> int:
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self.engine._poll_pool,
            functools.partial(self.engine.import_warm_prefixes,
                              payload))

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def sync_weights(self, packed: Dict[str, Any]) -> int:
        """Apply an int8-packed weight refresh (the
        :mod:`ray_tpu.rlhf.weight_sync` wire format) in-flight:
        dequantize on this actor-call thread, stage for the step
        thread's between-steps pointer swap. Returns the staged
        version. Decode never drains."""
        from ray_tpu.rlhf.weight_sync import unpack_weights
        params, version = unpack_weights(packed)
        self.engine.stage_weights(params, version)
        return version

    def pool_audit(self) -> List[str]:
        return self.engine.pool_audit()

    def device_info(self) -> Dict[str, Any]:
        """Which process this replica is and which devices it holds, as
        JAX reports them here — so a fleet can show one chip per
        replica and nothing on a chip it does not own."""
        import jax

        from ray_tpu.util import compile_cache
        devs = jax.devices()
        mem = devs[0].memory_stats() or {}
        return {
            "pid": os.getpid(),
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            # a pinned process sees its chip as device 0 at (0, 0, 0):
            # which chip it is shows only in what it was given
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            # programs this process loaded from / compiled into the
            # persistent cache since its engine was built
            "compile_cache": {"dir": compile_cache.cache_root(),
                              **compile_cache.stats()},
            "bytes_in_use": mem.get("bytes_in_use"),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "bytes_limit": mem.get("bytes_limit"),
        }

    async def kernel_truth(self) -> List[Dict[str, Any]]:
        """Check, inside this replica and on its own device, that the
        paged kernel (at the decode and the prefill-chunk shape this
        engine runs) matches the XLA reference and that logits through
        the cache match ``apply`` on the engine's params
        (:mod:`ray_tpu.models.kernel_truth`). Compiles its own small
        programs: a start-up or smoke check, not for use under load."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self.engine._poll_pool,
                                          self._kernel_truth)

    def _kernel_truth(self) -> List[Dict[str, Any]]:
        from ray_tpu.models import kernel_truth as KT
        mc, ec = self.model_config, self.engine_config
        shape = dict(heads=mc.n_heads, kv_heads=mc.kv_heads,
                     head_dim=mc.head_dim, block_size=ec.kv_block_size,
                     table_len=ec.blocks_per_seq, dtype=mc.dtype,
                     impl=mc.paged_impl)
        return [
            KT.paged_truth(batch=ec.decode_slots, chunk=1, **shape),
            KT.paged_truth(batch=1, chunk=ec.prefill_chunk, **shape),
            KT.cached_logits_truth(
                mc, self.engine._params, block_size=ec.kv_block_size,
                chunk=ec.prefill_chunk, table_len=ec.blocks_per_seq,
                prompt_len=min(ec.max_seq_len - 5,
                               ec.prefill_chunk * 3 // 2 + 3),
                n_decode=4),
        ]

    def kv_block_bytes(self) -> int:
        ec, mc = self.engine_config, self.model_config
        return ec.kv_block_size * ec.kv_bytes_per_token(mc)

    def check_health(self) -> None:
        if self.engine._dead is not None:
            raise EngineDeadError(repr(self.engine._dead))
