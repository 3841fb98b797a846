"""Every Pallas kernel on the chip path must pass the Pallas TPU
lowering at the shapes ``chip_smoke.py`` runs (GPT-J: 16 heads x 256,
bf16). The lowering — block shapes against the (8, 128) tiling rule,
supported ops — needs no chip:
``jit(f).trace(*shapes).lower(lowering_platforms=("tpu",))``. The Mosaic
compile proper and the numerics are the chip run's to prove — except
the serving step programs' handling of the KV pool, which the TPU
compiler itself is asked about (a described v5e, nothing attached):
whether a step copies the pool is decided by XLA's layout assignment
and buffer aliasing, and only the compiled program shows it."""

import functools
import re

import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_flash import paged_flash_attention

HEADS, HEAD_DIM, SEQ = 16, 256, 2048
BLOCK, WINDOW, SLOTS, CHUNK = 16, 1024, 8, 256


def _lower_for_tpu(fn, *shapes) -> str:
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize(
    "batch,chunk,heads,kv_heads,head_dim,window,block_r", [
        (SLOTS, 1, HEADS, HEADS, HEAD_DIM, WINDOW, None),  # decode (MHA)
        (SLOTS, 1, HEADS, 1, HEAD_DIM, WINDOW, None),  # all on 1 kv head
        (1, CHUNK, HEADS, HEADS, HEAD_DIM, WINDOW, None),  # prefill chunk
        (SLOTS, 5, HEADS, HEADS, HEAD_DIM, WINDOW, None),  # verify: k+1
        # the cells' own calls (benchmarks/configs, traffic): chat's
        # chunk at block_r 128 over a 2048 window; docqa's decode and
        # 256-token chunk, 32 heads on 8 kv heads x 128, window 4096
        (1, CHUNK, HEADS, HEADS, HEAD_DIM, 2048, 128),
        (16, 1, 32, 8, 128, 4096, 16),
        (1, CHUNK, 32, 8, 128, 4096, 512),
    ])
def test_paged_kernel_lowers_for_tpu(batch, chunk, heads, kv_heads,
                                     head_dim, window, block_r):
    t = window // BLOCK
    pool = _s((1 + batch * t, kv_heads, BLOCK, head_dim))
    text = _lower_for_tpu(
        functools.partial(paged_flash_attention, block_r=block_r),
        _s((batch, chunk, heads, head_dim)), pool, pool,
        _s((batch, t), jnp.int32), _s((batch, chunk), jnp.int32),
        _s((batch,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_kernel_lowers_for_tpu_on_the_whole_pool():
    """The serving form: the 5-D pool whole, the layer index traced and
    riding the scalar prefetch."""
    t = WINDOW // BLOCK
    pool = _s((6, 1 + SLOTS * t, HEADS, BLOCK, HEAD_DIM))
    text = _lower_for_tpu(
        lambda q, k, v, bt, pos, lens, layer: paged_flash_attention(
            q, k, v, bt, pos, lens, layer=layer),
        _s((SLOTS, 1, HEADS, HEAD_DIM)), pool, pool,
        _s((SLOTS, t), jnp.int32), _s((SLOTS, 1), jnp.int32),
        _s((SLOTS,), jnp.int32), _s((), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_forward_and_backward_lower_for_tpu_at_head_dim_256():
    q = _s((1, HEADS, SEQ, HEAD_DIM))
    attn = functools.partial(flash_attention, causal=True,
                             block_q=256, block_k=512)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    assert "tpu_custom_call" in _lower_for_tpu(attn, q, q, q)
    bwd = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # forward (for the residuals) + delta + dk/dv + dq
    assert bwd.count("tpu_custom_call") >= 4


def test_old_page_layout_would_not_lower():
    """The rule the cache layout exists for: a one-kv-head slice of a
    ``[N, block, kv_heads, D]`` pool puts kv_heads in the sublane
    position, which the TPU lowering refuses."""
    from jax.experimental import pallas as pl

    def kernel(k_ref, o_ref):
        o_ref[...] = k_ref[0, :, 0, :]

    def old_layout(k):
        return pl.pallas_call(
            kernel, grid=(1,),
            in_specs=[pl.BlockSpec((1, BLOCK, 1, HEAD_DIM),
                                   lambda i: (0, 0, 0, 0))],
            out_specs=pl.BlockSpec((BLOCK, HEAD_DIM), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((BLOCK, HEAD_DIM), k.dtype))(k)

    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _lower_for_tpu(old_layout, _s((4, BLOCK, HEADS, HEAD_DIM)))


# ------------------------- a call without a selection is the parent's
def _without_locations(text: str) -> str:
    """A lowered program with each Mosaic kernel's serialized body
    parsed and printed without source locations (the bytecode carries
    file lines, which any edit above the kernel moves), the call's own
    StableHLO behind them."""
    import base64
    import json

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    config = re.compile(r'backend_config = "((?:[^"\\]|\\.)*)"')
    bodies = []
    for found in config.finditer(text):
        cfg = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                                lambda h: chr(int(h.group(1), 16)),
                                found.group(1)))
        with ir.Context() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(
                base64.b64decode(cfg["custom_call_config"]["body"]))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    return "".join(bodies) + config.sub("", text)


def _kernel_operands(text: str) -> int:
    """Operands of the one Mosaic call of a lowered program."""
    operands, = re.findall(r"stablehlo\.custom_call @tpu_custom_call\("
                           r"([^)]*)\)", text)
    return len(operands.split(","))


#: batch, chunk, heads, kv heads, row width, table, block_r, v_width ->
#: sha256 of _without_locations at 06d4f20, the commit before the
#: ``chosen`` operand (jax 0.9.0)
UNSELECTED = {
    "dense_decode": ((16, 1, 32, 8, 128, 256, 16, None),
                     "9cf5d7b90d335343a375"),
    "dense_chunk": ((1, 256, 32, 8, 128, 256, 512, None),
                    "b73afbf62d0be0e26de1"),
    "latent_decode": ((16, 1, 128, 1, 640, 2048, 128, 512),
                      "c87a00c39adaf40ad829"),
    "latent_chunk": ((1, 64, 128, 1, 640, 2048, 128, 512),
                     "3157719faa4a2ff58321"),
}


@pytest.mark.parametrize("form", sorted(UNSELECTED))
def test_a_call_without_a_selection_lowers_as_it_did(form, monkeypatch):
    """``chosen=None`` adds no operand and changes no instruction: the
    dense and the latent form, one row block and several, lower to the
    kernel and the call the commit before the operand lowered to. (The
    dense form's chunk call serves four kv heads a grid step since
    PR 61: held to one, the grid it had, it is that kernel still; the
    body of a head did not change.)"""
    import hashlib

    import ray_tpu.ops.paged_flash as pf
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were taken under jax 0.9.0")
    monkeypatch.setattr(pf, "_MAX_ROWS_PER_CHUNK_STEP",
                        pf._MAX_ROWS_PER_STEP)
    (b, c, h, g, d, t, block_r, v_width), want = UNSELECTED[form]
    pool = _s((3, 1 + b * 8, g, BLOCK, d))
    pools = (pool,) if v_width else (pool, pool)

    def call(q, *rest):
        *kv, bt, pos, lens, layer = rest
        return paged_flash_attention(
            q, kv[0], None if v_width else kv[1], bt, pos, lens,
            layer=layer, block_r=block_r, v_width=v_width)
    text = _lower_for_tpu(
        call, _s((b, c, h, d)), *pools, _s((b, t), jnp.int32),
        _s((b, c), jnp.int32), _s((b,), jnp.int32), _s((), jnp.int32))
    assert _kernel_operands(text) == 5 + len(pools)
    assert hashlib.sha256(_without_locations(text).encode()) \
        .hexdigest()[:20] == want


def test_a_selection_is_one_more_operand_and_lowers_for_tpu():
    """Keye's decode call (8 x 32 heads on 4 x 128, a table of 2048
    pages) and a chunk's 256-row block with ``chosen``: one operand
    more than the call without."""
    t = 32768 // BLOCK
    pool = _s((6, 1 + 8 * 64, 4, BLOCK, 128))
    for b, c, block_r in ((8, 1, 8), (1, 256, 512)):
        text = _lower_for_tpu(
            lambda q, k, v, bt, pos, lens, layer, chosen:
            paged_flash_attention(q, k, v, bt, pos, lens, layer=layer,
                                  block_r=block_r, chosen=chosen),
            _s((b, c, 32, 128)), pool, pool, _s((b, t), jnp.int32),
            _s((b, c), jnp.int32), _s((b,), jnp.int32), _s((), jnp.int32),
            _s((b, c, 32768), jnp.bool_))
        assert _kernel_operands(text) == 8


# ------------------------------------------ the compiled step programs
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batch,chunk,heads,kv_heads,head_dim,window,"
                         "layers,blocks,block_r,sliding,selects", [
    (64, 1, 16, 16, 256, 2048, 6, 1921, 8, 0, False),     # chat decode
    (1, 256, 16, 16, 256, 2048, 6, 1921, 128, 0, False),  # chat chunk
    (16, 1, 32, 8, 128, 4096, 8, 3585, 16, 0, False),     # docqa decode
    (1, 256, 32, 8, 128, 4096, 8, 3585, 512, 0, False),   # docqa chunk
    # the widest chunk a cell sends, Laguna-XS.2's: a full layer's 24
    # row blocks a kv head, a window layer's 32 over its short table
    (1, 2048, 48, 8, 128, 65536, 2, 38913, 512, 0, False),
    (1, 2048, 64, 8, 128, 161 * BLOCK, 3, 4097, 512, 512, False),
    # the paged layers' chunks of the three state cells, at the heads a
    # step their kv heads allow (PR 61: four of Granite's 8, three of
    # Olmo-Hybrid's 30, both of Nemotron's 2)
    (1, 1024, 32, 8, 128, 8192, 1, 4097, 512, 0, False),
    (1, 2048, 30, 30, 128, 8192, 1, 3073, 512, 0, False),
    (1, 1024, 32, 2, 128, 8192, 2, 4097, 512, 0, False),
    # with ``chosen``: Keye's decode step (one int32 row a sequence, a
    # row of P x 16 lanes a grid step) and a chunk's 256-row block
    # (an int8 row a token, widened in the body)
    (8, 1, 32, 4, 128, 32768, 6, 15361, 8, 0, True),
    (1, 256, 32, 4, 128, 32768, 6, 15361, 512, 0, True),
])
def test_paged_kernel_compiles_for_v5e_at_the_cells_shapes(
        one_chip, batch, chunk, heads, kv_heads, head_dim, window, layers,
        blocks, block_r, sliding, selects):
    """The Mosaic compile proper, which the lowering above stops short
    of: the group's 2·P page copies out of the pool left in HBM, the
    read of P pages as one tile and the VMEM the step plans for
    (``_VMEM_BUDGET``, under the default scoped limit: the kernel asks
    for no other) are accepted for a described v5e at each of the
    cells' calls, on the whole pool with a traced layer, and the
    call has no temporaries. The chunks have more than one row block,
    each bounded by its highest live position: a max over the block's
    position column moved to a scalar and kept in an SMEM scratch word
    (the latent form's 512 blocks are the latent test's chunk, below)."""
    from jax.experimental.compilation_cache import compilation_cache

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = s((layers, blocks, kv_heads, BLOCK, head_dim))
    t = window // BLOCK
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda q, k, v, bt, pos, lens, layer, *chosen:
            paged_flash_attention(
                q, k, v, bt, pos, lens, layer=layer, block_r=block_r,
                window=sliding, chosen=chosen[0] if chosen else None)
        ).lower(s((batch, chunk, heads, head_dim)), pool, pool,
                s((batch, t), jnp.int32), s((batch, chunk), jnp.int32),
                s((batch,), jnp.int32), s((), jnp.int32),
                *[s((batch, chunk, window), jnp.bool_)] * selects
                ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _engine_program(cfg, entry, slots, table, chunk):
    """The engine's own wrapper of ``entry`` (serve/llm_engine.py
    ``_step_fns``: ``fn(params, rows, cache)``) and the shape of the one
    int32 array it is fed by."""
    from ray_tpu.serve.llm_engine import EngineConfig, _step_fns
    prefill_fn, decode_fn, _ = _step_fns(cfg, EngineConfig(
        decode_slots=slots, kv_block_size=BLOCK,
        max_seq_len=table * BLOCK, prefill_chunk=chunk))
    if entry == "decode_step":
        return decode_fn, (slots, 2 + table)
    return prefill_fn, (1, chunk + 2 + table)


def _readers_of_the_staged_rows(text):
    """(op, elements of its largest result) for every instruction of a
    compiled program that takes the ``rows`` parameter."""
    uses = re.findall(
        r"= (.*?) ([\w\-]+)\((?:[^()]*, )?%rows[.\d]*[,)]", text)
    return [(op, max(
        functools.reduce(lambda a, b: a * int(b), dims.split(","), 1)
        for dims in re.findall(r"\w+\[([\d,]+)\]", result)))
        for result, op in uses]


@pytest.mark.parametrize("entry", ["decode_step", "prefill"])
def test_compiled_step_updates_the_donated_pool_in_place(
        entry, one_chip, monkeypatch):
    """What "caches are donated so XLA updates them in place" rests on
    (serve/llm_engine.py): in the step program the TPU compiler builds,
    the pool is an aliased input/output, it is handed to the paged
    kernel, and NO other op produces an array with a page's dimensions —
    no copy into another layout for the scatter, no slice or
    update-slice of a layer. GQA at head_dim 128, two layers. The
    program is the engine's wrapper, whose integer inputs arrive as one
    staged array: unpacking it is static slices of that array (a few
    KiB), alone or fused into their readers, and nothing else."""
    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = TransformerConfig(
            vocab_size=512, d_model=512, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=128, d_ff=1024, max_seq_len=256,
            block_style="llama", dtype=jnp.bfloat16, remat_policy="none",
            paged_impl="kernel")
        # 2049 pages: a pool too large for the compiler to park in fast
        # memory, as a tiny one is (slices and copies of another kind)
        slots, table, blocks, chunk = 8, 16, 2049, 64

        def shaped(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip), tree)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32,
                                        sharding=one_chip)
        params = shaped(jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0))))
        cache = shaped(jax.eval_shape(
            lambda: init_kv_cache(cfg, blocks, BLOCK)))
        fn, rows = _engine_program(cfg, entry, slots, table, chunk)
        args = (params, i32(*rows), cache)
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        text = jax.jit(fn, donate_argnums=(2,)).lower(*args) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in text
    assert text.count("may-alias") + text.count("must-alias") >= 2
    page = f"{blocks},{cfg.kv_heads},{BLOCK},{cfg.head_dim}]"
    made = re.findall(
        r"= bf16\[(?:\d+,)?" + re.escape(page) + r"\S* ([\w\-]+)\(", text)
    moved = [op for op in made if op not in (
        "parameter", "get-tuple-element", "bitcast", "scatter", "fusion")]
    assert not moved, moved
    # the two scatters (k, v), alone or as the root of a fusion
    assert made.count("scatter") == 2
    # the staged rows: traced as static slices, compiled to slices and
    # fusions (or the rows moved whole into fast memory first) no result
    # of which is larger than the rows themselves
    staged, = [v for v in jaxpr.invars
               if v.aval.shape == rows and v.aval.dtype == jnp.int32]
    assert {e.primitive.name for e in jaxpr.eqns
            if staged in e.invars} == {"slice"}
    readers = _readers_of_the_staged_rows(text)
    assert len(readers) >= 2 and rows[0] * rows[1] * 4 < 4096
    assert {op for op, _ in readers} \
        <= {"slice", "fusion", "copy-start"}, readers
    assert max(n for _, n in readers) <= rows[0] * rows[1], readers


@pytest.mark.parametrize("entry", ["decode_step", "prefill"])
def test_compiled_step_casts_no_weight_of_the_engines_tree(
        entry, one_chip, monkeypatch):
    """The engine hands its programs ``inference_params`` of the model's
    tree, so the program the TPU compiler builds from those avals holds
    no ``convert`` whose result has a weight's shape (stacked, or one
    layer of it), and its temporaries are a few activations: under
    4 MiB here. From the f32 masters' avals the same source compiles to
    a program that casts every stacked weight ahead of its layer loop on
    every call and keeps the bf16 copy among its temporaries (over
    64 MiB here) — which is what the detector must see, and what this
    test would see if the engine's tree stopped being cast. Wide enough
    (2048 x 8192) that the compiler hoists the casts as it does at the
    served widths; narrower, it fuses them into the loop's matmuls."""
    from ray_tpu.models import (TransformerConfig, inference_params,
                                init_kv_cache, init_params)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cfg = TransformerConfig(
        vocab_size=512, d_model=2048, n_layers=2, n_heads=16,
        n_kv_heads=2, head_dim=128, d_ff=8192, max_seq_len=256,
        block_style="llama", dtype=jnp.bfloat16, remat_policy="none",
        paged_impl="kernel")
    slots, table, blocks, chunk = 8, 16, 2049, 64

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def compiled(params):
        cache = shaped(jax.eval_shape(
            lambda: init_kv_cache(cfg, blocks, BLOCK)))
        fn, rows = _engine_program(cfg, entry, slots, table, chunk)
        return jax.jit(fn, donate_argnums=(2,)).lower(
            shaped(params), i32(*rows), cache).compile()

    def masters():
        return init_params(cfg, jax.random.PRNGKey(0))
    try:
        served = jax.eval_shape(lambda: inference_params(cfg, masters()))
        on_served = compiled(served)
        on_masters = compiled(jax.eval_shape(masters))
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()

    shapes = set()
    for leaf in jax.tree.leaves(served):
        if leaf.dtype == jnp.bfloat16 and leaf.ndim >= 2:
            shapes.add(leaf.shape)
            shapes.add(leaf.shape[1:])       # one layer of a stacked leaf
    shapes = {",".join(map(str, s)) for s in shapes if len(s) >= 2}

    def weight_casts(program):
        return [s for s in re.findall(
            r"= bf16\[([\d,]+)\]\S* convert\(", program.as_text())
            if s in shapes]

    assert "tpu_custom_call" in on_served.as_text()
    assert weight_casts(on_served) == []
    assert on_served.memory_analysis().temp_size_in_bytes < 4 << 20
    stacked = weight_casts(on_masters)
    assert f"{cfg.n_layers},{cfg.d_model},{cfg.d_ff}" in stacked, stacked
    assert on_masters.memory_analysis().temp_size_in_bytes > 64 << 20


@pytest.mark.parametrize("entry", ["decode_step", "prefill"])
def test_compiled_selecting_routing_step_reads_the_expert_stack_in_place(
        entry, one_chip, monkeypatch):
    """The served forms of PR 34 (dropless top-k experts, an indexer and
    a third pool) at two layers of narrowed Keye widths: the TPU
    compiler accepts the whole step; the grouped products are its own
    ragged-dot kernels fed the WHOLE ``[layers, experts, ...]`` stack
    (scanned like the other leaves, each layer's experts were sliced
    out first: a copy of all of them in every layer of every call, 22
    of a decode step's 46 ms on the chip); K and V are written in place
    as before; and the temporaries stay a few tiles."""
    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = TransformerConfig(
            vocab_size=512, d_model=1024, n_layers=2, n_heads=8,
            n_kv_heads=2, head_dim=128, d_ff=1024, max_seq_len=4096,
            rotary_dim=128, block_style="llama", dtype=jnp.bfloat16,
            remat_policy="none", paged_impl="kernel", n_experts=16,
            experts_per_token=4, expert_width=512, qk_norm=True,
            index_topk=512, index_heads=4, index_dim=64)
        slots, table, blocks, chunk = 8, 256, 2049, 512

        def shaped(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip), tree)
        params = shaped(jax.eval_shape(lambda: init_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
        cache = shaped(jax.eval_shape(
            lambda: init_kv_cache(cfg, blocks, BLOCK)))
        fn, rows = _engine_program(cfg, entry, slots, table, chunk)
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(
            params, jax.ShapeDtypeStruct(rows, jnp.int32,
                                         sharding=one_chip),
            cache).compile()
        text = compiled.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert set(cache) == {"k", "v", "ki"}
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3
    # no op makes one layer's experts: [16, 1024, 512] or [16, 512, 1024]
    assert not re.findall(r"= bf16\[16,(?:1024,512|512,1024)\]", text)
    assert text.count("may-alias") + text.count("must-alias") >= 3
    page = f"{blocks},{cfg.kv_heads},{BLOCK},{cfg.head_dim}]"
    made = re.findall(
        r"= bf16\[(?:\d+,)?" + re.escape(page) + r"\S* ([\w\-]+)\(", text)
    assert not [op for op in made if op not in (
        "parameter", "get-tuple-element", "bitcast", "scatter", "fusion")]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# ------------------------------------------------ the latent (MLA) forms
@pytest.mark.parametrize("batch,chunk,block_r", [
    (16, 1, 128),           # the cell's decode step: a row a head
    (1, 2048, 512),         # its chunk: 262,144 rows in blocks of 512
])
def test_latent_kernel_compiles_for_v5e_at_the_cells_shapes(
        one_chip, batch, chunk, block_r):
    """The paged kernel over the one-pool latent cache
    (``v_width``: no V pool, a page's first 512 columns its value; the
    chunk's 512 row blocks each bounded by its own highest live
    position) at openPangu's widths: 128 heads
    on one 640-wide row (512 + 64 up to whole lane tiles; a 576-wide
    page copy is refused by Mosaic, "must be aligned to tiling (128)"),
    the whole 36,865-page pool with a traced layer, no temporaries."""
    from jax.experimental.compilation_cache import compilation_cache

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    t = 32768 // BLOCK
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda q, pool, bt, pos, lens, layer: paged_flash_attention(
                q, pool, None, bt, pos, lens, layer=layer, block_r=block_r,
                v_width=512, sm_scale=192 ** -0.5)
        ).lower(s((batch, chunk, 128, 640)), s((5, 36865, 1, BLOCK, 640)),
                s((batch, t), jnp.int32), s((batch, chunk), jnp.int32),
                s((batch,), jnp.int32), s((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    # the result leaves in the caller's layout: one transpose of it
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= batch * chunk * 128 * 512 * 2 + (1 << 20)


@pytest.mark.parametrize("entry", ["decode_step", "prefill"])
def test_compiled_latent_step_copies_no_pool_and_slices_no_expert_stack(
        entry, one_chip, monkeypatch):
    """The latent forms (a leading dense layer in a scan of its own, the
    expert layers after it, sandwich norms, a shared expert, 8 of 32
    experts held) at narrowed widths: the TPU compiler accepts the whole
    step; the ONE latent pool is an aliased input/output that only the
    row scatters of the two scans write; the latent kernel is called
    once a scan; the grouped products are fed the WHOLE ``[layers,
    held, ...]`` stack; the temporaries stay small."""
    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = TransformerConfig(
            vocab_size=512, d_model=1024, n_layers=3, n_heads=16,
            head_dim=192, d_ff=2048, max_seq_len=4096, rotary_dim=64,
            block_style="llama", dtype=jnp.bfloat16, remat_policy="none",
            paged_impl="kernel", norm_eps=1e-5, q_lora_rank=256,
            kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
            v_head_dim=128, sandwich_norm=True, n_dense_layers=1,
            n_experts=32, experts_per_token=4, expert_width=512,
            shared_expert_width=512, router_score="sigmoid",
            routed_scale=2.5, experts_held=8, expert_first=8)
        slots, table, blocks, chunk = 8, 256, 2049, 512

        def shaped(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip), tree)
        params = shaped(jax.eval_shape(lambda: init_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
        cache = shaped(jax.eval_shape(
            lambda: init_kv_cache(cfg, blocks, BLOCK)))
        fn, rows = _engine_program(cfg, entry, slots, table, chunk)
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(
            params, jax.ShapeDtypeStruct(rows, jnp.int32,
                                         sharding=one_chip),
            cache).compile()
        text = compiled.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert {k: v.shape for k, v in cache.items()} \
        == {"latent": (3, blocks, 1, BLOCK, 640)}
    assert text.count('custom_call_target="tpu_custom_call"') >= 2
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3
    # no op makes one layer's held experts: [8, 1024, 512] or its down
    assert not re.findall(r"= bf16\[8,(?:1024,512|512,1024)\]", text)
    assert text.count("may-alias") + text.count("must-alias") >= 1
    page = f"{blocks},1,{BLOCK},640]"
    made = re.findall(
        r"= bf16\[(?:\d+,)?" + re.escape(page) + r"\S* ([\w\-]+)\(", text)
    assert not [op for op in made if op not in (
        "parameter", "get-tuple-element", "bitcast", "scatter", "fusion")]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("entry,batch", [
    ("decode_step", 1), ("decode_step", 32), ("prefill", 1)])
def test_compiled_step_updates_the_recurrent_state_in_place(
        entry, batch, one_chip, monkeypatch):
    """A stack with "mamba" layers at the published scan widths (128
    heads of 64 over a state of 128, blocks of 256) and a narrow model
    around them, a period of ten layers with 36 of 72 experts held: the
    step programs compile for a described v5e, a decode step of ONE row
    among them (ten rows against 9 x 36 groups of the grouped product,
    which the compiler refuses unpadded: ``moe._FEW_ROWS``), and the
    per-slot state, 1.2 GB here, is updated where it lies: the program's
    temporaries are a small part of it (a chunk's program that ordered
    heads and channels its own way relaid the whole array on the way in
    and out: the state keeps them as one axis)."""
    from ray_tpu.models import (TransformerConfig, decode_step,
                                inference_params, init_kv_cache,
                                init_params, prefill)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cfg = TransformerConfig(
        vocab_size=512, d_model=512, n_layers=10, n_heads=4, n_kv_heads=2,
        head_dim=128, d_ff=256, max_seq_len=2048, rotary_dim=0,
        block_style="llama", dtype=jnp.bfloat16, remat_policy="none",
        paged_impl="kernel", norm_eps=1e-5,
        layer_pattern=["mamba"] * 5 + ["full"] + ["mamba"] * 4,
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_chunk=256,
        attn_scale=1 / 128, embed_scale=12.0, residual_scale=0.22,
        logit_scale=1 / 16, tie_embeddings=True, n_experts=72,
        experts_per_token=10, expert_width=256, shared_expert_width=256,
        experts_held=36)
    slots, table, chunk = 32, 128, 1024

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    try:
        params = shaped(jax.eval_shape(lambda: inference_params(
            cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype))))
        cache = shaped(jax.eval_shape(lambda: init_kv_cache(
            cfg, 1 + slots * table, BLOCK, state_slots=slots)))
        if entry == "decode_step":
            fn = functools.partial(decode_step, cfg)
            args = (params, i32(batch), cache, i32(batch, table), i32(batch))
        else:
            fn = functools.partial(prefill, cfg)
            args = (params, i32(1, chunk), cache, i32(1, table), i32(1),
                    i32(1), None, None, i32(1))
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    state = 9 * slots * 8192 * 128 * 4
    assert cache["ssm"].shape == (9, slots, 128, 8192)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state
    assert memory.temp_size_in_bytes < state // 4, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text                     # the paged layer
    if entry != "decode_step":
        return
    # the one-token update is the kernel, once a run of "mamba" layers,
    # on the whole array (its output aliased to it), and nothing else
    # makes an array of the rows' states, in either order of its axes
    kernels = re.findall(
        rf"%ssm_step[.\d]* = \(f32\[{batch},1,8192\]\S*, "
        rf"f32\[9,{slots},128,8192\]\S*\) custom-call\(", text)
    assert len(kernels) == 2, kernels
    made = re.findall(
        r"= f32\[(?:\d+,)+(?:128,8192|8192,128)\]\S* ([\w\-]+)\(", text)
    assert not [op for op in made if op not in (
        "parameter", "get-tuple-element", "bitcast")], made


def test_step_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip,
                                                          monkeypatch):
    """The one-token state update (``ops/ssm.py`` ``ssd_step_slots``) at
    the state-space cell's shapes: 64 rows on nine layers of 64 slots of
    ``[128, 8192]`` float32 (2.4 GB), a traced layer, row b in slot b and
    the slots given. The Mosaic compile accepts its tiles under the
    default scoped VMEM limit (the kernel asks for no other), the whole
    array is aliased to the output and the call has no temporaries."""
    from ray_tpu.ops.ssm import ssd_step_slots
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    rows, heads, width, n, layers = 64, 128, 64, 128, 9
    args = (s((rows, heads, width), jnp.bfloat16), s((rows, heads)),
            s((heads,)), s((rows, 1, n), jnp.bfloat16),
            s((rows, 1, n), jnp.bfloat16), s((heads,)),
            s((layers, rows, n, heads * width)), s((), jnp.int32))
    flags = (s((rows,), jnp.bool_), s((rows,), jnp.bool_))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for slots in (None, s((rows,), jnp.int32)):
            compiled = jax.jit(
                functools.partial(ssd_step_slots, impl="auto"),
                donate_argnums=(6,)).lower(*args, slots, *flags).compile()
            assert "tpu_custom_call" in compiled.as_text()
            memory = compiled.memory_analysis()
            assert memory.alias_size_in_bytes \
                >= layers * rows * n * heads * width * 4
            assert memory.temp_size_in_bytes < 1 << 20
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def test_delta_step_kernel_compiles_for_v5e_at_the_cells_shapes(
        one_chip, monkeypatch):
    """The delta rule's one-token update (``ops/delta.py``
    ``gated_delta_step_slots``) at the delta-rule cell's shapes: 16 rows
    on twelve layers of 16 slots of ``[96, 30 x 192]`` float32 (425 MB),
    a traced layer, row b in slot b and the slots given. It is ONE
    ``delta_step`` custom call whose result is the whole array, aliased
    to the argument, the Mosaic compile accepts its tile (a row's whole
    state, 2.2 MB, two in and two out) under the default scoped VMEM
    limit, and the program holds no temporary of the state's size: the
    rows' ``[4, 5760]`` and ``[96, 60]`` inputs and no more."""
    from ray_tpu.ops.delta import gated_delta_step_slots
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    rows, heads, dk, dv, layers = 16, 30, 96, 192, 12
    args = (s((rows, heads, dk)), s((rows, heads, dk)),
            s((rows, heads, dv), jnp.bfloat16), s((rows, heads)),
            s((rows, heads)), s((layers, rows, dk, heads * dv)),
            s((), jnp.int32))
    flags = (s((rows,), jnp.bool_), s((rows,), jnp.bool_))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for slots in (None, s((rows,), jnp.int32)):
            compiled = jax.jit(
                functools.partial(gated_delta_step_slots, impl="auto"),
                donate_argnums=(5,)).lower(*args, slots, *flags).compile()
            text = compiled.as_text()
            kernels = re.findall(
                rf"%delta_step[.\d]* = \(f32\[{rows},1,5760\]\S*, "
                rf"f32\[{layers},{rows},96,5760\]\S*\) custom-call\(",
                text)
            assert len(kernels) == 1, kernels
            made = re.findall(
                r"= f32\[(?:\d+,)+(?:96,5760|5760,96)\]\S* ([\w\-]+)\(",
                text)
            assert not [op for op in made if op not in (
                "parameter", "get-tuple-element", "bitcast")], made
            memory = compiled.memory_analysis()
            assert memory.alias_size_in_bytes \
                >= layers * rows * dk * heads * dv * 4
            assert memory.temp_size_in_bytes < 1 << 20
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("entry,batch", [
    ("decode_step", 1), ("decode_step", 16), ("prefill", 1)])
def test_compiled_step_updates_the_delta_state_in_place(
        entry, batch, one_chip, monkeypatch):
    """A stack with "delta" layers at the published widths of the mixer
    (30 heads, keys of 96 and values of 192, blocks of 64: a key width
    that is no multiple of the 128 lanes) and a narrow model around them,
    two periods of four layers in the output-normed block with the
    whole-width QK-norm: the step programs compile for a described v5e,
    a decode step of ONE row among them and a chunk's call that writes
    four snapshot rows, and the per-slot state ``[layers, slots, 96, 30 x
    192]`` and its snapshot rows are updated where they lie: the
    program's temporaries are a small part of them."""
    from ray_tpu.models import (TransformerConfig, decode_step,
                                inference_params, init_kv_cache,
                                init_params, prefill)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cfg = TransformerConfig(
        vocab_size=512, d_model=512, n_layers=8, n_heads=4, n_kv_heads=4,
        head_dim=128, d_ff=256, max_seq_len=2048, rotary_dim=0,
        block_style="llama", dtype=jnp.bfloat16, remat_policy="none",
        paged_impl="kernel", norm_eps=1e-6,
        layer_pattern=["delta", "delta", "delta", "full"],
        delta_heads=30, delta_key_dim=96, delta_value_dim=192,
        delta_neg_eigval=True, output_norm=True, qk_norm_whole=True)
    slots, table, chunk, snapshots = 16, 128, 2048, 96

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    try:
        params = shaped(jax.eval_shape(lambda: inference_params(
            cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype))))
        cache = shaped(jax.eval_shape(lambda: init_kv_cache(
            cfg, 1 + slots * table, BLOCK, state_slots=slots,
            state_snapshots=snapshots)))
        if entry == "decode_step":
            fn = functools.partial(decode_step, cfg)
            args = (params, i32(batch), cache, i32(batch, table), i32(batch))
        else:
            fn = functools.partial(prefill, cfg)
            args = (params, i32(1, chunk), cache, i32(1, table), i32(1),
                    i32(1), None, None, i32(1), i32(1, 4))
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert cache["delta"].shape == (6, slots, 96, 5760)
    assert cache["delta_snap"].shape == (6, 1 + snapshots, 96, 5760)
    state = 6 * (slots + 1 + snapshots) * 96 * 5760 * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state
    # a chunk's call holds its 2,048 tokens' float32 q, k, v, the blocks'
    # right-hand sides and solves (32 x 30 of [64, 96 + 192], twice) and
    # what the scan over blocks reads (0.47 GB: as much at the cell's own
    # widths, where the snapshot rows alone are 2.6 GB); a decode step a
    # few rows' states
    limit = state // 2 if entry == "prefill" else state // 32
    assert memory.temp_size_in_bytes < limit, memory.temp_size_in_bytes
    assert "tpu_custom_call" in compiled.as_text()       # the paged layers


@pytest.mark.parametrize("entry,batch", [
    ("decode_step", 1), ("decode_step", 48), ("prefill", 1)])
def test_compiled_step_of_one_sublayer_a_layer_keeps_its_state_in_place(
        entry, batch, one_chip, monkeypatch):
    """A stack whose layers are ONE sublayer each, the published
    pattern's first 22 letters (``MEMEMEM*EMEMEMEM*EMEME``: ten Mamba-2
    mixers at the published scan widths, 128 heads of 64 over a state of
    128 with EIGHT groups of B and C, two paged layers of 16 query heads a
    kv head, ten expert layers of 64 of 512 ungated experts of 1024 x 2688
    in a latent, 22 a token) and a narrow model around them: the step programs compile
    for a described v5e, a decode step of ONE row among them (22
    assignments against 10 x 64 groups of the grouped product: the case
    of ``moe._FEW_ROWS``), of 48 rows and a 1,024-token chunk (eight scan
    blocks of 128). The per-slot state, 2.0 GB here, is updated where it
    lies (heads and channels are one axis of it, and a group is 1,024
    consecutive lanes: a tile of the step kernel reads its group's
    column), the one-token update is the kernel once a mixer layer, and
    the expert layers slice no expert stack."""
    from ray_tpu.models import (TransformerConfig, decode_step,
                                inference_params, init_kv_cache,
                                init_params, prefill)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    kinds = {"M": "mamba", "E": "ffn", "*": "full"}
    cfg = TransformerConfig(
        vocab_size=512, d_model=512, n_layers=22, n_heads=32, n_kv_heads=2,
        head_dim=128, d_ff=256, max_seq_len=2048, rotary_dim=0,
        block_style="llama", dtype=jnp.bfloat16, remat_policy="none",
        paged_impl="kernel", norm_eps=1e-5, paged_block_r=16,
        paged_block_r_prefill=512,
        layer_pattern=[kinds[c] for c in "MEMEMEM*EMEMEMEM*EMEME"],
        mixer_only=True, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
        ssm_chunk=128, ssm_groups=8, n_experts=512, experts_per_token=22,
        expert_width=2688, shared_expert_width=512, router_score="sigmoid",
        router_bias=True, routed_scale=5.0, experts_held=64,
        expert_act="relu2", moe_latent=1024)
    slots, table, chunk = 48, 128, 1024

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    try:
        params = shaped(jax.eval_shape(lambda: inference_params(
            cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype))))
        cache = shaped(jax.eval_shape(lambda: init_kv_cache(
            cfg, 1 + slots * table, BLOCK, state_slots=slots)))
        if entry == "decode_step":
            fn = functools.partial(decode_step, cfg)
            args = (params, i32(batch), cache, i32(batch, table), i32(batch))
        else:
            fn = functools.partial(prefill, cfg)
            args = (params, i32(1, chunk), cache, i32(1, table), i32(1),
                    i32(1), None, None, i32(1))
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    state = 10 * slots * 8192 * 128 * 4
    assert cache["ssm"].shape == (10, slots, 128, 8192)
    assert cache["conv"].shape == (10, slots, 3, 8192 + 2 * 8 * 128)
    assert cache["k"].shape[0] == 2                 # the two paged layers
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state
    # a chunk's 22,528 assignments hold their rows between the experts'
    # two matrices (0.5 GB, as at the published model width); a decode
    # step a few rows' states
    limit = state // 2 if entry == "prefill" else state // 32
    assert memory.temp_size_in_bytes < limit, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text                     # the paged layers
    # no copy of the held experts of all ten layers, in either matrix
    stacks = re.findall(
        r"= bf16\[(?:10,64|640),(?:1024,2688|2688,1024)\]\S* ([\w\-]+)\(",
        text)
    assert not [op for op in stacks if op not in (
        "parameter", "get-tuple-element", "bitcast")], stacks
    if entry != "decode_step":
        return
    # the one-token update is the kernel, once a mixer layer (each a run
    # of its own between expert layers), on the whole array, and nothing
    # else makes an array of the rows' states, in either order of its axes
    kernels = re.findall(
        rf"%ssm_step[.\d]* = \(f32\[{batch},1,8192\]\S*, "
        rf"f32\[10,{slots},128,8192\]\S*\) custom-call\(", text)
    assert len(kernels) == 10, kernels
    made = re.findall(
        r"= f32\[(?:\d+,)+(?:128,8192|8192,128)\]\S* ([\w\-]+)\(", text)
    assert not [op for op in made if op not in (
        "parameter", "get-tuple-element", "bitcast")], made


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304), (1024, 1024)],
                         ids=["up", "down", "the widest blocks"])
def test_grouped_kernels_compile_for_v5e_at_the_rules_tiles(one_chip, k, n):
    """The trained experts' three kernels (``models/moe.py``: megablox
    ``gmm``, its transpose, ``tgmm``) at the tiles the shape rule picks
    for a turn of ``mellum2-12b-a2.5b.train_moe_8k`` (32,768 rows of 16
    experts, 2304 x 896 and back) and at the widest blocks it can pick
    (1024 x 1024: the most VMEM it accepts for the forward and dX; dW's
    most is the turn's own): the Mosaic compile accepts each for a
    described v5e. The next tile up of each is refused there for its
    VMEM (PERF.md, PR 64), which is what the rule's budget keeps out."""
    from jax.experimental.compilation_cache import compilation_cache
    from ray_tpu.models import moe
    rows, groups = 32768, 16
    fwd, dx, dw = moe._gmm_tiles(rows, k, n, "bfloat16")

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    sizes = s((groups,), jnp.int32)
    calls = [
        (lambda x, w, z: moe._megablox.gmm(x, w, z, jnp.float32, fwd),
         s((rows, k)), s((groups, k, n))),
        (lambda g, w, z: moe._megablox.gmm(g, w, z, jnp.bfloat16, dx,
                                           transpose_rhs=True),
         s((rows, n)), s((groups, k, n))),
        (lambda x, g, z: moe._megablox.tgmm(x.T, g, z, jnp.float32, dw),
         s((rows, k)), s((rows, n)))]
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for fn, a, b in calls:
            compiled = jax.jit(fn).lower(a, b, sizes).compile()
            assert "tpu_custom_call" in compiled.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
