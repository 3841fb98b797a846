"""Paged-decode parity: chunked prefill + N batched decode steps over
the paged KV cache must reproduce one full-context ``apply`` over the
concatenated sequence — per chunk position and per decode step, for both
block styles, with GQA, and across uneven last blocks. This is the
correctness contract the serving engine is built on: if it holds, the
engine can admit/evict/interleave freely without touching model code."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (TransformerConfig, decode_step,
                            init_kv_cache, init_params, prefill)
from ray_tpu.models.transformer import _forward_with_cache, apply
from ray_tpu.ops import attention_reference, paged_attention

pytestmark = pytest.mark.serve_llm

TOL = dict(rtol=2e-4, atol=2e-4)


def _cfg(**kw):
    base = dict(vocab_size=97, d_model=32, n_layers=2, n_heads=4,
                head_dim=8, d_ff=64, max_seq_len=64, rotary_dim=8,
                block_style="gptj", dtype=jnp.float32,
                remat_policy="none", ce_chunk_size=0)
    base.update(kw)
    return TransformerConfig(**base)


def _block_tables(batch, table_len, first_block=1):
    """Disjoint block tables like the engine allocates (block 0 is the
    engine's reserved trash block — kept out of the tables here too)."""
    bt = np.zeros((batch, table_len), np.int32)
    nxt = first_block
    for b in range(batch):
        for t in range(table_len):
            bt[b, t] = nxt
            nxt += 1
    return jnp.asarray(bt), nxt


def _run_paged(cfg, ids, prompt_len, block_size, table_len,
               chunk=3):
    """Chunked prefill of ``prompt_len`` tokens then decode the rest;
    returns (prefill_logits [B, prompt, V], decode_logits [B, n, V])."""
    B, total = ids.shape
    bt, n_used = _block_tables(B, table_len)
    cache = init_kv_cache(cfg, num_blocks=n_used, block_size=block_size)
    vocab = cfg.vocab_size
    pre = np.zeros((B, prompt_len, vocab), np.float32)
    start = 0
    while start < prompt_len:
        n = min(chunk, prompt_len - start)
        buf = np.zeros((B, chunk), np.int32)
        buf[:, :n] = np.asarray(ids[:, start:start + n])
        logits, cache = prefill(
            cfg, _run_paged.params, jnp.asarray(buf), cache, bt,
            jnp.full((B,), start, jnp.int32), jnp.full((B,), n, jnp.int32))
        pre[:, start:start + n] = np.asarray(logits[:, :n])
        start += n
    dec = []
    for i in range(prompt_len, total):
        logits, cache = decode_step(
            cfg, _run_paged.params, ids[:, i], cache, bt,
            jnp.full((B,), i, jnp.int32))
        dec.append(np.asarray(logits))
    return pre, np.stack(dec, axis=1) if dec else None


@pytest.mark.parametrize("style,kv_heads", [
    pytest.param("gptj", None, marks=pytest.mark.slow),
    pytest.param("llama", 2, marks=pytest.mark.slow)])
def test_prefill_decode_parity_vs_full_forward(style, kv_heads):
    """prompt=7 with block_size=4: the last block is UNEVEN (3 tokens);
    chunked prefill (3+3+1) and 9 decode steps must match apply()."""
    cfg = _cfg(block_style=style, n_kv_heads=kv_heads)
    params = init_params(cfg, jax.random.PRNGKey(0))
    _run_paged.params = params
    B, prompt, n_dec = 2, 7, 9
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, prompt + n_dec),
                             0, cfg.vocab_size)
    full = np.asarray(apply(cfg, params, ids))
    pre, dec = _run_paged(cfg, ids, prompt, block_size=4, table_len=8)
    np.testing.assert_allclose(pre, full[:, :prompt], **TOL)
    np.testing.assert_allclose(dec, full[:, prompt:], **TOL)


@pytest.mark.slow
def test_single_vs_chunked_prefill_identical():
    """Chunk size must be invisible: prefilling in chunks of 2 and in
    one chunk of 8 writes identical caches and logits."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(2))
    _run_paged.params = params
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, 97)
    pre_a, dec_a = _run_paged(cfg, ids, 8, block_size=4, table_len=4,
                              chunk=2)
    pre_b, dec_b = _run_paged(cfg, ids, 8, block_size=4, table_len=4,
                              chunk=8)
    np.testing.assert_allclose(pre_a, pre_b, **TOL)
    np.testing.assert_allclose(dec_a, dec_b, **TOL)


def test_paged_attention_matches_reference():
    """The op itself: gather+mask attention over scattered cache blocks
    == dense reference attention over the ordered sequence."""
    rng = np.random.default_rng(0)
    B, S, H, D, bs = 2, 12, 4, 8, 4
    T = S // bs
    k_seq = rng.normal(size=(B, S, H, D)).astype(np.float32)
    v_seq = rng.normal(size=(B, S, H, D)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)).astype(np.float32))
    # scatter the sequences into a shuffled block pool
    n_blocks = 1 + B * T
    kc = np.zeros((n_blocks, H, bs, D), np.float32)    # [N, KVH, bs, D]
    vc = np.zeros((n_blocks, H, bs, D), np.float32)
    order = rng.permutation(np.arange(1, n_blocks))
    bt = order.reshape(B, T)
    for b in range(B):
        for t in range(T):
            kc[bt[b, t]] = k_seq[b, t * bs:(t + 1) * bs].swapaxes(0, 1)
            vc[bt[b, t]] = v_seq[b, t * bs:(t + 1) * bs].swapaxes(0, 1)
    # query sits at position 9 -> attends positions 0..9 of 12 cached
    qpos = jnp.full((B, 1), 9, jnp.int32)
    out = paged_attention(q, jnp.asarray(kc), jnp.asarray(vc),
                          jnp.asarray(bt), qpos)
    ref = attention_reference(
        q, jnp.asarray(k_seq[:, :10]), jnp.asarray(v_seq[:, :10]),
        causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("style,kv_heads", [
    pytest.param("gptj", None, marks=pytest.mark.slow),
    pytest.param("llama", 2, marks=pytest.mark.slow),
])
def test_prefill_decode_parity_kernel_impl(style, kv_heads):
    """The full vertical with the Pallas kernel forced (interpret mode
    on CPU): chunked prefill + decode through ``paged_impl="interpret"``
    must reproduce apply() exactly like the reference path — uneven
    last block and GQA included."""
    cfg = _cfg(block_style=style, n_kv_heads=kv_heads,
               paged_impl="interpret")
    params = init_params(cfg, jax.random.PRNGKey(0))
    _run_paged.params = params
    B, prompt, n_dec = 2, 7, 5
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, prompt + n_dec),
                             0, cfg.vocab_size)
    full = np.asarray(apply(cfg, params, ids))
    pre, dec = _run_paged(cfg, ids, prompt, block_size=4, table_len=8)
    np.testing.assert_allclose(pre, full[:, :prompt], **TOL)
    np.testing.assert_allclose(dec, full[:, prompt:], **TOL)


def test_gqa_reference_read_parity_with_repeat_formulation():
    """Regression for the reshape-einsum GQA read: decode logits under
    a GQA config must be identical whether the paged reference gathers
    grouped heads (the new path) or a materialized ``jnp.repeat`` cache
    copy (the old one, reconstructed here)."""
    import math
    rng = np.random.default_rng(2)
    B, H, KVH, D, bs, T = 2, 8, 2, 8, 4, 3
    kc = rng.normal(size=(1 + B * T, KVH, bs, D)).astype(np.float32)
    vc = rng.normal(size=(1 + B * T, KVH, bs, D)).astype(np.float32)
    q = rng.normal(size=(B, 1, H, D)).astype(np.float32)
    bt = np.arange(1, 1 + B * T, dtype=np.int32).reshape(B, T)
    pos = np.array([[7], [10]], np.int32)
    new = paged_attention(q, kc, vc, bt, jnp.asarray(pos),
                          impl="reference")

    def repeated(cache):     # [N, KVH, bs, D] pool -> [B, K, H, D]
        g = jnp.take(jnp.asarray(cache), jnp.asarray(bt), axis=0)
        return jnp.repeat(g.transpose(0, 1, 3, 2, 4)
                          .reshape(B, T * bs, KVH, D), H // KVH, axis=2)
    k, v = repeated(kc), repeated(vc)
    mask = np.arange(T * bs)[None, None, :] <= pos[:, :, None]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (1.0 / math.sqrt(D))
    s = jnp.where(jnp.asarray(mask)[:, None], s, -1e30)
    old = jnp.einsum("bhqk,bkhd->bqhd",
                     jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(new), np.asarray(old),
                               rtol=1e-5, atol=1e-5)


def _engine_tokens(cfg_kw, engine_kw, prompts):
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine
    cfg = _cfg(**cfg_kw)
    eng = LLMEngine(cfg, EngineConfig(**engine_kw),
                    params=init_params(cfg, jax.random.PRNGKey(0)))
    try:
        return [list(eng.generate_sync(p, 8)) for p in prompts]
    finally:
        eng.shutdown()


def test_engine_greedy_decode_bitwise_stable_kernel_vs_reference():
    """Interpret-mode kernel vs XLA reference through the FULL
    LLMEngine: greedy token streams must be identical — and with
    prompt-lookup speculation on top of the kernel too (the spec-decode
    bit-exactness pin composes with the kernel dispatch)."""
    ekw = dict(decode_slots=2, kv_block_size=4, max_seq_len=32,
               prefill_chunk=8, max_new_tokens=8)
    prompts = [[5, 9, 2, 7, 11, 3], [4, 4, 8, 4, 4, 8, 4, 4]]
    ref = _engine_tokens(dict(block_style="llama", n_kv_heads=2),
                         ekw, prompts)
    ker = _engine_tokens(dict(block_style="llama", n_kv_heads=2,
                              paged_impl="interpret"), ekw, prompts)
    assert ref == ker
    spec = _engine_tokens(dict(block_style="llama", n_kv_heads=2,
                               paged_impl="interpret"),
                          dict(ekw, spec_tokens=3), prompts)
    assert ref == spec


def test_gqa_cache_stores_kv_heads_only():
    cfg = _cfg(block_style="llama", n_kv_heads=2)
    cache = init_kv_cache(cfg, num_blocks=5, block_size=4)
    assert cache["k"].shape == (cfg.n_layers, 5, 2, 4, cfg.head_dim)
    assert cache["v"].shape == cache["k"].shape


def test_moe_decode_unsupported():
    cfg = _cfg(n_experts=2)
    params_cfg = _cfg()   # params shape irrelevant; raise happens first
    params = init_params(params_cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(params_cfg, num_blocks=3, block_size=4)
    bt = jnp.ones((1, 2), jnp.int32)
    with pytest.raises(NotImplementedError):
        decode_step(cfg, params, jnp.zeros((1,), jnp.int32), cache, bt,
                    jnp.zeros((1,), jnp.int32))


# ------------------------------------------------ the pool is carried whole
def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, sub-jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_scans(sub))
    return found


@pytest.mark.parametrize("entry", ["decode_step", "prefill"])
def test_layer_scan_carries_the_pool_whole(entry):
    """The pool rides the layer scan's CARRY: nothing with a page's
    dimensions is among the scanned inputs or the stacked outputs (which
    XLA slices per layer and stacks into a new buffer: a copy of the
    whole pool every step), nor among the closed-over constants. With
    the cache donated that is what lets XLA update it in place."""
    cfg = _cfg(n_layers=3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, num_blocks=9, block_size=4)
    bt, _ = _block_tables(2, 4)
    if entry == "decode_step":
        jaxpr = jax.make_jaxpr(
            lambda p, c: decode_step(cfg, p, jnp.zeros((2,), jnp.int32),
                                     c, bt, jnp.full((2,), 5, jnp.int32))
        )(params, cache)
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, c: prefill(cfg, p, jnp.zeros((2, 3), jnp.int32), c,
                                 bt, jnp.zeros((2,), jnp.int32),
                                 jnp.full((2,), 3, jnp.int32))
        )(params, cache)
    scans = _scans(jaxpr.jaxpr)
    assert len(scans) == 1
    scan, = scans
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    pool, page = cache["k"].shape, cache["k"].shape[1:]
    consts = [v.aval.shape for v in scan.invars[:n_consts]]
    carry = [v.aval.shape for v in scan.invars[n_consts:n_consts + n_carry]]
    xs = [v.aval.shape for v in scan.invars[n_consts + n_carry:]]
    ys = [v.aval.shape for v in scan.outvars[n_carry:]]
    assert carry.count(pool) == 2           # k and v
    assert [v.aval.shape for v in scan.outvars[:n_carry]].count(pool) == 2
    for shape in consts + xs + ys:
        assert shape[-4:] != page, shape


def _old_trunk(c):
    """What the trunk computed before the pool was carried, as a plain
    loop: slice each layer's 4-D pool out, scatter the new rows into
    the slice, attend the slice, restack the slices into a new pool.
    Head, layer body and tail are each one compiled program, as the
    scan's body is, so the arithmetic fuses alike and bits can be
    compared."""
    from ray_tpu.models import transformer as T
    layout = "gptj" if c.block_style == "gptj" else "neox"
    e, dt = c.d_model, c.dtype

    @jax.jit
    def one_layer(x, lp, kc, vc, bt, positions, write_mask, lens):
        bs = kc.shape[2]
        sin, cos = T.rotary_table(
            bt.shape[1] * bs,
            c.rotary_dim if c.block_style == "gptj" else c.head_dim,
            c.rope_base)
        h = T.layer_norm(x, lp["ln_scale"], lp["ln_bias"]) \
            if c.block_style == "gptj" else T.rms_norm(x, lp["attn_norm"])

        def proj(w, n):
            return jnp.einsum("bse,ehd->bshd", h.astype(dt),
                              w.reshape(e, n, -1).astype(dt))
        q = T.apply_rotary(proj(lp["wq"], c.n_heads), sin, cos,
                           positions=positions, layout=layout)
        k = T.apply_rotary(proj(lp["wk"], c.kv_heads), sin, cos,
                           positions=positions, layout=layout)
        v = proj(lp["wv"], c.kv_heads)
        bid = jnp.take_along_axis(bt, positions // bs, axis=1)
        bid = jnp.where(write_mask, bid, kc.shape[0])
        slot = positions % bs
        kc = kc.at[bid, :, slot].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[bid, :, slot].set(v.astype(vc.dtype), mode="drop")
        att = paged_attention(q, kc, vc, bt, positions, lens=lens,
                              impl=c.paged_impl)
        att = jnp.einsum(
            "bshd,hde->bse", att,
            lp["wo"].reshape(c.n_heads, c.head_dim, e).astype(dt))
        if c.block_style == "gptj":
            mlp, _ = T._mlp_sublayer(c, h, lp)
            return x + (att + mlp).astype(x.dtype), kc, vc
        x = x + att.astype(x.dtype)
        h2 = T.rms_norm(x, lp["mlp_norm"]).astype(dt)
        mlp, _ = T._mlp_sublayer(c, h2, lp)
        return x + mlp.astype(x.dtype), kc, vc

    embed = jax.jit(lambda emb, ids: jnp.take(emb, ids, axis=0).astype(dt))
    tail = jax.jit(lambda p, x: T._lm_head(c, p, T._final_norm(c, p, x)))

    def forward(params, ids, cache, bt, positions, write_mask, lens):
        x = embed(params["embed"], ids)
        new_k, new_v = [], []
        for layer in range(c.n_layers):
            x, kc, vc = one_layer(
                x, jax.tree.map(lambda a: a[layer], params["layers"]),
                cache["k"][layer], cache["v"][layer], bt, positions,
                write_mask, lens)
            new_k.append(kc)
            new_v.append(vc)
        return tail(params, x), \
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    return forward


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("style,kv_heads", [
    ("gptj", None), ("gptj", 2), ("llama", None), ("llama", 2)])
def test_carried_pool_equals_sliced_and_restacked_pool(style, kv_heads,
                                                       impl):
    """Two prefill chunks (the second with a padded tail, one slot idle
    throughout) then three decode steps: logits and the returned pool
    equal, bit for bit, the per-layer slice / write / restack loop the
    trunk used to be. The pool starts as noise, so a write that strays
    shows: masked positions and the idle slot change no page but the
    trash block 0."""
    cfg = _cfg(block_style=style, n_kv_heads=kv_heads, n_layers=3,
               paged_impl=impl)
    params = init_params(cfg, jax.random.PRNGKey(0))
    bs, T, chunk = 4, 4, 4
    # slots 0 and 1 own blocks 1..8; slot 2 is idle: its table is the
    # engine's all-trash row; blocks 9 and 10 belong to nobody
    bt = np.zeros((3, T), np.int32)
    bt[:2] = np.arange(1, 1 + 2 * T).reshape(2, T)
    bt = jnp.asarray(bt)
    shape = init_kv_cache(cfg, num_blocks=11, block_size=bs)["k"].shape
    k0, v0 = jax.random.normal(jax.random.PRNGKey(5), (2,) + shape)
    first = {"k": k0, "v": v0}
    prompt = np.array([7, 5, 0])                  # tokens per slot
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (3, 12), 0, cfg.vocab_size))

    def run(forward):
        cache, logits = dict(first), []
        for start in (0, chunk):
            lens = np.clip(prompt - start, 0, chunk).astype(np.int32)
            start_pos = jnp.full((3,), start, jnp.int32)
            positions = start_pos[:, None] + jnp.arange(chunk,
                                                        dtype=jnp.int32)
            mask = jnp.arange(chunk)[None, :] < jnp.asarray(lens)[:, None]
            out, cache = forward(
                params, jnp.asarray(ids[:, start:start + chunk]), cache,
                bt, positions, mask, start_pos + jnp.asarray(lens))
            logits.append(out)
        seq = prompt.astype(np.int32)
        for i in range(3):
            toks = jnp.asarray(ids[np.arange(3), seq])[:, None]
            out, cache = forward(
                params, toks, cache, bt, jnp.asarray(seq)[:, None],
                jnp.ones((3, 1), bool), jnp.asarray(seq) + 1)
            logits.append(out)
            seq = seq + np.array([1, 1, 0], np.int32)   # idle stays at 0
        return logits, cache

    new_logits, new_cache = run(jax.jit(
        functools.partial(_forward_with_cache, cfg)))
    old_logits, old_cache = run(_old_trunk(cfg))
    for new, old in zip(new_logits, old_logits):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    for name in ("k", "v"):
        got = np.asarray(new_cache[name])
        np.testing.assert_array_equal(got, np.asarray(old_cache[name]))
        before = np.asarray(first[name])
        # nobody's pages keep their noise; so do the rows of owned
        # pages past each sequence's length (7+3 and 5+3 tokens)
        np.testing.assert_array_equal(got[:, 9:], before[:, 9:])
        np.testing.assert_array_equal(got[:, 3, :, 2:], before[:, 3, :, 2:])
        np.testing.assert_array_equal(got[:, 4], before[:, 4])
        np.testing.assert_array_equal(got[:, 7:9], before[:, 7:9])
        # the live rows were written, in every layer
        assert (got[:, 1:3] != before[:, 1:3]).all(axis=(1, 2, 3, 4)).all()


@pytest.mark.parametrize("style,kv_heads", [
    ("gptj", None), ("gptj", 2), ("llama", None), ("llama", 2)])
def test_inference_params_cast_once_gives_the_same_bits(style, kv_heads):
    """The tree a serving engine holds (``inference_params``: matmul and
    lookup leaves in the compute dtype, norm leaves f32) against the f32
    masters, through the same jitted trunk: two prefill chunks and three
    decode steps give logits and pools equal bit for bit, because the
    use sites round the f32 leaves exactly as the one cast did. Where
    nothing needs casting the function hands back the object it got."""
    from ray_tpu.models import inference_params
    cfg = _cfg(block_style=style, n_kv_heads=kv_heads, n_layers=3,
               dtype=jnp.bfloat16)
    masters = init_params(cfg, jax.random.PRNGKey(0))
    # biases and norms are zeros and ones at init: give them values
    # that rounding to bf16 changes
    leaves, treedef = jax.tree.flatten(masters)
    noise = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    masters = jax.tree.unflatten(treedef, [
        a + 0.02 * jax.random.normal(k, a.shape) if a.ndim <= 2 else a
        for a, k in zip(leaves, noise)])
    served = inference_params(cfg, masters)

    norms = {"attn_norm", "mlp_norm", "ln_scale", "ln_bias"}
    for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
        names = {k.key for k in path}
        is_norm = "final_norm" in names or bool(names & norms)
        assert leaf.dtype == (jnp.float32 if is_norm else jnp.bfloat16), \
            (path, leaf.dtype)
    assert any(jnp.any(a.astype(jnp.bfloat16).astype(jnp.float32) != a)
               for a in jax.tree.leaves(masters["final_norm"]))
    # nothing to cast: the same object comes back
    assert inference_params(cfg, served) is served
    f32 = _cfg(block_style=style, n_kv_heads=kv_heads, n_layers=3)
    assert inference_params(f32, masters) is masters

    bs, T, chunk = 4, 4, 4
    bt = np.zeros((3, T), np.int32)
    bt[:2] = np.arange(1, 1 + 2 * T).reshape(2, T)
    bt = jnp.asarray(bt)
    shape = init_kv_cache(cfg, num_blocks=11, block_size=bs)["k"].shape
    k0, v0 = jax.random.normal(jax.random.PRNGKey(5), (2,) + shape,
                               jnp.bfloat16)
    prompt = np.array([7, 5, 0], np.int32)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (3, 12), 0, cfg.vocab_size))
    jit_prefill = jax.jit(functools.partial(prefill, cfg))
    jit_decode = jax.jit(functools.partial(decode_step, cfg))

    def run(params):
        cache, logits = {"k": k0, "v": v0}, []
        for start in (0, chunk):
            lens = np.clip(prompt - start, 0, chunk).astype(np.int32)
            out, cache = jit_prefill(
                params, jnp.asarray(ids[:, start:start + chunk]), cache,
                bt, jnp.full((3,), start, jnp.int32), jnp.asarray(lens))
            logits.append(out)
        seq = prompt.copy()
        for _ in range(3):
            out, cache = jit_decode(
                params, jnp.asarray(ids[np.arange(3), seq]), cache, bt,
                jnp.asarray(seq))
            logits.append(out)
            seq = seq + np.array([1, 1, 0], np.int32)
        return logits, cache

    want_logits, want_cache = run(masters)
    got_logits, got_cache = run(served)
    for got, want in zip(got_logits, want_logits):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32))
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(got_cache[name], np.float32),
            np.asarray(want_cache[name], np.float32))
        assert (np.asarray(got_cache[name][:, 1:3], np.float32)
                != np.asarray(k0 if name == "k" else v0,
                              np.float32)[:, 1:3]).any()

    # an engine's own tree is drawn and rounded leaf by leaf: the same
    # bits, and never a whole f32 tree
    own = inference_params(cfg, init_params(cfg, jax.random.PRNGKey(0),
                                            dtype=cfg.dtype))
    plain = inference_params(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(plain)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
