"""Numerical-parity suite for the compute-path optimizations.

Three families, all fp32 on CPU so the comparisons are tight:

- chunked fused LM-head CE vs. the reference materialized-logits CE:
  loss AND grads (x / head weights / bias / mask), including z-loss and
  masked positions, uneven chunk boundaries (padding path), and the
  model-level ``lm_loss`` wiring on both block styles;
- every remat policy produces identical loss/grads to ``"full"`` (remat
  changes scheduling, never math);
- flash-attention block-size selection: chip-aware defaults tile the
  sequence, the autotune cache works, and autotuned block configs
  produce the same output as the defaults.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_config, lm_loss
from ray_tpu.models.transformer import REMAT_POLICIES, remat_policy_fn
from ray_tpu.ops import (
    attention_reference,
    autotune_flash_blocks,
    cross_entropy_loss,
    default_flash_blocks,
    flash_attention,
    fused_lm_head_loss,
)
from ray_tpu.ops.flash_attention import _AUTOTUNE_CACHE


# ------------------------------------------------------- fused CE parity
def _ce_inputs(key, b=2, s=13, e=32, v=97):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, e), jnp.float32)
    w = 0.1 * jax.random.normal(ks[1], (e, v), jnp.float32)
    bias = 0.1 * jax.random.normal(ks[2], (v,), jnp.float32)
    labels = jax.random.randint(ks[3], (b, s), 0, v)
    mask = (jax.random.uniform(ks[4], (b, s)) > 0.3).astype(jnp.float32)
    return x, w, bias, labels, mask


@pytest.mark.parametrize("z_loss", [0.0, 1e-3])
@pytest.mark.parametrize("chunk", [5, 13, 64])   # uneven, exact, single
@pytest.mark.parametrize("cotangent", [1.0, 0.25, 3.0])
def test_fused_ce_matches_reference(cotangent, z_loss, chunk):
    """Loss and the gradients for x, W, b and the (ragged) mask, pulled
    back from a cotangent of 1 (``jax.grad``'s) and from others: the
    fused rule forms its gradients at 1 and scales them afterwards."""
    x, w, bias, labels, mask = _ce_inputs(jax.random.PRNGKey(0))

    def ref(x, w, bias, mask):
        logits = jnp.dot(x, w) + bias
        return cross_entropy_loss(logits, labels, mask=mask,
                                  z_loss_coeff=z_loss)[0]

    def fused(x, w, bias, mask):
        return fused_lm_head_loss(x, w, labels, head_bias=bias, mask=mask,
                                  z_loss_coeff=z_loss,
                                  chunk_size=chunk)[0]

    def pulled_back(f):
        loss, vjp = jax.vjp(f, x, w, bias, mask)
        return loss, vjp(jnp.asarray(cotangent, loss.dtype))

    l_ref, g_ref = pulled_back(ref)
    l_fus, g_fus = jax.jit(lambda: pulled_back(fused))()
    np.testing.assert_allclose(np.asarray(l_fus), np.asarray(l_ref),
                               rtol=1e-6, atol=1e-6)
    for name, a, b in zip("xwbm", g_ref, g_fus):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6 * max(cotangent, 1),
                                   err_msg=name)


def _dots_by_scan(jaxpr):
    """(``dot_general`` count of each ``scan`` body, count outside any),
    through every nested jaxpr (custom_vjp call, pjit, closed calls)."""
    def subjaxprs(eqn):
        for v in eqn.params.values():
            for u in (v if isinstance(v, (tuple, list)) else (v,)):
                u = getattr(u, "jaxpr", u)
                if hasattr(u, "eqns"):
                    yield u

    def count(j):
        scans, outside = [], 0
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                outside += 1
            for sub in subjaxprs(eqn):
                inner_scans, inner = count(sub)
                if eqn.primitive.name == "scan":
                    scans.append(inner + sum(inner_scans))
                else:
                    scans += inner_scans
                    outside += inner
        return scans, outside
    return count(jaxpr)


def test_fused_ce_forms_its_gradients_in_the_forward_scan():
    """What the rule is for: differentiated, one scan over the chunks
    runs three matmuls a chunk (logits, dX, dW) and the backward none;
    not differentiated, the same call runs the one (no gradient work)."""
    x, w, bias, labels, mask = _ce_inputs(jax.random.PRNGKey(0))

    def fused(x, w, bias, mask):
        return fused_lm_head_loss(x, w, labels, head_bias=bias, mask=mask,
                                  z_loss_coeff=1e-3, chunk_size=5)[0]

    grad = jax.make_jaxpr(jax.grad(fused, argnums=(0, 1, 2, 3)))(
        x, w, bias, mask)
    assert _dots_by_scan(grad.jaxpr) == ([3], 0)
    primal = jax.make_jaxpr(fused)(x, w, bias, mask)
    assert _dots_by_scan(primal.jaxpr) == ([1], 0)


def _recomputing_rule_grads(x, w, bias, labels, mask, chunk, z, g_loss):
    """The rule the library had until it formed its gradients in the
    forward pass, kept here as a reference: per chunk the logits and
    their softmax are computed (again, then), ``dl`` is scaled by
    ``g_loss / n`` before its cast to the compute dtype, and dW / db
    accumulate in float32. Returns (dx, dw, db)."""
    e, v = w.shape
    wd = w.astype(x.dtype)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    dw, db, dxs = jnp.zeros((e, v)), jnp.zeros((v,)), []
    for lo in range(0, x.shape[1], chunk):
        xi, yi, mi = (a[:, lo:lo + chunk] for a in (x, labels, mask))
        logits = jnp.einsum("bce,ev->bcv", xi, wd,
                            preferred_element_type=jnp.float32) + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        p = jnp.exp(logits - lse[..., None])
        coef = (g_loss / n) * mi
        zf = (1.0 + 2.0 * z * lse) if z else 1.0
        dl = p * (coef * zf)[..., None] \
            - coef[..., None] * jax.nn.one_hot(yi, v, dtype=jnp.float32)
        db = db + jnp.sum(dl, axis=(0, 1))
        dlc = dl.astype(x.dtype)
        dxs.append(jnp.einsum(
            "bcv,ev->bce", dlc, wd,
            preferred_element_type=jnp.float32).astype(x.dtype))
        dw = dw + jnp.einsum("bce,bcv->ev", xi, dlc,
                             preferred_element_type=jnp.float32)
    return (jnp.concatenate(dxs, axis=1), dw.astype(w.dtype),
            db.astype(bias.dtype))


@pytest.mark.parametrize("cotangent,rtol", [
    (1.0, 1e-5),    # dl is formed by the operations the old rule used
    (0.25, 1e-5),   # a power of two commutes with dl's rounding
    (3.0, 2e-2),    # the scale lands after dl's bf16 rounding, not before
])
def test_fused_ce_bf16_matches_the_recomputing_rule(cotangent, rtol):
    """bf16 activations over float32 master weights, as training runs
    it: dW comes back in W's dtype, dX in x's, and both are what the
    recomputing rule gave on the same inputs."""
    x, w, bias, labels, mask = _ce_inputs(jax.random.PRNGKey(2))
    x = x.astype(jnp.bfloat16)
    chunk, z = 5, 1e-3

    def fused(x, w, bias):
        return fused_lm_head_loss(x, w, labels, head_bias=bias, mask=mask,
                                  z_loss_coeff=z, chunk_size=chunk)[0]

    _, vjp = jax.vjp(fused, x, w, bias)
    got = vjp(jnp.float32(cotangent))
    want = _recomputing_rule_grads(x, w, bias, labels, mask, chunk, z,
                                   cotangent)
    for name, g, r, like in zip("xwb", got, want, (x, w, bias)):
        assert g.dtype == r.dtype == like.dtype, name
        g, r = (np.asarray(a, np.float32) for a in (g, r))
        # dX is rounded to bf16 on both sides: two ulps of 2**-8
        np.testing.assert_allclose(
            g, r, rtol=rtol if name != "x" else max(rtol, 8e-3),
            atol=rtol * np.abs(r).max(), err_msg=name)


def test_fused_ce_n_tokens_and_no_bias():
    x, w, _, labels, mask = _ce_inputs(jax.random.PRNGKey(1))
    loss_f, n_f = fused_lm_head_loss(x, w, labels, mask=mask, chunk_size=4)
    loss_r, n_r = cross_entropy_loss(jnp.dot(x, w), labels, mask=mask)
    assert float(n_f) == float(n_r)
    np.testing.assert_allclose(float(loss_f), float(loss_r), rtol=1e-6)


@pytest.mark.parametrize("name", [
    pytest.param("gptj-tiny", marks=pytest.mark.slow),
    pytest.param("llama2-tiny", marks=pytest.mark.slow)])
def test_lm_loss_fused_matches_materialized(name):
    """Model-level wiring: ce_chunk_size>0 (fused, with chunk padding)
    vs ce_chunk_size=0 (reference logits path) — loss and param grads."""
    cfg = get_config(name)
    from ray_tpu.models import Transformer
    params = Transformer(cfg).init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (2, 16)) > 0.2
            ).astype(jnp.float32)
    batch = {"input_ids": ids, "loss_mask": mask}

    def loss_with(chunk, p):
        c = dataclasses.replace(cfg, ce_chunk_size=chunk)
        return lm_loss(c, p, batch)[0]

    # chunk 7 over s'=15 exercises the padded final chunk
    l_ref, g_ref = jax.value_and_grad(
        functools.partial(loss_with, 0))(params)
    l_fus, g_fus = jax.jit(jax.value_and_grad(
        functools.partial(loss_with, 7)))(params)
    np.testing.assert_allclose(float(l_fus), float(l_ref), rtol=1e-6)
    for pa, pb in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_fus)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=2e-5, atol=1e-6)


def test_fused_ce_is_moe_compatible():
    cfg = get_config("moe-tiny")
    from ray_tpu.models import Transformer
    params = Transformer(cfg).init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    for chunk in (0, 8):
        c = dataclasses.replace(cfg, ce_chunk_size=chunk)
        loss, aux = lm_loss(c, params, {"input_ids": ids})
        assert np.isfinite(float(loss))
        assert "moe_aux" in aux


# ------------------------------------------------------ remat policy parity
def _policy_loss_and_grads(cfg, params, batch, policy):
    c = dataclasses.replace(cfg, remat=None, remat_policy=policy)
    return jax.jit(jax.value_and_grad(
        lambda p: lm_loss(c, p, batch)[0]))(params)


@pytest.mark.parametrize("policy",
                         [p for p in REMAT_POLICIES
                          if p not in ("full", "offload")])
def test_remat_policies_match_full(policy):
    cfg = get_config("gptj-tiny")
    from ray_tpu.models import Transformer
    params = Transformer(cfg).init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids}
    l_full, g_full = _policy_loss_and_grads(cfg, params, batch, "full")
    l_p, g_p = _policy_loss_and_grads(cfg, params, batch, policy)
    np.testing.assert_allclose(float(l_p), float(l_full), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_remat_offload_policy():
    """Host-offload policy: parity with "full" where the platform
    supports pinned_host transfers; skip (not fail) where it doesn't."""
    cfg = get_config("gptj-tiny")
    from ray_tpu.models import Transformer
    params = Transformer(cfg).init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids}
    l_full, g_full = _policy_loss_and_grads(cfg, params, batch, "full")
    try:
        l_o, g_o = _policy_loss_and_grads(cfg, params, batch, "offload")
    except Exception as e:  # noqa: BLE001 — backend without host memories
        pytest.skip(f"pinned_host offload unsupported here: {e}")
    np.testing.assert_allclose(float(l_o), float(l_full), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_o)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_legacy_remat_bool_still_resolves():
    cfg = get_config("gptj-tiny")           # remat=False in registry
    assert cfg.resolved_remat_policy == "none"
    assert dataclasses.replace(cfg, remat=True) \
        .resolved_remat_policy == "full"
    assert dataclasses.replace(cfg, remat=None) \
        .resolved_remat_policy == cfg.remat_policy


def test_remat_policy_fn_rejects_unknown():
    with pytest.raises(ValueError):
        remat_policy_fn("bogus")


# --------------------------------------------------- flash block selection
def test_default_flash_blocks_tile_the_sequence():
    for chip in ("cpu", "v4", "v5e", "v5p", "v6e"):
        for seq in (128, 1024, 4096, 96):     # 96: non-power-of-two
            for d in (64, 128, 256):
                bq, bk = default_flash_blocks(seq, seq, d, chip=chip)
                assert bq >= 1 and bk >= 1
                assert seq % bq == 0 and seq % bk == 0, (chip, seq, d)


def test_autotune_picks_winner_and_caches():
    _AUTOTUNE_CACHE.clear()
    calls = []

    def timer(bq, bk):
        calls.append((bq, bk))
        return 1.0 if (bq, bk) != (256, 512) else 0.5

    best = autotune_flash_blocks(1024, 128, timer=timer, chip="v5e")
    assert best == (256, 512)
    assert len(calls) >= 2
    # cached: same key returns without timing
    n = len(calls)
    again = autotune_flash_blocks(1024, 128, timer=timer, chip="v5e")
    assert again == best and len(calls) == n
    _AUTOTUNE_CACHE.clear()


def test_autotune_off_tpu_returns_chip_default():
    _AUTOTUNE_CACHE.clear()
    assert autotune_flash_blocks(1024, 128, chip="cpu") \
        == default_flash_blocks(1024, 1024, 128, chip="cpu")
    _AUTOTUNE_CACHE.clear()


def test_autotune_survives_failing_candidate():
    _AUTOTUNE_CACHE.clear()

    def timer(bq, bk):
        if (bq, bk) == (256, 256):
            raise RuntimeError("vmem oom")
        return float(bq * bk)

    best = autotune_flash_blocks(
        256, 128, timer=timer, chip="v5e",
        candidates=((256, 256), (128, 128), (128, 256)))
    assert best == (128, 128)
    _AUTOTUNE_CACHE.clear()


def test_autotune_raises_when_no_candidate_compiles(tmp_path,
                                                    monkeypatch):
    """Every candidate rejected: the tuner raises instead of handing
    back (or persisting) a default nobody timed."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _AUTOTUNE_CACHE.clear()

    def timer(bq, bk):
        raise RuntimeError("vmem oom")

    with pytest.raises(RuntimeError, match="none of"):
        autotune_flash_blocks(256, 128, timer=timer, chip="v5e")
    assert not _AUTOTUNE_CACHE
    assert not (tmp_path / "flash_autotune.json").exists()


def test_autotune_winner_persists_across_processes(tmp_path,
                                                   monkeypatch):
    """A TIMED winner is written to disk keyed by (chip, jax version,
    seq, head_dim, causal); a fresh process (simulated: in-memory cache
    cleared, load flag reset) gets it back WITHOUT re-timing."""
    import json

    import importlib

    import jax as _jax

    # the module, not the identically-named function ray_tpu.ops
    # re-exports over it
    fa = importlib.import_module("ray_tpu.ops.flash_attention")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _AUTOTUNE_CACHE.clear()
    monkeypatch.setattr(fa, "_DISK_CACHE_LOADED", False)
    calls = []

    def timer(bq, bk):
        calls.append((bq, bk))
        return 1.0 if (bq, bk) != (512, 512) else 0.1

    best = autotune_flash_blocks(2048, 128, timer=timer, chip="v5e")
    assert best == (512, 512) and calls
    path = tmp_path / "flash_autotune.json"
    data = json.loads(path.read_text())
    key = f"v5e|{_jax.__version__}|2048|128|1"
    assert data[key] == [512, 512]

    # "new process": memory cache gone, disk cache not yet loaded
    _AUTOTUNE_CACHE.clear()
    monkeypatch.setattr(fa, "_DISK_CACHE_LOADED", False)
    n = len(calls)
    again = autotune_flash_blocks(2048, 128, timer=timer, chip="v5e")
    assert again == (512, 512)
    assert len(calls) == n, "disk-cached winner was re-timed"

    # entries from another jax version are ignored (recompute), and a
    # corrupt file never breaks autotuning
    _AUTOTUNE_CACHE.clear()
    monkeypatch.setattr(fa, "_DISK_CACHE_LOADED", False)
    path.write_text(json.dumps({f"v5e|other-ver|2048|128|1": [256, 256]}))
    assert autotune_flash_blocks(2048, 128, timer=timer, chip="v5e") \
        == (512, 512)
    _AUTOTUNE_CACHE.clear()
    monkeypatch.setattr(fa, "_DISK_CACHE_LOADED", False)
    path.write_text("{corrupt")
    assert autotune_flash_blocks(2048, 128, timer=timer, chip="v5e") \
        == (512, 512)
    _AUTOTUNE_CACHE.clear()


def test_autotune_default_path_not_persisted(tmp_path, monkeypatch):
    """Off-TPU default fallbacks (nothing was timed) must not litter
    the disk cache — they cost nothing to recompute."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _AUTOTUNE_CACHE.clear()
    monkeypatch.setattr(fa, "_DISK_CACHE_LOADED", False)
    autotune_flash_blocks(1024, 128, chip="cpu")
    assert not (tmp_path / "flash_autotune.json").exists()
    _AUTOTUNE_CACHE.clear()


@pytest.mark.parametrize("blocks", [(64, 64), (64, 128), (128, 64)])
def test_flash_output_invariant_to_blocks(blocks):
    """An autotuned block config must be a pure scheduling choice: the
    kernel output matches the default-block output and the reference."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 4, 64), jnp.float32)
               for kk in ks)
    ref = attention_reference(q, k, v, causal=True)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    base = flash_attention(qt, kt, vt, causal=True, block_q=128,
                           block_k=128, interpret=True)
    tuned = flash_attention(qt, kt, vt, causal=True, block_q=blocks[0],
                            block_k=blocks[1], interpret=True)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(base),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(tuned, 1, 2)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bwd_delta_kernel_grads_match_xla():
    """The fused delta-precompute feeds the Pallas dq/dk/dv kernels;
    their grads must still match the lax.scan XLA backward."""
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 128), jnp.float32)
               for kk in ks)

    def loss(mode):
        def f(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=64,
                                block_k=64, interpret=True, backward=mode)
            return jnp.sum(o ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(loss("pallas"), loss("xla")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
