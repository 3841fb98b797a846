"""The two plain references against ray_tpu's own forward, loss and
gradients at tiny widths on the CPU (float32 on both sides, so they
agree to rounding); the known departure (the program's RMSNorm eps is
1e-6, Mistral publishes 1e-5) and the weights that keep it out of the
comparison without hiding the layers; the check of what was served."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, harness, reference, spec
from ray_tpu.models import TransformerConfig, init_params
from ray_tpu.models.transformer import apply, lm_loss

GPTJ = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, head_dim=16,
            d_ff=128, max_seq_len=32, rotary_dim=8, block_style="gptj",
            dtype=jnp.float32, remat=False, attn_impl="reference")
GPTJ_HP = (("layer_norm_epsilon", 1e-5), ("n_head", 4), ("rotary_dim", 8))
MISTRAL = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
               head_dim=16, n_kv_heads=2, d_ff=96, max_seq_len=32,
               rotary_dim=16, rope_base=1e6, block_style="llama",
               dtype=jnp.float32, remat=False, attn_impl="reference")


def _mistral_hp(eps):
    return (("num_attention_heads", 4), ("num_key_value_heads", 2),
            ("rms_norm_eps", eps), ("rope_theta", 1e6))


def _setup(kw, seed=0):
    cfg = TransformerConfig(**kw)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    # unit-scale embeddings so the norm's eps is negligible, as in a
    # trained model; biases and norm weights off their init values
    rng = np.random.default_rng(seed)
    params = jax.tree.map(
        lambda x: x + 0.05 * rng.standard_normal(x.shape).astype(np.float32),
        params)
    params["embed"] = params["embed"] * 30.0
    ids = rng.integers(0, cfg.vocab_size, size=(2, 24)).astype(np.int32)
    return cfg, params, jnp.asarray(ids)


def _system(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        logits = apply(cfg, params, ids)
        batch = {"input_ids": ids,
                 "loss_mask": jnp.ones(ids.shape, jnp.float32)}
        (loss, _), grads = jax.value_and_grad(
            functools.partial(lm_loss, cfg), has_aux=True)(params, batch)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return logits, float(loss), float(norm)


@pytest.mark.parametrize("name,kw,hp", [
    ("gptj", GPTJ, GPTJ_HP), ("mistral", MISTRAL, _mistral_hp(1e-6))])
def test_reference_matches_the_program_forward_loss_and_gradients(
        name, kw, hp):
    cfg, params, ids = _setup(kw)
    ref = reference.load(name)
    logits, loss, norm = _system(cfg, params, ids)
    want = ref.forward(params, ids, hp)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert float(ref.loss(params, ids, hp)) == pytest.approx(loss, rel=1e-5)
    rl, rn = ref.loss_and_grad_norm(params, ids, hp)
    assert float(rl) == pytest.approx(loss, rel=1e-5)
    assert float(rn) == pytest.approx(norm, rel=1e-4)


def test_published_mistral_eps_is_a_small_departure_at_unit_scale():
    """With activations of order one the two eps agree to 1e-5; the
    program's seeded weights (embedding scale 0.02) make it about a
    percent, which benchmarks/check.py's tolerance names."""
    cfg, params, ids = _setup(MISTRAL)
    ref = reference.load("mistral")
    a = ref.forward(params, ids, _mistral_hp(1e-6))
    b = ref.forward(params, ids, _mistral_hp(1e-5))
    assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a))) < 1e-4


def _cell_weights(name, n_layers=2):
    """The program's init at a cell's rehearsal widths, at the stream
    scale its configuration states, with a sample of token ids."""
    cell = spec.load_cell(name, rehearse=True)
    kw = dict(cell.model_kwargs(), n_layers=n_layers, max_seq_len=64,
              attn_impl="reference", paged_impl="reference",
              dtype=jnp.float32)
    cfg = TransformerConfig(**kw)
    params = harness.scale_stream(
        init_params(cfg, jax.random.PRNGKey(3)), cell.config["weights"])
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, size=(1, 40)), jnp.int32)
    return cell, cfg, params, ids


def _without_attention(p):
    layers = dict(p["layers"], wo=jnp.zeros_like(p["layers"]["wo"]))
    return dict(p, layers=layers)


def _kv_heads_reversed(p):
    wk = p["layers"]["wk"]
    w = wk.reshape(wk.shape[:2] + (2, -1))[:, :, ::-1].reshape(wk.shape)
    return dict(p, layers=dict(p["layers"], wk=w))


def _three_mantissa_bits(p):
    def r(x):
        m, e = jnp.frexp(x)
        return jnp.ldexp(jnp.round(m * 16) / 16, e)
    return dict(p, layers={k: r(v) if v.ndim >= 3 else v
                           for k, v in p["layers"].items()})


def test_the_cells_weights_put_the_two_eps_within_rounding():
    cell, cfg, params, ids = _cell_weights("mistral-7b-v0.3.serve_docqa")
    assert cell.config["weights"]["stream_scale"] > 1
    ref = reference.load("mistral")
    hp = dict(cell.reference_hp())
    program = tuple(sorted(dict(hp, rms_norm_eps=1e-6).items()))
    want = ref.forward(params, ids, cell.reference_hp())
    assert harness.rel_err(ref.forward(params, ids, program), want) < 1e-4
    # and the program itself, whose eps is 1e-6, agrees with the
    # reference at the published 1e-5
    with jax.default_matmul_precision("highest"):
        got = apply(cfg, params, ids)
    assert harness.rel_err(got, want) < 1e-4


@pytest.mark.parametrize("fault", [_without_attention, _kv_heads_reversed,
                                   _three_mantissa_bits])
def test_a_fault_in_the_layers_moves_the_logits_as_it_does_at_scale_one(
        fault):
    """Scaling the embedding alone drowned the layers (REVIEW, PR 24).
    With every residual writer scaled, a layer's share of the stream,
    and so what a fault in it does to the logits, is the init's own:
    the same as at scale one under one eps. (How much that is at the
    cells' widths and depth is in PERF.md; at these widths attention
    hardly attends, so only leaving it out is large.)"""
    cell, cfg, params, ids = _cell_weights("mistral-7b-v0.3.serve_docqa")
    ref = reference.load("mistral")
    with jax.default_matmul_precision("highest"):
        on_cell = harness.rel_err(
            apply(cfg, fault(params), ids),
            ref.forward(params, ids, cell.reference_hp()))
    plain = init_params(cfg, jax.random.PRNGKey(3))
    program_eps = tuple(sorted(dict(cell.reference_hp(),
                                    rms_norm_eps=1e-6).items()))
    at_one = harness.rel_err(ref.forward(fault(plain), ids, program_eps),
                             ref.forward(plain, ids, program_eps))
    assert on_cell == pytest.approx(at_one, rel=0.1)
    drowned = dict(plain, embed=plain["embed"]
                   * cell.config["weights"]["stream_scale"])
    assert harness.rel_err(ref.forward(fault(drowned), ids, program_eps),
                           ref.forward(drowned, ids, program_eps)) \
        < 0.5 * on_cell
    if fault is _without_attention:
        assert on_cell > 2 * check.tolerances(cell)["logits"]


def _greedy(cfg, params, prompt, n):
    """Greedy tokens by the program's full forward pass, one shape: the
    tokens to come are zeros, which a causal model does not see."""
    ids = np.zeros((1, len(prompt) + n), np.int32)
    ids[0, :len(prompt)] = prompt
    fwd = jax.jit(functools.partial(apply, cfg))
    with jax.default_matmul_precision("highest"):
        for pos in range(len(prompt), len(prompt) + n):
            ids[0, pos] = int(jnp.argmax(fwd(params, ids)[0, pos - 1]))
    return [int(t) for t in ids[0, len(prompt):]]


@pytest.mark.parametrize("name", ["gptj-6b.serve_chat",
                                  "mistral-7b-v0.3.serve_docqa"])
def test_served_tokens_are_held_to_the_reference(name):
    cell, cfg, params, ids = _cell_weights(name)
    prompt = [int(t) for t in ids[0, :24]]
    good = _greedy(cfg, params, prompt, 6)
    sample = {"prompt": prompt, "asked": 6, "tokens": good}
    verdict = check.served_check(cell, params, [sample, sample])
    assert verdict["ok"] and verdict["argmax_agree"] == [12, 12]
    # what a stale page would serve: the answer to another prompt
    stale = _greedy(cfg, params, prompt[::-1], 6)
    assert stale != good
    verdict = check.served_check(
        cell, params, [sample, dict(sample, tokens=stale)])
    assert not verdict["ok"]
    assert verdict["errors"]["served_token_gap"] > verdict["tol"]
    # a stream that ended early is not correct either
    short = check.served_check(cell, params, [dict(sample, tokens=good[:3])])
    assert not short["ok"] and short["tokens_short"] == 3
    assert not check.served_check(cell, params, [])["ok"]


def test_references_import_nothing_from_the_program():
    import benchmarks.reference.common as c
    import benchmarks.reference.gptj as g
    import benchmarks.reference.mistral as m
    for mod in (c, g, m):
        src = open(mod.__file__).read()
        assert "import ray_tpu" not in src and "from ray_tpu" not in src
