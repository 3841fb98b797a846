"""The fused LM-head loss under a mesh that splits the batch: it runs per
chip (``ops.cross_entropy.PerChip``), sums dW on the chip through its
scan and reduces it across chips once a step.

Three things are held, on four of the virtual CPU devices: the compiled
step has no collective inside the loss's loop and one reduction of dW
outside it; the per-chip form's loss and gradients are the unsharded
call's; and the form follows the mesh and the rules (no mesh, one device,
``tp``, ``sp``: today's whole-array call, no ``shard_map`` at the loss).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, lm_loss
from ray_tpu.models import transformer
from ray_tpu.models.training import make_train_step
from ray_tpu.models.transformer import head_loss_form, init_params
from ray_tpu.ops.cross_entropy import PerChip, fused_lm_head_loss
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import DDP_RULES, FSDP_RULES

D_MODEL, VOCAB, CHUNK = 128, 1024, 16

MESHES = {
    "fsdp4": (MeshSpec(fsdp=4), FSDP_RULES),
    "dp2xfsdp2": (MeshSpec(dp=2, fsdp=2), FSDP_RULES),
    "ddp4": (MeshSpec(dp=4), DDP_RULES),
}


def _mesh(spec):
    n = int(np.prod(list(spec.axis_sizes().values())))
    return build_mesh(spec, jax.devices()[:n])


def _config(**kw):
    base = dict(vocab_size=VOCAB, d_model=D_MODEL, n_heads=4, head_dim=32,
                d_ff=256, n_layers=2, rotary_dim=32, max_seq_len=65,
                block_style="llama", dtype=jnp.float32,
                ce_chunk_size=CHUNK)
    base.update(kw)
    return TransformerConfig(**base)


# ------------------------------------------------- (a) the compiled text
_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")


def _computations(text):
    """HLO module text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                     line)
        if m and not line.startswith(" "):
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return comps


def _instruction(line):
    """(name, type, opcode) of one instruction line, or None."""
    m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(",
                 line)
    return m.groups() if m else None


def _reached_from(comps, root):
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for key in ("calls", "to_apply", "body", "condition"):
                todo += re.findall(key + r"=%?([\w.\-]+)", line)
            m = re.search(r"called_computations=\{([^}]*)\}", line)
            if m:
                todo += [c.strip().lstrip("%")
                         for c in m.group(1).split(",")]
    return seen


def _head_collectives(text):
    """The collectives of a compiled step that move a head-shaped array
    (the whole ``[d_model, vocab]`` or a chip's rows of it) under the
    ``lm_head_loss`` scope: (those inside the loss's loop, those outside),
    each as (opcode, type)."""
    comps = _computations(text)
    inside = set()
    for lines in comps.values():
        for line in lines:
            ins = _instruction(line)
            if ins and ins[2] == "while":
                body = _reached_from(
                    comps, re.search(r"body=%?([\w.\-]+)", line).group(1))
                if any("lm_head_loss" in l for c in body for l in comps[c]):
                    inside |= body
    assert inside, "no loop of the loss in the compiled step"
    shapes = re.compile(rf"\[(?:{D_MODEL}|{D_MODEL // 4}),{VOCAB}\]")
    found = ([], [])
    for c, lines in comps.items():
        for line in lines:
            ins = _instruction(line)
            if not ins or ins[2].removesuffix("-start") not in _COLLECTIVES:
                continue
            if "lm_head_loss" in line and shapes.search(ins[1]):
                found[0 if c in inside else 1].append((ins[2], ins[1]))
    return found


def _compiled_step(cfg, mesh, rules):
    bundle = make_train_step(cfg, mesh, rules=rules, telemetry_interval_s=0)
    state = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)
    batch = {"input_ids": ids,
             "loss_mask": jax.ShapeDtypeStruct(ids.shape, jnp.float32)}
    return bundle, bundle.step_fn.lower(state, batch).compile().as_text()


def test_fsdp4_step_reduces_dw_once_outside_the_loss_loop():
    mesh, rules = _mesh(MESHES["fsdp4"][0]), FSDP_RULES
    bundle, text = _compiled_step(_config(), mesh, rules)
    assert (bundle.loss_form, bundle.loss_chunks) == ("per_chip", 4)
    inside, outside = _head_collectives(text)
    assert inside == []
    reductions = [c for c in outside
                  if c[0].startswith(("all-reduce", "reduce-scatter"))]
    assert len(reductions) == 1, outside
    assert "f32" in reductions[0][1]
    gathers = [c for c in outside if c[0].startswith("all-gather")]
    assert len(gathers) == 1, outside


def test_the_reader_sees_gspmds_reduction_in_every_iteration(monkeypatch):
    """The whole-array call under the same mesh, which is what the step
    ran before: the carry takes the head's sharding and each chunk's dW is
    reduced onto it inside the loop. Holds the reader above to account."""
    monkeypatch.setattr(transformer, "head_loss_form",
                        lambda c, mesh, rules: ("gspmd", None))
    mesh, rules = _mesh(MESHES["fsdp4"][0]), FSDP_RULES
    _, text = _compiled_step(_config(), mesh, rules)
    inside, _ = _head_collectives(text)
    assert any(c[0].startswith(("all-reduce", "reduce-scatter"))
               for c in inside), inside


# ------------------------------------------------------- (b) the numbers
def _ce_inputs(b=4, s=50):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, D_MODEL), jnp.float32)
    w = 0.1 * jax.random.normal(ks[1], (D_MODEL, VOCAB), jnp.float32)
    bias = 0.1 * jax.random.normal(ks[2], (VOCAB,), jnp.float32)
    labels = jax.random.randint(ks[3], (b, s), 0, VOCAB)
    mask = (jax.random.uniform(ks[4], (b, s)) > 0.3).astype(jnp.float32)
    return x, w, bias, labels, mask


def _per_chip(mesh, rules):
    form, per_chip = head_loss_form(_config(), mesh, rules)
    assert form == "per_chip" and isinstance(per_chip, PerChip)
    return per_chip


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(
        got, want, rtol=1e-5, atol=1e-5 * float(np.abs(want).max()),
        err_msg=what)


# what each case changes of: 4 rows of 50 positions, chunks of 16 (the
# last one padded), a ragged mask, no z-loss
CASES = {
    "ragged_mask": {},
    "one_chips_rows_all_masked": {"masked_row": 2},
    "z_loss": {"z": 1e-3},
    "whole_chunks": {"s": 48},
    "one_chunk": {"chunk": 64},
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("mesh_name", MESHES)
def test_per_chip_loss_and_gradients_match_the_unsharded_call(
        mesh_name, case):
    """loss, n, dX, dW, db and the mask's gradient of the per-chip form
    against the same rule on whole arrays with no mesh."""
    spec, rules = MESHES[mesh_name]
    per_chip = _per_chip(_mesh(spec), rules)
    kw = CASES[case]
    x, w, bias, labels, mask = _ce_inputs(s=kw.get("s", 50))
    if "masked_row" in kw:      # that chip's share of n is 0
        mask = mask.at[kw["masked_row"]].set(0.0)

    def loss_and_grads(per_chip):
        def f(x, w, bias, mask):
            return fused_lm_head_loss(
                x, w, labels, head_bias=bias, mask=mask,
                z_loss_coeff=kw.get("z", 0.0),
                chunk_size=kw.get("chunk", CHUNK), per_chip=per_chip)
        (loss, n), vjp = jax.vjp(f, x, w, bias, mask)
        return (loss, n) + vjp((jnp.float32(0.5), jnp.zeros_like(n)))

    want = loss_and_grads(None)
    got = jax.jit(lambda: loss_and_grads(per_chip))()
    for what, g, r in zip(("loss", "n", "dx", "dw", "db", "dmask"),
                          got, want):
        _close(g, r, what)
    assert got[3].dtype == jnp.float32
    # evaluation (nobody differentiates) takes the per-chip form too
    ev = jax.jit(lambda: fused_lm_head_loss(
        x, w, labels, head_bias=bias, mask=mask,
        z_loss_coeff=kw.get("z", 0.0), chunk_size=kw.get("chunk", CHUNK),
        per_chip=per_chip))()
    _close(ev[0], want[0], "eval loss")


@pytest.mark.parametrize("block_style", ["llama", "gptj"])   # gptj: a bias
@pytest.mark.parametrize("mesh_name", MESHES)
def test_lm_loss_under_a_mesh_matches_no_mesh(mesh_name, block_style):
    spec, rules = MESHES[mesh_name]
    mesh = _mesh(spec)
    cfg = _config(block_style=block_style)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 50), 0, VOCAB)
    mask = jnp.ones((4, 50)).at[2].set(0.0).at[0, 30:].set(0.0)
    batch = {"input_ids": ids, "loss_mask": mask}

    def run(**kw):
        return jax.jit(jax.value_and_grad(
            lambda p: lm_loss(cfg, p, batch, **kw)[0]))(params)

    want_loss, want = run()
    got_loss, got = run(mesh=mesh, rules=rules)
    _close(got_loss, want_loss, "loss")
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        _close(g, r, jax.tree_util.keystr(path))


# ---------------------------------------------------- (c) when it engages
def _shard_maps_at_the_loss(jaxpr):
    """How many ``shard_map`` equations sit under the ``lm_head_loss``
    scope anywhere in ``jaxpr`` (sub-jaxprs included)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map" \
                and "lm_head_loss" in str(eqn.source_info.name_stack):
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _shard_maps_at_the_loss(sub)
    return n


def _loss_jaxpr(mesh, rules, grad=False):
    cfg = _config(n_layers=1)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"input_ids": jax.ShapeDtypeStruct((4, 64), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((4, 64), jnp.float32)}

    def f(p, b):
        return lm_loss(cfg, p, b, mesh=mesh, rules=rules)[0]
    return jax.make_jaxpr(jax.grad(f) if grad else f)(params, batch).jaxpr


@pytest.mark.parametrize("name,spec,form", [
    ("no_mesh", None, "gspmd"),
    ("one_device", MeshSpec(), "gspmd"),
    ("tp2", MeshSpec(fsdp=2, tp=2), "gspmd"),
    ("sp2", MeshSpec(fsdp=2, sp=2), "logits"),
])
def test_the_whole_array_call_stays_where_the_per_chip_form_does_not_apply(
        name, spec, form):
    mesh = _mesh(spec) if spec is not None else None
    rules = FSDP_RULES if spec is not None else None
    assert head_loss_form(_config(), mesh, rules) == (form, None)
    for grad in (False, True):
        assert _shard_maps_at_the_loss(_loss_jaxpr(mesh, rules, grad)) == 0


@pytest.mark.parametrize("mesh_name", MESHES)
def test_the_per_chip_form_is_one_shard_map_at_the_loss(mesh_name):
    spec, rules = MESHES[mesh_name]
    mesh = _mesh(spec)
    for grad in (False, True):
        assert _shard_maps_at_the_loss(_loss_jaxpr(mesh, rules, grad)) == 1


def test_the_form_is_logits_when_the_loss_is_not_fused():
    mesh = _mesh(MESHES["fsdp4"][0])
    cfg = dataclasses.replace(_config(), ce_chunk_size=0)
    assert head_loss_form(cfg, mesh, FSDP_RULES) == ("logits", None)
    bundle = make_train_step(cfg, mesh, telemetry_interval_s=0)
    assert (bundle.loss_form, bundle.loss_chunks) == ("logits", 0)
