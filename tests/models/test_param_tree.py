"""The builders of the parameter tree held to each other and to a
committed record (``data/param_tree_pins.json``), so that an edit to one
of ``init_params``, ``logical_axes``, ``TransformerConfig.num_params``,
``inference_params`` or ``init_kv_cache`` that the others do not follow
turns a case red and names the leaf.

Every cell of ``BENCHMARK.json`` at its published widths, through
``jax.eval_shape`` alone (no array of that size is made): the tree's
leaves, their logical axes, the parameter count, the dtypes a serving
engine holds, and a serving cell's cache. Sixteen small configurations
for real: the bits ``init_params`` draws from ``PRNGKey(0)``, because the
benchmark's weights come from ``--seed`` through these builders and a
draw that moves changes which experts a routed cell's tokens meet.

A cell or a stack with no entry in the record fails. A PR that moves a
leaf or a draw on purpose writes the record anew and shows the diff:

    JAX_PLATFORMS=cpu PYTHONPATH=. python tests/models/test_param_tree.py --write
"""
import hashlib
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import spec
from ray_tpu.models import TransformerConfig, init_kv_cache, init_params
from ray_tpu.models.transformer import (STATE_ARRAYS, _layer_plan,
                                        cache_pools, inference_params,
                                        logical_axes)
from test_delta_serving import OLMO
from test_layer_plan import STACKS, _cell_config
from test_mamba_serving import GRANITE
from test_nemotron_serving import NEMOTRON
from test_window_moe_training import TINY

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "param_tree_pins.json")
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SERVING = [name for name in CELLS if spec.load_cell(name).kind != "train"]
SMALL = {**STACKS, "granite, tiny": GRANITE, "nemotron, tiny": NEMOTRON,
         "olmo, tiny": OLMO, "mellum, tiny": TINY}
# a cache of few blocks, window blocks, state slots and snapshot rows,
# each a different number so that none can stand in for another
BLOCKS, WINDOW_BLOCKS, SLOTS, SNAPSHOTS = 7, 5, 3, 2


def _paths(tree, is_leaf=None):
    """``{path: leaf}`` of a tree of dicts, the path as ``a/b``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return {"/".join(k.key for k in path): leaf for path, leaf in flat}


def _described(arrays):
    """Sorted ``[path, shape, dtype]`` of ``{path: array}``."""
    return [[path, list(a.shape), jnp.dtype(a.dtype).name]
            for path, a in sorted(arrays.items())]


def _tree(c):
    return _paths(jax.eval_shape(
        lambda: init_params(c, jax.random.PRNGKey(0))))


def _axes(c):
    return _paths(logical_axes(c), is_leaf=lambda x: isinstance(x, tuple))


def _inference(c):
    return _paths(jax.eval_shape(lambda: inference_params(
        c, init_params(c, jax.random.PRNGKey(0)))))


def _cache(cell_name):
    c = _cell_config(cell_name)
    block_size = spec.load_cell(cell_name).params["engine"]["kv_block_size"]
    return jax.eval_shape(lambda: init_kv_cache(
        c, BLOCKS, block_size, WINDOW_BLOCKS, SLOTS, SNAPSHOTS))


def _axes_listed(axes):
    return [[path, list(a)] for path, a in sorted(axes.items())]


def _float32(held):
    return sorted(path for path, leaf in held.items()
                  if leaf.dtype == jnp.float32)


def _bits(name) -> str:
    """SHA-256 over the bytes of every leaf ``init_params`` draws from
    ``PRNGKey(0)`` for a small configuration, in path order. Eagerly, as
    an engine draws its weights: a jitted draw rounds a Mamba-2 vector
    otherwise."""
    c = TransformerConfig(**SMALL[name])
    digest = hashlib.sha256()
    for _, leaf in sorted(_paths(
            init_params(c, jax.random.PRNGKey(0))).items()):
        digest.update(np.asarray(leaf).tobytes())
    return digest.hexdigest()


def _all_bits():
    """Every small configuration's digest. A draw is some forty small
    compiles and little else, and the compiler holds no lock: four side
    by side take half the time."""
    with ThreadPoolExecutor(4) as pool:
        return dict(zip(sorted(SMALL), pool.map(_bits, sorted(SMALL))))


def _cell_record(cell_name):
    c = _cell_config(cell_name)
    record = {
        "tree": _described(_tree(c)),
        "axes": _axes_listed(_axes(c)),
        "count": c.num_params,
        "float32": _float32(_inference(c))}
    if cell_name in SERVING:
        record["cache"] = _described(_cache(cell_name))
    return record


@pytest.fixture(scope="module")
def pins():
    with open(PINS) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def drawn():
    return _all_bits()


# -------------------------------------- every cell, at published widths
@pytest.mark.parametrize("cell_name", CELLS)
def test_tree(cell_name, pins):
    assert _described(_tree(_cell_config(cell_name))) \
        == pins["cells"][cell_name]["tree"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_axes(cell_name, pins):
    c = _cell_config(cell_name)
    tree, axes = _tree(c), _axes(c)
    assert sorted(axes) == sorted(tree)
    assert {path: len(a) for path, a in axes.items()} \
        == {path: len(leaf.shape) for path, leaf in tree.items()}
    assert _axes_listed(axes) == pins["cells"][cell_name]["axes"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_count(cell_name, pins):
    c = _cell_config(cell_name)
    leaves = sum(int(np.prod(leaf.shape)) for leaf in _tree(c).values())
    assert c.num_params == leaves == pins["cells"][cell_name]["count"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_inference(cell_name, pins):
    c = _cell_config(cell_name)
    tree, held = _tree(c), _inference(c)
    assert {path: leaf.shape for path, leaf in held.items()} \
        == {path: leaf.shape for path, leaf in tree.items()}
    assert {leaf.dtype for leaf in held.values()} \
        <= {jnp.dtype(c.dtype), jnp.dtype(jnp.float32)}
    assert _float32(held) == pins["cells"][cell_name]["float32"]


@pytest.mark.parametrize("cell_name", SERVING)
def test_cache(cell_name, pins):
    kinds = _layer_plan(_cell_config(cell_name)).kinds
    cache = _cache(cell_name)
    pools = {pool.name for kind in kinds for pool in kind.pools}
    state = {name for kind in kinds for s in kind.state
             for name in (s.name, s.snap) if name}
    assert set(cache_pools(cache)) == pools
    assert set(cache) - pools == state <= set(STATE_ARRAYS)
    assert _described(cache) == pins["cells"][cell_name]["cache"]


# ------------------------------------------ the drawn bits, small sizes
@pytest.mark.parametrize("name", sorted(SMALL))
def test_bits(name, pins, drawn):
    assert drawn[name] == pins["bits"][name]


def _write():
    """The record, from the tree as it stands: a list's entries a line
    each, so that a diff names the leaf."""
    def lines(value, depth):
        pad = " " * depth
        if isinstance(value, dict):
            return "{\n" + ",\n".join(
                f"{pad} {json.dumps(k)}: {lines(v, depth + 1)}"
                for k, v in value.items()) + "\n" + pad + "}"
        if isinstance(value, list) and value:
            return "[\n" + ",\n".join(
                f"{pad} {json.dumps(v)}" for v in value) + "\n" + pad + "]"
        return json.dumps(value)
    record = {"cells": {name: _cell_record(name) for name in CELLS},
              "bits": _all_bits()}
    os.makedirs(os.path.dirname(PINS), exist_ok=True)
    with open(PINS, "w") as f:
        f.write(lines(record, 0) + "\n")


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        raise SystemExit(f"usage: {sys.argv[0]} --write")
    _write()
