"""One root for compiled programs and autotune winners
(util/compile_cache.py): ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, else one fixed path inside the checkout — the same
from every process, because a cache that moves is never hit."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_SCRIPT = r"""
import os, sys
sys.path.insert(0, {repo!r})
{pre}
from ray_tpu.util import compile_cache
root = compile_cache.enable()
import jax
print("ROOT", root)
print("ENV", os.environ.get("JAX_COMPILATION_CACHE_DIR"))
print("JAXCFG", jax.config.jax_compilation_cache_dir)
print("AUTOTUNE", __import__("ray_tpu.ops.flash_attention",
                             fromlist=["x"])._autotune_cache_path())
"""


def _run(env_dir=None, pre=""):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(repo=REPO, pre=pre)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd="/tmp")
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(ln.split(" ", 1) for ln in r.stdout.splitlines()
                if " " in ln)


def test_unset_every_process_gets_the_same_path_inside_the_checkout():
    a, b = _run(), _run(pre="import jax")   # jax imported after / before
    want = os.path.join(REPO, ".jax_cache")
    for out in (a, b):
        assert out["ROOT"] == want
        assert out["ENV"] == want          # exported: children inherit
        assert out["JAXCFG"] == want       # and jax itself uses it
        assert out["AUTOTUNE"] == os.path.join(want,
                                               "flash_autotune.json")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_set_from_outside_it_is_used_and_nothing_is_set_in_code(tmp_path):
    out = _run(env_dir=str(tmp_path))
    assert out["ROOT"] == out["ENV"] == str(tmp_path)
    assert out["JAXCFG"] == str(tmp_path)   # read by jax from the env
    assert out["AUTOTUNE"] == str(tmp_path / "flash_autotune.json")


def test_one_helper_holds_every_mention():
    """`grep -rn compilation_cache` finds the helper and nothing else
    in the program."""
    hits = []
    for top in ("ray_tpu", "chip_smoke.py"):
        path = os.path.join(REPO, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py")]
        for fp in files:
            with open(fp) as f:
                if "compilation_cache" in f.read():
                    hits.append(os.path.relpath(fp, REPO))
    assert hits == ["ray_tpu/util/compile_cache.py"]
