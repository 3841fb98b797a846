"""chip_smoke.py's contract where no chip is needed to check it: it
fails fast without an accelerator, a failing or overrunning phase fails
the run, and no failure path prints the result line. The pass itself is
a chip run (PERF.md); the CPU rehearsal of the full control flow is the
``slow`` leg here."""

import argparse
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*flags, timeout=120, cwd=REPO, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *flags],
                          capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=cwd)


def test_no_accelerator_fails_fast_and_prints_no_result():
    t0 = time.monotonic()
    r = _run()
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no accelerator" in r.stderr and "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    """Without the program beside it the script must not pass — even
    told to rehearse, its first real phase cannot import ray_tpu."""
    import shutil
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    r = _run("--rehearse", cwd=str(tmp_path), script=str(lone))
    assert r.returncode != 0
    assert "No module named 'ray_tpu'" in r.stderr
    assert '"ok"' not in r.stdout


def _args():
    return argparse.Namespace(rehearse=False, phases=None)


def test_phase_that_raises_fails_the_run(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setitem(chip_smoke.PHASE_BUDGET_S, "no_such_phase", 60.0)
    with pytest.raises(SystemExit) as e:
        chip_smoke.run_phase_child("no_such_phase", _args(), {},
                                   time.monotonic() + 60)
    assert e.value.code == 1


def test_phase_that_times_out_fails_the_run(monkeypatch, tmp_path,
                                            capsys):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    # the probe child needs seconds to import jax: 0.05 s cannot do
    monkeypatch.setitem(chip_smoke.PHASE_BUDGET_S, "probe", 0.05)
    with pytest.raises(SystemExit) as e:
        chip_smoke.run_phase_child("probe", _args(), {},
                                   time.monotonic() + 60)
    assert e.value.code == 1
    assert "timed out" in capsys.readouterr().err


def test_fallbacks_are_failures_not_fields():
    """The two judges every phase report passes through."""
    kernel = {"op": "paged", "impl": "kernel", "why": "auto", "count": 2}
    asked = {"op": "flash", "impl": "reference", "why": "requested",
             "count": 1}
    chip_smoke.check_dispatch([kernel, asked], "kernel")
    fell_back = {"op": "paged", "impl": "reference",
                 "why": "block_size 4 % 16 != 0", "count": 1}
    with pytest.raises(RuntimeError, match="fell back"):
        chip_smoke.check_dispatch([kernel, fell_back], "kernel")
    with pytest.raises(RuntimeError, match="fell back"):
        chip_smoke.check_dispatch([asked], "kernel")   # no kernel at all
    ok = {"name": "k", "ok": True, "compiled": True}
    chip_smoke.check_truth([ok], rehearse=False)
    with pytest.raises(RuntimeError, match="not compiled"):
        chip_smoke.check_truth([dict(ok, compiled=False)], rehearse=False)
    with pytest.raises(RuntimeError, match="disagrees"):
        chip_smoke.check_truth([dict(ok, ok=False)], rehearse=False)


@pytest.mark.slow
def test_rehearsal_walks_every_phase_and_never_prints_a_pass():
    r = _run("--rehearse", timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REHEARSAL complete" in r.stdout
    assert "phase train: ok" in r.stdout and "phase serve: ok" in r.stdout
    assert '{"ok": true' not in r.stdout
