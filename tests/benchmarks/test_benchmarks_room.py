"""The room a ``model_config`` PR has: a copy of the tree grown as such a
PR grows it (one made-up serving configuration, one closed-loop cell of
it behind the accepted cells, one per-layer entry with a reader and a
test file of its own; files and entries only) leaves every test of
``tests/benchmarks`` that reads the manifest passing, the grown tree's
own among them. Such a test has ``manifest`` in its name or its file's
(``manifest_by_name.py`` has the rule they keep)."""
import json
import os
import re
import shutil
import subprocess
import sys

from benchmarks import spec

ACCEPTED = "mistral-7b-v0.3"
DOCQA = ACCEPTED + ".serve_docqa"
CONFIG = "made-up-7b"
CELL = CONFIG + ".serve_docqa"
ENTRY = {"name": "mlp_width_ratio.tok", "unit": "ratio", "better": "lower",
         "source": "program_counter", "layer": "engine",
         "moves": "serve_tok_s", "workloads": [CELL]}
#: the reader that comes with the configuration reads widths of its
#: ``program`` group that ``serve_cell.py`` never named
READER = '''"""A made-up configuration's reader."""


def read(obs):
    m = obs.get("model") or {}
    return m["d_ff"] / m["d_model"] if "d_ff" in m else None
'''
#: the test file that comes with it, written to ``manifest_by_name``'s rule
TEST = f'''"""A made-up configuration's test."""
import manifest_by_name
from benchmarks import serve_cell, spec

CELL = "{CELL}"
ENTRY = {ENTRY!r}


def test_the_manifest_holds_the_made_up_cell_and_its_entry():
    assert manifest_by_name.configuration("{CONFIG}")["reduced"] \\
        == ["num_hidden_layers"]
    assert manifest_by_name.cell(CELL)["config"] == "{CONFIG}"
    entry, cells = manifest_by_name.metric(ENTRY["name"])
    assert dict(entry, workloads=[CELL]) == ENTRY and CELL in cells
    assert manifest_by_name.line_of(CELL) \\
        >= {{ENTRY["name"], "decode_device_ms.tok", "tick_ms.tok"}}
    assert CELL in manifest_by_name.metric("serve_tok_s")[1]
    cell = spec.load_cell(CELL)
    engine = cell.params["engine"]
    model = dict(cell.model_kwargs(), max_seq_len=engine["max_seq_len"])
    obs = {{"model": serve_cell.observed_model(
        cell.config["program"], model, engine)}}
    assert spec.read_metrics([ENTRY], obs) == {{
        ENTRY["name"]: {{"value": 14336 / 4096, "unit": "ratio"}}}}
'''


def grow(root):
    """The tree (``BENCHMARK.json``, its ``paths``, ``PERF.md``) copied
    to ``root`` and grown by what a ``model_config`` PR adds. Returns
    the bytes of every file that was there, by path."""
    for path in spec.benchmark()["paths"]:
        shutil.copytree(
            os.path.join(spec.ROOT, path), root / path,
            ignore=shutil.ignore_patterns("__pycache__", ".bench_tmp"))
    shutil.copy(os.path.join(spec.ROOT, "PERF.md"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads(
        (root / f"benchmarks/configs/{ACCEPTED}.json").read_text())
    cfg["source"] = "https://example.org/made-up-7b/config.json"
    (root / f"benchmarks/configs/{CONFIG}.json").write_text(json.dumps(cfg))
    (root / "benchmarks/metrics/mlp_width_ratio.json").write_text(json.dumps(
        {"reader": "made_up", "args": {}, "what": "d_ff over d_model"}))
    (root / "benchmarks/readers/made_up.py").write_text(READER)
    (root / "tests/benchmarks/test_benchmarks_made_up.py").write_text(TEST)
    bench = spec.benchmark()
    bench["configs"].append({
        "name": CONFIG, "source": cfg["source"],
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers"], "why": "a test's configuration"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "serve_docqa", "chips": 1,
        "why": "a test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if DOCQA in m.get("workloads", []) and (
                m["name"] == "serve_tok_s" or m["name"].endswith(".tok")):
            m["workloads"].append(CELL)
    bench["per_layer"].append(ENTRY)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return before


def test_a_seventh_configuration_and_cell_leave_every_accepted_test_passing(
        tmp_path):
    before = grow(tmp_path)
    # the copy's own ``benchmarks`` and tests (the directory comes first
    # on the path of ``python -m``), the program from this checkout
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmarks", "-k", "manifest",
         "-v", "-p", "no:cacheprovider"], capture_output=True, text=True,
        timeout=420, env=env, cwd=tmp_path, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-2000:]
    passed = set(re.findall(r"^tests/benchmarks/(\S+) PASSED", r.stdout,
                            re.M))
    # the accepted files' tests of the manifest ran against the grown
    # tree, and its own
    for file, test in (
            ("manifest", "test_configs"), ("manifest", "test_workloads"),
            ("manifest", "test_every_cell_reports_what_its_metrics_move"),
            ("pangu", "test_the_manifest_holds_the_configuration_the_cell_"
                      "and_the_entries"),
            ("keye", "test_the_manifest_holds_the_cell_and_the_new_entries_"
                     "read"),
            ("passthrough", "test_the_new_entries_are_in_the_manifest_with_"
                            "their_cells"),
            ("program_time", "test_the_entries_are_in_the_manifest_with_"
                             "their_cells"),
            ("program_span", "test_the_manifest_declares_it_for_the_"
                             "training_cells"),
            ("made_up", "test_the_manifest_holds_the_made_up_cell_and_its_"
                        "entry")):
        assert f"test_benchmarks_{file}.py::{test}" in passed, (file, test)
    assert not re.search(r"\d+ (failed|error)", r.stdout), r.stdout[-3000:]
    # files and entries only: nothing that was there is touched
    assert {p: p.read_bytes() for p in before} == before
