"""BENCHMARK.json against the contract's limits that can be checked
without a chip: names, units, lengths, keys, files, bounds."""
import json
import os
import re

import pytest

from benchmarks import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = spec.benchmark()


def _one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    raw = open(os.path.join(spec.ROOT, "BENCHMARK.json")).read()
    assert len(raw.encode()) <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_one_line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells must fit
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    cfgs = BENCH["configs"]
    assert 1 <= len(cfgs) <= 24
    names = [c["name"] for c in cfgs]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in cfgs]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in cfgs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        # never a width; the rows of the vocabulary held are a count
        # (a chip's slice of the embedding and the head), the one key
        # ending in ``_size`` that is none
        for k in c["reduced"]:
            assert k == "vocab_size" or not re.search(
                r"(_dim$|_rank$|_size$|head_dim|per_tok"
                r"|n_embd|n_inner|d_ff|d_model)", k), k
        body = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert body["source"] == c["source"]
        assert body["depth"]["key"] in c["reduced"]
        for key in ("published", "assumed", "deployment", "reference",
                    "program", "blocks"):
            assert key in body, (c["name"], key)
        # every block size the program would otherwise pick is pinned
        for b in ("attn_block_q", "attn_block_k", "paged_block_r",
                  "paged_block_r_prefill"):
            assert body["blocks"][b] > 0


def test_workloads():
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _one_line(w["why"])
        assert os.path.exists(os.path.join(spec.HERE, "traffic",
                                           w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_metric_names_units_and_sources():
    names = [m["name"] for m in _metrics()]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in _metrics():
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
        spec.metric_reader(m["name"])           # its reader resolves
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _one_line(m["layer"])


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert m["moves"] in mine, (w["name"], m["name"], m["moves"])


def test_chat_is_judged_on_a_ttft_and_the_ttft_side_says_so():
    """A PR that starves prefill to speed decode must not pass: the chat
    cell keeps an end-to-end TTFT (PERF.md section 2: the median over
    every request, the slowest tenth's mean beside it per layer), and a
    metric of the TTFT side names a TTFT under ``moves``. The decode
    side is judged on the median request's time per token since PR 39
    (the 90th percentile read the machine's stalls; it stays per layer),
    and a metric of that side names it."""
    chat = spec.load_cell("gptj-6b.serve_chat")
    assert {m["name"] for m in chat.end_to_end} >= {
        "ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    per_layer = {m["name"]: m for m in chat.per_layer}
    assert "ttft_slow10_ms" in per_layer and "ttft_p90_ms.chat" in per_layer
    assert "tpot_p90_ms.chat" in per_layer
    for name, m in per_layer.items():
        if "ttft" in name:
            assert m["moves"] == "ttft_p50_ms", name
        if "tpot" in name:
            assert m["moves"] == "tpot_p50_ms", name


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)
    perf = open(os.path.join(spec.ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_have_plain_names():
    for p in BENCH["paths"]:
        for root, dirs, files in os.walk(os.path.join(spec.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), spec.ROOT)
                assert PATH.match(rel), rel
