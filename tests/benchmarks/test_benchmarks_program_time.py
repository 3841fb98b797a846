"""The metrics that read the engine's own timing of its programs (the
walls by class and ``fetch_found_ready_total``, ``LLMEngine.stats()``
through the pass-through): on a recorded pair of snapshots each reads
to the digit; None where a key or a sample is missing (a program from
before these books, speculation, a window without such a launch); and
the seven entries stand in the manifest with their cells, and every
serving cell's line still resolves."""
import copy
import json
import os

import pytest

from benchmarks import serve_cell, spec
from benchmarks.readers import program_time

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = ["gptj-6b.serve_chat"]
#: the closed-loop cell that may list them: test_benchmarks_keye.py and
#: test_benchmarks_pangu.py hold the other two cells' lines to a fixed
#: set of names (PERF.md section 7); their engines keep the same books
#: and ``obs["engine"]`` carries them
CLOSED = ["mistral-7b-v0.3.serve_docqa"]
#: name -> (unit, source, layer, cells of .tpot, cells of .tok)
ENTRIES = {
    "decode_device_ms": ("ms", "program_span", "device", CHAT, CLOSED),
    "prefill_device_ms": ("ms", "program_span", "device", None, CLOSED),
    "decode_exposed_ms": ("ms", "program_span", "engine", CHAT, CLOSED),
    "fetch_found_ready_share": ("%", "program_counter", "engine", CHAT,
                                CLOSED),
}
CLASS_KEYS = [f"{kind}_{cls}_{what}"
              for kind, unit in (("prefill", "chunks"), ("decode", "steps"))
              for cls in ("device", "serial") for what in (unit, "s")]


@pytest.fixture()
def obs():
    with open(os.path.join(HERE, "data",
                           "engine_stats_pair_classes.json")) as f:
        both = json.load(f)
    return {"engine": serve_cell.counters_delta(both["after"],
                                                both["before"])}


def _read(name, obs):
    read, args = spec.metric_reader(name)
    return read(obs, **args)


def test_the_pass_through_carries_the_classes_and_the_ready_count(obs):
    eng = obs["engine"]
    assert {k: eng[k] for k in CLASS_KEYS} == pytest.approx({
        "prefill_device_chunks": 2, "prefill_device_s": 0.000709,
        "prefill_serial_chunks": 5, "prefill_serial_s": 0.003359,
        "decode_device_steps": 1, "decode_device_s": 0.00021,
        "decode_serial_steps": 14, "decode_serial_s": 0.009704})
    assert eng["fetch_found_ready_total"] == {"prefill": 3, "decode": 4}
    # device + serial <= all, a kind; what is left out, a ready fetch
    # on either side, is no more than the ready fetches
    found = sum(eng["fetch_found_ready_total"].values())
    for kind, all_ in (("prefill", "prefill_chunks"),
                       ("decode", "decode_steps")):
        unit = all_.split("_")[1]
        classed = eng[f"{kind}_device_{unit}"] + eng[f"{kind}_serial_{unit}"]
        assert 0 <= eng[all_] - classed <= found
    assert eng["h2d_transfers_total"] \
        == eng["prefill_chunks"] + eng["decode_steps"] == 32


@pytest.mark.parametrize("name,value", [
    ("decode_device_ms", 1e3 * 0.00021 / 1),
    ("prefill_device_ms", 1e3 * 0.000709 / 2),
    ("decode_exposed_ms", 1e3 * (0.009704 / 14 - 0.00021 / 1)),
    ("fetch_found_ready_share", 100.0 * (3 + 4) / 32),
])
def test_each_metric_reads_the_recorded_pair_to_the_digit(obs, name, value):
    for suffix in ("tpot", "tok"):
        assert _read(f"{name}.{suffix}", obs) == pytest.approx(value,
                                                               rel=1e-12)


@pytest.mark.parametrize("name,needs", [
    ("decode_device_ms", ["decode_device_steps"]),
    ("prefill_device_ms", ["prefill_device_chunks"]),
    ("decode_exposed_ms", ["decode_device_steps", "decode_serial_steps"]),
    ("fetch_found_ready_share", ["fetch_found_ready_total",
                                 "h2d_transfers_total"]),
])
def test_a_missing_key_or_sample_reads_none(obs, name, needs):
    name += ".tok"
    assert _read(name, obs) is not None
    assert _read(name, {}) is None and _read(name, {"engine": {}}) is None
    for key in needs:
        older = copy.deepcopy(obs)          # a program without the key
        del older["engine"][key]
        assert _read(name, older) is None
        if not key.endswith("_total"):
            empty = copy.deepcopy(obs)      # a window without a sample
            empty["engine"][key] = 0
            assert _read(name, empty) is None
    # no ready fetch is a reading, not a gap
    none = copy.deepcopy(obs)
    none["engine"]["fetch_found_ready_total"] = {"prefill": 0, "decode": 0}
    assert _read("fetch_found_ready_share.tok", none) == 0.0
    with pytest.raises(ValueError):
        program_time.read(obs, "no_such_quantity")


def test_the_seven_entries_are_in_the_manifest_with_their_cells():
    per_layer = spec.benchmark()["per_layer"]
    by_name = {m["name"]: m for m in per_layer}
    assert sum(n.split(".")[0] in ENTRIES for n in by_name) == 7
    for base, (unit, source, layer, chat, closed) in ENTRIES.items():
        for suffix, cells, moves in (("tpot", chat, "tpot_p50_ms"),
                                     ("tok", closed, "serve_tok_s")):
            if cells is None:
                # no chunk is launched behind a program in chat
                assert f"{base}.{suffix}" not in by_name
                continue
            assert by_name[f"{base}.{suffix}"] == {
                "name": f"{base}.{suffix}", "unit": unit, "better": "lower",
                "source": source, "layer": layer, "moves": moves,
                "workloads": cells}
        assert os.path.exists(os.path.join(spec.HERE, "metrics",
                                           base + ".json"))
