"""The metrics that read the engine's own timing of its programs (the
walls by class and ``fetch_found_ready_total``, ``LLMEngine.stats()``
through the pass-through): on a recorded pair of snapshots each reads
to the digit; None where a key or a sample is missing (a program from
before these books, speculation, a window without such a launch); and
the entries stand in the manifest, found by name, with their cells
among those that list them."""
import copy
import json
import os

import pytest

import manifest_by_name
from benchmarks import serve_cell, spec
from benchmarks.readers import program_time

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = ["gptj-6b.serve_chat"]
DOCQA = ["mistral-7b-v0.3.serve_docqa"]
#: every closed-loop cell's engine keeps these books and ``obs["engine"]``
#: carries them (PR 44 listed Keye's and openPangu's cells)
CLOSED = DOCQA + ["keye-vl-2.0-30b-a3b.serve_longdoc",
                  "openpangu-ultra-moe-718b.serve_longdoc16"]
#: name -> (unit, source, layer, the end-to-end metric it moves, cells
#: that list it today). ``decode_exposed_ms.tok`` stays docqa's: in the
#: long-document cells it is a difference of two unlike means and names
#: no idle time (PERF.md section 6, PR 41, item 2b)
ENTRIES = {
    "decode_device_ms.tpot": ("ms", "program_span", "device",
                              "tpot_p50_ms", CHAT),
    "decode_device_ms.tok": ("ms", "program_span", "device",
                             "serve_tok_s", CLOSED),
    "prefill_device_ms.ttft": ("ms", "program_span", "device",
                               "ttft_p50_ms", CHAT),
    "prefill_device_ms.tok": ("ms", "program_span", "device",
                              "serve_tok_s", CLOSED),
    "decode_exposed_ms.tpot": ("ms", "program_span", "engine",
                               "tpot_p50_ms", CHAT),
    "decode_exposed_ms.tok": ("ms", "program_span", "engine",
                              "serve_tok_s", DOCQA),
    "fetch_found_ready_share.tpot": ("%", "program_counter", "engine",
                                     "tpot_p50_ms", CHAT),
    "fetch_found_ready_share.tok": ("%", "program_counter", "engine",
                                    "serve_tok_s", CLOSED),
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
    for suffix in ("tpot", "ttft", "tok"):
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


def test_the_entries_are_in_the_manifest_with_their_cells():
    for name, (unit, source, layer, moves, cells) in ENTRIES.items():
        entry, listed = manifest_by_name.metric(name)
        assert entry == {
            "name": name, "unit": unit, "better": "lower",
            "source": source, "layer": layer, "moves": moves}
        assert set(cells) <= set(listed), name
        assert os.path.exists(os.path.join(
            spec.HERE, "metrics", name.split(".")[0] + ".json"))
