"""The metrics that read the tick's books by kind (``PhaseClock.books``;
``LLMEngine.stats()`` through the pass-through, a training loop's clock
in its process): the reader's arithmetic on a written observation;
nothing, and no error, beside a program without the books; every entry
in the manifest, found by name; and each read off a CPU engine's own
``stats()``, differenced as a cell differences them."""
import math

import jax.numpy as jnp
import pytest

import manifest_by_name
from benchmarks import serve_cell, spec
from benchmarks.readers import tick_kinds

CHAT = ["gptj-6b.serve_chat"]
TOK = ["mistral-7b-v0.3.serve_docqa", "keye-vl-2.0-30b-a3b.serve_longdoc",
       "openpangu-ultra-moe-718b.serve_longdoc16",
       "laguna-xs.2.serve_repoqa", "a.x-k2.serve_longdoc64",
       "granite-4.0-h-small.serve_sessions64",
       "olmo-hybrid-7b.serve_shared_docs12"]
TRAIN = ["gptj-6b.train_2k", "mistral-7b-v0.3.train_fsdp4_4k"]
FULL = ["full", "full_decode", "full_verify"]
PART = ["part", "part_decode", "part_verify"]
#: base name -> the reader's arguments (one file a base name)
ARGS = {
    "tick_decode_ms": dict(what="mean_ms", kinds=["decode"]),
    "tick_chunk_ms": dict(what="mean_ms", kinds=FULL),
    "tick_part_chunk_ms": dict(what="mean_ms", kinds=PART),
    "tick_decode_host_ms": dict(what="host_ms", kinds=["decode"]),
    "tick_chunk_gap_ms": dict(what="gap_ms", kinds=FULL + PART),
    "tick_chunk_share": dict(what="share", kinds=FULL + PART),
    "tick_decode_p99_ms": dict(what="quantile_ms", kinds=["decode"], q=0.99),
    "tick_slow_share": dict(what="slow_share"),
    "step_p99_ms": dict(what="quantile_ms", kinds=["step"], q=0.99,
                        owner="train"),
}
#: name -> (unit, source, layer, the end-to-end metric it moves, cells
#: that list it today)
ENTRIES = {
    "tick_decode_ms.tpot": ("ms", "program_span", "engine", "tpot_p50_ms",
                            CHAT),
    "tick_decode_ms.tok": ("ms", "program_span", "engine", "serve_tok_s",
                           TOK),
    "tick_chunk_ms.tok": ("ms", "program_span", "engine", "serve_tok_s",
                          TOK),
    "tick_part_chunk_ms.tok": ("ms", "program_span", "engine",
                               "serve_tok_s", TOK),
    "tick_part_chunk_ms.ttft": ("ms", "program_span", "engine",
                                "ttft_p50_ms", CHAT),
    "tick_decode_host_ms.tpot": ("ms", "program_span", "engine",
                                 "tpot_p50_ms", CHAT),
    "tick_decode_host_ms.tok": ("ms", "program_span", "engine",
                                "serve_tok_s", TOK),
    "tick_chunk_gap_ms.tok": ("ms", "program_span", "engine",
                              "serve_tok_s", TOK),
    "tick_chunk_share.tok": ("%", "program_span", "engine", "serve_tok_s",
                             TOK),
    "tick_decode_p99_ms.tpot": ("ms", "program_span", "engine",
                                "tpot_p50_ms", CHAT),
    "tick_decode_p99_ms.tok": ("ms", "program_span", "engine",
                               "serve_tok_s", TOK),
    "tick_slow_share.tpot": ("%", "program_counter", "engine",
                             "tpot_p50_ms", CHAT),
    "tick_slow_share.tok": ("%", "program_counter", "engine",
                            "serve_tok_s", TOK),
    "step_p99_ms": ("ms", "program_span", "train step", "train_tok_s",
                    TRAIN),
}


def _read(name, obs):
    read, args = spec.metric_reader(name)
    return read(obs, **args)


#: a window of 100 ticks over 3.0 s, as ``counters_delta`` leaves them:
#: 60 fetched a decode step alone, 20 a full chunk and a step, 10 a part
#: chunk and a step, 6 a full chunk alone, 4 nothing; the kind `part`
#: was met before the window and not in it
ENGINE = {
    "tick_wall_s": 3.0, "host_gap_s": 0.09,
    "phases": {"engine.tick": {"count": 100, "seconds": 3.0}},
    "tick_kind_total": {"decode": 60, "full_decode": 20,
                        "part_decode": 10, "full": 6, "idle": 4, "part": 0},
    "tick_kind_s": {"decode": 0.6, "full_decode": 1.6, "part_decode": 0.3,
                    "full": 0.496, "idle": 0.004, "part": 0.0},
    "tick_kind_wait_s": {"decode": 0.48, "full_decode": 1.5,
                         "part_decode": 0.25, "full": 0.47, "idle": 0.0,
                         "part": 0.0},
    "tick_kind_gap_s": {"decode": 0.03, "full_decode": 0.02,
                        "part_decode": 0.012, "full": 0.024, "idle": 0.004,
                        "part": 0.0},
    # 59 ticks in the bucket from 2**-7 s (7.8 to 8.5 ms), one of 125 ms
    "tick_hist_decode": {2.0 ** -7: 59, 2.0 ** -3: 1,
                         2.0 ** -6: 0},     # met before the window only
    "tick_slow_total": {"decode": 1, "full_decode": 0},
    "tick_slow_s": {"engine.decode.wait": 0.117, "engine.admit": 0.003},
}


def test_the_readers_arithmetic_on_a_written_window():
    obs = {"engine": ENGINE}
    want = {
        "tick_decode_ms": 10.0,                 # 0.6 s / 60
        "tick_chunk_ms": 1e3 * 2.096 / 26,      # full + full_decode
        "tick_part_chunk_ms": 30.0,             # part_decode alone
        "tick_decode_host_ms": 2.0,             # (0.6 - 0.48) / 60
        "tick_chunk_gap_ms": 1e3 * 0.056 / 36,
        "tick_chunk_share": 100 * 2.396 / 3.0,
        "tick_slow_share": 4.0,                 # 0.12 of 3 s
    }
    for base, value in want.items():
        assert tick_kinds.read(obs, **ARGS[base]) == pytest.approx(value), \
            base
    # the 99th of 60 is rank 59.4: the slow tick's bucket, 0.4 into it
    assert tick_kinds.read(obs, **ARGS["tick_decode_p99_ms"]) \
        == pytest.approx(125.0 * 2 ** (0.4 / 8))
    # the median: rank 30 of the 59 in the first bucket
    assert tick_kinds.read(obs, what="quantile_ms", kinds=["decode"],
                           q=0.5) == pytest.approx(
        1e3 * 2.0 ** -7 * 2 ** (30 / 59 / 8))
    with pytest.raises(ValueError):
        tick_kinds.read(obs, what="no_such_quantity", kinds=["decode"])


def test_a_percentile_reads_back_within_a_buckets_width():
    from ray_tpu.util import tracing
    hist, lengths = {}, [0.002 * 1.013 ** i for i in range(400)]
    for s in lengths:
        edge = tracing.bucket_edge(
            math.floor(math.log2(s) * tracing.HIST_PER_OCTAVE))
        hist[edge] = hist.get(edge, 0) + 1
    assert tick_kinds.PER_OCTAVE == tracing.HIST_PER_OCTAVE
    width = 2 ** (1 / tick_kinds.PER_OCTAVE)
    for q in (0.1, 0.5, 0.9, 0.99):
        exact = lengths[int(q * 400) - 1]
        assert exact / width <= tick_kinds.quantile(hist, q) \
            <= exact * width
    assert tick_kinds.quantile({}, 0.5) is None
    assert tick_kinds.quantile({0.01: 0}, 0.5) is None


def test_nothing_to_read_is_none_and_never_raises(monkeypatch):
    # a program from before the books; a stretch that met no such tick
    older = {k: v for k, v in ENGINE.items() if not k.startswith("tick_")}
    quiet = dict(ENGINE, tick_kind_total=dict.fromkeys(
        ENGINE["tick_kind_total"], 0), tick_kind_s=dict.fromkeys(
        ENGINE["tick_kind_s"], 0.0), tick_hist_decode={}, tick_wall_s=0.0)
    for base, args in ARGS.items():
        if "owner" in args:
            continue
        for obs in ({}, {"engine": None}, {"engine": older},
                    {"engine": quiet}):
            assert tick_kinds.read(obs, **args) is None, (base, obs)
    # a window without a chunk still has its decode ticks and a share
    decoding = dict(ENGINE,
                    tick_kind_total={"decode": 60}, tick_kind_s={"decode": 0.6})
    assert tick_kinds.read({"engine": decoding}, **ARGS["tick_chunk_ms"]) \
        is None
    assert tick_kinds.read({"engine": decoding},
                           **ARGS["tick_chunk_share"]) == 0.0
    # a training cell: no clock of that owner, a clock without books,
    # a program without the registry
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "clocks", lambda: {})
    assert tick_kinds.read({}, **ARGS["step_p99_ms"]) is None
    monkeypatch.setattr(tracing, "clocks", lambda: {"train": object()})
    assert tick_kinds.read({}, **ARGS["step_p99_ms"]) is None
    monkeypatch.delattr(tracing, "clocks")
    assert tick_kinds.read({}, **ARGS["step_p99_ms"]) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_manifest_declares_the_entry(name):
    # found by name, its fields as written, the reader by its base
    # name's file; its cells are among those that list it, each carries
    # it on its traced line, and all of them report the metric it moves
    unit, source, layer, moves, cells = ENTRIES[name]
    entry, listed = manifest_by_name.metric(name)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves}
    assert set(cells) <= set(listed)
    _, reporting = manifest_by_name.metric(moves)
    assert set(listed) <= set(reporting)
    for cell in cells:
        assert name in manifest_by_name.line_of(cell)
    read, args = spec.metric_reader(name)
    assert read is tick_kinds.read and args == ARGS[name.split(".")[0]]


@pytest.fixture(scope="module")
def window():
    """A CPU engine's own ``stats()`` around a stretch with every kind
    of tick the cells meet (a decoding sequence, beside it prompts of
    two chunks and a half), differenced as ``obs["engine"]`` is."""
    from ray_tpu.models import TransformerConfig
    from ray_tpu.serve.llm_engine import EngineConfig, LLMEngine
    eng = LLMEngine(
        TransformerConfig(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
                          head_dim=8, d_ff=32, max_seq_len=64, rotary_dim=8,
                          dtype=jnp.float32, remat_policy="none"),
        EngineConfig(decode_slots=2, kv_block_size=4, max_seq_len=48,
                     prefill_chunk=8, max_new_tokens=40,
                     enable_prefix_sharing=False),
        replica_tag="tick-kinds")

    def serve():
        a = eng.submit(list(range(2, 6)), 36)
        assert isinstance(a.out.get(timeout=60), int)
        b = eng.submit(list(range(10, 30)), 4)
        for req in (b, a):
            while isinstance(req.out.get(timeout=60), int):
                pass
    try:
        eng.warmup()
        serve()                 # before the window: the kinds are met
        before = eng.stats()
        for _ in range(2):
            serve()
        after = eng.stats()
    finally:
        eng.shutdown()
    return {"engine": serve_cell.counters_delta(after, before)}


def test_the_kinds_of_a_differenced_window_add_up(window):
    eng = window["engine"]
    assert "slow_ticks" not in eng
    met = {k: n for k, n in eng["tick_kind_total"].items() if n}
    assert met.pop("idle", 0) <= 2
    assert met == {"full_decode": 4, "part_decode": 4,
                   "decode": eng["decode_steps"] - 8}
    assert sum(eng["tick_kind_total"].values()) \
        == eng["phases"]["engine.tick"]["count"]
    assert sum(eng["tick_kind_s"].values()) == pytest.approx(
        eng["tick_wall_s"], rel=1e-9)
    assert sum(eng["tick_kind_gap_s"].values()) == pytest.approx(
        eng["host_gap_s"], rel=1e-6)
    for kind, n in eng["tick_kind_total"].items():
        assert sum(eng[f"tick_hist_{kind}"].values()) == n


@pytest.mark.parametrize("name", sorted(n for n in ENTRIES if "." in n))
def test_a_serving_entry_reads_off_the_engines_own_stats(window, name):
    value = _read(name, window)
    eng = window["engine"]
    assert value is not None and value >= 0
    mean_ms = 1e3 * eng["tick_wall_s"] / eng["phases"]["engine.tick"]["count"]
    base = name.split(".")[0]
    if base == "tick_decode_ms":
        assert value == pytest.approx(
            1e3 * eng["tick_kind_s"]["decode"]
            / eng["tick_kind_total"]["decode"])
    elif base == "tick_decode_host_ms":
        assert value <= _read("tick_decode_ms.tok", window)
    elif base == "tick_decode_p99_ms":
        # read from buckets 9% wide: no tick is longer than its bucket
        assert value >= _read("tick_decode_ms.tok", window) / 1.1
        assert value <= 1.1e3 * max(eng["tick_hist_decode"])
    elif base in ("tick_chunk_share", "tick_slow_share"):
        assert value <= 100.0
    elif base == "tick_chunk_gap_ms":
        assert value <= max(_read("tick_chunk_ms.tok", window),
                            _read("tick_part_chunk_ms.tok", window))
    else:
        assert value > 0 and mean_ms > 0


def test_the_training_entry_reads_off_the_loops_own_clock():
    """``step_p99_ms`` off a clock driven as ``parallel/plan.py`` drives
    its own: the compiling step is its own kind and stays out."""
    import time

    from ray_tpu.util.tracing import PhaseClock
    clock = PhaseClock("train", steps=True)
    for i in range(12):
        clock.tick()
        with clock.phase("train.step"):
            with clock.phase("train.dispatch"):
                if i == 0:
                    time.sleep(0.5)
                    clock.kind = "compile"
            with clock.phase("train.wait"):
                time.sleep(0.004)
    value = _read("step_p99_ms", {})
    assert 4.0 / 1.1 <= value < 400.0
    assert value == pytest.approx(1e3 * clock.quantile("step", 0.99))
    assert clock.books()["tick_kind_total"] == {"compile": 1, "step": 11}
