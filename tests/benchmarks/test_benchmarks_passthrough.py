"""The pass-through of ``LLMEngine.stats()`` into the observations
(``serve_cell.numerics`` / ``counters_delta``) on a recorded pair of
snapshots, and the metrics that read what it carries: each resolves to
a reader by its file's name, reads the recorded pair, and returns
nothing for a program that keeps no such books or off the chip."""
import copy
import json
import os

import pytest

import manifest_by_name
from benchmarks import serve_cell, spec
from benchmarks.readers import device_trace, tick

HERE = os.path.dirname(os.path.abspath(__file__))
#: the fixed list the pass-through took the place of (PR 39)
OLD_COUNTERS = ("prefill_wall_s", "prefill_chunks", "decode_wall_s",
                "decode_steps", "tokens_total", "prefix_hit_blocks_total",
                "prompt_blocks_total", "decode_pages_live")
#: per-layer entries that read the engine's books, not the trace
FROM_THE_BOOKS = ("tick_ms", "host_ms_per_tick", "decode_launch_ms",
                  "prefill_launch_ms", "host_gap_share",
                  "programs_ahead_share", "ttft_queue_ms",
                  "ttft_prefill_wait_ms", "ttft_prefill_ms",
                  "profiler_launch_stretch")
FROM_THE_TRACE = ("idle_in_tick_share", "head_loss_share",
                  "sparse_attn_share", "kv_write_share")


@pytest.fixture()
def pair():
    """JSON as recorded: a dict's integer keys are strings in it."""
    with open(os.path.join(HERE, "data", "engine_stats_pair.json")) as f:
        both = json.load(f)
    return both["before"], both["after"]


def test_the_eight_old_counters_come_out_as_they_did(pair):
    before, after = pair
    delta = serve_cell.counters_delta(after, before)
    for key in OLD_COUNTERS:
        assert delta[key] == after[key] - before[key]
        assert type(delta[key]) is type(after[key] - before[key])
    was = before["occupancy_hist"]
    assert delta["occupancy_hist"] == {
        int(k): v - was.get(k, 0) for k, v in after["occupancy_hist"].items()}
    assert delta["decode_steps"] > 0 and delta["prefill_chunks"] > 0


def test_every_numeric_key_and_phase_is_carried(pair):
    before, after = pair
    delta = serve_cell.counters_delta(after, before)
    for key, v in after.items():
        numeric = isinstance(v, (int, float)) and not isinstance(v, bool)
        assert (key in delta) == (numeric or isinstance(v, dict)), key
        if numeric:
            assert delta[key] == v - before[key]
    # one level down, by reason; a reason that never fired stays at 0
    assert delta["ahead_blocked_total"] == {
        "last_chunk": 5, "no_backlog": 11, "op_or_swap": 0, "speculative": 0}
    assert delta["programs_ahead_total"] \
        == after["programs_ahead_total"] - before["programs_ahead_total"]
    # phases as {"count", "seconds"}; the one first entered inside the
    # stretch counts from nought
    assert set(delta["phases"]) == set(after["phases"])
    assert "engine.admit.evict" not in before["phases"]
    count, seconds = after["phases"]["engine.admit.evict"]
    assert delta["phases"]["engine.admit.evict"] == {
        "count": count, "seconds": seconds}
    c0, s0 = before["phases"]["engine.tick"]
    c1, s1 = after["phases"]["engine.tick"]
    assert delta["phases"]["engine.tick"] == {"count": c1 - c0,
                                              "seconds": s1 - s0}
    assert delta["tick_wall_s"] == pytest.approx(s1 - s0)
    # strings, lists and None are dropped, at either level
    for key in ("paged_impl", "attention_dispatch", "prefix_fingerprints",
                "dead"):
        assert key in after and key not in delta
    json.dumps(delta)                       # plain data


def test_a_key_one_side_lacks_is_left_out_and_nothing_raises(pair):
    before, after = pair
    older = {k: v for k, v in before.items()
             if k not in ("phases", "programs_ahead_total", "host_gap_s")}
    delta = serve_cell.counters_delta(after, older)
    assert not {"phases", "programs_ahead_total", "host_gap_s"} & set(delta)
    assert delta["decode_steps"] == after["decode_steps"] \
        - before["decode_steps"]
    # the other way round, and a key whose kind changed between the two
    newer = dict(after, made_up_total=3, tokens_total={"a": 1})
    delta = serve_cell.counters_delta(newer, before)
    assert "made_up_total" not in delta and "tokens_total" not in delta
    # a string among a dict's values is dropped, the numbers stay
    mixed = dict(after, ahead_blocked_total=dict(
        after["ahead_blocked_total"], note="a string"))
    assert "note" not in serve_cell.counters_delta(
        mixed, before)["ahead_blocked_total"]
    assert serve_cell.counters_delta({}, {}) == {}


def test_the_end_snapshot_keeps_the_gauges(pair):
    _, after = pair
    end = serve_cell.numerics(after)
    for gauge in ("queue_depth", "free_blocks", "active_slots",
                  "total_blocks", "tokens_per_s"):
        assert end[gauge] == after[gauge]
    assert end["occupancy_hist"] == {
        int(k): v for k, v in after["occupancy_hist"].items()}
    assert end["phases"]["engine.tick"] == {
        "count": after["phases"]["engine.tick"][0],
        "seconds": after["phases"]["engine.tick"][1]}
    assert "paged_impl" not in end and "dead" not in end


def _obs(pair, traced=True):
    before, after = pair
    eng = serve_cell.counters_delta(after, before)
    stretched = copy.deepcopy(eng)
    stretched["phases"]["engine.decode.dispatch"]["seconds"] *= 4.0
    return {"engine": eng, "engine_end": serve_cell.numerics(after),
            "device": {"platform": "cpu", "kind": "cpu"},
            "trace": {"engine": stretched} if traced else None}


def test_the_metrics_of_the_books_read_the_recorded_pair(pair):
    obs = _obs(pair)
    eng = obs["engine"]
    ticks = eng["phases"]["engine.tick"]["count"]

    def value(base):
        read, args = spec.metric_reader(base)
        return read(obs, **args)

    assert value("tick_ms") == pytest.approx(1e3 * eng["tick_wall_s"] / ticks)
    waited = sum(eng["phases"][w]["seconds"]
                 for w in ("engine.decode.wait", "engine.prefill.wait"))
    assert value("host_ms_per_tick") == pytest.approx(
        1e3 * (eng["tick_wall_s"] - waited) / ticks)
    assert 0 < value("host_ms_per_tick") < value("tick_ms")
    for base, phase in (("decode_launch_ms", "engine.decode.dispatch"),
                        ("prefill_launch_ms", "engine.prefill.dispatch")):
        p = eng["phases"][phase]
        assert value(base) == pytest.approx(1e3 * p["seconds"] / p["count"])
    assert value("host_gap_share") == pytest.approx(
        100.0 * eng["host_gap_s"] / eng["tick_wall_s"])
    assert value("programs_ahead_share") == pytest.approx(
        100.0 * eng["programs_ahead_total"]
        / (eng["prefill_chunks"] + eng["decode_steps"]))
    # the three parts of the engine's mean first-token time
    parts = [value(b) for b in ("ttft_queue_ms", "ttft_prefill_wait_ms",
                                "ttft_prefill_ms")]
    assert sum(parts) == pytest.approx(
        1e3 * eng["ttft_s"] / eng["ttft_requests"])
    assert value("profiler_launch_stretch") == pytest.approx(4.0)
    # without a traced stretch there is no stretch to read
    read, args = spec.metric_reader("profiler_launch_stretch")
    assert read(_obs(pair, traced=False), **args) is None
    with pytest.raises(ValueError, match="unknown quantity"):
        tick.read(obs, "no_such")


@pytest.mark.parametrize("base", FROM_THE_BOOKS + FROM_THE_TRACE)
def test_a_new_metric_reads_nothing_where_there_is_nothing(base, pair):
    """A program from before the phase clock (none of the keys), a
    stretch in which nothing ran (all of them nought), and a
    rehearsal's CPU trace each give None, never 0 and never an error."""
    read, args = spec.metric_reader(base)
    older = {"engine": {"decode_steps": 5, "prefill_chunks": 2,
                        "decode_wall_s": 0.1},
             "device": {"platform": "tpu", "kind": "TPU v5 lite"},
             "trace": None}
    assert read(older, **args) is None
    assert read({"device": older["device"]}, **args) is None
    before, _ = pair
    still = {"engine": serve_cell.counters_delta(before, before),
             "device": older["device"], "trace": None}
    assert read(still, **args) is None
    if base in FROM_THE_TRACE:
        cpu = {"device": {"platform": "cpu", "kind": "cpu"},
               "model": {"num_kv_blocks": 8, "kv_heads": 2,
                         "kv_block_size": 16, "head_dim": 8},
               "trace": {"window_s": 1.0, "busy_s": 0.5, "chips": 1,
                         "by_scope": {"layer/mlp": 0.5}, "op_calls": {
                             "fusion.1_bf16_4_4_": {
                                 "kind": "fusion", "scope": "layer/mlp",
                                 "name": "%fusion.1 = bf16[4,4]{1,0} fusion(",
                                 "seconds": 0.5}},
                         "idle_by_phase": {"(outside a tick)": 0.5},
                         "idle_outside_tick_s": 0.5}}
        assert read(cpu, **args) is None
        # on the chip, a trace of a program without these scopes or
        # annotations: nothing to read either
        assert read(dict(cpu, device=older["device"]), **args) is None


def test_the_new_entries_are_in_the_manifest_with_their_cells():
    """Each entry found by name; the cells that are in the manifest
    today are among those it lists (a later cell may join them), and
    it moves the end-to-end metric of its side."""
    chat = "gptj-6b.serve_chat"
    docqa = "mistral-7b-v0.3.serve_docqa"
    keye = "keye-vl-2.0-30b-a3b.serve_longdoc"
    closed = {docqa, keye, "openpangu-ultra-moe-718b.serve_longdoc16"}

    def listed(name, moves=None):
        entry, cells = manifest_by_name.metric(name)
        assert moves is None or entry["moves"] == moves, name
        return set(cells)

    for base in FROM_THE_BOOKS + ("idle_in_tick_share",):
        first = "ttft" if base.startswith(("ttft_", "prefill_")) else "tpot"
        assert chat in listed(f"{base}.{first}", {
            "ttft": "ttft_p50_ms", "tpot": "tpot_p50_ms"}[first])
        assert closed <= listed(f"{base}.tok", "serve_tok_s")
    assert {"gptj-6b.train_2k", "mistral-7b-v0.3.train_fsdp4_4k"} \
        <= listed("head_loss_share")
    assert keye in listed("sparse_attn_share.tok")
    assert chat in listed("kv_write_share.tpot")
    assert docqa in listed("kv_write_share.tok")
    # retired in favour of kv_write_share.* (PERF.md, PR 39)
    assert not [m["name"] for m in spec.benchmark()["per_layer"]
                if m["name"].startswith("pool_copy_share")]
    assert not os.path.exists(os.path.join(
        spec.HERE, "metrics", "pool_copy_share.json"))
    obs = {"trace": {"window_s": 1.0, "busy_s": 0.5, "chips": 1, "by_scope": {
        "layer": 0.3, "layer/attn": 0.2, "layer/attn/kv_write": 0.05,
        "kv_copy": 0.01}, "op_calls": {
            # the in-place row scatter: the whole pool its result, the
            # layer scan's name its only path
            "fusion.9_bf16_512_8_": {
                "kind": "fusion", "scope": "", "seconds": 0.04,
                "name": "%fusion.9 = bf16[512,8]{1,0} fusion("},
            # under the scope already: not counted twice
            "fusion.8_bf16_512_8_": {
                "kind": "fusion", "scope": "layer/attn/kv_write",
                "seconds": 0.03,
                "name": "%fusion.8 = bf16[512,8]{1,0} fusion("},
            "paged_attention.1_bf16_512_8_": {
                "kind": "paged_attention", "scope": "", "seconds": 0.1,
                "name": "%paged_attention.1 = bf16[512,8]{1,0} custom-call("},
            "fusion.7_bf16_16_8_": {
                "kind": "fusion", "scope": "", "seconds": 0.2,
                "name": "%fusion.7 = bf16[16,8]{1,0} fusion("}}},
        "model": {"num_kv_blocks": 4, "kv_heads": 2, "kv_block_size": 16,
                  "head_dim": 8},
        "device": {"platform": "tpu"}}
    assert device_trace.read(obs, "scope_share", scopes=[
        "layer/attn/kv_write", "kv_copy"]) == pytest.approx(12.0)
    read, args = spec.metric_reader("kv_write_share.tpot")
    assert read(obs, **args) == pytest.approx(100.0 * (0.06 + 0.04) / 0.5)


SERVING = [w["name"] for w in spec.benchmark()["workloads"]
           if spec.load_cell(w["name"]).kind != "train"]


@pytest.mark.parametrize("name", SERVING)
def test_every_serving_cell_of_the_manifest_observes_its_program_group_whole(
        name):
    """``obs["model"]`` holds what it held until PR 44 (the attention's
    sizes, the engine's, and a routed model's four widths) with the same
    values, and every key of the configuration's ``program`` group under
    its own name as the model was built from it: a reader that comes
    with a configuration reads whatever widths that configuration has."""
    cell = spec.load_cell(name)
    engine, program = cell.params["engine"], cell.config["program"]
    model = dict(cell.model_kwargs(), remat_policy="none",
                 max_seq_len=engine["max_seq_len"])
    got = serve_cell.observed_model(program, model, engine)
    held = {"n_layers": cell.depth, "n_heads": program["n_heads"],
            "kv_heads": program.get("n_kv_heads") or program["n_heads"],
            "head_dim": program["head_dim"],
            "kv_block_size": engine["kv_block_size"],
            "num_kv_blocks": engine["num_kv_blocks"],
            "prefill_chunk": engine["prefill_chunk"], "itemsize": 2,
            **{k: program[k] for k in ("d_model", "expert_width",
                                       "experts_per_token", "n_experts")
               if k in program}}
    assert {k: got[k] for k in held} == held
    assert set(program) <= set(got)
    for key, value in program.items():
        # as run: the window's length is the engine's
        assert got[key] == (engine["max_seq_len"] if key == "max_seq_len"
                            else value), key
    json.dumps(got)                         # plain data


def test_the_observed_model_drops_what_is_not_plain_and_keeps_its_own_keys():
    program = {"d_model": 8, "n_heads": 2, "head_dim": 4, "dtype": "float32",
               "router_score": "sigmoid", "qk_norm": True,
               "layer_kinds": ["full", "window", "window", "window"],
               "heads_by_kind": {"full": 48, "window": 64},
               "n_layers": 40, "absent": None, "mixed": [1, None]}
    model = dict(program, n_layers=5, d_model=16)       # as run
    engine = {"kv_block_size": 16, "num_kv_blocks": 9, "prefill_chunk": 32}
    got = serve_cell.observed_model(program, model, engine)
    assert "absent" not in got and "mixed" not in got
    assert got["layer_kinds"] == program["layer_kinds"]
    assert got["heads_by_kind"] == {"full": 48, "window": 64}
    assert (got["router_score"], got["qk_norm"]) == ("sigmoid", True)
    # the value the model was built from, and the derived keys win
    assert (got["d_model"], got["n_layers"]) == (16, 5)
    assert (got["kv_heads"], got["itemsize"], got["num_kv_blocks"]) \
        == (2, 4, 9)
