"""The Laguna-XS.2 configuration and its cell, `serve_repoqa`: the file
holds the published numbers under their own keys and states its cut,
the traffic file the cell's stated parameters, the counting rules by
kind of layer against shapes counted by hand, the new readers on
made-up observations, the manifest's configuration, cell and entries
found by name (``manifest_by_name.py``'s rule: names, ``in``, ``>=``),
the harness's check at the rehearsal's widths, and the cell rehearsed
end to end on the CPU."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_window, spec
from benchmarks.readers import window

CONFIG = "laguna-xs.2"
CELL = CONFIG + ".serve_repoqa"
#: the catalog's row (model-configs guide), the lists by their pattern
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
#: what ``obs["model"]`` carries of it (``serve_cell.observed_model``)
MODEL = {"n_layers": 5, "n_heads": 48, "window_heads": 64, "kv_heads": 8,
         "head_dim": 128, "kv_block_size": 16, "itemsize": 2,
         "prefill_chunk": 2048, "sliding_window": 512,
         "layer_pattern": ["full", "window", "window", "window"]}
ENTRIES = [{"name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "serve_tok_s"}
           for name, better, source, layer in (
    ("window_attn_share.tok", "lower", "device_trace",
     "kernels, paged attention"),
    ("full_attn_share.tok", "lower", "device_trace",
     "kernels, paged attention"),
    ("paged_window_decode_roofline.tok", "higher", "device_trace",
     "kernels, paged attention"),
    ("paged_window_prefill_roofline.tok", "higher", "device_trace",
     "kernels, paged attention"),
    ("window_pool_pinned_share.tok", "lower", "program_counter", "engine"),
    ("prefix_hits_cut_share.tok", "lower", "program_counter", "engine"))]


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cut():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert cfg["published"] == PUBLISHED
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key          # as published
    assert cfg["num_hidden_layers"] == cell.depth == 5
    depth = cfg["depth"]
    assert (depth["key"], depth["published"], depth["here"]) \
        == ("num_hidden_layers", 40, 5)
    assert "3,868" not in depth["why"] and "3,869,857,792" in depth["why"]
    assert "eight pipeline stages" in cfg["deployment"]
    for item in ("gate", "router", "qk_norm", "rotary_layout", "yarn",
                 "window"):
        assert item in cfg["assumed"], item
    assert "refuse" in cfg["departures"]["training"]
    assert "refuse" in cfg["departures"]["hand_off"]
    # what the program is built from says the same widths, by kind
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["window_heads"],
            kw["n_kv_heads"], kw["head_dim"], kw["d_ff"]) \
        == (2048, 48, 64, 8, 128, 8192)
    assert (kw["layer_pattern"], kw["sliding_window"], kw["head_gate"],
            kw["n_dense_layers"]) \
        == (["full", "window", "window", "window"], 512, True, 1)
    assert (kw["rotary_dim"], kw["rope_base"], kw["window_rotary_dim"],
            kw["window_rope_base"]) == (64, 5e5, 128, 1e4)
    assert kw["rope_yarn"] == [64.0, 4096, 64.0, 1.0, 1.4158883083359672]
    assert (kw["n_experts"], kw["experts_per_token"], kw["expert_width"],
            kw["shared_expert_width"], kw["router_score"],
            kw["routed_scale"]) == (256, 8, 512, 512, "sigmoid", 2.5)
    assert kw["vocab_size"] == 100352 and kw["n_layers"] == 5
    assert kw["norm_eps"] == 1e-6 and kw["dtype"] == "bfloat16"
    hp = dict(cell.reference_hp())
    assert (hp["num_attention_heads"], hp["window_heads"],
            hp["sliding_window"], hp["num_experts_per_tok"],
            hp["yarn_factor"], hp["layer_pattern"]) \
        == (48, 64, 512, 8, 64.0, "full window window window")
    assert cfg["reference"] == "laguna"
    # the limit lies between the sound readings and the precision
    # control's, on weights whose routed part is an eighth (and the
    # file says what that costs and what is not told apart)
    assert set(cfg["tolerance"]) == {"logits", "why"}
    assert cfg["tolerance"]["logits"] == 0.04
    assert "NOT told apart" in cfg["tolerance"]["why"]
    assert cfg["weights"]["residual_writers"] == [
        "layers.we_down", "window_layers.we_down"]
    assert cfg["weights"]["stream_scale"] == 0.125
    assert "The price" in cfg["weights"]["why"]
    # the rehearsal keeps every form, at a narrow width
    small = spec.load_cell(CELL, rehearse=True).model_kwargs()
    assert small["layer_pattern"] == kw["layer_pattern"] \
        and small["window_heads"] > small["n_heads"] \
        and small["sliding_window"] < 64 and small["head_gate"] \
        and small["n_dense_layers"] == 1 < small["n_layers"] \
        and small["experts_per_token"] < small["n_experts"]


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "laguna.py")).read()
    assert "ray_tpu" not in src.split('"""', 2)[2]
    assert "from .common import F32, make_api" in src


@pytest.mark.parametrize("wrong_hp,ok,least", [
    ({}, True, 0.0), ({"sliding_window": 10**6}, False, 0.1),
    ({"gating": False}, False, 0.1), ({"window_heads": 6}, None, 0.0)])
def test_the_harness_check_holds_the_program_to_the_reference(
        wrong_hp, ok, least):
    """``check.serve_check`` as the cell runs it, at the rehearsal's
    widths in float32: ONE identity table for both kinds of layer and
    ``init_kv_cache(cfg, 1 + table, bs)``, a prompt past one chunk and
    three windows long, against ``reference/laguna.py``. A reference
    without the window or without the gate is refused; one with another
    head count cannot even read the weights."""
    import jax
    from benchmarks import check, harness
    from ray_tpu.models import (TransformerConfig, inference_params,
                                init_params)
    cell = spec.load_cell(CELL, rehearse=True)
    cell.config = dict(cell.config, rehearse_hp=dict(
        cell.config["rehearse_hp"], **wrong_hp))
    engine = cell.params["engine"]
    kw = dict(cell.model_kwargs(), remat_policy="none",
              max_seq_len=engine["max_seq_len"])
    kw["dtype"] = harness.resolve_dtype(kw["dtype"])
    model = TransformerConfig(**kw)
    params = harness.scale_stream(inference_params(model, init_params(
        model, jax.random.PRNGKey(spec.weight_seed(2**31 + 45)),
        dtype=model.dtype)), cell.config["weights"])
    if ok is None:
        with pytest.raises(TypeError):
            check.serve_check(cell, model, params, engine, 2**31 + 45)
        return
    verdict = check.serve_check(cell, model, params, engine, 2**31 + 45)
    assert verdict["sample"] == {"prompt_len": 99, "n_decode": 8}
    assert 99 > 3 * kw["sliding_window"]
    assert verdict["tol"]["logits"] == cell.config["tolerance"]["logits"]
    assert verdict["ok"] is ok
    if least:
        assert verdict["errors"]["logits"] > least
    else:
        assert verdict["errors"]["logits"] < 1e-4
        assert verdict["argmax_agree"] == [9, 9]


def test_the_traffic_file_holds_the_cells_parameters():
    p = spec.load_cell(CELL).params
    assert p["kind"] == "closed_loop" and p["n_layers"] == 5
    assert (p["clients"], p["client_threads"], p["doc_stride"]) \
        == (16, 16, 5)
    assert p["doc_lengths"] == [2048, 16384, 49152, 4096, 24576, 61440,
                                8192, 32768, 3072, 12288, 40960, 6144]
    assert sum(p["doc_lengths"]) / len(p["doc_lengths"]) == 21760
    assert p["answer_lengths"] == [64, 96, 128, 192, 256, 384, 80, 160,
                                   224, 320]
    assert (p["questions_per_doc"], p["question_len"],
            p["trace_seconds"]) == (4, 64, 6)
    # sixteen clients start on twelve different lengths
    starts = {(c * p["doc_stride"]) % len(p["doc_lengths"])
              for c in range(p["clients"])}
    assert len(starts) == 12
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["kv_block_size"], e["max_new_tokens"]) \
        == (16, 65536, 2048, 16, 384)
    # two kinds of page: 131,072 B a full page over two layers, 196,608
    # a window page over three; the window pool holds what sixteen
    # sequences can pin (161 each) and as much again in cached tails
    assert e["num_window_blocks"] == 4097 >= 1 + 16 * 161
    # the largest full pool that passes the harness's set-up check, in
    # steps of 2,048 pages (the file's why): 38,912 pages of 131,072 B
    # over the two full layers
    assert e["num_kv_blocks"] == 38913 >= 1 + 65536 // 16
    assert "38,912" in p["why"] and p["warm_seconds"] == 13.5
    longest = max(p["doc_lengths"]) + p["question_len"] \
        + max(p["answer_lengths"])
    assert longest < e["max_seq_len"]
    # the aligned state (every client on its k-th document) fits the
    # full pool whatever k
    for k in range(12):
        tokens = sum(p["doc_lengths"][(c * 5 + k) % 12] + 64 + 384
                     for c in range(16))
        assert -(-tokens // 16) + 16 < e["num_kv_blocks"], k
    from benchmarks import traffic
    assert traffic.check_sample(e) == {"prompt_len": 3075, "n_new": 8,
                                       "shared": 2560}
    # the set-up's second request resumes 515 positions behind the
    # first's end: over a window
    assert 3075 - 2560 > 512


def test_counts_by_kind_by_hand():
    assert roofline_window.layers_by_kind(MODEL) \
        == {"full": 2, "window": 3}
    assert roofline_window.layers_by_kind(dict(MODEL, n_layers=40)) \
        == {"full": 10, "window": 30}
    assert roofline_window.layers_by_kind(
        {"n_layers": 6}) == {"full": 6, "window": 0}
    assert roofline_window.heads_by_kind(MODEL) \
        == {"full": 48, "window": 64}
    assert roofline_window.page_bytes(MODEL) == 65536
    assert roofline_window.key_flops(48, MODEL) == 4 * 48 * 128
    # a decode step of one sequence at 30,000 rows: 1,876 full pages a
    # full layer, 33 a window layer
    work = roofline_window.decode({"full": 1876, "window": 33}, MODEL)
    assert work["full"] == (1876 * 2 * 16 * 24576.0, 1876 * 2 * 65536.0)
    assert work["window"] == (33 * 3 * 16 * 32768.0, 33 * 3 * 65536.0)
    # bytes bind both: 6 and 8 FLOP a byte against the chip's 240
    for flops, nbytes in work.values():
        assert flops / nbytes < 10
    # a full chunk at 28,672: position p meets p + 1 keys on a full
    # layer and 512 on a window layer
    n, start = 2048, 28672
    keys = {"full": n * start + n * (n + 1) // 2, "window": n * 512}
    pages = {"full": (start + n) // 16, "window": (512 + n) // 16 + 1}
    work = roofline_window.prefill(pages, keys, n, MODEL)
    assert work["full"][0] == 2 * keys["full"] * 24576.0
    assert work["window"][0] == 3 * n * 512 * 32768.0
    assert work["full"][1] == 2 * (pages["full"] * 65536
                                   + 2 * n * 48 * 128 * 2)
    assert work["window"][1] == 3 * (161 * 65536 + 2 * n * 64 * 128 * 2)
    # compute binds the full layers' chunk (1.5e12 FLOP a layer)
    kind = "TPU v5 lite"
    assert roofline.min_seconds(*work["full"], kind) \
        == work["full"][0] / 197e12 > 0.01


def _obs():
    return {
        "model": dict(MODEL),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine": {"prefix_hits_cut": 3, "prefix_hits": 60},
        "engine_end": {"window_pages_pinned": 544,
                       "window_total_blocks": 4096},
        "trace": {"chips": 1, "busy_s": 4.0, "window_s": 6.0,
                  "by_scope": {"layer/attn/window": 0.5,
                               "layer/attn/window/gate": 0.01,
                               "layer/attn/full": 1.5, "layer/mlp/moe": 1.0},
                  "engine": {"prefill_chunks": 10, "decode_steps": 100,
                             "decode_pages_live_full": 2_000_000,
                             "decode_pages_live_window": 52_800,
                             "prefill_pages_live_full": 12_000,
                             "prefill_pages_live_window": 1_610,
                             "prefill_keys_live_full": 400_000_000,
                             "prefill_keys_live_window": 10_485_760},
                  "by_module_kind": {
                      "jit__decode_fn|paged_attention": 0.5,
                      "jit__prefill_fn|paged_attention": 1.25,
                      "jit__decode_fn|fusion": 1.0}}}


def test_the_readers_on_made_up_observations():
    obs, kind = _obs(), "TPU v5 lite"
    eng = obs["trace"]["engine"]
    work = roofline_window.decode(
        {"full": eng["decode_pages_live_full"],
         "window": eng["decode_pages_live_window"]}, MODEL)
    least = sum(roofline.min_seconds(f, b, kind) for f, b in work.values())
    # the full layers' pages alone: 2e6 x 2 x 65,536 B at 819 GB/s
    assert least == pytest.approx(
        (2e6 * 2 + 52_800 * 3) * 65536 / 819e9)
    got = window.read(obs, "decode_roofline", module="decode")
    assert got == pytest.approx(100.0 * least / 0.5) and got < 100
    work = roofline_window.prefill(
        {"full": 12_000, "window": 1_610},
        {"full": 400_000_000, "window": 10_485_760}, 10 * 2048, MODEL)
    least = sum(roofline.min_seconds(f, b, kind) for f, b in work.values())
    got = window.read(obs, "prefill_roofline", module="prefill")
    assert got == pytest.approx(100.0 * least / 1.25) and got < 100
    line = spec.read_metrics(ENTRIES, obs)
    assert set(line) == {m["name"] for m in ENTRIES}
    assert line["window_attn_share.tok"]["value"] == pytest.approx(12.5)
    assert line["full_attn_share.tok"]["value"] == pytest.approx(37.5)
    assert line["window_pool_pinned_share.tok"]["value"] \
        == pytest.approx(100 * 544 / 4096)
    assert line["prefix_hits_cut_share.tok"]["value"] == pytest.approx(5.0)
    # a program without the counters by kind or the scopes (the parent
    # under these files), a rehearsal, no trace: nothing, and no error
    bare = _obs()
    for key in ("decode_pages_live_window", "prefill_pages_live_window"):
        del bare["trace"]["engine"][key]
    bare["trace"]["by_scope"] = {"layer/attn": 2.0}
    bare["engine"], bare["engine_end"] = {"prefix_hit_blocks_total": 4}, {}
    assert spec.read_metrics(ENTRIES, bare) == {}
    assert spec.read_metrics(
        ENTRIES, dict(_obs(), trace=None, engine={}, engine_end={})) == {}
    cpu = dict(_obs(), device={"platform": "cpu", "kind": "cpu"})
    assert window.read(cpu, "decode_roofline", module="decode") is None
    none = _obs()
    none["trace"]["by_module_kind"] = {"jit__decode_fn|fusion": 1.0}
    assert window.read(none, "decode_roofline", module="decode") is None
    with pytest.raises(ValueError, match="unknown quantity"):
        window.read(_obs(), "no_such")


def test_the_manifest_holds_the_configuration_the_cell_and_the_entries():
    """One configuration, one cell on one chip and six per-layer
    entries, each found by its name and as it was written; the accepted
    ``.tok`` entries that mean the same here list the cell, those that
    count ONE page size and ONE table do not; no other cell's line
    carries the six."""
    config = manifest_by_name.configuration(CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entered = manifest_by_name.cell(CELL)
    assert (entered["config"], entered["traffic"], entered["chips"]) \
        == (CONFIG, "serve_repoqa", 1)
    assert "overstated" in entered["why"]
    for m in ENTRIES:
        entry, cells = manifest_by_name.metric(m["name"])
        assert entry == m and CELL in cells
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tok_s", "setup_s"}
    accepted = {"prefill_chunk_ms.tok", "decode_step_ms.tok",
                "decode_occupancy.tok", "kv_pool_live_share.tok",
                "prefix_hit_rate.tok", "closed_ttft_p50_ms",
                "device_idle_share.tok", "ready_s", "hbm_in_use_share",
                "compiles_in_window", "moe_share.tok",
                "moe_gmm_roofline.tok"}
    books = {f"{base}.tok" for base in (
        "tick_ms", "host_ms_per_tick", "decode_launch_ms",
        "prefill_launch_ms", "host_gap_share", "programs_ahead_share",
        "ttft_queue_ms", "ttft_prefill_wait_ms", "ttft_prefill_ms",
        "idle_in_tick_share", "profiler_launch_stretch",
        "decode_device_ms", "prefill_device_ms", "fetch_found_ready_share")}
    line = manifest_by_name.line_of(CELL)
    assert line >= accepted | books | {m["name"] for m in ENTRIES}
    # one page size, one table: not this cell's (PERF.md section 7, c);
    # the kernel's share of busy time counts neither, and is
    assert "paged_kernel_share.tok" in line
    assert not line & {"paged_decode_roofline.tok",
                       "paged_prefill_roofline.tok", "kv_write_share.tok",
                       "decode_exposed_ms.tok"}
    assert manifest_by_name.carried_only_by(
        {m["name"] for m in ENTRIES}, CELL)
    for name in ("paged_window_decode_roofline.tok",
                 "paged_window_prefill_roofline.tok"):
        read, args = spec.metric_reader(name)
        assert read is window.read and args["kinds"] == ["paged_attention"]


def test_the_cell_rehearses_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 45), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=420,
        env=env, cwd=spec.ROOT, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert {"prefill_chunk_ms.tok", "decode_step_ms.tok",
            "prefix_hit_rate.tok", "compiles_in_window",
            "window_pool_pinned_share.tok"} <= names
    # device numbers are not taken from a CPU
    assert not names & {"device_idle_share.tok", "window_attn_share.tok",
                        "full_attn_share.tok",
                        "paged_window_decode_roofline.tok",
                        "paged_window_prefill_roofline.tok"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    notes = [l for l in r.stderr.splitlines() if "[bench] notes" in l][-1]
    check = json.loads(notes.split("notes: ", 1)[1])
    assert check["check"]["sample"]["prompt_len"] == 99
    assert check["check"]["errors"]["logits"] < 1e-4
    assert check["served_check"]["prefix_hit_blocks"][1] >= 5
    assert check["pool_audit"] == []
    assert set(check["programs"]) == {"prefill", "copy", "decode"}
