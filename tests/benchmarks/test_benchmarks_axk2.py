"""The A.X-K2 configuration and its long-document cell: the file holds
the published numbers under their own keys and states its cuts, the
traffic file the cell's stated parameters, the counting rules of the
selected work against numbers worked by hand, the reader on made-up
observations (and silent where the program has nothing for it, as the
parent), the manifest's configuration, cell and entries found by name,
and the cell rehearsed end to end on the CPU."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_sparse_latent, spec
from benchmarks.readers import device_trace, sparse_latent

CONFIG = "a.x-k2"
CELL = CONFIG + ".serve_longdoc64"
WIDTHS = {"n_heads": 64, "kv_lora_rank": 512, "qk_rope_dim": 64,
          "index_heads": 64, "index_dim": 128}
SCOPES = ["layer/attn/indexer", "layer/attn/indexer_scores",
          "layer/attn/select", "layer/attn/sparse_latent_attn"]
#: as written but for their ``workloads``, which hold this cell
ENTRIES = [{"name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": "kernels, sparse attention",
            "moves": "serve_tok_s"}
           for name, better in (
    ("sparse_latent_attn_share.tok", "lower"),
    ("sparse_latent_decode_roofline.tok", "higher"),
    ("sparse_latent_prefill_roofline.tok", "higher"))]


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "A.X-K2"][0]


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cuts():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/skt/A.X-K2/blob/main/config.json"
    published = cfg["published"]
    row = _catalog_row()
    if row is not None:                  # the catalog's own numbers
        assert published == row["config"]
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key      # as published, groups whole
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (61, 256, 163840)
    assert cfg["num_hidden_layers"] == cell.depth == 5
    assert cfg["n_routed_experts"] == 16
    assert cfg["vocab_size"] == cfg["program"]["vocab_size"] == 20480
    assert (cfg["held"]["vocab_rows"]["published"],
            cfg["held"]["vocab_rows"]["here"]) == (163840, 20480)
    assert "16 chips share each layer" in cfg["deployment"]
    for item in ("gated_norm", "head_gate", "indexer_k_norm",
                 "indexer_rotary", "indexer_weights", "indexer_query",
                 "mscale", "rotary_layout", "router", "weights"):
        assert item in cfg["assumed"], item
    assert "normal(0, 0.01)" in cfg["assumed"]["weights"]
    assert "refuses" in cfg["departures"]["training"]
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["head_dim"], kw["d_ff"]) \
        == (7168, 64, 192, 18432)
    assert (kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_nope_dim"],
            kw["qk_rope_dim"], kw["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (kw["index_topk"], kw["index_heads"], kw["index_dim"],
            kw["index_q_lora"]) == (2048, 64, 128, True)
    assert (kw["n_experts"], kw["experts_per_token"], kw["expert_width"],
            kw["shared_expert_width"], kw["experts_held"],
            kw["expert_first"]) == (256, 8, 2048, 2048, 16, 0)
    assert (kw["n_group"], kw["topk_group"], kw["router_bias"],
            kw["router_score"], kw["routed_scale"]) \
        == (8, 4, True, "sigmoid", 2.5)
    assert (kw["head_gate"], kw["gated_norm_rank"], kw["n_dense_layers"],
            kw["norm_eps"], kw["rope_base"]) == (True, 16, 1, 1e-6, 1e6)
    assert kw["rope_yarn"] == [2.0, 131072, 32.0, 1.0, 1.0]
    assert kw["rope_softmax_scale"] == pytest.approx(1.1434, abs=1e-4)
    assert kw["vocab_size"] == 20480 and kw["n_layers"] == 5
    hp = dict(cell.reference_hp())
    assert (hp["expert_first"], hp["experts_held"], hp["index_topk"],
            hp["n_group"], hp["topk_group"]) == (0, 16, 2048, 8, 4)
    assert "control" not in hp
    assert cfg["reference"] == "axk2"
    assert set(cfg["tolerance"]) == {"logits", "why"}
    assert "NOT told apart" in cfg["tolerance"]["why"]
    # the rehearsal keeps every form, at a narrow width, past its top-k
    small = spec.load_cell(CELL, rehearse=True)
    kw = small.model_kwargs()
    assert kw["kv_lora_rank"] and kw["index_q_lora"] and kw["head_gate"] \
        and kw["gated_norm_rank"] and kw["router_bias"] and kw["n_group"] \
        and kw["n_dense_layers"] == 1 < kw["n_layers"] \
        and kw["experts_held"] < kw["n_experts"]
    from benchmarks import traffic
    assert kw["index_topk"] == 32 \
        < traffic.check_sample(small.params["engine"])["prompt_len"]


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "axk2.py")).read()
    assert "ray_tpu" not in src.split('"""', 2)[2]
    assert "from .common import F32, make_api" in src
    from benchmarks.reference import axk2
    cfg = spec.load_cell(CELL).config
    # every assumed mechanism is a departure the reference states
    assert {"gated_norm", "head_gate", "indexer_k_norm", "indexer_rotary",
            "indexer_weights", "indexer_query", "mscale", "rotary",
            "router_bias"} <= set(axk2.departures)
    assert set(axk2.departures) - {"rotary", "router_bias"} \
        <= set(cfg["assumed"])


def test_the_traffic_file_holds_the_cells_parameters():
    p = spec.load_cell(CELL).params
    assert p["kind"] == "closed_loop" and p["n_layers"] == 5
    assert (p["clients"], p["client_threads"]) == (12, 12)
    assert p["doc_lengths"] == [16384, 24576, 32768, 40960, 49152, 57344,
                                20480, 28672, 36864, 45056, 53248, 18432]
    assert p["answer_lengths"] == [128, 192, 256, 320, 384, 448, 512, 160,
                                   224, 288]
    assert sum(p["doc_lengths"]) / 12 == pytest.approx(35328)
    assert sum(p["answer_lengths"]) / 10 == pytest.approx(291.2)
    assert (p["questions_per_doc"], p["question_len"]) == (5, 64)
    assert p["trace_seconds"] == 6 and "warm_seconds" in p["why"]
    # twelve clients start on twelve different lengths
    starts = {(c * p["doc_stride"]) % len(p["doc_lengths"])
              for c in range(p["clients"])}
    assert len(starts) == 12
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["kv_block_size"], e["max_new_tokens"]) \
        == (12, 65536, 2048, 16, 512)
    # not fewer than ISSUE 48's 40,960 pages and the trash page, in
    # steps of 2,048: a page is 16 x (640 + 128) x 2 B in five layers
    assert e["num_kv_blocks"] >= 40961
    assert (e["num_kv_blocks"] - 1) % 2048 == 0
    assert 16 * (640 + 128) * 2 * 5 == 122880
    longest = max(p["doc_lengths"]) + p["question_len"] \
        + max(p["answer_lengths"])
    assert longest < e["max_seq_len"]
    # the mean working set of twelve sequences fits, with room for the
    # trie's cached documents
    mean = sum(p["doc_lengths"]) / 12 + p["question_len"] + 291
    assert p["clients"] * -(-mean // 16) < 0.7 * e["num_kv_blocks"]
    from benchmarks import traffic
    sample = traffic.check_sample(e)
    assert sample["prompt_len"] == 3075 > 2048       # past the top-k


def test_the_selected_work_by_hand():
    cost = roofline_sparse_latent.pair_costs(WIDTHS)
    # a scored pair: 64 heads x a 128-wide dot; an index key 128 x 2 B
    assert cost["scored"] == (16384.0, 256.0)
    # an attended pair: 64 heads x (576-wide dot + 512-wide value row) x 2
    assert cost["attended"] == (139264.0, 1152.0)
    # a decode step of one sequence at 30,000 keys, five layers: every
    # key scored, 2048 attended, each read by its one query
    flops, nbytes = roofline_sparse_latent.selected_work(
        5 * 30000, 5 * 2048, 1, WIDTHS)
    assert flops == 5 * (30000 * 16384 + 2048 * 139264)
    assert nbytes == 5 * (30000 * 256 + 2048 * 1152)
    # the bytes bind: 50.2 MB at 819 GB/s is 61 us, the FLOPs 20 us
    least = roofline.min_seconds(flops, nbytes, "TPU v5 lite")
    assert least == pytest.approx(nbytes / 819e9)
    assert least == pytest.approx(61.3e-6, rel=0.01)
    # a chunk of 2048 queries behind 28,672 keys, one layer: query p
    # scores p + 1 keys and attends 2048; a key's bytes serve up to 2048
    # queries of the program
    scored = sum(range(28673, 28673 + 2048))
    flops, nbytes = roofline_sparse_latent.selected_work(
        scored, 2048 * 2048, 2048, WIDTHS)
    assert flops == scored * 16384 + 2048 * 2048 * 139264
    assert nbytes == (scored * 256 + 2048 * 2048 * 1152) / 2048
    # the FLOPs bind by far: 1.58 TFLOP is 8.0 ms, the bytes 12 us
    assert roofline.min_seconds(flops, nbytes, "TPU v5 lite") \
        == pytest.approx(flops / 197e12)
    assert flops / 197e12 == pytest.approx(8.0e-3, rel=0.01)
    assert roofline_sparse_latent.selected_work(0, 0, 1, WIDTHS) \
        == (0.0, 0.0)


def _obs():
    ops = {
        "fusion.1": {"module": "jit__decode_fn", "seconds": 0.25,
                     "scope": "layer/attn/indexer_scores"},
        "sort.2": {"module": "jit__decode_fn", "seconds": 0.125,
                   "scope": "layer/attn/select"},
        "fusion.3": {"module": "jit__decode_fn", "seconds": 0.125,
                     "scope": "layer/attn/sparse_latent_attn"},
        "fusion.4": {"module": "jit__decode_fn", "seconds": 1.0,
                     "scope": "layer/moe"},
        "fusion.5": {"module": "jit__prefill_fn", "seconds": 1.5,
                     "scope": "layer/attn/sparse_latent_attn/mla_attn"},
        "fusion.6": {"module": "jit__prefill_fn", "seconds": 0.5,
                     "scope": "layer/attn/indexer"},
        "fusion.7": {"module": "jit__prefill_fn", "seconds": 0.75,
                     "scope": "layer/attn/selected"},
    }
    return {
        "model": dict(WIDTHS, n_layers=5, prefill_chunk=2048, itemsize=2,
                      kv_block_size=16),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {"chips": 1, "busy_s": 5.0, "window_s": 6.0,
                  "op_calls": ops,
                  "by_scope": {"layer/attn/indexer": 0.5,
                               "layer/attn/indexer_scores": 0.25,
                               "layer/attn/select": 0.125,
                               "layer/attn/sparse_latent_attn": 1.625,
                               "layer/attn": 3.25},
                  "engine": {
                      "indexer_keys_scored_decode_total": 5 * 12 * 30000,
                      "keys_attended_decode_total": 12 * 2048,
                      "indexer_keys_scored_prefill_total": 5 * 60_000_000,
                      "keys_attended_prefill_total": 4_000_000}}}


def test_the_readers_on_made_up_observations():
    obs = _obs()
    kind = "TPU v5 lite"
    assert device_trace.read(obs, "scope_share", scopes=SCOPES) \
        == pytest.approx(100.0 * 2.5 / 5.0)
    # decode: the ops of decode programs under the four scopes, 0.5 s
    least = roofline.min_seconds(*roofline_sparse_latent.selected_work(
        5 * 12 * 30000, 5 * 12 * 2048, 1, WIDTHS), kind)
    assert sparse_latent.read(obs, "decode_roofline", module="decode",
                              scopes=SCOPES) \
        == pytest.approx(100.0 * least / 0.5)
    # prefill: 2.0 s (a scope that only starts like one is not counted)
    least = roofline.min_seconds(*roofline_sparse_latent.selected_work(
        5 * 60_000_000, 5 * 4_000_000, 2048, WIDTHS), kind)
    assert sparse_latent.read(obs, "prefill_roofline", module="prefill",
                              scopes=SCOPES) \
        == pytest.approx(100.0 * least / 2.0)
    # a program without the by-kind counters or the scopes (the parent),
    # a rehearsal, no trace: nothing to read, and no error
    old = _obs()
    old["trace"]["engine"] = {"indexer_keys_scored_total": 7}
    bare = _obs()
    bare["trace"]["op_calls"] = {"fusion.4": bare["trace"]["op_calls"][
        "fusion.4"]}
    for o in (old, bare, dict(obs, trace=None),
              dict(obs, device={"platform": "cpu", "kind": "cpu"})):
        for what, module in (("decode_roofline", "decode"),
                             ("prefill_roofline", "prefill")):
            assert sparse_latent.read(o, what, module=module,
                                      scopes=SCOPES) is None
    with pytest.raises(ValueError, match="unknown quantity"):
        sparse_latent.read(obs, "no_such", module="decode", scopes=SCOPES)


def test_the_manifest_holds_the_configuration_the_cell_and_its_entries():
    """One configuration, one cell and three per-layer metrics, each
    found by its name and as it was written; the accepted ``.tok``
    metrics that read this cell rightly list it; no other cell's line
    carries the three."""
    config = manifest_by_name.configuration(CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entered = manifest_by_name.cell(CELL)
    assert (entered["config"], entered["traffic"], entered["chips"]) \
        == (CONFIG, "serve_longdoc64", 1)
    assert CELL in manifest_by_name.metric("serve_tok_s")[1]
    for m in ENTRIES:
        entry, cells = manifest_by_name.metric(m["name"])
        assert entry == m and CELL in cells
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tok_s", "setup_s"}
    accepted = {f"{base}.tok" for base in (
        "prefill_chunk_ms", "decode_step_ms", "decode_occupancy",
        "kv_pool_live_share", "prefix_hit_rate", "device_idle_share",
        "moe_share", "decode_device_ms", "prefill_device_ms", "fetch_found_ready_share",
        "tick_ms", "host_ms_per_tick", "decode_launch_ms",
        "prefill_launch_ms", "host_gap_share", "programs_ahead_share",
        "ttft_queue_ms", "ttft_prefill_wait_ms", "ttft_prefill_ms",
        "idle_in_tick_share", "profiler_launch_stretch")} | {
        "closed_ttft_p50_ms", "ready_s", "hbm_in_use_share",
        "compiles_in_window"}
    line = manifest_by_name.line_of(CELL)
    assert line >= accepted | {m["name"] for m in ENTRIES}
    # left out, and why (PERF.md section 4): they name openPangu's file
    # or kernel, count every assignment over every layer, read scopes
    # this path does not have, or are held to Keye's cell alone by its
    # accepted test (sparse_select_share.tok, topk_sort_share.tok)
    assert not line & {"latent_decode_roofline.tok", "moe_held_roofline.tok",
                       "latent_prefill_roofline.tok", "latent_attn_share.tok",
                       "moe_gmm_roofline.tok", "sparse_attn_share.tok",
                       "paged_kernel_share.tok"}
    assert manifest_by_name.carried_only_by(
        {m["name"] for m in ENTRIES}, CELL)
    read, args = spec.metric_reader("sparse_latent_attn_share.tok")
    assert read is device_trace.read and args["scopes"] == SCOPES
    for kind in ("decode", "prefill"):
        read, args = spec.metric_reader(f"sparse_latent_{kind}_roofline.tok")
        assert read is sparse_latent.read
        assert args == {"what": f"{kind}_roofline", "module": kind,
                        "scopes": SCOPES}
    got = spec.read_metrics(ENTRIES, _obs())
    assert set(got) == {m["name"] for m in ENTRIES}
    assert got["sparse_latent_attn_share.tok"]["value"] \
        == pytest.approx(50.0)
    assert all(0 < v["value"] < 100 for v in got.values())


def test_the_cell_rehearses_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 48), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=420,
        env=env, cwd=spec.ROOT, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert {"prefill_chunk_ms.tok", "decode_step_ms.tok",
            "prefix_hit_rate.tok", "compiles_in_window"} <= names
    # device numbers are not taken from a CPU
    assert not names & {"device_idle_share.tok",
                        "sparse_latent_attn_share.tok",
                        "sparse_latent_decode_roofline.tok",
                        "sparse_latent_prefill_roofline.tok"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    notes = [l for l in r.stderr.splitlines() if "[bench] notes" in l][-1]
    check = json.loads(notes.split("notes: ", 1)[1])
    # a sample past one chunk and past the rehearsal's top-k of 32:
    # prefill then decode through the two-pool cache
    assert check["check"]["sample"]["prompt_len"] == 99
    assert check["check"]["errors"]["logits"] < 1e-4
    assert check["served_check"]["prefix_hit_blocks"][1] >= 5
    assert check["pool_audit"] == []
