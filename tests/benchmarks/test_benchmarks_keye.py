"""The Keye configuration and its long-document cell: the file holds the
published numbers under their own keys, the traffic file the cell's
stated parameters, the new counting rules against shapes counted by
hand, the new readers on made-up observations, and the cell rehearsed
end to end on the CPU with the selection at work."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline_sparse, spec
from benchmarks.readers import sparse

CELL = "keye-vl-2.0-30b-a3b.serve_longdoc"
#: the catalog's row (model-configs guide), the language model's keys
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "moe_intermediate_size": 768, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000, "vocab_size": 151936,
    "decoder_sparse_step": 1}
SA_CONFIG = {"indexer_head_dim": 64, "indexer_num_heads": 16,
             "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
             "q_chunk_size": 512, "topk": 2048}


def test_the_file_holds_the_published_numbers_and_the_manifest_one_cut():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        assert cfg["published"][key] == value, key
        if key != "num_hidden_layers":
            assert cfg[key] == value, key      # as run: the same number
    assert cfg["num_hidden_layers"] == cell.depth == 6
    assert cfg["sa_config"] == cfg["published"]["sa_config"] == SA_CONFIG
    assert cfg["norm_topk_prob"] is True
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    for item in ("qk_norm", "indexer_k_norm", "indexer_rotary",
                 "indexer_weights", "indexer_query", "chunk_sizes", "mrope"):
        assert item in cfg["assumed"], item
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]) \
        == (2048, 32, 4, 128)
    assert (kw["n_experts"], kw["experts_per_token"], kw["expert_width"]) \
        == (128, 8, 768)
    assert (kw["index_heads"], kw["index_dim"], kw["index_topk"]) \
        == (16, 64, 2048)
    assert kw["vocab_size"] == 151936 and kw["qk_norm"] is True
    hp = dict(cell.reference_hp())
    assert hp["topk"] == 2048 and hp["num_experts_per_tok"] == 8
    # the rehearsal's documents (64-128 tokens) reach past its topk
    small = spec.load_cell(CELL, rehearse=True)
    assert small.model_kwargs()["index_topk"] == 32 \
        < min(small.params["doc_lengths"])


def test_the_traffic_file_holds_the_cells_parameters():
    p = spec.load_cell(CELL).params
    assert p["kind"] == "closed_loop" and p["n_layers"] == 6
    assert (p["clients"], p["client_threads"], p["doc_stride"]) == (8, 8, 3)
    assert p["doc_lengths"] == [8192, 12288, 16384, 20480, 24576, 28672,
                                10240, 18432]
    assert p["answer_lengths"] == [64, 96, 128, 160, 192, 80, 112, 144]
    assert (p["questions_per_doc"], p["question_len"]) == (4, 64)
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["max_new_tokens"]) == (8, 32768, 2048, 192)
    # 15,360 pages of 16 besides the reserved one: 245,760 tokens
    assert (e["num_kv_blocks"] - 1) * e["kv_block_size"] == 245760
    # the check's sample reaches past topk
    from benchmarks import traffic
    assert traffic.check_sample(e)["prompt_len"] == 3075 > 2048
    # the longest request fits the window
    assert max(p["doc_lengths"]) + p["question_len"] \
        + max(p["answer_lengths"]) < e["max_seq_len"]


@pytest.mark.parametrize("first,n,topk,visible,attended", [
    (0, 4, 8, 1 + 2 + 3 + 4, 1 + 2 + 3 + 4),         # all below topk
    (0, 4, 2, 10, 1 + 2 + 2 + 2),                    # crosses it
    (6, 3, 5, 7 + 8 + 9, 15),                        # all above
    (2047, 2, 2048, 2048 + 2049, 2 * 2048),
])
def test_visible_and_attended_by_hand(first, n, topk, visible, attended):
    assert roofline_sparse.visible_and_attended(first, n, topk) \
        == (visible, attended)


def test_grouped_product_counts_by_hand():
    # 8 tokens to 2 experts each, gated experts 64 -> 16 -> 64, bf16:
    # 16 rows through three 64x16 matrices
    flops, nbytes = roofline_sparse.moe_grouped(8, 64, 16, 2)
    assert flops == 2 * 3 * 16 * 64 * 16
    assert nbytes == 2 * 3 * 64 * 16 * 2 + 16 * 2 * (2 * 64 + 3 * 16)
    # the cell's decode step: 8 slots x 8 experts of 2048 x 768
    flops, nbytes = roofline_sparse.moe_grouped(8, 2048, 768, 8)
    assert flops == 6 * 64 * 2048 * 768
    assert nbytes == 8 * 3 * 2048 * 768 * 2 + 64 * 2 * (4096 + 2304)


def _obs():
    req = {"due": 1.0, "tokens": [1.5, 1.6, 1.7], "prompt_len": 40,
           "shared": 35}
    return {
        "window_s": 10.0, "requests": [req, dict(req, tokens=[], due=2.0),
                                       dict(req, due=11.0)],
        # with the configuration's ``program`` widths, as
        # ``serve_cell.observed_model`` carries them
        "model": {"kv_block_size": 16, "prefill_chunk": 64, "n_layers": 2,
                  "itemsize": 2, "d_model": 64, "expert_width": 16,
                  "experts_per_token": 2},
        "engine_config": {"decode_slots": 4},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {"chips": 1, "busy_s": 2.0,
                  "engine": {"prefill_chunks": 3, "decode_steps": 10},
                  "by_module_kind": {"jit__decode_fn|ragged-dot-none": 0.25,
                                     "jit__prefill_fn|ragged-dot-none": 0.25,
                                     "jit__decode_fn|sort": 0.1,
                                     "jit__decode_fn|fusion": 1.0}}}


def test_the_readers_on_made_up_observations():
    obs = _obs()
    # one request counts: 32 tokens cached (two whole pages), positions
    # 32..39 prefilled and 40, 41 decoded, topk 36
    visible = sum(p + 1 for p in range(32, 42))
    attended = sum(min(p + 1, 36) for p in range(32, 42))
    assert sparse.read(obs, "select_share", topk=36) \
        == pytest.approx(100.0 * attended / visible)
    assert sparse.read(obs, "kernel_share", kinds=["ragged-dot-none"]) \
        == pytest.approx(25.0)
    assert sparse.read(obs, "kernel_share", kinds=["sort"]) \
        == pytest.approx(5.0)
    from benchmarks import roofline
    least = sum(calls * 2 * roofline.min_seconds(
        *roofline_sparse.moe_grouped(tokens, 64, 16, 2), "TPU v5 lite")
        for calls, tokens in ((3, 64), (10, 4)))
    # the widths are the observation's own (its configuration's)
    assert sparse.read(obs, "moe_roofline", kinds=["ragged-dot-none"]) \
        == pytest.approx(100.0 * least / 0.5)
    # a program without these kernels (the parent), a rehearsal, no trace:
    # nothing to read, and no error
    assert sparse.read(obs, "kernel_share", kinds=["no-such-kernel"]) is None
    assert sparse.read(dict(obs, device={"platform": "cpu"}),
                       "kernel_share", kinds=["sort"]) is None
    assert sparse.read(dict(obs, trace=None), "moe_roofline",
                       kinds=["ragged-dot-none"]) is None
    assert sparse.read({}, "select_share", topk=8) is None


#: what PR 39 lets every closed-loop cell read of the engine's own books
#: (and, traced, of its annotations), and PR 41's timing of its programs
#: (listed for this cell by PR 44)
BOOKS = {f"{base}.tok" for base in (
    "tick_ms", "host_ms_per_tick", "decode_launch_ms", "prefill_launch_ms",
    "host_gap_share", "programs_ahead_share", "ttft_queue_ms",
    "ttft_prefill_wait_ms", "ttft_prefill_ms", "idle_in_tick_share",
    "profiler_launch_stretch", "decode_device_ms", "prefill_device_ms",
    "fetch_found_ready_share")}
#: the per-layer entries the new readers are for (entered by PR 35), as
#: written but for their ``workloads``, which hold this cell
ENTRIES = [{"name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "serve_tok_s"}
           for name, better, source, layer in (
    ("sparse_select_share.tok", "lower", "host_clock",
     "kernels, sparse attention"),
    ("topk_sort_share.tok", "lower", "device_trace",
     "kernels, sparse attention"),
    ("moe_share.tok", "lower", "device_trace", "kernels, experts"),
    ("moe_gmm_roofline.tok", "higher", "device_trace", "kernels, experts"))]


def test_the_cell_rehearses_with_the_selection_at_work():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "1",
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
    assert "device_idle_share.tok" not in names
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    notes = [l for l in r.stderr.splitlines() if "[bench] notes" in l][-1]
    check = json.loads(notes.split("notes: ", 1)[1])
    # documents of 64-128 tokens under the rehearsal's topk 32, and a
    # check sample of 99
    assert check["check"]["sample"]["prompt_len"] == 99
    assert check["check"]["errors"]["logits"] < 1e-4
    assert check["served_check"]["prefix_hit_blocks"][1] >= 5
    assert check["pool_audit"] == []


def test_the_manifest_holds_the_cell_and_the_new_entries_read():
    """The cell reports the accepted metrics that mean the same thing
    here, each moving ``serve_tok_s``; the four new entries resolve to
    the new reader by their files' names and print beside them."""
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tok_s", "setup_s"}
    # the line holds at least these: a later PR may list the cell in more
    assert manifest_by_name.line_of(CELL) >= {
        "prefill_chunk_ms.tok", "decode_step_ms.tok", "decode_occupancy.tok",
        "kv_pool_live_share.tok", "prefix_hit_rate.tok",
        "closed_ttft_p50_ms", "device_idle_share.tok", "ready_s",
        "hbm_in_use_share", "compiles_in_window"} | {
        m["name"] for m in ENTRIES} | BOOKS | {"sparse_attn_share.tok"}
    # entered by PR 35, each found by name with its fields as they were
    # written here, and this cell among those it lists
    for m in ENTRIES:
        entry, cells = manifest_by_name.metric(m["name"])
        assert entry == m and CELL in cells
    # what reads the selection means nothing in a cell without one: no
    # other cell's line carries it (the experts' two entries may be
    # carried by any routed cell)
    assert manifest_by_name.carried_only_by(
        {"sparse_select_share.tok", "topk_sort_share.tok",
         "sparse_attn_share.tok"}, CELL)
    for m in ENTRIES:
        read, args = spec.metric_reader(m["name"])
        assert read is sparse.read and args["what"]
    obs = _obs()
    line = spec.read_metrics(ENTRIES, obs)
    assert set(line) == {m["name"] for m in ENTRIES}
    assert all(v["value"] > 0.0 and v["unit"] == "%" for v in line.values())
    assert line["moe_share.tok"]["value"] == pytest.approx(25.0)
    # on the parent's trace (none of these kernels) only the share that
    # positions give is read, and nothing raises
    obs["trace"]["by_module_kind"] = {"jit__decode_fn|fusion": 1.0}
    assert set(spec.read_metrics(ENTRIES, obs)) == {"sparse_select_share.tok"}


def test_the_experts_roofline_reads_what_the_files_widths_gave_to_the_digit():
    """``moe_gmm_roofline.tok`` took its three widths from this
    configuration's file by name until PR 44; it takes them from
    ``obs["model"]``, which carries the file's ``program`` group whole:
    on this cell's own sizes the same number to the last digit, and no
    configuration named in the metric's file, so a second routed
    configuration may list its cell."""
    from benchmarks import roofline, serve_cell
    cell = spec.load_cell(CELL)
    engine, prog = cell.params["engine"], cell.config["program"]
    model = dict(cell.model_kwargs(), max_seq_len=engine["max_seq_len"])
    obs = dict(_obs(), engine_config=dict(engine),
               model=serve_cell.observed_model(prog, model, engine))
    least = 0.0
    for calls, tokens in ((3, engine["prefill_chunk"]),
                          (10, engine["decode_slots"])):
        flops, nbytes = roofline_sparse.moe_grouped(
            tokens, prog["d_model"], prog["expert_width"],
            prog["experts_per_token"], 2)
        least += calls * cell.depth * roofline.min_seconds(
            flops, nbytes, "TPU v5 lite")
    read, args = spec.metric_reader("moe_gmm_roofline.tok")
    assert "config" not in args
    assert read(obs, **args) == 100.0 * least / 0.5
