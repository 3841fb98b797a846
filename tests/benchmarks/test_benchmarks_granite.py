"""The Granite-4.0-H-Small configuration and its sessions cell: the file
holds the published numbers under their own keys and states its cuts,
the traffic file the cell's stated parameters, the counting rules of the
recurrence against numbers worked by hand, the reader on made-up
observations (and silent where the program has nothing for it, as the
parent), the manifest's configuration, cell and entries found by name,
the reference apart from the program, and the cell rehearsed end to end
on the CPU."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_ssm, spec
from benchmarks.readers import device_trace, ssm

CONFIG = "granite-4.0-h-small"
CELL = CONFIG + ".serve_sessions64"
PERIOD = ["mamba"] * 5 + ["full"] + ["mamba"] * 4
WIDTHS = {"ssm_heads": 128, "ssm_head_dim": 64, "ssm_state": 128,
          "ssm_conv": 4, "ssm_chunk": 256, "layer_pattern": PERIOD}
#: as written but for their ``workloads``, which hold this cell
ENTRIES = [{"name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": "kernels, state-space scan",
            "moves": "serve_tok_s"}
           for name, better in (
    ("ssm_share.tok", "lower"),
    ("ssm_scan_decode_roofline.tok", "higher"),
    ("ssm_scan_prefill_roofline.tok", "higher"))]


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == CONFIG][0]


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cuts():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    published = cfg["published"]
    row = _catalog_row()
    if row is not None:                  # the catalog's own numbers
        assert published == row["config"]
        assert entry["source"] == row["source_url"]
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key      # as published, groups whole
    assert (published["num_hidden_layers"], published["num_local_experts"],
            published["vocab_size"]) == (40, 72, 100352)
    assert cfg["num_hidden_layers"] == cell.depth == 10
    assert cfg["num_local_experts"] == 36
    assert cfg["vocab_size"] == cfg["program"]["vocab_size"] == 50176
    assert (cfg["held"]["vocab_rows"]["published"],
            cfg["held"]["vocab_rows"]["here"]) == (100352, 50176)
    assert (cfg["held"]["num_local_experts"]["published"],
            cfg["held"]["num_local_experts"]["here"]) == (72, 36)
    assert "two share each layer" in cfg["deployment"]
    # the ten layers run are one whole period of the published forty
    types = published["layer_types"]
    assert len(types) == 40 and types == types[:10] * 4
    assert [{"attention": "full"}.get(t, t) for t in types[:10]] == PERIOD
    for item in ("state_dtype", "conv_tail", "in_proj_layout", "dt",
                 "gated_norm", "expert_width", "router", "attention",
                 "weights"):
        assert item in cfg["assumed"], item
    assert "float32" in cfg["assumed"]["state_dtype"]
    assert "refuse" in cfg["departures"]["training"]
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]) \
        == (4096, 32, 8, 128)
    assert kw["layer_pattern"] == PERIOD and kw["rotary_dim"] == 0
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_conv"], kw["ssm_chunk"]) == (128, 64, 128, 4, 256)
    assert kw["ssm_heads"] * kw["ssm_head_dim"] \
        == published["mamba_expand"] * published["hidden_size"]
    assert (kw["attn_scale"], kw["embed_scale"], kw["residual_scale"],
            kw["logit_scale"], kw["tie_embeddings"]) \
        == (0.0078125, 12.0, 0.22, 1 / 16, True)
    assert (kw["n_experts"], kw["experts_per_token"], kw["expert_width"],
            kw["shared_expert_width"], kw["experts_held"],
            kw["expert_first"], kw["router_score"]) \
        == (72, 10, 768, 1536, 36, 0, "softmax")
    assert kw["norm_eps"] == 1e-5 and kw["vocab_size"] == 50176 \
        and kw["n_layers"] == 10
    # all four pins set, so that no measured run tunes
    assert all(cfg["blocks"][k] > 0 for k in (
        "attn_block_q", "attn_block_k", "paged_block_r",
        "paged_block_r_prefill"))
    hp = dict(cell.reference_hp())
    assert (hp["expert_first"], hp["experts_held"],
            hp["num_experts_per_tok"]) == (0, 36, 10)
    assert hp["layer_types"].split(",") == types[:10]
    assert (hp["attention_multiplier"], hp["embedding_multiplier"],
            hp["residual_multiplier"], hp["logits_scaling"]) \
        == (0.0078125, 12.0, 0.22, 16.0)
    assert "control" not in hp and "logits_from" not in hp
    assert cfg["reference"] == "granite"
    assert set(cfg["tolerance"]) == {"logits", "why"}
    assert set(cfg["weights"]) == {"stream_scale", "residual_writers", "why"}
    # the rehearsal keeps every form, at a narrow width
    kw = spec.load_cell(CELL, rehearse=True).model_kwargs()
    assert kw["layer_pattern"] == PERIOD and kw["tie_embeddings"] \
        and kw["experts_held"] < kw["n_experts"] and kw["n_layers"] == 6 \
        and kw["ssm_heads"] * kw["ssm_head_dim"] == 2 * kw["d_model"]


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "granite.py")).read()
    code = src.split('"""', 2)[2]
    assert "ray_tpu" not in code
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import jax", "import jax.numpy as jnp",
                       "from .common import F32, make_api"]
    # token by token, not the blocked form
    assert "jax.lax.scan(" in code and "mamba_chunk_size" not in code
    from benchmarks.reference import granite
    assert {"no_embedding_multiplier", "no_residual_multiplier",
            "no_logits_scaling", "rotary", "sqrt_scale"} \
        <= set(granite.CONTROLS)


def test_the_traffic_file_holds_the_cells_parameters():
    p = spec.load_cell(CELL).params
    assert p["kind"] == "closed_loop" and p["n_layers"] == 10
    assert (p["clients"], p["client_threads"]) == (64, 64)
    assert p["doc_lengths"] == [128, 192, 256, 384, 512, 640, 768, 896,
                                1024, 1280, 1536, 2048, 2560, 3072, 4096,
                                6144]
    assert p["answer_lengths"] == [48, 64, 96, 128, 160, 192, 256, 320,
                                   384, 448, 512]
    docs = sorted(p["doc_lengths"])
    assert (docs[7] + docs[8]) / 2 == 960                   # the median
    assert sum(docs) / 16 == pytest.approx(1596)
    assert sum(p["answer_lengths"]) / 11 == pytest.approx(237.1, abs=0.1)
    # every request a fresh prompt: nothing for a prefix cache to serve
    assert (p["questions_per_doc"], p["question_len"], p["doc_stride"]) \
        == (1, 32, 5)
    assert (p["trace_seconds"], p["drain_seconds"]) == (6, 20)
    assert "warm_seconds" in p["why"]
    # sixty-four clients start on all sixteen lengths, four on each
    starts = [(c * p["doc_stride"]) % 16 for c in range(64)]
    assert all(starts.count(i) == 4 for i in range(16))
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["kv_block_size"], e["max_new_tokens"]) \
        == (64, 8192, 1024, 16, 512)
    assert e["enable_prefix_sharing"] is False
    # four scan blocks a call: the state is carried inside a call and
    # across calls
    assert e["prefill_chunk"] == 4 * WIDTHS["ssm_chunk"]
    # not fewer than ISSUE 52's 16,384 pages and the trash page, in steps
    # of 2,048, and no more than the auto size
    assert 16385 <= e["num_kv_blocks"] <= 1 + 64 * 8192 // 16
    assert (e["num_kv_blocks"] - 1) % 2048 == 0
    longest = max(docs) + p["question_len"] + max(p["answer_lengths"])
    assert longest < e["max_seq_len"]
    # sixty-four of the longest sequences fit at once
    assert 64 * -(-longest // 16) < e["num_kv_blocks"]
    from benchmarks import traffic
    assert traffic.check_sample(e)["prompt_len"] == 1539
    r = spec.load_cell(CELL, rehearse=True).params
    assert r["engine"]["enable_prefix_sharing"] is False


def test_the_counting_rule_by_hand():
    # a sequence and layer: 128 x 64 x 128 float32 = 4 MiB, and three
    # rows of 8192 + 2 x 128 bf16
    assert roofline_ssm.state_bytes(WIDTHS) == 4 * 2**20 + 3 * 8448 * 2 \
        == 4244992
    assert roofline_ssm.mamba_layers(WIDTHS, 10) == 9
    assert roofline_ssm.mamba_layers(WIDTHS, 40) == 36
    assert roofline_ssm.mamba_layers(WIDTHS, 5) == 5
    # a decode step of 64 live rows in nine layers: each reads and
    # writes its state once, 5 FLOP a state element
    flops, nbytes = roofline_ssm.scan_decode(64 * 9, WIDTHS)
    assert flops == 64 * 9 * 5 * 2**20
    assert nbytes == 64 * 9 * 2 * 4244992
    # the bytes bind: 4.89 GB at 819 GB/s is 5.97 ms, the FLOPs 15 us
    least = roofline.min_seconds(flops, nbytes, "TPU v5 lite")
    assert least == pytest.approx(nbytes / 819e9)
    assert least == pytest.approx(5.97e-3, rel=0.01)
    # a whole chunk of 1,024 tokens, one sequence, one layer: a head
    # 2 x 256 x 64 in its block and 2 x 2 x 64 x 128 through the state,
    # the group's C B^T 2 x 256 x 128 once
    per_token = 128 * (2 * 256 * 64 + 4 * 64 * 128) + 2 * 256 * 128
    assert per_token == 8454144
    flops, nbytes = roofline_ssm.scan_prefill(1024, 1, WIDTHS)
    assert flops == 1024 * per_token
    assert nbytes == 1024 * (8448 + 128 + 8192) * 2 + 2 * 4244992
    # 8.66 GFLOP is 44 us, 42.8 MB is 52 us: the bytes bind, just
    assert roofline.min_seconds(flops, nbytes, "TPU v5 lite") \
        == pytest.approx(nbytes / 819e9)
    assert nbytes / 819e9 == pytest.approx(52.3e-6, rel=0.01)
    assert roofline_ssm.scan_decode(0, WIDTHS) == (0.0, 0.0)
    assert roofline_ssm.scan_prefill(0, 0, WIDTHS) == (0.0, 0.0)


def _obs():
    ops = {
        "fusion.1": {"module": "jit__decode_fn", "seconds": 0.5,
                     "scope": "layer/ssm/ssm_scan"},
        "fusion.2": {"module": "jit__decode_fn", "seconds": 0.25,
                     "scope": "layer/ssm/ssm_conv"},
        "fusion.3": {"module": "jit__decode_fn", "seconds": 1.0,
                     "scope": "layer/ssm/ssm_in_proj"},
        "fusion.4": {"module": "jit__decode_fn", "seconds": 1.0,
                     "scope": "layer/moe"},
        "fusion.5": {"module": "jit__prefill_fn", "seconds": 0.125,
                     "scope": "layer/ssm/ssm_scan/while"},
        "fusion.6": {"module": "jit__prefill_fn", "seconds": 0.125,
                     "scope": "layer/ssm/ssm_conv"},
        "fusion.7": {"module": "jit__prefill_fn", "seconds": 0.75,
                     "scope": "layer/ssm/ssm_scanner"},
    }
    return {
        "model": dict(WIDTHS, n_layers=10, prefill_chunk=1024, itemsize=2,
                      kv_block_size=16),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {"chips": 1, "busy_s": 5.0, "window_s": 6.0,
                  "op_calls": ops,
                  "by_scope": {"layer/ssm": 3.0, "layer/ssm/ssm_scan": 0.625,
                               "layer/moe": 1.0},
                  "engine": {"ssm_decode_rows_total": 60 * 100,
                             "ssm_prefill_tokens_total": 90_000,
                             "ssm_prefill_calls_total": 110}}}


def test_the_readers_on_made_up_observations():
    obs = _obs()
    kind = "TPU v5 lite"
    assert device_trace.read(obs, "scope_share", scopes=["layer/ssm"]) \
        == pytest.approx(60.0)
    # decode: the scan's and the convolution's ops of decode programs,
    # 0.75 s, against 6,000 rows in nine layers
    least = roofline.min_seconds(
        *roofline_ssm.scan_decode(6000 * 9, WIDTHS), kind)
    assert ssm.read(obs, "decode_roofline") \
        == pytest.approx(100.0 * least / 0.75)
    # prefill: 0.25 s (a scope that only starts like one is not counted)
    least = roofline.min_seconds(
        *roofline_ssm.scan_prefill(90_000 * 9, 110 * 9, WIDTHS), kind)
    assert ssm.read(obs, "prefill_roofline") \
        == pytest.approx(100.0 * least / 0.25)
    # a program without the counters, the widths or the scopes (the
    # parent), a rehearsal, no trace: nothing to read, and no error
    old = _obs()
    old["trace"]["engine"] = {"decode_steps": 7}
    dense = _obs()
    dense["model"] = {"n_layers": 8, "itemsize": 2}
    bare = _obs()
    bare["trace"]["op_calls"] = {"fusion.4": bare["trace"]["op_calls"][
        "fusion.4"]}
    for o in (old, dense, bare, dict(obs, trace=None),
              dict(obs, device={"platform": "cpu", "kind": "cpu"})):
        for what in ("decode_roofline", "prefill_roofline"):
            assert ssm.read(o, what) is None
    with pytest.raises(ValueError, match="unknown quantity"):
        ssm.read(obs, "no_such")


def test_the_manifest_holds_the_configuration_the_cell_and_its_entries():
    """One configuration, one cell and three per-layer metrics, each
    found by its name and as it was written; the accepted ``.tok``
    metrics that read this cell rightly list it; no other cell's line
    carries the three."""
    config = manifest_by_name.configuration(CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entered = manifest_by_name.cell(CELL)
    assert (entered["config"], entered["traffic"], entered["chips"]) \
        == (CONFIG, "serve_sessions64", 1)
    assert len(entered["why"]) <= 200
    assert CELL in manifest_by_name.metric("serve_tok_s")[1]
    for m in ENTRIES:
        entry, cells = manifest_by_name.metric(m["name"])
        assert entry == m and CELL in cells
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tok_s", "setup_s"}
    accepted = {f"{base}.tok" for base in (
        "prefill_chunk_ms", "decode_step_ms", "decode_occupancy",
        "kv_pool_live_share", "device_idle_share", "moe_share",
        "paged_kernel_share", "decode_device_ms", "prefill_device_ms",
        "fetch_found_ready_share", "tick_ms", "host_ms_per_tick",
        "decode_launch_ms", "prefill_launch_ms", "host_gap_share",
        "programs_ahead_share", "ttft_queue_ms", "ttft_prefill_wait_ms",
        "ttft_prefill_ms", "idle_in_tick_share",
        "profiler_launch_stretch")} | {
        "closed_ttft_p50_ms", "ready_s", "hbm_in_use_share",
        "compiles_in_window"}
    line = manifest_by_name.line_of(CELL)
    assert line >= accepted | {m["name"] for m in ENTRIES}
    # left out, and why (PERF.md section 4): nothing is shared; they
    # multiply one page size by n_layers where one layer in ten has
    # pages; they count every expert as held or name another's file
    assert not line & {
        "prefix_hit_rate.tok", "paged_decode_roofline.tok",
        "paged_prefill_roofline.tok", "kv_write_share.tok",
        "moe_gmm_roofline.tok", "moe_held_roofline.tok"}
    assert manifest_by_name.carried_only_by(
        {m["name"] for m in ENTRIES}, CELL)
    read, args = spec.metric_reader("ssm_share.tok")
    assert read is device_trace.read
    assert args == {"what": "scope_share", "scopes": ["layer/ssm"]}
    for kind in ("decode", "prefill"):
        read, args = spec.metric_reader(f"ssm_scan_{kind}_roofline.tok")
        assert read is ssm.read and args == {"what": f"{kind}_roofline"}
    got = spec.read_metrics(ENTRIES, _obs())
    assert set(got) == {m["name"] for m in ENTRIES}
    assert got["ssm_share.tok"]["value"] == pytest.approx(60.0)
    assert all(0 < v["value"] < 100 for v in got.values())


def test_the_cell_rehearses_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 52), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=420,
        env=env, cwd=spec.ROOT, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert {"prefill_chunk_ms.tok", "decode_step_ms.tok",
            "kv_pool_live_share.tok", "compiles_in_window"} <= names
    # device numbers are not taken from a CPU, and nothing is shared
    assert not names & {"ssm_share.tok", "ssm_scan_decode_roofline.tok",
                        "ssm_scan_prefill_roofline.tok",
                        "device_idle_share.tok", "prefix_hit_rate.tok"}
    assert line["compared"]["logits"][0] < 1e-4
