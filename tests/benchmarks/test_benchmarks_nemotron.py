"""The Nemotron-3-Super-120B-A12B configuration and its reasoning cell:
the file holds the published numbers under their own keys and states its
cuts and its 32-chip deployment, the parameter counts follow from the
file's keys, the traffic file holds the cell's stated parameters, the
counting rule of the latent experts' grouped products against numbers
worked by hand, the reader on made-up observations (and silent where the
program has nothing for it, as the parent), the manifest's
configuration, cell and entries found by name, the reference apart from
the program, and the cell rehearsed end to end on the CPU."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_latent_moe, spec
from benchmarks.readers import device_trace, latent_moe

CONFIG = "nemotron-3-super-120b-a12b"
CELL = CONFIG + ".serve_reason48"
LETTERS = "MEMEMEM*EMEMEMEM*EMEME"
KIND = {"M": "mamba", "E": "ffn", "*": "full"}
WIDTHS = {"moe_latent": 1024, "expert_width": 2688, "experts_held": 64,
          "n_experts": 512, "experts_per_token": 22}
#: as written but for their ``workloads``, which hold this cell
ENTRIES = [{"name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": "kernels, experts",
            "moves": "serve_tok_s"}
           for name, better in (
    ("moe_latent_proj_share.tok", "lower"),
    ("moe_latent_gmm_decode_roofline.tok", "higher"),
    ("moe_latent_gmm_prefill_roofline.tok", "higher"))]


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows
            if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"][0]


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cuts():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    published = cfg["published"]
    row = _catalog_row()
    if row is not None:                  # the catalog's own numbers
        assert published == row["config"]
        assert entry["source"] == row["source_url"]
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key      # as published
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (88, 512, 131072)
    assert cfg["num_hidden_layers"] == cell.depth == 22
    assert cfg["n_routed_experts"] == 64
    assert cfg["vocab_size"] == cfg["program"]["vocab_size"] == 16384
    assert (cfg["held"]["n_routed_experts"]["published"],
            cfg["held"]["n_routed_experts"]["here"]) == (512, 64)
    assert (cfg["held"]["vocab_rows"]["published"],
            cfg["held"]["vocab_rows"]["here"]) == (131072, 16384)
    # the 32-chip deployment: four stages of 22 layers, eight a layer
    assert "32 chips" in cfg["deployment"] \
        and "four pipeline stages of 22 layers" in cfg["deployment"] \
        and "eight chips sharing each layer" in cfg["deployment"]
    # the 22 layers run are the published pattern's first 22 letters: a
    # quarter of the 88 and a quarter of each kind
    pattern = published["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern[:22] == LETTERS
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (40, 40, 8)
    assert (LETTERS.count("M"), LETTERS.count("E"), LETTERS.count("*")) \
        == (10, 10, 2)
    assert cfg["depth"]["pattern_here"] == LETTERS
    for item in ("nope", "latent", "state_dtype", "conv_tail",
                 "in_proj_layout", "dt", "gated_norm", "router", "expert",
                 "weights"):
        assert item in cfg["assumed"], item
    assert "float32" in cfg["assumed"]["state_dtype"]
    assert "refuse" in cfg["departures"]["training"]
    assert "not run" in cfg["departures"]["multi_token_prediction"]
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]) \
        == (published["hidden_size"], published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"]) \
        == (4096, 32, 2, 128)
    assert kw["layer_pattern"] == [KIND[c] for c in LETTERS]
    assert kw["mixer_only"] is True and kw["rotary_dim"] == 0
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_conv"], kw["ssm_chunk"], kw["ssm_groups"]) \
        == (published["mamba_num_heads"], published["mamba_head_dim"],
            published["ssm_state_size"], published["conv_kernel"],
            published["chunk_size"], published["n_groups"]) \
        == (128, 64, 128, 4, 128, 8)
    assert kw["ssm_heads"] * kw["ssm_head_dim"] \
        == published["expand"] * published["hidden_size"]
    assert (kw["n_experts"], kw["experts_per_token"], kw["expert_width"],
            kw["shared_expert_width"], kw["moe_latent"], kw["routed_scale"]) \
        == (published["n_routed_experts"], published["num_experts_per_tok"],
            published["moe_intermediate_size"],
            published["moe_shared_expert_intermediate_size"],
            published["moe_latent_size"],
            published["routed_scaling_factor"]) \
        == (512, 22, 2688, 5376, 1024, 5)
    assert (kw["experts_held"], kw["expert_first"], kw["router_score"],
            kw["router_bias"], kw["expert_act"]) \
        == (64, 0, "sigmoid", True, published["mlp_hidden_act"])
    assert kw["norm_eps"] == published["layer_norm_epsilon"] == 1e-5
    assert kw["vocab_size"] == 16384 and kw["n_layers"] == 22
    # every pin set, so that no measured run tunes
    assert all(cfg["blocks"][k] > 0 for k in (
        "attn_block_q", "attn_block_k", "paged_block_r",
        "paged_block_r_prefill"))
    hp = dict(cell.reference_hp())
    assert (hp["expert_first"], hp["experts_held"],
            hp["num_experts_per_tok"], hp["n_groups"], hp["pattern"]) \
        == (0, 64, 22, 8, LETTERS)
    assert "control" not in hp and "logits_from" not in hp
    assert cfg["reference"] == "nemotron_h"
    assert set(cfg["tolerance"]) == {"logits", "why"}
    assert set(cfg["weights"]) == {"stream_scale", "residual_writers", "why"}
    # the rehearsal keeps every form, at a narrow width
    kw = spec.load_cell(CELL, rehearse=True).model_kwargs()
    assert kw["mixer_only"] and kw["ssm_groups"] > 1 and kw["moe_latent"] \
        and kw["expert_act"] == "relu2" and kw["n_layers"] == 11 \
        and kw["experts_held"] < kw["n_experts"] \
        and kw["ssm_heads"] * kw["ssm_head_dim"] == 2 * kw["d_model"]


def _counts(cfg, layers, experts, vocab):
    """Parameters, from the published keys: (an M layer, a * layer, an E
    layer outside its routed experts, a routed expert, the whole)."""
    e = cfg["hidden_size"]
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = e * (di + conv + cfg["mamba_num_heads"]) + di * e \
        + conv * (cfg["conv_kernel"] + 1) + 3 * cfg["mamba_num_heads"] \
        + di + e
    heads = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attn = 2 * e * heads + 2 * e * kv + e
    ffn = e * cfg["n_routed_experts"] + cfg["n_routed_experts"] \
        + 2 * e * cfg["moe_latent_size"] \
        + 2 * e * cfg["moe_shared_expert_intermediate_size"] + e
    expert = 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
    pattern = cfg["hybrid_override_pattern"][:layers]
    whole = pattern.count("M") * mamba + pattern.count("*") * attn \
        + pattern.count("E") * (ffn + experts * expert) + 2 * vocab * e + e
    return mamba, attn, ffn, expert, whole


def test_the_parameter_counts_follow_from_the_files_keys():
    cfg = spec.load_cell(CELL).config
    pub = cfg["published"]
    mamba, attn, ffn, expert, whole = _counts(pub, 88, 512, 131072)
    assert (mamba, attn, ffn, expert) \
        == (109_640_064, 35_655_680, 54_530_560, 5_505_024)
    assert whole == pytest.approx(120.7e9, rel=2e-3)
    *_, here = _counts(pub, cfg["num_hidden_layers"],
                       cfg["n_routed_experts"], cfg["vocab_size"])
    assert here == pytest.approx(5370e6, rel=2e-4)
    # and the program's own count says the same of what it builds
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    kw = dict(spec.load_cell(CELL).model_kwargs(), dtype=jnp.bfloat16)
    assert TransformerConfig(**kw).num_params == here
    assert 2 * here == pytest.approx(10.74e9, rel=1e-3)      # bf16 served


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "nemotron_h.py")).read()
    code = src.split('"""', 2)[2]
    assert "ray_tpu" not in code
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import jax", "import jax.numpy as jnp",
                       "from .common import F32, make_api"]
    # token by token and a dense loop over the held experts: no block, no
    # sort, no grouped product
    assert "jax.lax.scan(" in code and "fori_loop(0, hp[\"experts_held\"]" \
        in code
    assert "ragged_dot" not in code and "argsort(flat" not in code \
        and "chunk_size" not in code
    from benchmarks.reference import nemotron_h
    assert {"parallel_pairs", "one_group", "norm_all_channels",
            "ungated_norm", "rotary", "bias_weighs", "no_routed_scale",
            "one_expert_fewer", "gated_expert"} == set(nemotron_h.CONTROLS)


def test_the_traffic_file_holds_the_cells_parameters():
    p = spec.load_cell(CELL).params
    assert p["kind"] == "closed_loop" and p["n_layers"] == 22
    assert (p["clients"], p["client_threads"]) == (48, 48)
    assert p["doc_lengths"] == [224, 480, 736, 992, 1504, 2016, 3040]
    assert p["answer_lengths"] == [512, 768, 1024, 1280, 1536, 2048, 2560,
                                   3072]
    # every request a fresh prompt: nothing for a prefix cache to serve
    assert (p["questions_per_doc"], p["question_len"], p["doc_stride"]) \
        == (1, 32, 5)
    prompts = [n + p["question_len"] for n in p["doc_lengths"]]
    assert (min(prompts), max(prompts)) == (256, 3072)
    assert sum(prompts) / 7 == pytest.approx(1317, abs=0.5)
    # more out than in
    assert sum(p["answer_lengths"]) / 8 == 1600 > sum(prompts) / 7
    # a traced stretch as long as a burst period (256 tokens of answer,
    # 11 s) and a burst, so that it holds chunks
    assert (p["trace_seconds"], p["drain_seconds"]) == (14, 20)
    assert "warm_seconds" in p["why"]
    # out of step: the 48 clients start on all seven lengths
    starts = [(c * p["doc_stride"]) % 7 for c in range(48)]
    assert all(starts.count(i) in (6, 7) for i in range(7))
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["kv_block_size"], e["max_new_tokens"], e["num_kv_blocks"]) \
        == (48, 8192, 1024, 16, 3072, 24577)
    assert e["enable_prefix_sharing"] is False
    assert e["decode_slots"] == p["clients"] >= 40
    # eight scan blocks of 128 a call
    assert e["prefill_chunk"] == 8 * 128
    # the auto size: every slot's whole window, and the trash page
    assert e["num_kv_blocks"] == 1 + 48 * 8192 // 16
    longest = max(prompts) + max(p["answer_lengths"])
    assert longest < e["max_seq_len"]
    from benchmarks import traffic
    assert traffic.check_sample(e)["prompt_len"] == 1539
    r = spec.load_cell(CELL, rehearse=True).params
    assert r["engine"]["enable_prefix_sharing"] is False
    assert (r["engine"]["decode_slots"], r["engine"]["prefill_chunk"],
            r["engine"]["max_new_tokens"]) == (3, 64, 8)


def test_the_counting_rule_by_hand():
    kind = "TPU v5 lite"
    # one routed expert: two matrices of 1024 x 2688, bf16
    assert roofline_latent_moe.expert_bytes(WIDTHS) == 2 * 1024 * 2688 * 2 \
        == 11_010_048
    # a 48-row decode step, one expert layer: 48 x 22 = 1,056 assignments,
    # an eighth of them (132) land on the 64 held experts
    flops, nbytes = roofline_latent_moe.grouped(1056, 1, WIDTHS)
    assert flops == 132 * 2 * 2 * 1024 * 2688 == 1_453_326_336
    met = 64 * (1 - (1 - 1 / 64) ** 132)
    assert met == pytest.approx(56.0, abs=0.05)     # nearly every one
    assert roofline_latent_moe.experts_met(132, 64) == pytest.approx(met)
    assert nbytes == pytest.approx(132 * 2 * 1024 * 2 + met * 11_010_048)
    # the weights bind: 0.617 GB at 819 GB/s is 0.75 ms, the FLOPs 7 us
    least = roofline.min_seconds(flops, nbytes, kind)
    assert least == pytest.approx(nbytes / 819e9)
    assert least == pytest.approx(0.754e-3, rel=0.01)
    # ten expert layers of 100 steps: the same a (step, layer)
    f10, b10 = roofline_latent_moe.grouped(1056 * 1000, 1000, WIDTHS)
    assert f10 == pytest.approx(1000 * flops) \
        and b10 == pytest.approx(1000 * nbytes)
    # a whole chunk of 1,024 tokens, one layer: 2,816 rows land, 44 an
    # expert; all 64 are read (0.70 GB) and the FLOPs bind: 31 GFLOP
    flops, nbytes = roofline_latent_moe.grouped(1024 * 22, 1, WIDTHS)
    assert flops == 2816 * 4 * 1024 * 2688 == pytest.approx(31.0e9, rel=2e-3)
    assert nbytes == pytest.approx(
        2816 * 4096 + 64 * 11_010_048, rel=1e-6)
    assert roofline.min_seconds(flops, nbytes, kind) \
        == pytest.approx(nbytes / 819e9)        # 0.87 ms against 0.16
    assert roofline_latent_moe.grouped(0, 0, WIDTHS) == (0.0, 0.0)


def _obs():
    return {
        "model": dict(WIDTHS, n_layers=22, prefill_chunk=1024, itemsize=2,
                      kv_block_size=16),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine_end": {"ffn_layers": 10},
        "trace": {"chips": 1, "busy_s": 7.5, "window_s": 8.0,
                  # seconds by (program, kind of op): the compiler keeps
                  # no scope path on a grouped product
                  "by_module_kind": {
                      "jit__decode_fn|ragged-dot-none": 2.0,
                      "jit__decode_fn|ragged-dot-metadata": 1.0,
                      "jit__decode_fn|fusion": 1.5,
                      "jit__prefill_fn|ragged-dot-none": 0.125,
                      "jit__prefill_fn|sort": 0.75},
                  "by_scope": {"layer/mlp/moe": 3.0,
                               "layer/mlp/moe/moe_latent_down": 0.5,
                               "layer/mlp/moe/moe_latent_up": 0.25},
                  "engine": {"moe_decode_assignments_total":
                             200 * 48 * 22 * 10,
                             "moe_prefill_assignments_total":
                             7 * 900 * 22 * 10,
                             "decode_steps": 200, "prefill_chunks": 7}}}


def test_the_readers_on_made_up_observations():
    obs = _obs()
    kind = "TPU v5 lite"
    read, args = spec.metric_reader("moe_latent_proj_share.tok")
    assert read is device_trace.read
    assert read(obs, **args) == pytest.approx(10.0)       # 0.75 of 7.5
    # decode: both kinds of grouped-product op of the decode programs,
    # 3 s, against 200 steps x 10 layers
    least = roofline.min_seconds(*roofline_latent_moe.grouped(
        200 * 48 * 22 * 10, 200 * 10, WIDTHS), kind)
    assert latent_moe.read(obs, "gmm_decode_roofline") \
        == pytest.approx(100.0 * least / 3.0)
    # chunks: 0.125 s, against 7 chunks x 10 layers
    least = roofline.min_seconds(*roofline_latent_moe.grouped(
        7 * 900 * 22 * 10, 7 * 10, WIDTHS), kind)
    assert latent_moe.read(obs, "gmm_prefill_roofline") \
        == pytest.approx(100.0 * least / 0.125)
    # a program without the counters, the widths, the kernels or the
    # expert layers' count (the parent), a stretch without a chunk, a
    # rehearsal, no trace: nothing to read, and no error
    old = _obs()
    old["trace"]["engine"] = {"decode_steps": 7, "prefill_chunks": 1}
    gated = _obs()
    gated["model"] = {"n_layers": 8, "itemsize": 2, "expert_width": 768}
    dense = _obs()
    dense["trace"]["by_module_kind"] = {"jit__decode_fn|fusion": 1.5}
    uncounted = dict(_obs(), engine_end={})
    no_chunk = _obs()
    del no_chunk["trace"]["by_module_kind"]["jit__prefill_fn|ragged-dot-none"]
    assert latent_moe.read(no_chunk, "gmm_prefill_roofline") is None
    assert latent_moe.read(no_chunk, "gmm_decode_roofline") is not None
    for o in (old, gated, dense, uncounted, dict(obs, trace=None),
              dict(obs, device={"platform": "cpu", "kind": "cpu"})):
        for what in ("gmm_decode_roofline", "gmm_prefill_roofline"):
            assert latent_moe.read(o, what) is None
    with pytest.raises(ValueError, match="unknown quantity"):
        latent_moe.read(obs, "no_such")


def test_the_manifest_holds_the_configuration_the_cell_and_its_entries():
    """One configuration, one cell and three per-layer metrics, each
    found by its name and as it was written; the accepted ``.tok``
    metrics that read this cell rightly list it; no other cell's line
    carries the three."""
    config = manifest_by_name.configuration(CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entered = manifest_by_name.cell(CELL)
    assert (entered["config"], entered["traffic"], entered["chips"]) \
        == (CONFIG, "serve_reason48", 1)
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
        "ttft_prefill_ms", "idle_in_tick_share", "profiler_launch_stretch",
        "tick_decode_ms", "tick_chunk_ms", "tick_part_chunk_ms",
        "tick_decode_host_ms", "tick_chunk_gap_ms", "tick_chunk_share",
        "tick_decode_p99_ms", "tick_slow_share")} | {
        "closed_ttft_p50_ms", "ready_s", "hbm_in_use_share",
        "compiles_in_window"}
    line = manifest_by_name.line_of(CELL)
    assert line >= accepted | {m["name"] for m in ENTRIES}
    # left out, and why (PERF.md sections 4 and 7): nothing is shared;
    # they multiply one page size by n_layers, or count an "ffn" layer as
    # paged, where two layers in 22 have pages; they count three
    # model-wide matrices an expert; they count one group of B and C; the
    # state-space cell's accepted test holds ssm_share.tok to that cell
    assert not line & {
        "prefix_hit_rate.tok", "paged_decode_roofline.tok",
        "paged_prefill_roofline.tok", "kv_write_share.tok",
        "paged_layers_decode_roofline.tok",
        "paged_layers_prefill_roofline.tok", "moe_gmm_roofline.tok",
        "moe_held_roofline.tok", "ssm_scan_decode_roofline.tok",
        "ssm_scan_prefill_roofline.tok", "ssm_share.tok"}
    assert manifest_by_name.carried_only_by(
        {m["name"] for m in ENTRIES}, CELL)
    read, args = spec.metric_reader("moe_latent_gmm_decode_roofline.tok")
    assert read is latent_moe.read \
        and args == {"what": "gmm_decode_roofline"}
    got = spec.read_metrics(ENTRIES, _obs())
    assert set(got) == {m["name"] for m in ENTRIES}
    assert all(0 < v["value"] < 100 for v in got.values())


def test_the_cell_rehearses_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 60), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=420,
        env=env, cwd=spec.ROOT, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert {"prefill_chunk_ms.tok", "decode_step_ms.tok",
            "kv_pool_live_share.tok", "compiles_in_window"} <= names
    # device numbers are not taken from a CPU
    assert not names & {m["name"] for m in ENTRIES}
    assert not names & {"device_idle_share.tok", "moe_share.tok"}
    assert line["compared"]["logits"][0] < 1e-4
