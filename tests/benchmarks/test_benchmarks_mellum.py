"""The Mellum2-12B-A2.5B configuration and its training cell: the file
holds the published numbers under their own keys and states its cuts and
its deployment, the cut's arithmetic follows from the file's keys and is
the program's own count, the traffic file holds the cell's stated
parameters, the counting rules of a routed, windowed training step
against numbers worked by hand, the readers on made-up observations (and
silent where the program has nothing for them, as the parent), the
manifest's configuration, cell and entries found by name, the reference
apart from the program, and the cell rehearsed end to end on the CPU."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_moe_train as R, spec
from benchmarks.readers import device_trace, moe_train

CONFIG = "mellum2-12b-a2.5b"
CELL = CONFIG + ".train_moe_8k"
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
#: as written but for their ``workloads``, which hold this cell alone
ENTRIES = [{"name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "train_tok_s"}
           for name, better, source, layer in (
    ("mfu_routed", "higher", "host_clock", "train step"),
    ("moe_train_share", "lower", "device_trace", "kernels, experts"),
    ("moe_experts_train_roofline", "higher", "device_trace",
     "kernels, experts"),
    ("flash_window_roofline", "higher", "device_trace",
     "kernels, flash attention"),
    ("attn_window_share", "lower", "device_trace",
     "kernels, flash attention"),
    ("attn_full_share", "lower", "device_trace",
     "kernels, flash attention"),
    ("optimizer_share", "lower", "device_trace", "train step"))]
#: accepted entries that mean the same thing in this cell
SHARED = ("train_tok_s", "setup_s", "step_ms", "train_tok_s_block_median",
          "device_idle_share.train", "step_host_share", "head_loss_share",
          "step_p99_ms", "flash_share", "flash_roofline")
#: accepted entries that would MISREAD this cell: ``mfu`` counts every
#: held expert's parameters and every layer's attention full and causal;
#: ``collective_exposed_share`` is a four-chip cell's
NOT_HERE = ("mfu", "collective_exposed_share")


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct"][0]


def _sizes():
    cell = spec.load_cell(CELL)
    return dict(cell.config["program"], n_layers=cell.depth)


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cuts():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    published = cfg["published"]
    row = _catalog_row()
    if row is not None:                  # the catalog's own numbers
        assert published == row["config"]
        assert entry["source"] == row["source_url"]
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key      # as published
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (28, 64, 98304)
    assert cfg["num_hidden_layers"] == cell.depth == 4
    # ISSUE 62's cut: this chip HOLDS 16 of the 64 experts; the router
    # keeps its 64 outputs and its top-8 of 64 (no width is cut)
    assert cfg["num_experts"] == cfg["program"]["experts_held"] == 16
    assert (cfg["program"]["n_experts"], cfg["program"]["expert_first"]) \
        == (published["num_experts"], 0)
    assert "keeps its 64 outputs" in cfg["held"]["why"] \
        and "2,048 rows an expert" in cfg["held"]["why"]
    assert cfg["vocab_size"] == cfg["program"]["vocab_size"] == 24576
    assert (cfg["depth"]["published"], cfg["depth"]["here"]) == (28, 4)
    assert (cfg["held"]["published"], cfg["held"]["here"]) == (64, 16)
    assert (cfg["vocabulary"]["published"], cfg["vocabulary"]["here"]) \
        == (98304, 24576)
    assert "595,153,152" in cfg["depth"]["why"]
    # four chips a layer, seven pipeline groups of four layers
    assert "Seven pipeline groups of four chips" in cfg["deployment"] \
        and "16 of the 64 experts a chip" in cfg["deployment"]
    # the published pattern: three window layers, then a full one, seven
    # times over; every MLP sparse; depth 4 is one whole period
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    assert [kinds[t] for t in published["layer_types"]] \
        == ["window", "window", "window", "full"] * 7
    assert set(published["mlp_layer_types"]) == {"sparse"}
    for item in ("qk_norm", "rotary_layout", "window", "yarn", "router",
                 "balance", "weights"):
        assert item in cfg["assumed"], item
    assert "no key" in cfg["departures"]["mtp"]
    assert "does not serve" in cfg["departures"]["serving"]
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["expert_width"], kw["d_ff"]) \
        == (published["hidden_size"], published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"],
            published["moe_intermediate_size"],
            published["intermediate_size"]) \
        == (2304, 32, 4, 128, 896, 7168)
    assert kw["layer_pattern"] == ["window", "window", "window", "full"]
    rope = published["rope_parameters"]
    assert (kw["sliding_window"], kw["rope_base"], kw["window_rope_base"],
            kw["rotary_dim"], kw["window_rotary_dim"]) \
        == (published["sliding_window"], rope["full_attention"]["rope_theta"],
            rope["sliding_attention"]["rope_theta"], 128, 128) \
        == (1024, 500000, 500000, 128, 128)
    full = rope["full_attention"]
    assert kw["rope_yarn"] == [
        full["factor"], full["original_max_position_embeddings"],
        full["beta_fast"], full["beta_slow"], full["attention_factor"]]
    import math
    assert full["attention_factor"] == pytest.approx(
        0.1 * math.log(full["factor"]) + 1)
    assert (kw["n_experts"], kw["experts_held"], kw["expert_first"],
            kw["experts_per_token"], kw["router_score"],
            kw["routed_scale"]) \
        == (published["num_experts"], published["num_experts"] // 4, 0,
            published["num_experts_per_tok"], "softmax", 1.0)
    assert published["norm_topk_prob"] is True
    assert kw["norm_eps"] == published["rms_norm_eps"] == 1e-6
    assert kw["n_layers"] == 4 and kw["block_style"] == "llama"
    # every pin set, so that no measured run tunes
    assert (cfg["blocks"]["attn_block_q"], cfg["blocks"]["attn_block_k"]) \
        == (512, 1024)
    hp = dict(cell.reference_hp())
    assert (hp["experts_held"], hp["expert_first"], hp["num_experts"],
            hp["num_experts_per_tok"], hp["sliding_window"],
            hp["layer_pattern"]) \
        == (16, 0, 64, 8, 1024, "window window window full")
    assert "control" not in hp and "norm_topk_prob" not in hp
    assert cfg["reference"] == "mellum"
    assert set(cfg["weights"]) == {"stream_scale", "residual_writers", "why"}
    # the embedding is drawn wide, so that a token routes by its own
    # embedding and every seed does the same work; the check multiplies
    # it back to the 0.02 of every other leaf, the weights its limits
    # were read on
    std = cfg["program"]["embed_init_std"]
    assert std >= 0.32 and cfg["weights"]["residual_writers"] == ["embed"]
    assert abs(std * cfg["weights"]["stream_scale"] - 0.02) < 1e-9
    # limits of its own, between the sound program's largest readings
    # and the harness's (check.TOL), with what they cannot tell beside
    from benchmarks import check
    tol = cfg["tolerance"]
    assert 0 < tol["loss"] < check.TOL["loss"] / 10
    assert 0 < tol["grad_norm"] < check.TOL["grad_norm"] / 10
    assert check.tolerances(cell)["loss"] == tol["loss"]
    assert "NOT TOLD" in tol["why"]
    # the rehearsal keeps every form, at a narrow width
    r = spec.load_cell(CELL, rehearse=True)
    kw = r.model_kwargs()
    assert kw["experts_per_token"] <= kw["experts_held"] < kw["n_experts"] \
        and kw["rope_yarn"] \
        and kw["sliding_window"] < r.params["check_seq"] // 2 \
        and kw["layer_pattern"] == ["window", "window", "window", "full"]


def test_the_cuts_arithmetic_is_the_programs_own_count():
    pub = spec.load_cell(CELL).config["published"]
    e = pub["hidden_size"]
    heads = pub["num_attention_heads"] * pub["head_dim"]
    kv = pub["num_key_value_heads"] * pub["head_dim"]
    attention = 2 * e * heads + 2 * e * kv
    router = e * pub["num_experts"]
    expert = 3 * e * pub["moe_intermediate_size"]
    assert (attention, router, expert) == (21_233_664, 147_456, 6_193_152)
    whole_layer = attention + router + 2 * e + 64 * expert
    assert whole_layer == pytest.approx(417.7e6, rel=1e-3)
    # this chip's 16 experts under the whole router
    held_layer = attention + router + 2 * e + 16 * expert
    assert held_layer == 120_476_160
    here = 4 * held_layer + 2 * 24576 * e + e
    assert here == 595_153_152
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig
    from ray_tpu.models.transformer import untrained_keys
    kw = dict(spec.load_cell(CELL).model_kwargs(), dtype=jnp.bfloat16)
    cfg = TransformerConfig(**kw)
    assert cfg.num_params == here
    # the four keys the refusal lets through, and nothing left at fault
    assert set(cfg.served_keys) == {"experts_per_token", "layer_pattern",
                                    "sliding_window", "rope_yarn"}
    assert untrained_keys(cfg) == ()
    # 16 B a parameter trained, 12 B resident between steps
    assert 16 * here == pytest.approx(9.52e9, rel=1e-3)
    assert 12 * here == pytest.approx(7.14e9, rel=1e-3)


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "mellum.py")).read()
    code = src.split('"""', 2)[2]
    assert "ray_tpu" not in code and "laguna" not in code
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import functools", "import math", "import jax",
                       "import jax.numpy as jnp", "import numpy as np",
                       "from .common import F32, make_api"]
    # its own YaRN frequencies and window mask; one checkpoint a layer,
    # a head group at a time, the held experts in a loop: no sort, no
    # grouped product
    assert "def yarn_inv_freq(" in code and "def window_mask(" in code
    assert "jax.lax.fori_loop(0, held" in code and "jax.lax.map(" in code
    assert "ragged_dot" not in code and "argsort" not in code
    from benchmarks.reference import mellum
    assert mellum.CONTROLS == ("router_gradient_stopped",)
    assert callable(mellum.forward) and callable(mellum.loss) \
        and callable(mellum.loss_and_grad_norm)


def test_the_traffic_file_holds_the_cells_parameters():
    cell = spec.load_cell(CELL)
    p = cell.params
    assert p["kind"] == "train" and p["plan"] == {"fsdp": 1}
    assert (p["n_layers"], p["batch"], p["seq"]) == (4, 2, 8192)
    assert p["batch"] * p["seq"] == 16384
    assert (p["block_steps"], p["warm_steps"], p["distinct_batches"],
            p["trace_steps"], p["check_seq"]) == (8, 2, 4, 4, 4096)
    assert p["learning_rate"] == 0.0001          # train_2k's
    # four windows, and 2 x 4096 = 8192 tokens: the checked program runs
    # the experts in two turns, as every timed step does
    from ray_tpu.models import moe
    assert p["check_seq"] == 4 * cell.config["program"]["sliding_window"]
    assert 2 * p["check_seq"] == 2 * moe._MANY_TOKENS
    assert p["remat_policy"] in ("dots", "full") and "remat" in p["why"]
    assert manifest_by_name.cell(CELL)["chips"] == 1
    r = spec.load_cell(CELL, rehearse=True).params
    assert (r["batch"], r["seq"], r["n_layers"]) == (2, 128, 4)


def test_the_counting_rules_by_hand():
    sizes = _sizes()
    # the live pairs of a window: 1024 x 1025 / 2 + 7168 x 1024
    assert R.window_pairs(8192, 1024) == 7_864_832
    assert R.window_pairs(8192, 0) == R.window_pairs(8192, 8192) \
        == R.window_pairs(8192, 9000) == 8192 * 8193 // 2
    assert R.window_pairs(8192, 1) == 8192
    assert R.window_pairs(8192, 1024) / 8192 == pytest.approx(960.06,
                                                              abs=0.01)
    assert R.layers_by_kind(sizes) == {"full": 1, "window": 3}
    assert R.expert_params(sizes) == 6_193_152
    # an even router lands k x held / n = 2 of a token's 8 assignments on
    # the 16 held of 64; with every expert held all 8
    assert R.assignments_here(1, sizes) == 2
    assert R.assignments_here(16384, sizes) == 32768
    whole = dict(sizes, n_experts=16, experts_held=0)
    assert R.assignments_here(1, whole) == 8
    # the issue's count a token, term by term (MFLOP)
    per_token = R.train_flops_per_token(sizes, 8192)
    projections = 4 * 6 * 21_233_664
    router = 4 * 6 * 147_456
    experts = 4 * 2 * 6 * 6_193_152
    window = 3 * 12 * 4096 * 7_864_832 / 8192
    full = 12 * 4096 * 4096.5
    head = 6 * 2304 * 24576
    assert [round(x / 1e6, 1) for x in (projections, router, experts,
                                        window, full, head)] \
        == [509.6, 3.5, 297.3, 141.6, 201.4, 339.7]
    assert per_token == pytest.approx(
        projections + router + experts + window + full + head)
    assert per_token == pytest.approx(1493e6, rel=1e-3)      # the issue's
    # a fifth of the model's FLOPs in the experts here; the deployment's
    # chip, its experts fed by four chips' tokens, spends half in them
    assert experts / per_token == pytest.approx(0.2, abs=0.01)
    assert 4 * experts / (per_token + 3 * experts) == pytest.approx(
        0.5, abs=0.01)
    # against what ``mfu`` would count: every held expert's parameters,
    # every layer full and causal
    held = 4 * (21_233_664 + 147_456 + 4608 + 16 * 6_193_152) \
        + 2304 * 24576 + 2304
    wrong = roofline.train_flops_per_token(held, 4, 32, 128, 8192)
    assert wrong / per_token == pytest.approx(2.7, abs=0.1)
    # one expert layer's nine products at 32,768 rows: 1.22 TFLOP,
    # 6.18 ms at the peak, and the bytes far below (FLOPs bind)
    flops, nbytes = R.expert_products(32768, sizes)
    assert flops == 18 * 32768 * 2304 * 896 == pytest.approx(1.21763e12,
                                                             rel=1e-4)
    assert nbytes == 16 * 6_193_152 * 8 + 4 * 32768 * 2304 * 2
    kind = "TPU v5 lite"
    assert roofline.min_seconds(flops, nbytes, kind) \
        == pytest.approx(flops / 197e12) == pytest.approx(6.181e-3, rel=1e-3)
    # a windowed forward call at the cell's shape: 2 matmuls over the
    # live pairs
    f, b = R.flash_window_call("flash_window_fwd", (2, 32, 8192, 128), 1024)
    assert f == 2 * 2 * 2 * 32 * 7_864_832 * 128
    assert b == 4 * 2 * 32 * 8192 * 128 * 2
    full_f, _ = roofline.flash_call("flash_fwd", (2, 32, 8192, 128))
    assert f / full_f == pytest.approx(0.234, abs=0.001)     # "23%"
    assert R.flash_window_call("flash_window_bwd_dkdv",
                               (2, 32, 8192, 128), 1024)[0] == 2 * f
    assert R.flash_window_call("flash_window_bwd_delta",
                               (2, 32, 1, 8192), 1024) == (0.0, 0.0)


def _obs(rate_tokens_per_s=50_000.0):
    step_s = 16384 / rate_tokens_per_s
    fwd = "%flash_window_fwd.1 = (bf16[2,32,8192,128]{3,2,1,0}, f32[2]) cu"
    return {
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "train": {"step_ends": [i * step_s for i in range(9)],
                  "tokens_per_step": 16384, "block_steps": 8, "seq": 8192,
                  "n_layers": 4, "n_heads": 32, "head_dim": 128,
                  "matmul_params": 538_087_680, "chips": 1},
        "trace": {"chips": 1, "busy_s": 1.0, "window_s": 1.02,
                  "by_scope": {"layer/mlp/moe": 0.25,
                               "layer/mlp/moe/moe_experts": 0.20,
                               "layer/attn/window": 0.12,
                               "layer/attn/full": 0.08, "optimizer": 0.03},
                  "op_calls": {
                      "flash_window_fwd.1_bf16_2_32_8192_128_": {
                          "kind": "flash_window_fwd", "name": fwd,
                          "module": "jit_step_raw",
                          "scope": "layer/attn/window/flash_window_fwd",
                          "calls": 12, "seconds": 0.04},
                      "ragged-dot-none.3_f32_32768_896_": {
                          "kind": "ragged-dot-none", "name": "%ragged-dot-"
                          "none.3 = f32[32768,896]{1,0} custom-call(",
                          "module": "jit_step_raw", "scope": "",
                          "calls": 48, "seconds": 0.15},
                      "fusion.9_f32_": {
                          "kind": "fusion", "name": "%fusion.9 = f32[] fus",
                          "module": "jit_step_raw", "scope": "optimizer",
                          "calls": 4, "seconds": 0.001}}}}


def test_the_readers_on_made_up_observations():
    obs = _obs()
    sizes = _sizes()
    kind = "TPU v5 lite"
    read, args = spec.metric_reader("mfu_routed")
    assert read is moe_train.read and args["config"] == CONFIG
    # 50,000 tokens/s x 1,493 MFLOP over 197 TFLOP/s
    assert read(obs, **args) == pytest.approx(
        100 * 50_000 * R.train_flops_per_token(sizes, 8192) / 197e12)
    assert read(obs, **args) == pytest.approx(37.9, abs=0.1)
    # it cannot pass 100 at the peak: the rate the peak allows reads 100
    at_peak = 197e12 / R.train_flops_per_token(sizes, 8192)
    assert read(_obs(at_peak), **args) == pytest.approx(100.0)
    # the expert layers' share: the scope and the unscoped grouped
    # products, 0.25 + 0.15 of 1.0
    read, args = spec.metric_reader("moe_train_share")
    assert read(obs, **args) == pytest.approx(40.0)
    # the experts' roofline: 4 traced steps (the optimizer's op ran four
    # times) x 4 layers x 6.18 ms at an even router's 32,768 rows, over
    # 0.20 + 0.15 s
    read, args = spec.metric_reader("moe_experts_train_roofline")
    least = 4 * 4 * roofline.min_seconds(
        *R.expert_products(32768, sizes), kind)
    assert read(obs, **args) == pytest.approx(100 * least / 0.35)
    read, args = spec.metric_reader("flash_window_roofline")
    least = 12 * roofline.min_seconds(*R.flash_window_call(
        "flash_window_fwd", (2, 32, 8192, 128), 1024), kind)
    assert read(obs, **args) == pytest.approx(100 * least / 0.04)
    for name, share in (("attn_window_share", 12.0),
                        ("attn_full_share", 8.0), ("optimizer_share", 3.0)):
        read, args = spec.metric_reader(name)
        assert read is device_trace.read
        assert read(obs, **args) == pytest.approx(share)
    # a program without the scopes or the kernels (the parent), a
    # rehearsal, no trace, no training window: nothing to read, no error
    parent = _obs()
    parent["trace"]["by_scope"] = {"layer/attn": 0.2, "optimizer": 0.03}
    parent["trace"]["op_calls"] = {}
    rehearsal = dict(_obs(), device={"platform": "cpu", "kind": "cpu"})
    untraced = dict(_obs(), trace=None)
    serving = {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    for name in ("moe_train_share", "moe_experts_train_roofline",
                 "flash_window_roofline", "attn_window_share",
                 "attn_full_share"):
        read, args = spec.metric_reader(name)
        for o in (parent, rehearsal, untraced):
            assert read(o, **args) is None, name
    read, args = spec.metric_reader("mfu_routed")
    assert read(rehearsal, **args) is None and read(serving, **args) is None
    assert read(untraced, **args) is not None       # host clock alone


def test_the_manifest_has_the_cell_and_its_entries_by_name():
    cell = manifest_by_name.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "train_moe_8k", 1)
    assert len(cell["why"]) <= 200 and "2048 rows" in cell["why"] \
        and "8192 deployed" in cell["why"]
    line = manifest_by_name.line_of(CELL)
    for want in ENTRIES:
        entry, workloads = manifest_by_name.metric(want["name"])
        assert entry == want
        assert workloads == [CELL]           # this cell's own, appended
        assert want["name"] in line
    assert manifest_by_name.carried_only_by(
        [e["name"] for e in ENTRIES], CELL)
    for name in SHARED:
        _, workloads = manifest_by_name.metric(name)
        assert not workloads or CELL in workloads, name
    # ``flash_share`` / ``flash_roofline`` read the ``flash_*`` calls
    # alone: in this cell the ONE full layer's; the three window layers'
    # calls carry names of their own and ``flash_window_roofline``
    assert line >= {"flash_share", "flash_roofline", "flash_window_roofline",
                    "head_loss_share", "device_idle_share.train",
                    "hbm_in_use_share", "compiles_in_window"}
    assert not set(roofline.FLASH_MATMULS) & set(R.WINDOW_KINDS)
    for name in NOT_HERE:
        _, workloads = manifest_by_name.metric(name)
        assert CELL not in workloads, name
    names = {m["name"] for m in spec.load_cell(CELL).end_to_end}
    assert names >= {"train_tok_s", "setup_s"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_end_to_end(trace):
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", "3000000017", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=spec.ROOT, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert set(compared) >= {"loss", "grad_norm", "loss_rise"}
    assert all(number <= limit for number, limit in compared.values())
    metrics = line["metrics"]
    if trace:
        assert "compiles_in_window" in metrics
    else:
        assert set(metrics) >= {"train_tok_s", "setup_s"}
