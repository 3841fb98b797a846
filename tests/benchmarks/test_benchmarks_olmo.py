"""The Olmo-Hybrid-7B configuration and its shared-documents cell: the
file holds the published numbers under their own keys and states its
cut, the parameter count from the file's keys, the traffic file the
cell's stated parameters, the counting rules of the delta rule against
numbers worked by hand, the readers on made-up observations (and silent
where the program has nothing for them, as the parent), the manifest's
configuration, cell and entries found by name, the reference apart from
the program, and the cell rehearsed end to end on the CPU."""
import json
import os
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_delta, spec
from ray_tpu.ops import delta as delta_ops
from benchmarks.readers import (delta, device_trace, engine, field,
                                paged_layers)

CONFIG = "olmo-hybrid-7b"
CELL = CONFIG + ".serve_shared_docs12"
PERIOD = ["delta", "delta", "delta", "full"]
WIDTHS = {"delta_heads": 30, "delta_key_dim": 96, "delta_value_dim": 192,
          "delta_conv": 4, "layer_pattern": PERIOD}
KERNELS = "kernels, delta-rule scan"
#: as written but for their ``workloads``, which hold this cell
ENTRIES = [{"name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "serve_tok_s"}
           for name, better, source, layer in (
    ("delta_share.tok", "lower", "device_trace", KERNELS),
    ("delta_scan_decode_roofline.tok", "higher", "device_trace", KERNELS),
    ("delta_scan_prefill_roofline.tok", "higher", "device_trace", KERNELS),
    ("state_cut_share.tok", "lower", "program_counter", "engine"),
    ("state_snapshot_live_share.tok", "lower", "program_counter",
     "engine"))]
#: the paged kernel and the rows' scatter of a stack in which four layers
#: of sixteen have pages (a reader of their own: readers/paged_layers.py)
PAGED_ENTRIES = [{"name": name, "unit": "%", "better": better,
                  "source": "device_trace", "layer": layer,
                  "moves": "serve_tok_s"}
                 for name, better, layer in (
    ("paged_layers_decode_roofline.tok", "higher",
     "kernels, paged attention"),
    ("paged_layers_prefill_roofline.tok", "higher",
     "kernels, paged attention"),
    ("kv_rows_write_share.tok", "lower", "device"))]


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["name"] == "Olmo-Hybrid-7B"][0]


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cut():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
        "config.json")
    assert len(entry["why"]) <= 200
    published = cfg["published"]
    row = _catalog_row()
    if row is not None:                  # the catalog's own numbers
        assert published == row["config"]
        assert entry["source"] == row["source_url"]
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key      # as published, groups whole
    assert published["num_hidden_layers"] == 32
    assert cfg["num_hidden_layers"] == cell.depth == 16
    assert (cfg["depth"]["published"], cfg["depth"]["here"]) == (32, 16)
    assert "CACHE" in cfg["depth"]["why"]
    assert "two pipeline stages of sixteen" in cfg["deployment"]
    # the sixteen layers run are four whole periods of the published list
    types = published["layer_types"]
    assert len(types) == 32 and types == types[:4] * 8
    assert [{"linear_attention": "delta", "full_attention": "full"}[t]
            for t in types[:4]] == PERIOD
    assert published["rope_parameters"] == {"rope_theta": None}
    for item in ("block", "qk_norm", "nope", "mixer", "state_dtype",
                 "conv_tail", "snapshot_stride", "weights"):
        assert item in cfg["assumed"], item
    assert "float32" in cfg["assumed"]["state_dtype"]
    assert "CACHE" in cfg["assumed"]["snapshot_stride"]
    assert "refuse" in cfg["departures"]["training"]
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["d_ff"], kw["vocab_size"]) \
        == (3840, 30, 30, 128, 11008, 100352) \
        == (published["hidden_size"], published["num_attention_heads"],
            published["num_key_value_heads"],
            published["hidden_size"] // published["num_attention_heads"],
            published["intermediate_size"], published["vocab_size"])
    assert kw["layer_pattern"] == PERIOD and kw["rotary_dim"] == 0
    assert (kw["delta_heads"], kw["delta_key_dim"], kw["delta_value_dim"],
            kw["delta_conv"], kw["delta_neg_eigval"]) \
        == (30, 96, 192, 4, True) \
        == (published["linear_num_value_heads"],
            published["linear_key_head_dim"],
            published["linear_value_head_dim"],
            published["linear_conv_kernel_dim"],
            published["linear_allow_neg_eigval"])
    assert published["linear_num_key_heads"] == 30
    # the counting rule's block is the program's, and no key of the file
    assert delta_ops.BLOCK == roofline_delta.BLOCK == 64 \
        and "delta_chunk" not in kw
    assert kw["output_norm"] is True and kw["qk_norm_whole"] is True
    assert kw["norm_eps"] == published["rms_norm_eps"] == 1e-6
    assert kw["n_layers"] == 16 and "tie_embeddings" not in kw
    # all four pins set, so that no measured run tunes
    assert all(cfg["blocks"][k] > 0 for k in (
        "attn_block_q", "attn_block_k", "paged_block_r",
        "paged_block_r_prefill"))
    hp = dict(cell.reference_hp())
    assert hp["layer_types"].split(",") == types[:4]
    assert (hp["linear_key_head_dim"], hp["linear_value_head_dim"],
            hp["linear_allow_neg_eigval"]) == (96, 192, True)
    assert "control" not in hp and "logits_from" not in hp
    assert cfg["reference"] == "olmo_hybrid"
    assert set(cfg["tolerance"]) == {"logits", "why"}
    assert cfg["weights"]["stream_scale"] == 1.0 \
        and cfg["weights"]["residual_writers"] == []
    # the rehearsal keeps every form, at a narrow width
    kw = spec.load_cell(CELL, rehearse=True).model_kwargs()
    assert kw["layer_pattern"] == PERIOD and kw["output_norm"] \
        and kw["qk_norm_whole"] and kw["n_layers"] == 8 \
        and kw["delta_value_dim"] == 2 * kw["delta_key_dim"]


def test_the_parameter_count_from_the_files_keys():
    """7.43B published deep, 4.10B at the sixteen layers run, counted
    from the published keys alone; the program's own count agrees."""
    from benchmarks import harness
    from ray_tpu.models import TransformerConfig
    cell = spec.load_cell(CELL)
    p = cell.config["published"]
    e, v, ff = p["hidden_size"], p["vocab_size"], p["intermediate_size"]
    h, dk, dv = p["linear_num_value_heads"], p["linear_key_head_dim"], \
        p["linear_value_head_dim"]
    mlp = 3 * e * ff + 2 * e                    # and the block's two norms
    conv_w = h * (2 * dk + dv)
    linear = e * conv_w + e * h * dv + e * 2 * h + h * dv * e \
        + conv_w * p["linear_conv_kernel_dim"] + 2 * h + dv + mlp
    full = 4 * e * e + 2 * e + mlp              # and the QK-norm's weights
    assert (linear, full) == (215_570_172, 185_809_920)
    ends = 2 * v * e + e
    types = p["layer_types"]

    def count(depth):
        kinds = types[:depth]
        return kinds.count("linear_attention") * linear \
            + kinds.count("full_attention") * full + ends
    assert count(32) == 7_430_870_688 and count(16) == 4_100_788_944
    kw = dict(cell.model_kwargs(), dtype=harness.resolve_dtype("bfloat16"))
    assert TransformerConfig(**kw).num_params == count(16)
    assert TransformerConfig(**{**kw, "n_layers": 32}).num_params \
        == count(32)


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "olmo_hybrid.py")).read()
    code = src.split('"""', 2)[2]
    assert "ray_tpu" not in code
    imports = [line for line in code.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import jax", "import jax.numpy as jnp",
                       "from .common import F32, make_api"]
    # token by token: no block, no triangular solve
    assert "jax.lax.scan(" in code
    assert "solve" not in code and "tril" not in code.replace(
        "jnp.tril(jnp.ones((s, s), bool))", "")
    from benchmarks.reference import olmo_hybrid
    assert {"no_attn_out_norm", "no_mlp_out_norm", "head_qk_norm", "rotary",
            "beta_1", "no_q_l2", "no_k_l2"} <= set(olmo_hybrid.CONTROLS)


def test_the_traffic_file_holds_the_cells_parameters():
    p = spec.load_cell(CELL).params
    assert p["kind"] == "closed_loop" and p["n_layers"] == 16
    assert (p["clients"], p["client_threads"]) == (12, 12)
    assert p["doc_lengths"] == [1100, 1500, 2000, 2700, 3100, 4000, 5000,
                                6000]
    assert p["answer_lengths"] == [64, 96, 128, 192, 256, 320, 384]
    assert sum(p["doc_lengths"]) / 8 == 3175
    assert sum(p["answer_lengths"]) / 7 == pytest.approx(205.7, abs=0.1)
    assert (p["questions_per_doc"], p["question_len"], p["doc_stride"]) \
        == (4, 32, 3)
    assert (p["trace_seconds"], p["drain_seconds"]) == (6, 20)
    assert "warm_seconds" in p["why"]
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["kv_block_size"], e["max_new_tokens"]) \
        == (16, 8192, 2048, 16, 384)
    assert e["enable_prefix_sharing"] is True
    stride = e["state_snapshot_stride"]
    assert stride == 512 and stride % e["kv_block_size"] == 0 \
        and e["prefill_chunk"] % stride == 0 \
        and stride % roofline_delta.BLOCK == 0
    # on purpose no document ends on the stride: a hit loses 28-476
    # tokens to the cut, 295 in the mean
    lost = [n % stride for n in p["doc_lengths"]]
    assert min(lost) == 28 and max(lost) == 476 and sum(lost) / 8 == 295
    # the sizes ISSUE 56 names (set-up passes beside them), a snapshot
    # row a stride of the pool's tokens: what the engine would take by
    # itself (``num_state_snapshots`` 0)
    assert e["num_kv_blocks"] == 3073 and e["num_state_snapshots"] == 96
    assert e["num_state_snapshots"] * stride \
        == (e["num_kv_blocks"] - 1) * e["kv_block_size"]
    from ray_tpu.serve.llm_engine import EngineConfig
    assert EngineConfig(**{**e, "num_state_snapshots": 0}) \
        .resolved_state_snapshots == 96
    longest = max(p["doc_lengths"]) + p["question_len"] \
        + max(p["answer_lengths"])
    assert longest < e["max_seq_len"]
    from benchmarks import traffic
    sample = traffic.check_sample(e)
    # the served check's second request resumes from a snapshot that is
    # there: 160 pages, five strides
    assert (sample["prompt_len"], sample["shared"]) == (3075, 2560)
    assert sample["shared"] % stride == 0
    r = spec.load_cell(CELL, rehearse=True).params
    assert r["engine"]["enable_prefix_sharing"] is True
    assert traffic.check_sample(r["engine"])["shared"] \
        % r["engine"]["state_snapshot_stride"] == 0


def test_the_counting_rule_by_hand():
    # a sequence and layer: 30 x 96 x 192 float32, and three rows of
    # 30 x (96 + 96 + 192) bf16
    assert roofline_delta.state_bytes(WIDTHS) \
        == 30 * 96 * 192 * 4 + 3 * 11520 * 2 == 2_280_960
    assert roofline_delta.delta_layers(WIDTHS, 16) == 12
    assert roofline_delta.delta_layers(WIDTHS, 32) == 24
    assert roofline_delta.delta_layers(WIDTHS, 3) == 3
    # a decode step of 12 live rows in twelve layers: each reads and
    # writes its state once, 7 FLOP a state element
    flops, nbytes = roofline_delta.scan_decode(12 * 12, WIDTHS)
    assert flops == 144 * 7 * 552_960
    assert nbytes == 144 * 2 * 2_280_960
    # the bytes bind: 657 MB at 819 GB/s is 0.80 ms, the FLOPs 2.8 us
    least = roofline.min_seconds(flops, nbytes, "TPU v5 lite")
    assert least == pytest.approx(nbytes / 819e9)
    assert least == pytest.approx(0.802e-3, rel=0.01)
    # a whole chunk of 2,048 tokens, one sequence, one layer, four
    # snapshots: a token and head Q K^T and K K^T 2 x 2 x 64 x 96, the
    # solve applied to K and V 2 x 64 x 288, W S_0 and Q S_0 2 x 2 x 96 x
    # 192, tril(.) V' 2 x 64 x 192, the state's update 2 x 96 x 192
    per_head = 24_576 + 36_864 + 73_728 + 24_576 + 36_864
    assert per_head == 196_608
    flops, nbytes = roofline_delta.scan_prefill(2048, 1, 4, WIDTHS)
    assert flops == 2048 * 30 * per_head
    assert nbytes == 2048 * (11520 + 60 + 5760) * 2 + (2 + 4) * 2_280_960
    # 12.1 GFLOP is 61 us, 84.7 MB is 103 us: the bytes bind
    assert roofline.min_seconds(flops, nbytes, "TPU v5 lite") \
        == pytest.approx(nbytes / 819e9)
    assert nbytes / 819e9 == pytest.approx(103.4e-6, rel=0.01)
    assert roofline_delta.scan_decode(0, WIDTHS) == (0.0, 0.0)
    assert roofline_delta.scan_prefill(0, 0, 0, WIDTHS) == (0.0, 0.0)


def _obs():
    ops = {
        "fusion.1": {"module": "jit__decode_fn", "seconds": 0.5,
                     "scope": "layer/delta/delta_scan"},
        "fusion.2": {"module": "jit__decode_fn", "seconds": 0.25,
                     "scope": "layer/delta/delta_conv"},
        "fusion.3": {"module": "jit__decode_fn", "seconds": 1.0,
                     "scope": "layer/delta/delta_in_proj"},
        "fusion.4": {"module": "jit__decode_fn", "seconds": 1.0,
                     "scope": "layer/mlp"},
        "fusion.5": {"module": "jit__prefill_fn", "seconds": 0.125,
                     "scope": "layer/delta/delta_scan/while"},
        "fusion.6": {"module": "jit__prefill_fn", "seconds": 0.125,
                     "scope": "layer/delta/delta_conv"},
        "fusion.7": {"module": "jit__prefill_fn", "seconds": 0.75,
                     "scope": "layer/delta/delta_scanner"},
    }
    return {
        "model": dict(WIDTHS, n_layers=16, prefill_chunk=2048, itemsize=2,
                      kv_block_size=16),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "engine": {"state_cut_blocks_total": 90,
                   "state_matched_blocks_total": 1000},
        "engine_end": {"state_snapshots_live": 72,
                       "state_snapshots_total": 96},
        "engine_config": {"decode_slots": 16},
        "trace": {"chips": 1, "busy_s": 5.0, "window_s": 6.0,
                  "op_calls": ops,
                  "by_scope": {"layer/delta": 3.0,
                               "layer/delta/delta_scan": 0.625,
                               "layer/mlp": 1.0},
                  "engine": {"delta_decode_rows_total": 11 * 100,
                             "delta_prefill_tokens_total": 20_000,
                             "delta_prefill_calls_total": 25,
                             "state_snapshots_taken_total": 18}}}


def test_the_readers_on_made_up_observations():
    obs = _obs()
    kind = "TPU v5 lite"
    assert device_trace.read(obs, "scope_share", scopes=["layer/delta"]) \
        == pytest.approx(60.0)
    # decode: the scan's and the convolution's ops of decode programs,
    # 0.75 s, against 1,100 rows in twelve layers
    least = roofline.min_seconds(
        *roofline_delta.scan_decode(1100 * 12, WIDTHS), kind)
    assert delta.read(obs, "decode_roofline") \
        == pytest.approx(100.0 * least / 0.75)
    # prefill: 0.25 s (a scope that only starts like one is not counted)
    least = roofline.min_seconds(*roofline_delta.scan_prefill(
        20_000 * 12, 25 * 12, 18 * 12, WIDTHS), kind)
    assert delta.read(obs, "prefill_roofline") \
        == pytest.approx(100.0 * least / 0.25)
    assert engine.read(obs, num="state_cut_blocks_total",
                       den="state_matched_blocks_total", scale=100.0) == 9.0
    assert field.read(obs, ["engine_end", "state_snapshots_live"], 100.0,
                      ["engine_end", "state_snapshots_total"]) == 75.0
    # a program without the counters, the widths or the scopes (the
    # parent), a rehearsal, no trace: nothing to read, and no error
    old = _obs()
    old["trace"]["engine"] = {"decode_steps": 7}
    old["engine"], old["engine_end"] = {"decode_steps": 9}, {"free_slots": 1}
    dense = _obs()
    dense["model"] = {"n_layers": 8, "itemsize": 2}
    bare = _obs()
    bare["trace"]["op_calls"] = {"fusion.4": bare["trace"]["op_calls"][
        "fusion.4"]}
    for o in (old, dense, bare, dict(obs, trace=None),
              dict(obs, device={"platform": "cpu", "kind": "cpu"})):
        for what in ("decode_roofline", "prefill_roofline"):
            assert delta.read(o, what) is None
    assert spec.read_metrics(ENTRIES[3:], old) == {}
    with pytest.raises(ValueError, match="unknown quantity"):
        delta.read(obs, "no_such")


def _paged_obs():
    """A traced stretch of a stack whose layers are one in four paged:
    30 MHA heads of 128, pages of 16, a pool of 100 pages and a trash
    page in the FOUR paged layers."""
    pool = 4 * 101 * 30 * 16 * 128
    def op(ident, shape, seconds, scope=""):
        return {"name": f"%{ident} = {shape}{{1,0}} fusion(%p.1)",
                "kind": ident.split(".")[0], "seconds": seconds,
                "scope": scope}
    ops = {
        # the paged kernel: 0.5 s in decode programs, 0.25 in prefill
        "a": op("paged_attention.1", "bf16[16,30,16,128]", 0.75),
        # the rows' scatters: the pool whole, whatever scope they kept
        "b": op("fusion.2", f"bf16[{pool // 128},128]", 0.25),
        "c": op("fusion.3", f"bf16[{pool // 128},128]", 0.125, "layer/attn"),
        # the index arithmetic: counted once, by its scope
        "d": op("fusion.4", "s32[1,2048,1]", 0.125, "layer/attn/kv_write"),
        # the snapshot rows of the state: larger than a layer of the
        # pool, and no write of it
        "e": op("fusion.5", "f32[12,97,96,5760]", 1.0,
                "layer/delta/delta_scan"),
    }
    reqs = [
        # a hit: 1,100 shared tokens are cut back to 1,024
        {"due": 1.0, "tokens": [1.5], "prompt_len": 1132, "shared": 1100},
        # a whole-prompt match stops short of the last token: 2,048 of
        # 2,560 cached
        {"due": 2.0, "tokens": [2.5], "prompt_len": 2560, "shared": 2560},
        # a miss; one due after the window; one that got nothing
        {"due": 3.0, "tokens": [3.5], "prompt_len": 3000, "shared": 0},
        {"due": 7.0, "tokens": [7.5], "prompt_len": 999, "shared": 0},
        {"due": 4.0, "tokens": [], "prompt_len": 999, "shared": 0}]
    return {
        "model": dict(WIDTHS, n_layers=16, prefill_chunk=2048, itemsize=2,
                      kv_block_size=16, num_kv_blocks=101, n_heads=30,
                      kv_heads=30, head_dim=128),
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "window_s": 6.0, "requests": reqs,
        "engine": {"prefill_chunks": 8},
        "engine_config": {"state_snapshot_stride": 512},
        "trace": {"chips": 1, "busy_s": 5.0, "window_s": 6.0,
                  "op_calls": ops,
                  "by_module_kind": {
                      "jit__decode_fn|paged_attention": 0.5,
                      "jit__prefill_fn|paged_attention": 0.25,
                      "jit__prefill_fn|fusion": 3.0},
                  "by_scope": {"layer/attn/kv_write": 0.125},
                  "engine": {"decode_pages_live": 50_000,
                             "prefill_chunks": 2}}}


def test_the_paged_layers_reader_counts_the_layers_that_have_pages():
    obs = _paged_obs()
    m, kind = obs["model"], "TPU v5 lite"
    assert paged_layers.paged_layers(m) == 4
    assert paged_layers.paged_layers(dict(m, n_layers=6)) == 1
    # decode: 50,000 live pages a layer, FOUR layers (not sixteen)
    least = roofline.min_seconds(*roofline.paged_decode(50_000 * 4, m), kind)
    assert paged_layers.read(obs, "decode_roofline", module="decode") \
        == pytest.approx(100.0 * least / 0.5)
    page = 2 * 30 * 16 * 128 * 2          # k and v of one page, one layer
    assert least == pytest.approx(50_000 * 4 * page / 819e9)
    # prefill: the three requests of the window that were answered, each
    # from what a hit is cut back to; a quarter of the window's chunks
    work = [roofline.paged_prefill(n, cached, 2048, m) for n, cached in (
        (1132, 1024), (2560, 2048), (3000, 0))]
    least = 4 * (2 / 8) * roofline.min_seconds(
        sum(f for f, _ in work), sum(b for _, b in work), kind)
    assert paged_layers.read(obs, "prefill_roofline", module="prefill") \
        == pytest.approx(100.0 * least / 0.25)
    # the writes of the pool: the two scatters and the scope, of 5 s busy
    scopes = ["layer/attn/kv_write", "kv_copy"]
    assert paged_layers.read(obs, "kv_write_share", scopes=scopes) \
        == pytest.approx(100.0 * (0.25 + 0.125 + 0.125) / 5.0)
    # device_trace's size test would have taken the snapshot rows too
    assert device_trace.read(obs, "kv_write_share", scopes=scopes) \
        == pytest.approx(100.0 * 1.5 / 5.0)
    # through the metric files
    got = spec.read_metrics(PAGED_ENTRIES, obs)
    assert set(got) == {e["name"] for e in PAGED_ENTRIES}
    assert all(0 < v["value"] < 100 for v in got.values())
    # nothing to read: every layer paged (device_trace's to read), no
    # paged kernel in the stretch, a rehearsal, no trace
    dense = _paged_obs()
    dense["model"].pop("layer_pattern")
    idle = _paged_obs()
    idle["trace"]["by_module_kind"] = {"jit__prefill_fn|fusion": 3.0}
    for o in (dense, dict(obs, trace=None),
              dict(obs, device={"platform": "cpu", "kind": "cpu"})):
        assert spec.read_metrics(PAGED_ENTRIES, o) == {}
    assert set(spec.read_metrics(PAGED_ENTRIES, idle)) \
        == {"kv_rows_write_share.tok"}
    with pytest.raises(ValueError, match="unknown quantity"):
        paged_layers.read(obs, "no_such")


def test_the_manifest_holds_the_configuration_the_cell_and_its_entries():
    """One configuration, one cell and eight per-layer metrics, each found
    by its name and as it was written; the accepted ``.tok`` metrics
    that read this cell rightly list it; no other cell's line carries
    the five."""
    config = manifest_by_name.configuration(CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    entered = manifest_by_name.cell(CELL)
    assert (entered["config"], entered["traffic"], entered["chips"]) \
        == (CONFIG, "serve_shared_docs12", 1)
    assert len(entered["why"]) <= 200
    assert CELL in manifest_by_name.metric("serve_tok_s")[1]
    for m in ENTRIES + PAGED_ENTRIES:
        entry, cells = manifest_by_name.metric(m["name"])
        assert entry == m and CELL in cells
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tok_s", "setup_s"}
    accepted = {f"{base}.tok" for base in (
        "prefill_chunk_ms", "decode_step_ms", "decode_occupancy",
        "kv_pool_live_share", "device_idle_share", "prefix_hit_rate",
        "paged_kernel_share", "decode_device_ms", "prefill_device_ms",
        "fetch_found_ready_share", "tick_ms", "host_ms_per_tick",
        "decode_launch_ms", "prefill_launch_ms", "host_gap_share",
        "programs_ahead_share", "ttft_queue_ms", "ttft_prefill_wait_ms",
        "ttft_prefill_ms", "idle_in_tick_share",
        "profiler_launch_stretch")} | {
        "closed_ttft_p50_ms", "ready_s", "hbm_in_use_share",
        "compiles_in_window"}
    line = manifest_by_name.line_of(CELL)
    assert line >= accepted | {m["name"] for m in ENTRIES + PAGED_ENTRIES}
    # left out, and why (PERF.md section 4): no experts; another
    # recurrence's reader; the window layers' cut; they multiply one
    # page size by n_layers where four layers in sixteen have pages
    # (readers/paged_layers.py counts the layers that have)
    assert not line & {
        "moe_share.tok", "ssm_share.tok", "ssm_scan_decode_roofline.tok",
        "ssm_scan_prefill_roofline.tok", "prefix_hits_cut_share.tok",
        "paged_decode_roofline.tok", "paged_prefill_roofline.tok",
        "kv_write_share.tok"}
    assert manifest_by_name.carried_only_by(
        {m["name"] for m in ENTRIES + PAGED_ENTRIES}, CELL)
    for what in ("decode", "prefill"):
        read, args = spec.metric_reader(
            f"paged_layers_{what}_roofline.tok")
        assert read is paged_layers.read
        assert args == {"what": f"{what}_roofline", "module": what}
    read, args = spec.metric_reader("kv_rows_write_share.tok")
    assert read is paged_layers.read and args["what"] == "kv_write_share"
    read, args = spec.metric_reader("delta_share.tok")
    assert read is device_trace.read
    assert args == {"what": "scope_share", "scopes": ["layer/delta"]}
    for kind in ("decode", "prefill"):
        read, args = spec.metric_reader(f"delta_scan_{kind}_roofline.tok")
        assert read is delta.read and args == {"what": f"{kind}_roofline"}
    assert spec.metric_reader("state_cut_share.tok")[0] is engine.read
    assert spec.metric_reader("state_snapshot_live_share.tok")[0] \
        is field.read
    got = spec.read_metrics(ENTRIES, _obs())
    assert set(got) == {m["name"] for m in ENTRIES}
    assert got["delta_share.tok"]["value"] == pytest.approx(60.0)
    assert got["state_cut_share.tok"]["value"] == 9.0
    assert got["state_snapshot_live_share.tok"]["value"] == 75.0
    assert all(0 < v["value"] < 100 for v in got.values())


def test_the_cell_rehearses_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 56), "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=420,
        env=env, cwd=spec.ROOT, preexec_fn=lambda: os.nice(15))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert {"prefill_chunk_ms.tok", "decode_step_ms.tok",
            "kv_pool_live_share.tok", "compiles_in_window",
            "prefix_hit_rate.tok", "state_cut_share.tok",
            "state_snapshot_live_share.tok"} <= names
    # the prefix cache serves a model with recurrent state
    assert line["metrics"]["prefix_hit_rate.tok"]["value"] > 10
    # device numbers are not taken from a CPU
    assert not names & {"delta_share.tok", "delta_scan_decode_roofline.tok",
                        "delta_scan_prefill_roofline.tok",
                        "device_idle_share.tok"}
    assert line["compared"]["logits"][0] < 1e-4
