"""The openPangu-Ultra-MoE configuration and its long-document cell: the
file holds the published numbers under their own keys and states its
cuts, the traffic file the cell's stated parameters, the new counting
rules against shapes counted by hand, the new readers on made-up
observations, the manifest's configuration, cell and entries found by
name, the cell rehearsed end to end on the CPU, and the dense configurations' step
programs compiled for a described v5e with the ops they had before this
configuration came."""
import json
import os
import re
import subprocess
import sys

import pytest

import manifest_by_name
from benchmarks import roofline, roofline_latent, spec
from benchmarks.readers import latent

CONFIG = "openpangu-ultra-moe-718b"
CELL = CONFIG + ".serve_longdoc16"
#: the catalog's row (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
WIDTHS = {"n_heads": 128, "kv_lora_rank": 512, "qk_rope_dim": 64,
          "d_model": 7680, "expert_width": 2048, "experts_per_token": 8,
          "experts_held": 16, "n_experts": 256, "n_dense_layers": 1}


def test_the_file_and_the_manifest_hold_the_published_numbers_and_the_cuts():
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = manifest_by_name.configuration(cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert cfg["published"] == PUBLISHED
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key      # as published
    assert cfg["num_hidden_layers"] == cell.depth == 5
    assert cfg["depth"]["published"] == 61 and cfg["depth"]["here"] == 5
    assert cfg["n_routed_experts"] == 16
    assert cfg["held"]["n_routed_experts"]["published"] == 256
    # the vocabulary is sliced too: the key as run, listed in
    # ``reduced`` (since PR 44), the published count beside it
    rows = cfg["held"]["vocab_rows"]
    assert (rows["published"], rows["here"]) == (153600, 19200)
    assert cfg["vocab_size"] == cfg["program"]["vocab_size"] == 19200
    assert "reduced" in rows["why"]
    assert "16 chips share each layer" in cfg["deployment"]
    for item in ("router", "sandwich_norm", "rotary_layout", "rope",
                 "softmax_scale"):
        assert item in cfg["assumed"], item
    assert "NOT run" in cfg["departures"]["num_nextn_predict_layers"]
    assert "refuses" in cfg["departures"]["training"]
    # what the program is built from says the same widths
    kw = cell.model_kwargs()
    assert (kw["d_model"], kw["n_heads"], kw["head_dim"], kw["d_ff"]) \
        == (7680, 128, 192, 18432)
    assert (kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_nope_dim"],
            kw["qk_rope_dim"], kw["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (kw["n_experts"], kw["experts_per_token"], kw["expert_width"],
            kw["shared_expert_width"], kw["experts_held"],
            kw["expert_first"]) == (256, 8, 2048, 2048, 16, 0)
    assert (kw["router_score"], kw["routed_scale"], kw["sandwich_norm"],
            kw["n_dense_layers"], kw["norm_eps"], kw["rope_base"]) \
        == ("sigmoid", 2.5, True, 1, 1e-5, 25.6e6)
    assert kw["vocab_size"] == 19200 and kw["n_layers"] == 5
    hp = dict(cell.reference_hp())
    assert (hp["expert_first"], hp["experts_held"],
            hp["num_experts_per_tok"]) == (0, 16, 8)
    assert cfg["reference"] == "pangu"
    # the limit lies between the sound readings and the precision
    # control's, on weights whose routed part is an eighth (and the file
    # says what that costs)
    assert set(cfg["tolerance"]) == {"logits", "why"}
    assert cfg["tolerance"]["logits"] == 0.05
    assert "NOT told apart" in cfg["tolerance"]["why"]
    assert cfg["weights"]["residual_writers"] == ["layers.we_down"]
    assert cfg["weights"]["stream_scale"] == 0.125
    assert "The price" in cfg["weights"]["why"]
    # the rehearsal keeps every form, at a narrow width
    small = spec.load_cell(CELL, rehearse=True).model_kwargs()
    assert small["kv_lora_rank"] and small["sandwich_norm"] \
        and small["n_dense_layers"] == 1 < small["n_layers"] \
        and small["experts_held"] < small["n_experts"]


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(spec.HERE, "reference", "pangu.py")).read()
    assert "ray_tpu" not in src.split('"""', 2)[2]
    assert "from .common import F32, make_api" in src


@pytest.mark.parametrize("wrong_hp,ok,least", [
    ({}, True, 0.0), ({"rms_norm_eps": 0.5}, False, 0.1),
    ({"routed_scaling_factor": 1.0}, True, 0.005)])
def test_the_harness_check_holds_the_program_to_the_reference(
        wrong_hp, ok, least):
    """``check.serve_check`` as the cell runs it, at the rehearsal's
    widths in float32, on the cell's own weights (the routed experts'
    down-projections at an eighth, ``weights``): a prompt past one chunk
    (prefill, then decode through the paged latent cache) against
    ``reference/pangu.py``. A reference with another norm is refused. One
    that scales the routed weights otherwise reads fifty times the sound
    program's error and is NOT refused by the cell's limit: the price of
    the eighth, which ``tolerance.why`` states."""
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
        model, jax.random.PRNGKey(spec.weight_seed(2**31 + 37)),
        dtype=model.dtype)), cell.config["weights"])
    verdict = check.serve_check(cell, model, params, engine, 2**31 + 37)
    assert verdict["sample"] == {"prompt_len": 99, "n_decode": 8}
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
    assert (p["clients"], p["client_threads"]) == (16, 16)
    assert p["doc_lengths"] == [8192, 12288, 16384, 20480, 24576, 28672,
                                30720, 10240, 14336, 18432, 22528, 26624]
    assert p["answer_lengths"] == [64, 96, 128, 160, 192, 224, 256, 80,
                                   112, 144]
    assert (p["questions_per_doc"], p["question_len"]) == (4, 64)
    # sixteen clients start on twelve different lengths (a stride that
    # shares a factor with 12 would start them all on three or four)
    starts = {(c * p["doc_stride"]) % len(p["doc_lengths"])
              for c in range(p["clients"])}
    assert len(starts) == 12
    e = p["engine"]
    assert (e["decode_slots"], e["max_seq_len"], e["prefill_chunk"],
            e["kv_block_size"], e["max_new_tokens"]) \
        == (16, 32768, 2048, 16, 256)
    # ISSUE 37's 40,960 pages and the trash page: 4.19 GB of 640-wide
    # rows in five layers, the largest tried; it ran at 86.6% of memory
    assert e["num_kv_blocks"] == 40961 >= 32769
    assert (e["num_kv_blocks"] - 1) * 16 * 640 * 2 * 5 == 4194304000
    # every client at its longest at once fits the pool
    longest = max(p["doc_lengths"]) + p["question_len"] \
        + max(p["answer_lengths"])
    assert longest < e["max_seq_len"]
    assert p["clients"] * -(-longest // 16) < e["num_kv_blocks"]
    from benchmarks import traffic
    assert traffic.check_sample(e)["prompt_len"] == 3075 > e["prefill_chunk"]


def test_latent_counts_by_hand():
    # a key: 128 heads x (576-wide dot + 512-wide value row) x 2, 1152 B
    assert roofline_latent.key_cost(WIDTHS) == (278528.0, 1152.0)
    # on the v5e's ridge: 242 against 197e12 / 819e9 = 240.5
    assert 278528 / 1152 == pytest.approx(241.8, abs=0.1)
    flops, nbytes = roofline_latent.latent_decode(10, 16, WIDTHS)
    assert (flops, nbytes) == (160 * 278528.0, 160 * 1152.0)
    # a 40-token prompt, 32 cached, chunks of 4: positions 32..39 meet
    # 33..40 keys; chunk one reads pages of 36 rows (3), chunk two 40
    flops, nbytes = roofline_latent.latent_prefill(40, 32, 4, 16, WIDTHS)
    assert flops == sum(range(33, 41)) * 278528.0
    assert nbytes == (3 + 3) * 16 * 1152 + 2 * 4 * 128 * (1024 + 64) * 2
    # the cell's decode step: 16 tokens x 8 choices x 16/256 = 8 rows
    # through three 7680 x 2048 matrices; eight rows land on 6.45 of the
    # sixteen held experts, whose weights are what must be read
    one = 3 * 7680 * 2048 * 2
    flops, nbytes = roofline_latent.moe_held(16, WIDTHS)
    assert flops == 6 * 8 * 7680 * 2048
    touched = 16 * (1 - (15 / 16) ** 8)
    assert touched == pytest.approx(6.45, abs=0.01)
    assert nbytes == pytest.approx(
        touched * one + 8 * 2 * (2 * 7680 + 3 * 2048))
    # a chunk: 1024 rows touch all sixteen (1.5 GB a layer, not one
    # expert's 94 MB)
    flops, nbytes = roofline_latent.moe_held(2048, WIDTHS)
    assert flops == 6 * 1024 * 7680 * 2048
    assert nbytes == pytest.approx(
        16 * one + 1024 * 2 * (2 * 7680 + 3 * 2048))
    assert 16 * one == pytest.approx(1.51e9, rel=0.01)
    assert roofline_latent.moe_held(0, WIDTHS) == (0.0, 0.0)


def _obs():
    req = {"due": 1.0, "tokens": [1.5, 1.6, 1.7], "prompt_len": 40,
           "shared": 35}
    return {
        "window_s": 10.0, "requests": [req, dict(req, tokens=[], due=2.0),
                                       dict(req, due=11.0)],
        "model": {"kv_block_size": 16, "prefill_chunk": 64, "n_layers": 3,
                  "itemsize": 2},
        "engine": {"prefill_chunks": 6},
        "engine_config": {"decode_slots": 4},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "trace": {"chips": 1, "busy_s": 2.0,
                  "engine": {"prefill_chunks": 3, "decode_steps": 10,
                             "decode_pages_live": 500},
                  "by_module_kind": {"jit__decode_fn|mla_attn": 0.25,
                                     "jit__prefill_fn|mla_attn": 0.5,
                                     "jit__decode_fn|ragged-dot-none": 0.125,
                                     "jit__prefill_fn|ragged-dot-none": 0.125,
                                     "jit__decode_fn|fusion": 1.0}}}


#: as written but for their ``workloads``, which hold this cell
ENTRIES = [{"name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "serve_tok_s"}
           for name, better, layer in (
    ("latent_attn_share.tok", "lower", "kernels, latent attention"),
    ("latent_decode_roofline.tok", "higher", "kernels, latent attention"),
    ("latent_prefill_roofline.tok", "higher", "kernels, latent attention"),
    ("moe_held_roofline.tok", "higher", "kernels, experts"))]


def test_the_readers_on_made_up_observations(tmp_path, monkeypatch):
    obs = _obs()
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "made-up.json").write_text(
        json.dumps({"program": WIDTHS}))
    monkeypatch.setattr(spec, "HERE", str(tmp_path))
    kind = "TPU v5 lite"
    assert latent.read(obs, "kernel_share", kinds=["mla_attn"]) \
        == pytest.approx(37.5)
    least = roofline.min_seconds(
        *roofline_latent.latent_decode(500 * 3, 16, WIDTHS), kind)
    assert latent.read(obs, "decode_roofline", kinds=["mla_attn"],
                       module="decode", config="made-up") \
        == pytest.approx(100.0 * least / 0.25)
    # one request counts (32 of its 40 tokens cached); the stretch ran
    # half the window's chunks
    least = 3 * 0.5 * roofline.min_seconds(
        *roofline_latent.latent_prefill(40, 32, 64, 16, WIDTHS), kind)
    assert latent.read(obs, "prefill_roofline", kinds=["mla_attn"],
                       module="prefill", config="made-up") \
        == pytest.approx(100.0 * least / 0.5)
    # two of the three layers route
    least = sum(calls * 2 * roofline.min_seconds(
        *roofline_latent.moe_held(tokens, WIDTHS), kind)
        for calls, tokens in ((3, 64), (10, 4)))
    assert latent.read(obs, "moe_held_roofline", kinds=["ragged-dot-none"],
                       config="made-up") \
        == pytest.approx(100.0 * least / 0.25)
    # a program without these kernels (the parent), a rehearsal, no
    # trace: nothing to read, and no error
    assert latent.read(obs, "kernel_share", kinds=["no-such"]) is None
    obs["trace"]["by_module_kind"] = {"jit__decode_fn|fusion": 1.0}
    for what in ("kernel_share", "decode_roofline", "prefill_roofline"):
        assert latent.read(obs, what, kinds=["mla_attn"],
                           config="made-up") is None
    assert latent.read(dict(obs, device={"platform": "cpu"}),
                       "kernel_share", kinds=["fusion"]) is None
    assert latent.read(dict(obs, trace=None), "decode_roofline",
                       kinds=["mla_attn"], config="made-up") is None
    with pytest.raises(ValueError, match="unknown quantity"):
        latent.read(_obs(), "no_such", kinds=["mla_attn"],
                    config="made-up")


def test_the_manifest_holds_the_configuration_the_cell_and_the_entries():
    """One configuration, one cell and four per-layer metrics, each
    found by its name and as it was written; the accepted ``.tok``
    metrics that mean the same thing here list the cell; no other
    cell's line carries the four, which read this configuration's
    kernels and file."""
    config = manifest_by_name.configuration(CONFIG)
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == spec.load_cell(CELL).config["source"]
    entered = manifest_by_name.cell(CELL)
    assert (entered["config"], entered["traffic"], entered["chips"]) \
        == (CONFIG, "serve_longdoc16", 1)
    for m in ENTRIES:
        entry, cells = manifest_by_name.metric(m["name"])
        assert entry == m and CELL in cells
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tok_s", "setup_s"}
    accepted = {"prefill_chunk_ms.tok", "decode_step_ms.tok",
                "decode_occupancy.tok", "kv_pool_live_share.tok",
                "prefix_hit_rate.tok", "closed_ttft_p50_ms",
                "device_idle_share.tok", "ready_s", "hbm_in_use_share",
                "compiles_in_window"}
    # what PR 39 lets every closed-loop cell read of the engine's own
    # books (traced, of its annotations), PR 41's timing of its programs
    # and the grouped products' share (listed for this cell by PR 44)
    books = {f"{base}.tok" for base in (
        "tick_ms", "host_ms_per_tick", "decode_launch_ms",
        "prefill_launch_ms", "host_gap_share", "programs_ahead_share",
        "ttft_queue_ms", "ttft_prefill_wait_ms", "ttft_prefill_ms",
        "idle_in_tick_share", "profiler_launch_stretch",
        "decode_device_ms", "prefill_device_ms", "fetch_found_ready_share",
        "moe_share")}
    # the line holds at least these: a later PR may list the cell in more
    assert manifest_by_name.line_of(CELL) \
        >= accepted | books | {m["name"] for m in ENTRIES}
    assert manifest_by_name.carried_only_by(
        {m["name"] for m in ENTRIES}, CELL)
    for m in ENTRIES:
        read, args = spec.metric_reader(m["name"])
        assert read is latent.read and args["kinds"]
    line = spec.read_metrics(ENTRIES, _obs() | {"requests": _obs()["requests"]})
    assert set(line) <= {m["name"] for m in ENTRIES}
    assert line["latent_attn_share.tok"]["value"] == pytest.approx(37.5)


def test_the_cell_rehearses_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 37), "--seconds", "2", "--trace", "1",
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
    assert not names & {"device_idle_share.tok", "latent_attn_share.tok",
                        "latent_decode_roofline.tok"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    notes = [l for l in r.stderr.splitlines() if "[bench] notes" in l][-1]
    check = json.loads(notes.split("notes: ", 1)[1])
    # a sample past one chunk: prefill then decode through the cache
    assert check["check"]["sample"]["prompt_len"] == 99
    assert check["check"]["errors"]["logits"] < 1e-4
    assert check["served_check"]["prefix_hit_blocks"][1] >= 5
    assert check["pool_audit"] == []


# --------------------------------------- the dense cells' step programs
def _step_ops(cell_name):
    """Instructions of the (prefill, decode) step programs of a dense
    serving cell, compiled for a described v5e at the cell's own sizes
    (nothing attached; the kernels chosen as on the chip)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmarks import harness
    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params)
    from ray_tpu.serve.llm_engine import EngineConfig, _step_fns
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cell = spec.load_cell(cell_name)
    eng = cell.params["engine"]
    kw = dict(cell.model_kwargs(), remat_policy="none",
              max_seq_len=eng["max_seq_len"])
    kw["dtype"] = harness.resolve_dtype(kw["dtype"])
    cfg = TransformerConfig(**kw)
    ec = EngineConfig(**eng)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)
    params = shaped(jax.eval_shape(lambda: init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    cache = shaped(jax.eval_shape(lambda: init_kv_cache(
        cfg, ec.resolved_num_blocks, ec.kv_block_size)))
    prefill_fn, decode_fn, _ = _step_fns(cfg, ec)
    counts = []
    for fn, rows in ((prefill_fn, (1, ec.prefill_chunk + 2
                                   + ec.blocks_per_seq)),
                     (decode_fn, (ec.decode_slots, 2 + ec.blocks_per_seq))):
        text = jax.jit(fn, donate_argnums=(2,)).lower(
            params, jax.ShapeDtypeStruct(rows, jnp.int32, sharding=one),
            cache).compile().as_text()
        counts.append(len(re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = ", text,
                                     re.M)))
    return tuple(counts)


@pytest.mark.parametrize("cell_name,ops", [
    ("gptj-6b.serve_chat", (1084, 1033)),
    ("mistral-7b-v0.3.serve_docqa", (887, 908)),
])
def test_the_dense_step_programs_have_the_ops_they_had(
        cell_name, ops, monkeypatch):
    """The paged kernel gained a latent form, the row scatter a function
    of its own and the layer scan a second kind of layer: the dense
    configurations' compiled step programs are, instruction for
    instruction, what the commit before this configuration compiled
    (counted there with this function)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        got = _step_ops(cell_name)
    except RuntimeError as e:           # no topology can be described
        pytest.skip(str(e))
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert got == ops
