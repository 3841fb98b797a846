"""The trace reduction on a small trace recorded on a v5e (PR 24) and on
hand-made intervals."""
import json
import os

import pytest

from benchmarks import roofline, trace as T
from benchmarks.readers import device_trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "v5e_trace.json")) as f:
        return json.load(f)


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.total(T.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert T.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert T.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert T.clip([(0, 5), (7, 9)], 4, 8) == [(4, 5), (7, 8)]


def test_op_names_are_read_from_the_hlo_text():
    name = ("%flash_fwd.1 = (bf16[1,16,2048,256]{3,2,1,0:T(8,128)(2,1)S(1)}, "
            "f32[1,16,1,2048]{3,2,1,0:T(1,128)}) custom-call(bf16[1,16")
    assert T.op_id(name) == "flash_fwd.1"
    assert T.op_kind(name) == "flash_fwd"
    assert T.result_shape(name) == ("bf16", (1, 16, 2048, 256))
    assert T.short_name(name) == "flash_fwd.1_bf16_1_16_2048_256_"
    assert T.op_kind("%copy.148 = bf16[6,1921,16,16,256]{4,3} copy(") == "copy"
    assert T.is_collective("%all-gather-start.3 = (bf16[8,1024]")
    assert T.is_collective("%reduce-scatter.7 = f32[1024]{0} reduce-scatter(")
    assert not T.is_collective("%fusion.12 = bf16[4,4]{1,0} fusion(")


def test_recorded_trace_reduces_to_busy_idle_and_kernels(recorded):
    s = T.summarize(recorded)
    assert s["chips"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    gaps = sum(s["idle_gaps"].values())
    assert gaps + s["busy_s"] == pytest.approx(s["window_s"], rel=1e-6)
    assert s["exposed_collective_s"] == 0.0          # one chip: none
    kinds = {k.split("|")[1] for k in s["by_module_kind"]}
    assert {"flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq",
            "paged_attention"} <= kinds
    assert s["by_module_kind"]["jit_fwd|flash_fwd"] > 0
    top = s["breakdown"]["device_ops"]
    assert len(top) <= 10 and top[0][0].startswith("paged_attention")
    assert top == sorted(top, key=lambda kv: -kv[1])
    assert len(s["breakdown"]["idle_gaps"]) <= 10


def test_flash_roofline_from_the_recorded_trace_is_a_share(recorded):
    s = T.summarize(recorded)
    obs = {"trace": s, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    share = device_trace.read(obs, "flash_roofline")
    # 2048 x 2048 causal at head_dim 256: compute bound, well under peak
    assert 10.0 < share < 100.0
    assert 0.0 < device_trace.read(obs, "flash_share") < 100.0
    assert device_trace.read(obs, "idle_share") == pytest.approx(
        100.0 * (1 - s["busy_s"] / s["window_s"]))
    # nothing to read without a trace, or off the chip
    assert device_trace.read({"trace": None, "device": obs["device"]},
                             "idle_share") is None
    assert device_trace.read({"trace": s, "device": {"platform": "cpu"}},
                             "idle_share") is None


def test_exposed_collective_time_is_what_no_compute_covers():
    chip = {"ops": [["%fusion.1 = f32[8]{0} fusion(", 0.0, 4.0],
                    ["%fusion.2 = f32[8]{0} fusion(", 8.0, 2.0]],
            "async": [["%all-gather-start.1 = (f32[8]{0}) all-gather-start(",
                       3.0, 4.0]],
            "modules": [["jit_step(1)", 0.0, 10.0]]}
    tr = {"chips": {"/device:TPU:0": chip, "/device:TPU:1": chip},
          "host": [["t", T.OPEN_MARK, -1e-6, 1e-6],
                   ["t", T.CLOSE_MARK, 10.0 - 1e-6, 1e-6],
                   ["t", "$engine.py:1 step", 7.0, 1.0]]}
    s = T.summarize(tr)
    assert s["chips"] == 2 and s["window_s"] == pytest.approx(10.0)
    assert s["exposed_collective_s"] == pytest.approx(3.0)   # 4..7
    assert s["busy_s"] == pytest.approx(9.0)                 # idle 7..8
    assert s["idle_gaps"] == {"$engine.py:1 step": pytest.approx(1.0)}


def test_counting_rules_and_peaks():
    flops, nbytes = roofline.flash_call("flash_fwd", (1, 16, 2048, 256))
    assert flops == 2 * 16 * 2048 * 2048 * 256
    assert nbytes == 4 * 16 * 2048 * 256 * 2
    assert roofline.min_seconds(197e12, 0, "TPU v5 lite") == pytest.approx(1.0)
    assert roofline.min_seconds(0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")                  # unknown kind: an error
    m = {"kv_block_size": 16, "head_dim": 256, "kv_heads": 16,
         "n_heads": 16, "itemsize": 2}
    flops, nbytes = roofline.paged_decode(10, m)
    assert nbytes == 10 * 2 * 16 * 16 * 256 * 2
    assert flops == 10 * 16 * 4 * 16 * 256
    # a fully cached prompt needs no prefill work
    f0, b0 = roofline.paged_prefill(512, 512, 256, m)
    assert f0 == 0 and b0 == 0
    f1, _ = roofline.paged_prefill(512, 0, 256, m)
    assert f1 == 4.0 * 16 * 256 * (512 * 513 // 2)
    per_token = roofline.train_flops_per_token(10, 2, 4, 8, 16)
    assert per_token == 6 * 10 + 6 * 2 * 4 * 8 * 16
