"""bench_serve.py smoke: the serving benchmark must run end-to-end on
the CPU backend (tiny workload) and emit a record the serve perf gate
can parse — the CI guard that keeps the SERVE metric producible."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

pytestmark = [pytest.mark.serve_llm]


@pytest.mark.slow
def test_bench_serve_smoke_subprocess():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_serve.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = [ln for ln in r.stdout.strip().splitlines()
            if ln.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["metric"] == "serve_tokens_per_s_chip"
    assert rec["value"] > 0
    d = rec["detail"]
    assert d["backend"] == "cpu"
    for mode in ("continuous", "serial"):
        assert d[mode]["errors"] == [], d[mode]
        assert d[mode]["requests_done"] == d["requests"]
        assert d[mode]["ttft_ms"]["p50"] is not None
    # the fleet leg: 2 replicas behind gauge routing with a shared
    # system prompt >= 4 KV blocks — CI exercises the radix trie, the
    # speculative verify path and the router without a full record,
    # and it must stay CI-sized (<= 60s)
    fleet = d["fleet"]
    assert fleet["replicas"] == 2
    assert fleet["routing"] == "gauge"
    assert fleet["system_prompt_tokens"] >= \
        4 * d["engine"]["kv_block_size"]
    assert fleet["errors"] == [] and \
        fleet["baseline"]["errors"] == [], fleet
    assert fleet["requests_done"] == fleet["requests"]
    assert fleet["leg_wall_s"] <= 60.0, fleet["leg_wall_s"]
    assert fleet["prefix_hit_rate"] >= 0.5, fleet
    assert fleet["baseline"]["prefix_hit_rate"] in (0, 0.0), fleet
    assert fleet["spec_drafted"] > 0
    assert fleet["baseline"]["routing"] == "round_robin"
    # paged-kernel legs: exact parity at fp32-softmax tolerance and a
    # real mixed-length work reduction (FLOPs proportional to live
    # tokens, not the serving window)
    pk = d["paged_kernel"]
    assert pk["parity_max_abs"] < 1e-4
    assert 0 < pk["work_reduction"] < 1
    assert pk["pages_live"] < pk["pages_window"]
    ml = d["mixed_len"]
    assert ml["errors"] == []
    assert ml["work_reduction"] > 0.3, ml
    assert ml["decode_wall_s"] > 0 and ml["prefill_wall_s"] > 0
    # autoscaling under load: the fleet scaled up MID-RUN and the gauge
    # router actually sent traffic to the new replica
    su = d["scale_up"]
    assert su["errors"] == []
    assert su["scaled_up"] is True, su
    assert su["new_replica_tokens"] > 0, su
    assert su["replicas_end"] == 2
    assert su["ttft_recovery"] is not None
    # trace-overhead guard: both legs replay the same schedule clean,
    # the span-record hot path holds its <=20µs budget, and the
    # tokens/s ratio is recorded (within_2pct is the TPU-record gate;
    # on a noisy shared CPU the ratio itself is informational)
    to = d["trace_overhead"]
    assert to["tracing_on"]["errors"] == [], to
    assert to["tracing_off"]["errors"] == [], to
    assert to["tracing_on"]["tokens_total"] == \
        to["tracing_off"]["tokens_total"]
    assert to["span_record_us"] <= to["span_budget_us"], to
    assert to["overhead_pct"] is not None
    assert isinstance(to["within_2pct"], bool)
    # the record feeds the gate, fleet rows included
    from tools.perf_gate import extract_serve_metrics, parse_bench_record
    m = extract_serve_metrics(parse_bench_record(rec))
    assert m["serve_tokens_per_s_chip"] == rec["value"]
    assert m["serve/fleet_tokens_per_s_chip"] == \
        fleet["tokens_per_s_chip"]
    assert m["serve/fleet_prefix_hit_rate"] == fleet["prefix_hit_rate"]
    assert m["serve/mixed_len_work_reduction"] == ml["work_reduction"]
    assert m["serve/scaleup_new_replica_share"] == \
        su["new_replica_share"]
    # spans/µs inverse-cost row: >= 0.05 is exactly the <=20µs budget
    assert m["serve/trace_span_record_inv"] >= 0.05
    assert "serve/paged_kernel_speedup" not in m   # CPU: no kernel wall


def test_workload_is_seeded_and_stable():
    from bench_serve import make_workload
    a = make_workload(12, 4, seed=7, mean_interarrival_s=0.01)
    b = make_workload(12, 4, seed=7, mean_interarrival_s=0.01)
    assert a == b
    c = make_workload(12, 4, seed=8, mean_interarrival_s=0.01)
    assert a != c
    assert all(r["client"] < 4 for r in a)


def test_workload_shared_system_prompt_prefixes_every_request():
    from bench_serve import make_workload
    sys_p = [9] * 32
    w = make_workload(8, 4, seed=3, mean_interarrival_s=0.01,
                      prompt_rng=(2, 6), system_prompt=sys_p)
    assert all(r["prompt"][:32] == sys_p for r in w)
    # tails still vary (the per-request user suffix)
    assert len({tuple(r["prompt"][32:]) for r in w}) > 1
    # the fleet tail sampling is part of the same seeded schedule
    assert w == make_workload(8, 4, seed=3, mean_interarrival_s=0.01,
                              prompt_rng=(2, 6), system_prompt=sys_p)


def test_mixed_workload_is_seeded_and_bimodal():
    from bench_serve import make_mixed_workload
    engine = {"max_seq_len": 64}
    a = make_mixed_workload(12, 4, 7, engine)
    assert a == make_mixed_workload(12, 4, 7, engine)
    longs = [r for r in a if r["long"]]
    shorts = [r for r in a if not r["long"]]
    assert len(longs) == 6 and len(shorts) == 6
    # long requests decode out to the window; short ones stop early
    assert all(len(r["prompt"]) + r["max_new_tokens"] >= 50
               for r in longs)
    assert all(r["max_new_tokens"] <= 8 for r in shorts)


def test_bench_paged_kernel_cpu_leg_shape():
    """The op-level kernel leg must run standalone on CPU: parity at
    fp32-softmax tolerance, live pages counted from the mixed lens, no
    wall-clock claim without a compiled kernel."""
    from bench_serve import bench_paged_kernel
    out = bench_paged_kernel(on_tpu=False, seed=3)
    assert out["parity_max_abs"] < 1e-4
    assert out["kernel_mode"] == "interpret"
    assert out["pages_live"] < out["pages_window"]
    assert 0 < out["work_reduction"] < 1
    assert "kernel_speedup" not in out
    # work accounting agrees with the shared pages helper
    import numpy as np
    from ray_tpu.ops import paged_work_pages
    lens = np.asarray(out["lens"], np.int64)
    assert out["pages_live"] == \
        int(paged_work_pages(lens, out["block_size"]).sum())
