"""Workflow event system (reference: python/ray/workflow/
event_listener.py + http_event_provider.py): durable DAGs blocking on
external signals that survive cluster restarts."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import workflow


def test_wait_for_event_completes_on_delivery(ray_session, tmp_path):
    workflow.init_storage(str(tmp_path))

    @ray_tpu.remote
    def combine(evt, base):
        return f"{base}:{evt['go']}"

    @ray_tpu.remote
    def prep():
        return "ready"

    ev = workflow.wait_for_event(workflow.HTTPListener, "ev-basic",
                                 timeout_s=120)
    dag = combine.bind(ev, prep.bind())

    fut = workflow.run_async(dag, workflow_id="wf_events_basic")
    time.sleep(1.0)
    assert workflow.get_status("wf_events_basic") == "RUNNING"
    workflow.deliver_event("ev-basic", {"go": 42})
    assert fut.result(timeout=120) == "ready:42"
    # the event payload is checkpointed with the workflow
    assert workflow.get_output("wf_events_basic") == "ready:42"


def test_http_event_provider_delivers(ray_session, tmp_path):
    workflow.init_storage(str(tmp_path))
    provider = workflow.start_http_event_provider()
    try:
        req = urllib.request.Request(
            f"{provider.address}/event/ev-http", method="POST",
            data=json.dumps({"n": 7}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.load(resp)["delivered"] == "ev-http"

        @ray_tpu.remote
        def double(evt):
            return evt["n"] * 2

        out = workflow.run(
            double.bind(workflow.wait_for_event(
                workflow.HTTPListener, "ev-http", timeout_s=60)),
            workflow_id="wf_events_http")
        assert out == 14
        # idempotent: a second POST with a different payload is ignored
        req2 = urllib.request.Request(
            f"{provider.address}/event/ev-http", method="POST",
            data=json.dumps({"n": 999}).encode())
        urllib.request.urlopen(req2, timeout=30).read()
        assert workflow.run(
            double.bind(workflow.wait_for_event(
                workflow.HTTPListener, "ev-http", timeout_s=60)),
            workflow_id="wf_events_http") == 14
    finally:
        provider.stop()


def test_timer_listener(ray_session, tmp_path):
    workflow.init_storage(str(tmp_path))

    @ray_tpu.remote
    def after(ts):
        return "fired"

    target = time.time() + 1.0
    out = workflow.run(
        after.bind(workflow.wait_for_event(
            workflow.TimerListener, target)),
        workflow_id="wf_timer")
    assert out == "fired"
    assert time.time() >= target


@pytest.mark.slow
def test_event_survives_cluster_restart(tmp_path):
    """The VERDICT scenario: a workflow waits on an event, the cluster
    goes down mid-wait, an HTTP POST delivers the event while/after the
    restart, and the resumed workflow produces a durable output."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    store = str(tmp_path / "wf")
    phase1 = f"""
import sys, threading, time
sys.path.insert(0, {repo!r})
import ray_tpu
from ray_tpu import workflow
ray_tpu.init(num_cpus=2, _num_initial_workers=1)
workflow.init_storage({store!r})

@ray_tpu.remote
def pre():
    return "pre"

@ray_tpu.remote
def combine(evt, p):
    return f"{{p}}+{{evt}}"

dag = combine.bind(
    workflow.wait_for_event(workflow.HTTPListener, "ev-restart",
                            timeout_s=300), pre.bind())
fut = workflow.run_async(dag, workflow_id="wf_restart")
time.sleep(3)   # the pre() task checkpoints; the event wait parks
print("STATUS1", workflow.get_status("wf_restart"), flush=True)
import os
os._exit(0)     # simulate the whole cluster dying mid-wait
"""
    p1 = subprocess.run([sys.executable, "-c", phase1],
                        capture_output=True, text=True, timeout=300,
                        env=dict(os.environ))
    assert p1.returncode == 0, (p1.stdout, p1.stderr)
    assert "STATUS1 RUNNING" in p1.stdout

    phase2 = f"""
import sys, json, urllib.request
sys.path.insert(0, {repo!r})
import ray_tpu
from ray_tpu import workflow
ray_tpu.init(num_cpus=2, _num_initial_workers=1)  # fresh cluster
workflow.init_storage({store!r})
provider = workflow.start_http_event_provider()
req = urllib.request.Request(
    provider.address + "/event/ev-restart", method="POST",
    data=json.dumps("late-event").encode())
urllib.request.urlopen(req, timeout=30).read()
out = workflow.resume("wf_restart")
assert out == "pre+late-event", out
assert workflow.get_output("wf_restart") == "pre+late-event"
provider.stop()
ray_tpu.shutdown()
print("RESTART-OK")
"""
    p2 = subprocess.run([sys.executable, "-c", phase2],
                        capture_output=True, text=True, timeout=300,
                        env=dict(os.environ))
    assert p2.returncode == 0, (p2.stdout[-2000:], p2.stderr[-2000:])
    assert "RESTART-OK" in p2.stdout
