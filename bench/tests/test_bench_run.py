"""Whole runs of a cell at a tiny size on the CPU, with the harness's look
for a chip skipped: the result line's shape, the traced run's per-layer
metrics, and the refusal off the TPU."""
import json
import os
import subprocess
import sys


import _tiny
from bench.harness import spec


def test_refuses_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smartcar-100k.poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=_tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_sound_run_is_correct():
    out = _tiny.run_tiny("smartcar-100k.poisson", seed=2**35 + 11)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in
                                   spec.cell("smartcar-100k.poisson")
                                   .end_to_end}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["info"]["window_compile_events"] == {}
    assert out["info"]["kernel_traces_in_window"] == 0
    assert out["info"]["decisions"] > 0 and out["info"]["pass_rows"] > 0
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics():
    out = _tiny.run_tiny("tenants5-64k.burst", seed=5, trace=True)
    assert out["correct"] is True
    allowed = {m["name"] for m in spec.cell("tenants5-64k.burst").per_layer}
    got = set(out["metrics"])
    assert got <= allowed
    # host-side readers find something on any platform; device readers
    # only where the trace holds device operations
    assert {"admit_wait_ms", "bucket_fill", "passes_per_bucket",
            "fleet_ms"} <= got
    assert out["metrics"]["passes_per_bucket"]["value"] >= 1.0
    assert "busy_s" in out["device"] and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_closed_loop_run():
    """The closed-loop cell reports the served rate, and its traced run
    the per-layer metrics that move it."""
    out = _tiny.run_tiny("smartcar-100k.closed", seed=17)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"served_qps", "setup_s"}
    assert out["metrics"]["served_qps"]["value"] > 0
    traced = _tiny.run_tiny("smartcar-100k.closed", seed=18, trace=True)
    assert traced["correct"] is True
    assert "bucket_fill.closed" in traced["metrics"]
    assert set(traced["metrics"]) <= {
        m["name"] for m in spec.cell("smartcar-100k.closed").per_layer}
