"""The trace reduction: device busy time is the union of operation
intervals inside the window, averaged over devices; idle gaps are named by
the benchmark host span that covers most of each."""
import os

import pytest

import _tiny  # noqa: F401
from bench.harness import layers, trace

MS = 1_000_000  # ns


def test_busy_is_the_union_of_op_intervals():
    ops = [[(0, 2 * MS, "a"), (1 * MS, 3 * MS, "b"), (5 * MS, 6 * MS, "a"),
            (9 * MS, 12 * MS, "c")]]
    mods = [[(0, 3 * MS, "jit__pass(1)"), (5 * MS, 6 * MS, "jit__pass(1)")]]
    spans = [(3 * MS, 5 * MS, "bench.select"), (6 * MS, 9 * MS, "bench.fleet"),
             (6 * MS, 7 * MS, "bench.submit")]
    out = trace.reduce_events(ops, mods, spans, (0, 10 * MS))
    assert out["window_s"] == pytest.approx(0.010)
    # [0,3] + [5,6] + [9,10] (clipped to the window) = 5 ms
    assert out["busy_s"] == pytest.approx(0.005)
    assert out["module_n"] == {"jit__pass(1)": 2}
    assert out["module_s"]["jit__pass(1)"] == pytest.approx(0.004)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps == [["bench.fleet", pytest.approx(0.003)],
                    ["bench.select", pytest.approx(0.002)]]
    top = dict(out["breakdown"]["device_ops"])
    assert top["a"] == pytest.approx(0.003) and top["c"] == \
        pytest.approx(0.003)


def test_busy_is_averaged_over_devices():
    ops = [[(0, 4 * MS, "x")], [(0, 2 * MS, "x")]]
    out = trace.reduce_events(ops, [[], []], [], (0, 4 * MS))
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(0.003)
    assert out["breakdown"]["idle_gaps"] == [["host:other",
                                              pytest.approx(0.002)]]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "smartcar-100k.poisson.xplane.pb")


def test_recorded_chip_trace():
    """Half a second of ``smartcar-100k.poisson`` traced on a TPU v5e
    (``--seconds 0.5 --trace 1``): 27 selection passes, each with one
    Pallas retrieve kernel."""
    out = trace.reduce_file(RECORDED)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    passes = sum(n for k, n in out["module_n"].items()
                 if k.startswith(layers.PASS_MODULE))
    kernels = sum(n for k, n in out["op_n"].items()
                  if k.startswith(layers.RETRIEVE_OP))
    assert passes == kernels == 27
    # operations are named by their own instruction, not their operands
    assert all(" = " not in k for k in out["op_s"])
    names = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert names <= {"bench.select", "bench.fleet", "bench.submit",
                     "host:other"}
    assert out["breakdown"]["device_ops"][0][0].startswith(layers.RETRIEVE_OP)
