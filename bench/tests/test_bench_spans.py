"""The per-layer metrics read from the program's own spans: a tiny traced
run of each cell reports them, and per request the split adds up to the
benchmark's outside measurements of the same request (``admit_wait_ms``'s
``selected - admitted`` less the outside select span, and ``fleet_ms``'s
``completed - dispatched``), up to the wrapper's and the settle callback's
own time."""
from types import SimpleNamespace

import pytest

import _tiny
from bench import run as R
from bench.harness import spans as S
from bench.harness import spec

NEW = {"smartcar-100k.poisson": {"queue_wait_ms", "fill_wait_ms",
                                 "handoff_ms", "select_fetch_ms",
                                 "select_decide_ms", "fleet_queue_ms",
                                 "fleet_exec_ms", "settle_hop_ms"},
       "smartcar-100k.closed": {"handoff_ms.closed", "fleet_exec_ms.closed"}}
NEW["tenants5-64k.burst"] = NEW["smartcar-100k.poisson"]


def _close(a: float, b: float) -> bool:
    return abs(a - b) <= max(0.05 * abs(b), 1e-4)  # 5% or 0.1 ms


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_run_splits_admission_and_fleet(cell, monkeypatch):
    seen = {}
    inner = R.per_layer

    def per_layer(dep, c, res, trace, device):
        seen["res"] = res
        return inner(dep, c, res, trace, device)

    monkeypatch.setattr(R, "per_layer", per_layer)
    out = _tiny.run_tiny(cell, seed=2**34 + 7, trace=True)
    assert out["correct"] is True
    assert NEW[cell] <= set(out["metrics"])
    assert set(out["metrics"]) <= {m["name"]
                                   for m in spec.cell(cell).per_layer}
    notes = out["info"]["eco_spans"]
    assert notes["requests"] > 0 and notes["dropped"] == 0

    res = seen["res"]
    w = S.window(SimpleNamespace(records=res["records"], spans=res["spans"],
                                 notes={}))
    by_admit = {r.event("admitted"): r for r in res["records"]}
    admit, admit_out, fleet, fleet_out = [], [], [], []
    for t in w.requests:
        r = by_admit[t.admitted]
        sel, span = r.event("selected"), r.span()
        if None not in (t.queue, t.handoff, sel, span):
            admit.append(t.queue + t.fill + t.bucket + t.handoff)
            admit_out.append(sel - t.admitted - (span[1] - span[0]))
        if None not in (t.fleet_queue, t.settle_hop, r.event("completed")):
            fleet.append(t.fleet_queue + t.fleet_exec + t.respond
                         + t.settle_hop)
            fleet_out.append(r.event("completed") - r.event("dispatched"))
    assert len(admit) == len(w.requests) and len(fleet) == len(w.requests)
    assert _close(sum(admit) / len(admit), sum(admit_out) / len(admit_out))
    assert _close(sum(fleet) / len(fleet), sum(fleet_out) / len(fleet_out))
