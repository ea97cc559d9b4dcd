"""Deployment kinds: a configuration names its kind, and a kind added as a
new file takes part in a run (its own check decides ``correct``); the
traffic of the cells is what it was before kinds, and a request form a
kind does not serve is refused before set-up."""
import hashlib
import sys

import numpy as np
import pytest

import _tiny
from bench import run as R
from bench.harness import drive, spec

SEED = 2**33 + 17

# (arrivals of a 40 s window, warm-up arrivals): sha256 of each arrival's
# fields, as the harness made them before configurations named a kind
DIGESTS = {
    "smartcar-100k.poisson": (("d826a24f5e3e6834", 16038),
                              ("7a0c1b6008eec32a", 512)),
    "tenants5-64k.burst": (("8e54c4a83baabaf5", 5969),
                           ("cc8c2721857448bf", 512)),
    "smartcar-100k.closed": (("bc629465f8e1ff80", 240256),
                             ("7a0c1b6008eec32a", 512)),
}

EXTRA_KIND = '''
"""eco_select with one more check, ``extra_err``."""
from bench.kinds import eco_select as base
from bench.kinds.eco_select import (  # noqa: F401
    build, check_traffic, close, counters, cpu_cut, extend, info,
    install_recorders, layer_context, make_request, pools, warm)

EXTRA_ERR = {value!r}


def check_names():
    return base.check_names() + ("extra_err",)


def compare(dep, res, seed):
    out = base.compare(dep, res, seed)
    out["extra_err"] = (EXTRA_ERR, dep.config["check_limits"]["extra_err"])
    return out
'''


def digest(arrivals) -> tuple[str, int]:
    h = hashlib.sha256()
    for a in arrivals:
        h.update(repr((a.due_s, a.qid, a.domain, a.max_latency_s,
                       a.max_cost_usd, a.tenant)).encode())
    return h.hexdigest()[:16], len(arrivals)


@pytest.mark.parametrize("cell", sorted(DIGESTS))
def test_arrivals_unchanged(cell):
    c = spec.cell(cell)
    kind = spec.kind_of(c)
    # eco_select's pools: each domain's held-out ids follow its log rows
    pools = {d["name"]: np.arange(d["log_rows"], d["log_rows"] + d["pool"])
             for d in c.config["domains"]}
    window = R.schedule(kind, c.config, c.traffic, pools, 40, SEED)
    warm = drive.warm_arrivals(kind, c.config, c.traffic, pools, SEED)
    assert (digest(window), digest(warm)) == DIGESTS[cell]


@pytest.mark.parametrize("value,correct", [(0.25, True), (0.75, False)])
def test_kind_added_as_a_file_decides_correct(tmp_path, monkeypatch, value,
                                              correct):
    c = _tiny.tiny_cell("smartcar-100k.poisson")
    (tmp_path / "eco_extra.py").write_text(EXTRA_KIND.format(value=value))
    monkeypatch.setattr(spec, "KINDS_DIR", tmp_path)
    cfg = dict(c.config, kind="eco_extra",
               check_limits=dict(c.config["check_limits"], extra_err=0.5))
    cell = spec.Cell(c.name, cfg, c.traffic, c.chips, c.end_to_end,
                     c.per_layer)
    try:
        out = _tiny.run_cell(cell, seed=2**36 + 3)
    finally:
        sys.modules.pop("bench.kinds.eco_extra", None)
    assert list(out["checks"]) == ["decision_gap", "score_err", "unsettled",
                                   "extra_err"]
    assert out["checks"]["extra_err"] == {"value": value, "limit": 0.5}
    others = [k for k in out["checks"] if k != "extra_err"]
    assert all(out["checks"][k]["value"] <= out["checks"][k]["limit"]
               for k in others)
    assert out["correct"] is correct


def test_unknown_request_form_is_refused_before_setup(monkeypatch):
    kind = spec.kind("eco_select")

    def build(*a, **k):
        raise AssertionError("set-up began")

    monkeypatch.setattr(kind, "build", build)
    c = _tiny.tiny_cell("smartcar-100k.poisson")
    cell = spec.Cell(c.name, c.config, dict(c.traffic, request="text"),
                     c.chips, c.end_to_end, c.per_layer)
    with pytest.raises(ValueError, match="'text'"):
        _tiny.run_cell(cell, seed=1)
