"""BENCHMARK.json and the files it names: every cell's configuration,
traffic and per-layer readers are found by name, and the file keeps the
benchmark contract's shape."""
import json
import re

import pytest

import _tiny  # noqa: F401  (puts the checkout on the path)
from bench.harness import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_pieces_found_by_name(cell):
    c = spec.cell(cell)
    assert c.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert c.traffic["loop"] in ("open", "closed")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in names, (cell, m["name"])


def test_names_units_and_text_fields():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]


def test_bounds_and_setup():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_config_files_match_entries():
    for c in BENCH["configs"]:
        cfg = spec.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        names = spec.kind(cfg["kind"]).check_names()
        assert len(set(names)) == len(names)
        assert set(cfg["check_limits"]) == set(names)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_kind_has_the_full_interface(config):
    kind = spec.kind(spec.config(config)["kind"])
    assert kind is spec.kind(spec.config(config)["kind"])  # loaded once
    for name in spec.KIND_INTERFACE:
        assert callable(getattr(kind, name, None)), (config, name)


def test_missing_kind_names_its_file():
    with pytest.raises(KeyError, match=r"kinds/no_such_kind\.py"):
        spec.kind("no_such_kind")


def test_metric_lists_follow_workloads():
    burst = spec.cell("tenants5-64k.burst")
    assert "retrieval_topk_roofline" not in {m["name"] for m in
                                             burst.per_layer}
    assert "passes_per_bucket" not in {
        m["name"] for m in spec.cell("smartcar-100k.poisson").per_layer}
    bench = {"workloads": [{"name": "x", "config": "smartcar-100k",
                            "traffic": "closed", "chips": 1}],
             "end_to_end": [{"name": "setup_s"},
                            {"name": "served_qps", "workloads": ["x"]},
                            {"name": "decide_p50_ms", "workloads": ["y"]}],
             "per_layer": [{"name": "fill", "moves": "served_qps"},
                           {"name": "idle", "moves": "decide_p50_ms"}]}
    c = spec.cell("x", bench)
    assert [m["name"] for m in c.end_to_end] == ["setup_s", "served_qps"]
    assert [m["name"] for m in c.per_layer] == ["fill"]


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


@pytest.mark.parametrize("name", sorted({m["name"] for m in
                                         BENCH["per_layer"]}))
def test_reader_finds_nothing_in_an_empty_window(name):
    """A reader with nothing to read returns None, never 0."""
    from types import SimpleNamespace

    from bench.harness.drive import Spans

    empty_trace = {"busy_s": 0.0, "window_s": 0.0, "module_s": {},
                   "module_n": {}, "op_s": {}, "op_n": {}}
    ctx = SimpleNamespace(
        records=[], spans=Spans(), trace=empty_trace,
        admission={"batches": 0, "dispatched": 0}, max_batch=32,
        peaks=spec.peaks("TPU v5 lite"), config=spec.config("smartcar-100k"),
        shapes={}, pass_rows=[], notes={})
    assert spec.metric_reader(name)(ctx) is None
