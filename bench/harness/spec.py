"""Find a cell's pieces by name: ``BENCHMARK.json`` at the checkout root,
``bench/configs/<config>.json``, the deployment kind the configuration
names (``"kind"``) at ``bench/kinds/<kind>.py``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.  A
later cell, configuration, kind, traffic mix or per-layer metric is a new
file here and a new entry in ``BENCHMARK.json``; nothing in this module
names one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
KINDS_DIR = BENCH_DIR / "kinds"

# What every kind module defines (``bench/kinds/eco_select.py`` documents
# each): the shared harness calls these and reads nothing else of a
# deployment but its ``config`` and its ``server``.
KIND_INTERFACE = ("check_traffic", "check_names", "build", "warm", "close",
                  "pools", "extend", "make_request", "install_recorders",
                  "counters", "compare", "layer_context", "info", "cpu_cut")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    """Peak rates of ``device_kind``; an unknown device is an error."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def kind(name: str):
    """The module of deployment kind ``name``, ``<KINDS_DIR>/<name>.py``,
    loaded once as ``bench.kinds.<name>`` (so that a kind that imports
    another gets the same module); a missing file is a ``KeyError``."""
    path = KINDS_DIR / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no deployment kind {name!r}: {path} does not exist")
    mod_name = f"bench.kinds.{name}"
    mod = sys.modules.get(mod_name)
    if mod is not None and Path(mod.__file__).resolve() == path.resolve():
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def kind_of(cell: "Cell"):
    """The kind module of ``cell``'s configuration."""
    return kind(cell.config["kind"])


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with its configuration, traffic and the
    metrics it reports (``end_to_end`` with tracing off, ``per_layer``
    with it on)."""

    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    lists; without the key, an end-to-end metric goes to every cell and a
    per-layer one wherever the metric it ``moves`` is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = benchmark() if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name, set()))
    names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reports(m, name, names))
    return Cell(name, config(entry["config"]), traffic(entry["traffic"]),
                int(entry["chips"]), e2e, per_layer)
