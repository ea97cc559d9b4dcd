"""Run one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, from the root of a checkout: refuse any platform but a TPU
with the chips the cell asks for; build the cell's deployment by the kind
its configuration names (``bench/kinds/<kind>.py``) and warm every shape
its traffic uses (set-up, ``setup_s``); drive the cell's traffic for
``--seconds`` through the program's front door; compare what the window
produced with the kind's plain reference; print one JSON line.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window (kept under ``TMPDIR`` and deleted) and the benchmark's host spans.
The numbers compared for ``correct`` come last, on stderr and in the
line's ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu writes no logs

from bench.harness import drive, e2e, spec  # noqa: E402
from bench.harness import trace as trace_mod  # noqa: E402
from bench.harness import traffic as traffic_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class Refused(Exception):
    """The run cannot be measured here; no result line is printed."""


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def accelerator(chips: int) -> dict:
    """JAX's devices, refused unless they are TPUs, at least ``chips``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise Refused(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                      f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the checkout root (a fixed path: the
    path is part of the cache key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX traces, compiles and persistent-cache reads, with a
    separate tally while ``window`` is set."""

    def __init__(self):
        import jax

        self.window = False
        self.setup: dict[str, int] = {}
        self.in_window: dict[str, int] = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def _add(self, name: str) -> None:
        tally = self.in_window if self.window else self.setup
        tally[name] = tally.get(name, 0) + 1

    def _event(self, name: str, **_) -> None:
        if name.startswith("/jax/compilation_cache/cache_"):
            self._add(name.rsplit("/", 1)[1])

    def _duration(self, name: str, _secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self._add(name.rsplit("/", 1)[1])


def start_trace(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def schedule(kind, cfg: dict, mix: dict, pools: dict, seconds: float,
             seed: int) -> list:
    """The window's arrivals from the run seed (closed loop: the sequence
    the clients take from), with the kind's own fields."""
    if mix["loop"] == "closed":
        n = int(mix["max_rate_qps"] * seconds) + mix["outstanding"]
        arrivals = traffic_mod.closed_loop(mix, pools, n, seed)
    else:
        arrivals = traffic_mod.open_loop(mix, pools, seconds, seed)
    return kind.extend(cfg, mix, arrivals, seed)


async def session(dep, cell: spec.Cell, seed: int, seconds: float,
                  trace_dir: str | None, counter: CompileCounter) -> dict:
    """Warm the front door, run the window, let it drain."""
    import jax

    kind = spec.kind_of(cell)
    mix = cell.traffic
    pools = kind.pools(dep)
    front = drive.make_front(dep, mix, list(pools))
    watch = drive.LoopWatch()
    await front.start()
    try:
        await drive.warm(front, kind, dep, mix, pools, seed)
        spans = drive.Spans() if trace_dir is not None else None
        rec = kind.install_recorders(dep, seed, spans)
        selects = drive.SelectTimes()
        for orch in drive.orchestrators(front):
            selects.wrap(orch)
        if spans is not None:
            for orch in drive.orchestrators(front):
                spans.wrap_select(orch)
        closed = mix["loop"] == "closed"
        arrivals = schedule(kind, dep.config, mix, pools, seconds, seed)
        request = functools.partial(kind.make_request, dep)
        counts0 = kind.counters(dep)
        adm0 = drive.admission_totals(front)
        # the deployment's objects live for the whole run: move them out of
        # the collector's reach so that a full collection of set-up garbage
        # does not stall the window at a random point
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        watch.start()
        if trace_dir is not None:
            start_trace(trace_dir)
        counter.window = True
        rec.on = True
        t0 = time.perf_counter() + drive.WINDOW_LEAD_S
        t_close = t0 + seconds
        window = (jax.profiler.TraceAnnotation("bench.window")
                  if trace_dir is not None else None)
        while time.perf_counter() < t0:
            await asyncio.sleep(t0 - time.perf_counter())
        if window is not None:
            window.__enter__()
        if closed:
            records = await drive.closed_window(
                front, arrivals, request, mix["outstanding"], t_close,
                spans)
        else:
            records = await drive.open_window(front, arrivals, request, t0,
                                              spans)
            if time.perf_counter() < t_close:
                await asyncio.sleep(t_close - time.perf_counter())
        if window is not None:
            window.__exit__(None, None, None)
        rec.on = False
        await drive.settle(records, t_close + drive.SETTLE_GRACE_S)
        await watch.stop()  # before the profiler's stop, which holds the loop
        counter.window = False
        if trace_dir is not None:
            jax.profiler.stop_trace()
        adm1 = drive.admission_totals(front)
        counts1 = kind.counters(dep)
    finally:
        await watch.stop()
        await front.stop()
    return {"records": records, "recorder": rec, "spans": spans,
            "loop": {**watch.summary(t0), **selects.summary(t0)},
            "setup_s": setup_s, "t0": t0, "t_close": t_close,
            "admission": {k: adm1[k] - adm0[k] for k in adm0},
            "counters": {k: counts1[k] - counts0[k] for k in counts0},
            "max_batch": dep.config["serving"]["max_batch"]}


def peak_memory() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def per_layer(dep, cell: spec.Cell, res: dict, trace: dict,
              device: dict) -> tuple[dict, dict]:
    ctx = SimpleNamespace(
        records=res["records"], spans=res["spans"], trace=trace,
        admission=res["admission"], max_batch=res["max_batch"],
        peaks=spec.peaks(device["kind"]), config=dep.config, notes={},
        **spec.kind_of(cell).layer_context(dep, res))
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out, ctx.notes


def generator_lag(records) -> dict:
    lag = sorted(r.sent - r.due for r in records)
    if not lag:
        return {}
    return {"generator_lag_p50_ms": 1e3 * e2e.percentile(lag, 50),
            "generator_lag_p95_ms": 1e3 * e2e.percentile(lag, 95)}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    kind = spec.kind_of(cell)
    kind.check_traffic(cell.traffic)
    device = accelerator(cell.chips)
    cache = enable_compile_cache()
    counter = CompileCounter()
    try:
        t = time.perf_counter()
        dep = kind.build(cell.config, seed)
        build_s = time.perf_counter() - t
        try:
            out = measure(dep, cell, seed, seconds, trace, device, counter)
        finally:
            kind.close(dep)
    finally:
        counter.close()
    out["info"].update(build_s=build_s, compile_cache=cache)
    out["checks"] = out.pop("checks")  # the compared numbers come last
    return out


def measure(dep, cell: spec.Cell, seed: int, seconds: float, trace: bool,
            device: dict, counter: CompileCounter,
            with_checks: bool = True) -> dict:
    """Warm ``dep`` for the cell, run one window and read it."""
    kind = spec.kind_of(cell)
    kind.warm(dep)
    device = dict(device)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        res = asyncio.run(session(dep, cell, seed, seconds, trace_dir,
                                  counter))
        device["memory_peak_bytes"] = peak_memory()
        reduced = None
        if trace_dir is not None:
            reduced = trace_mod.reduce_file(trace_mod.xplane_path(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    records = [r for r in res["records"] if r.due <= res["t_close"]]
    res["records"] = records
    checks = kind.compare(dep, res, seed) if with_checks else {}
    notes = {}
    if trace:
        metrics, notes = per_layer(dep, cell, res, reduced, device)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        names = {m["name"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in
                   e2e.end_to_end(names, records, res["t0"],
                                  res["t_close"]).items()}
        metrics["setup_s"] = {"value": res["setup_s"], "unit": "s"}
    n_fail = sum(1 for r in records if r.event("completed") is None)
    out = {"correct": all(v <= lim for v, lim in checks.values()),
           "attempted": len(records), "failed": n_fail,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = reduced["breakdown"]
    last = max((r.last() for r in records), default=res["t_close"])
    out["info"] = {
        "setup_s": res["setup_s"],
        "setup_compile_events": dict(counter.setup),
        "window_compile_events": dict(counter.in_window),
        **res["counters"],
        "buckets_in_window": res["admission"]["batches"],
        "drain_s": last - res["t_close"],
        **generator_lag(records), **res["loop"],
        **res.get("check_counts", {}), **notes, **kind.info(dep, res),
    }
    if trace:
        out["info"]["trace_ops"] = reduced["op_n"]
        out["info"]["trace_modules"] = reduced["module_n"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def _finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return e2e.INF_MS
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec.cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(_finite(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
