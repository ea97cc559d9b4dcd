"""Run one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, from the root of a checkout: refuse any platform but a TPU
with the chips the cell asks for; build the cell's deployment
(``bench/harness/deploy.py``) and warm every bucket shape its traffic
uses (set-up, ``setup_s``); drive the cell's traffic for ``--seconds``
through the program's front door; compare what the window produced with
the float64 reference (``bench/harness/check.py``); print one JSON line.

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

import numpy as np  # noqa: E402

from bench.harness import check, drive, e2e, spec  # noqa: E402
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


async def session(dep, cell: spec.Cell, seed: int, seconds: float,
                  trace_dir: str | None, counter: CompileCounter) -> dict:
    """Warm the front door, run the window, let it drain."""
    import jax

    mix = cell.traffic
    pools = {d.name: d.pool_qids for d in dep.domains}
    front = drive.make_front(dep, mix)
    watch = drive.LoopWatch()
    await front.start()
    try:
        await drive.warm(front, dep, mix, pools, seed)
        rec = drive.install_recorder(dep, seed)
        selects = drive.SelectTimes()
        for orch in drive.orchestrators(front):
            selects.wrap(orch)
        spans = None
        if trace_dir is not None:
            spans = drive.Spans()
            for orch in drive.orchestrators(front):
                spans.wrap_select(orch)
            for d in dep.domains:
                spans.wrap_fleet(d.executor)
            if dep.multi:
                spans.wrap_sharded(dep.server.sharded_selector())
        closed = mix["loop"] == "closed"
        if closed:
            n = int(mix["max_rate_qps"] * seconds) + mix["outstanding"]
            arrivals = traffic_mod.closed_loop(mix, pools, n, seed)
        else:
            arrivals = traffic_mod.open_loop(mix, pools, seconds, seed)
        traces0 = deploy_traces(dep)
        adm0 = drive.admission_totals(front)
        hedges0 = dep.server.fleet.hedge_count
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
                front, arrivals, dep.multi, mix["outstanding"], t_close,
                spans)
        else:
            records = await drive.open_window(front, arrivals, dep.multi,
                                              t0, spans)
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
        traces1 = deploy_traces(dep)
        hedges = dep.server.fleet.hedge_count - hedges0
    finally:
        await watch.stop()
        await front.stop()
    return {"records": records, "recorder": rec, "spans": spans,
            "loop": {**watch.summary(t0), **selects.summary(t0)},
            "setup_s": setup_s, "t0": t0, "t_close": t_close,
            "admission": {k: adm1[k] - adm0[k] for k in adm0},
            "kernel_traces_in_window": traces1 - traces0,
            "fleet_hedges": hedges,
            "max_batch": dep.config["serving"]["max_batch"]}


def deploy_traces(dep) -> int:
    if dep.multi:
        return dep.server.sharded_selector().kernel_trace_count
    return dep.domains[0].rps.kernel_trace_count


def peak_memory() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def served_rows(records) -> list:
    """(domain, qid, max_lat, max_cost, path_key, set_id, fallback) of every
    request the window served."""
    return [(r.arrival.domain, r.arrival.qid, r.arrival.max_latency_s,
             r.arrival.max_cost_usd, *r.decision)
            for r in records if r.outcome == "ok"]


def unsettled(records) -> int:
    """Requests with no response: failed, or still open."""
    return sum(1 for r in records if r.outcome not in ("ok", "shed"))


def compare(dep, res: dict, seed: int) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    from bench.harness.reference import Reference

    limits = dep.config["check_limits"]
    refs = {d.name: Reference(d.ref) for d in dep.domains}
    names = [d.name for d in dep.domains]
    emb = {d.name: d.data.query_embeddings for d in dep.domains}
    passes = [(np.asarray(e), np.asarray(s), None if did is None else
               int(did), tuple(np.asarray(x) for x in out))
              for e, s, did, out in res["recorder"].kept]
    gap, n_dec, skip_dec = check.decision_gap(
        refs, served_rows(res["records"]),
        lambda dom, q: emb[dom][q], seed)
    err, n_rows, skip_rows = check.score_err(refs, passes, names)
    res["check_counts"] = {"decisions": n_dec, "decision_ties": skip_dec,
                           "pass_rows": n_rows, "pass_row_ties": skip_rows,
                           "passes": len(passes)}
    return {"decision_gap": (gap, limits["decision_gap"]),
            "score_err": (err, limits["score_err"]),
            "unsettled": (unsettled(res["records"]), limits["unsettled"])}


def per_layer(dep, cell: spec.Cell, res: dict, trace: dict,
              device: dict) -> tuple[dict, dict]:
    spans = res["spans"]
    if dep.multi:
        first = dep.domains[0].name
        pass_rows = [(n, first if d == "default" else d)
                     for n, d in spans.pass_rows]
    else:
        pass_rows = [(n, dep.domains[0].name) for _, _, n in spans.select]
    shapes = {}
    n_max = max(d.ref["log_emb"].shape[0] for d in dep.domains)
    for d in dep.domains:
        n = d.ref["log_emb"].shape[0]
        # the corpus as the retrieve stage is called: padded to the sharded
        # maximum, or to the kernel's 512-row tile
        padded = n_max if dep.multi else -(-n // 512) * 512
        shapes[d.name] = {"n_log": n, "n_log_padded": padded,
                          "n_sets": d.ref["protos"].shape[0]}
    ctx = SimpleNamespace(
        records=res["records"], spans=spans, trace=trace,
        admission=res["admission"], max_batch=res["max_batch"],
        peaks=spec.peaks(device["kind"]), config=dep.config, shapes=shapes,
        pass_rows=pass_rows, notes={})
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
    device = accelerator(cell.chips)
    cache = enable_compile_cache()
    counter = CompileCounter()
    from bench.harness import deploy

    try:
        t = time.perf_counter()
        dep = deploy.build(cell.config, seed)
        build_s = time.perf_counter() - t
        try:
            out = measure(dep, cell, seed, seconds, trace, device, counter)
        finally:
            dep.server.fleet.close()
    finally:
        counter.close()
    out["info"].update(build_s=build_s, compile_cache=cache)
    out["checks"] = out.pop("checks")  # the compared numbers come last
    return out


def measure(dep, cell: spec.Cell, seed: int, seconds: float, trace: bool,
            device: dict, counter: CompileCounter,
            with_checks: bool = True) -> dict:
    """Warm ``dep`` for the cell, run one window and read it."""
    from bench.harness import deploy

    deploy.warm_selection(dep)
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
    checks = compare(dep, res, seed) if with_checks else {}
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
        "kernel_traces_in_window": res["kernel_traces_in_window"],
        "fleet_hedges": res["fleet_hedges"],
        "selection_passes_in_window": res["recorder"].calls,
        "buckets_in_window": res["admission"]["batches"],
        "drain_s": last - res["t_close"],
        **generator_lag(records), **res["loop"],
        **res.get("check_counts", {}), **notes,
        **{f"sets.{d.name}": int(d.ref["protos"].shape[0])
           for d in dep.domains},
        **{f"sets_unmatched.{d.name}": d.ref["sets_unmatched"]
           for d in dep.domains},
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
