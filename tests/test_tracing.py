"""The serving path's spans (``repro.runtime.tracing``): nothing is
recorded while no profiler session captures; under one, every ``eco.*``
span nests within its thread in the trace (none is held across an
``await``), and the bucket ids on the selection and fleet spans are the
tickets'.  A trace recorded on a TPU v5e shows the spans on the device
operations' clock."""
import asyncio
import glob
import os
from collections import defaultdict

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.slo import SLO
from repro.launch.serve import build_server
from repro.runtime import tracing
from repro.runtime.orchestrator import Orchestrator
from repro.runtime.server import Request

SLOS = [SLO(), SLO(max_latency_s=2.0, max_cost_usd=0.004),
        SLO(max_latency_s=1e-6, max_cost_usd=0.0)]  # the last: fallback
NAMES = {"eco.submit", "eco.bucket", "eco.select", "eco.select.resolve",
         "eco.select.pass", "eco.select.launch", "eco.select.fetch",
         "eco.select.decide", "eco.fleet.exec", "eco.fleet.respond",
         "eco.settle"}
CHIP_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "eco_spans.tpu.xplane.pb")
CLOCK_SLACK_NS = 50_000


@pytest.fixture(scope="module")
def served():
    server, test_idx = build_server("agriculture", n_queries=40, budget=3.0,
                                    seed=3, use_kernel=True)
    yield server, test_idx
    server.fleet.close()


def serve(server, test_idx, n: int = 12):
    """``n`` held-out requests in three waves through a fresh orchestrator;
    returns (tickets, stats)."""
    reqs = [Request(prompt="", qid=int(q), slo=SLOS[i % len(SLOS)])
            for i, q in enumerate(test_idx[:n])]

    async def main():
        async with Orchestrator(server, max_batch=4, max_wait_ms=5) as orch:
            tickets = []
            for wave in range(0, n, n // 3):
                tickets += [await orch.submit(r)
                            for r in reqs[wave:wave + n // 3]]
                await asyncio.gather(*(t.wait() for t in tickets))
        return tickets, orch.stats()

    return asyncio.run(main())


def test_off_records_nothing_and_counts_the_work(served):
    tracing.store.clear()
    assert not TraceAnnotation.is_enabled()
    tickets, st = serve(*served)
    assert tracing.store.snapshot() == ([], [])
    assert tracing.span("eco.x") is tracing.OFF
    assert tracing.span("eco.y", 7, rows=3) is tracing.OFF
    # the counters count where the work happens, traced or not
    assert st["select_passes"] == st["batches"] >= 3
    fallback = sum(t.request.slo == SLOS[2] for t in tickets)
    assert st["fallback_rows"] == fallback > 0
    assert all(t.bucket is not None and t.row is not None for t in tickets)
    assert len({(t.bucket, t.row) for t in tickets}) == len(tickets)


def test_store_counts_what_it_drops():
    store = tracing.Store(capacity=2)
    for i in range(5):
        store.add(tracing.Timeline(i, 0, ()))
    spans, timelines = store.snapshot()
    assert spans == [] and [t.bucket for t in timelines] == [3, 4]
    assert store.dropped == 3


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """One traced serve: (tickets, spans, timelines, xplane path)."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    tracing.store.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        tickets, _ = serve(*served)
    spans, timelines = tracing.store.snapshot()
    tracing.store.clear()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return tickets, spans, timelines, path


def _events(path: str, plane_prefix: str = "/host:"):
    """{(plane, line index): [(start_ns, end_ns, name, stats)]} of the
    eco.* events, and the profile itself."""
    pd = ProfileData.from_file(path)
    lines = defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("eco."):
                    lines[plane.name, i].append(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns),
                         e.name, dict(e.stats)))
    return lines, pd


def test_spans_nest_within_each_thread(traced):
    _, spans, _, path = traced
    lines, _ = _events(path)
    seen = set()
    for events in lines.values():
        stack = []
        for a, b, name, _ in sorted(events, key=lambda e: (e[0], -e[1])):
            while stack and stack[-1][1] <= a:
                stack.pop()
            # a partial overlap would mean a span held across an await
            assert not stack or b <= stack[-1][1], (name, stack[-1][2])
            stack.append((a, b, name))
            seen.add(name)
    assert seen == NAMES == {s.name for s in spans}


def test_bucket_ids_are_the_tickets(traced):
    tickets, spans, timelines, path = traced
    buckets = {t.bucket for t in tickets}
    by_name = defaultdict(list)
    for s in spans:
        by_name[s.name].append(s)
    assert {s.bucket for s in by_name["eco.bucket"]} == buckets
    assert {s.bucket for s in by_name["eco.select"]} == buckets
    for name in ("eco.select.pass", "eco.select.fetch", "eco.select.decide"):
        assert {s.bucket for s in by_name[name]} == buckets, name
    for name in ("eco.fleet.exec", "eco.fleet.respond", "eco.settle"):
        keys = {(s.bucket, dict(s.counts)["row"]) for s in by_name[name]}
        assert keys == {(t.bucket, t.row) for t in tickets}, name
    won = [s for s in by_name["eco.fleet.exec"] if ("won", 1) in s.counts]
    assert len(won) == len(tickets)
    assert sorted((t.bucket, t.row) for t in timelines) == sorted(
        (t.bucket, t.row) for t in tickets)
    # the trace carries the same ids as span metadata
    lines, _ = _events(path)
    in_trace = {st["bucket"] for ev in lines.values()
                for _, _, name, st in ev if name == "eco.select"}
    assert in_trace == buckets


def test_chip_trace_shares_the_device_clock():
    """``chip_smoke.py --trace-dir`` on a TPU v5e: every execution of the
    selection pass on the device lies inside an ``eco.select.pass`` span
    of the host, on one clock."""
    host, pd = _events(CHIP_TRACE)
    passes = [(a, b) for ev in host.values() for a, b, name, _ in ev
              if name == "eco.select.pass"]
    modules = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
               for plane in pd.planes if plane.name.startswith("/device:TPU")
               for line in plane.lines if line.name == "XLA Modules"
               for e in line.events if e.name.startswith("jit__pass")]
    assert len(modules) >= 10 and len(passes) >= len(modules)
    for a, b in modules:
        assert any(p0 - CLOCK_SLACK_NS <= a and b <= p1 + CLOCK_SLACK_NS
                   for p0, p1 in passes), (a, b)
