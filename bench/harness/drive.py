"""Drive one window of traffic through the deployment's front door
(``Orchestrator.submit`` or ``TenantRouter.submit``) and record what the
end-to-end metrics, the per-layer readers and the correctness check need.

Open loop: each request is sent at its due time, whatever the system does,
and timed from that due time (a stall delays every later request, and the
delay counts).  How late the generator itself ran is recorded apart.
Closed loop: ``outstanding`` clients each send their next request when the
last one settles.

With ``trace`` on, the benchmark's own host spans are written into the
profiler's trace around the calls into each layer (``bench.submit`` around
admission, ``bench.select`` around ``Orchestrator._select``,
``bench.fleet`` around the host emulation of a path) and kept on the host
clock for the readers.
"""
from __future__ import annotations

import asyncio
import gc
import itertools
import sys
import threading
import time
import traceback

import numpy as np

from bench.harness import traffic as traffic_mod

SETTLE_GRACE_S = 60.0  # how long past the close a request may still settle
WINDOW_LEAD_S = 0.05   # the first due time lies this far after the start
TICK_S = 0.02          # how often the loop marks itself alive
STALL_S = 0.25         # a mark this late is a stall of the loop
KEEP_STALLS = 3        # stalls whose stacks are kept
SAMPLE_S = 0.5         # how often a stall's loop frame is sampled
SELECT_SLOW_S = 0.1    # a bucket's selection this long is noted
GC_SLOW_S = 0.02       # collections at least this long are noted


MARKS = ("admitted", "selected", "dispatched", "completed", "failed", "shed")


class Record:
    """One request of the window.  While it is open it holds its ticket;
    when the ticket settles, what the metrics and the check read is copied
    out (the ticket's marks, the outcome, the decision the response
    carries, the select span in a traced run) and the ticket is let go, so
    that the window's requests, responses and unread streamed chunks do not
    pile up for the interpreter's collector."""

    __slots__ = ("arrival", "due", "sent", "ticket", "marks", "outcome",
                 "decision", "select_span", "_spans")

    def __init__(self, arrival: traffic_mod.Arrival, due: float, sent: float,
                 ticket, spans: "Spans | None" = None):
        self.arrival = arrival
        self.due = due            # perf_counter of the due time
        self.sent = sent          # perf_counter when submit() was called
        self.ticket = ticket      # repro Ticket, until it settles
        self.marks: tuple = ()
        self.outcome: str | None = None  # ok, shed, failed, cancelled
        self.decision: tuple | None = None  # (path_key, set_id, fallback)
        self.select_span: tuple | None = None
        self._spans = spans
        fut = ticket._future
        if fut.done():
            self._settled(fut)
        else:
            fut.add_done_callback(self._settled)

    def _settled(self, fut) -> None:
        from repro.runtime.orchestrator import Overloaded

        t = self.ticket
        self.marks = tuple(t.event(m) for m in MARKS)
        if fut.cancelled():
            self.outcome = "cancelled"
        elif fut.exception() is not None:
            self.outcome = "failed"
        else:
            resp = fut.result()
            if isinstance(resp, Overloaded):
                self.outcome = "shed"
            else:
                self.outcome = "ok"
                self.decision = (resp.path_key, resp.meta["set_id"],
                                 resp.meta["fallback"])
        if self._spans is not None:
            self.select_span = self._spans.span_of(t.request)
        self.ticket = self._spans = None

    def event(self, name: str) -> float | None:
        """The request's ``name`` mark (one of ``MARKS``), or None."""
        if self.ticket is not None:
            return self.ticket.event(name)
        return self.marks[MARKS.index(name)]

    def last(self) -> float:
        """The request's latest mark (its send time if it has none)."""
        if self.ticket is not None:
            ev = self.ticket.events
            return ev[-1][1] if ev else self.sent
        return max((m for m in self.marks if m is not None),
                   default=self.sent)

    def span(self) -> tuple | None:
        """The select span of the request's bucket (traced runs)."""
        if self.ticket is not None and self._spans is not None:
            return self._spans.select_of.get(id(self.ticket.request))
        return self.select_span


class SelectTimes:
    """How long each bucket's selection took (``Orchestrator._select``, on
    an executor thread), to find the slow ones."""

    def __init__(self):
        self.lock = threading.Lock()
        self.n = 0
        self.max_s = 0.0
        self.slow: list[tuple[float, float]] = []  # (start, seconds)

    def wrap(self, orch) -> None:
        inner = orch._select

        def select(reqs):
            t0 = time.perf_counter()
            out = inner(reqs)
            dt = time.perf_counter() - t0
            with self.lock:
                self.n += 1
                self.max_s = max(self.max_s, dt)
                if dt >= SELECT_SLOW_S:
                    self.slow.append((t0, dt))
            return out

        orch._select = select

    def summary(self, t0: float) -> dict:
        return {"selects": self.n, "select_max_ms": 1e3 * self.max_s,
                "select_slow": len(self.slow),
                "select_slow_at": [(a - t0, d) for a, d in
                                   self.slow[:KEEP_STALLS]]}


class Spans:
    """Host-clock spans of the benchmark's wraps (trace mode only)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.select: list[tuple[float, float, int]] = []  # (t0, t1, rows)
        self.select_of: dict[int, tuple[float, float]] = {}  # id(request)
        # (real rows, domain) of each domain-sharded pass
        self.pass_rows: list[tuple[int, str]] = []

    def span_of(self, request) -> tuple | None:
        with self.lock:
            return self.select_of.pop(id(request), None)

    def wrap_select(self, orch) -> None:
        import jax

        inner = orch._select

        def select(reqs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.select"):
                out = inner(reqs)
            t1 = time.perf_counter()
            with self.lock:
                self.select.append((t0, t1, len(reqs)))
                for r in reqs:
                    self.select_of[id(r)] = (t0, t1)
            return out

        orch._select = select

    def wrap_fleet(self, executor) -> None:
        import jax

        for name in ("run", "run_stream"):
            inner = getattr(executor, name)

            def call(*a, _inner=inner, **k):
                with jax.profiler.TraceAnnotation("bench.fleet"):
                    return _inner(*a, **k)

            setattr(executor, name, call)

    def wrap_sharded(self, sharded) -> None:
        inner = sharded.select_batch

        def select_batch(query_embs, slos, domain):
            with self.lock:
                self.pass_rows.append((len(query_embs), domain))
            return inner(query_embs, slos, domain)

        sharded.select_batch = select_batch


class LoopWatch:
    """Finds stalls of the event loop and what held it.  A task on the loop
    marks it alive every ``TICK_S``; a thread notes each time a mark is
    ``STALL_S`` late, with every thread's stack at that moment, the loop
    thread's innermost frame every ``SAMPLE_S`` while the stall lasts, and
    the cross-thread wakeups of the loop (``call_soon_threadsafe``) during
    it; and the interpreter's collections that took ``GC_SLOW_S`` or
    more."""

    def __init__(self):
        self.last = time.perf_counter()
        self.loop_thread = threading.get_ident()
        self.stalls: list[dict] = []
        self.gc_slow: list[tuple[int, float]] = []
        self.wakeups = 0
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._task = None
        self._thread = None
        self._loop = None

    async def _beat(self) -> None:
        while not self._stop.is_set():
            self.last = time.perf_counter()
            await asyncio.sleep(TICK_S)

    def _stacks(self, depth: int) -> dict[str, list[str]]:
        names = {t.ident: t.name for t in threading.enumerate()}
        out = {}
        for ident, frame in sys._current_frames().items():
            if ident == threading.get_ident():
                continue
            name = "loop" if ident == self.loop_thread else names.get(
                ident, str(ident))
            out[name] = [f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno} "
                         f"{f.name}" for f in
                         traceback.extract_stack(frame)[-depth:]]
        return out

    def _watch(self) -> None:
        current, next_sample = None, 0.0
        while not self._stop.wait(TICK_S):
            now = time.perf_counter()
            late = now - self.last
            if late > STALL_S and current is None:
                current = {"at": self.last, "s": late,
                           "wakeups0": self.wakeups, "samples": [],
                           "threads": self._stacks(6)}
                self.stalls.append(current)
                next_sample = now + SAMPLE_S
            elif current is not None:
                if late > STALL_S:
                    current["s"] = late
                    if now >= next_sample and len(current["samples"]) < 12:
                        top = self._stacks(1).get("loop", ["?"])
                        current["samples"].append(top[-1])
                        next_sample = now + SAMPLE_S
                else:
                    current["wakeups"] = self.wakeups - current.pop(
                        "wakeups0")
                    current = None

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            dt = time.perf_counter() - self._gc_t0
            if dt >= GC_SLOW_S:
                self.gc_slow.append((info["generation"], dt))

    def start(self) -> None:
        self.last = time.perf_counter()
        self._loop = asyncio.get_running_loop()
        inner = self._loop._write_to_self

        def write_to_self():
            self.wakeups += 1
            inner()

        self._loop._write_to_self = write_to_self
        self._task = self._loop.create_task(self._beat())
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-loop-watch")
        self._thread.start()
        gc.callbacks.append(self._gc)

    async def stop(self) -> None:
        """Stop the task and the thread (a watch never started is left
        as it is)."""
        if self._thread is None:
            return
        self._stop.set()
        gc.callbacks.remove(self._gc)
        self._thread.join()
        await self._task
        del self._loop._write_to_self
        self._thread = None

    def summary(self, t0: float) -> dict:
        """Stalls (seconds after ``t0``, length, stacks, wakeups), slow
        collections and the loop's cross-thread wakeups, for ``info``."""
        return {"loop_stalls": len(self.stalls),
                "loop_stall_max_s": max((x["s"] for x in self.stalls),
                                        default=0.0),
                "loop_wakeups": self.wakeups,
                "loop_stall_detail": [
                    {"at_s": x["at"] - t0, "s": x["s"],
                     "wakeups": x.get("wakeups"), "samples": x["samples"],
                     "threads": x["threads"]}
                    for x in self.stalls[:KEEP_STALLS]],
                "gc_slow": len(self.gc_slow),
                "gc_slow_max_s": max((x[1] for x in self.gc_slow),
                                     default=0.0)}


def make_front(dep, mix: dict, domains: list[str]):
    """The deployment's front door, not yet started; tenant ``i`` of a
    router is served by ``domains[i mod D]``."""
    srv = dep.config["serving"]
    if dep.config["front"] == "orchestrator":
        return dep.server.orchestrator(max_batch=srv["max_batch"],
                                       max_wait_ms=srv["max_wait_ms"],
                                       max_queue=srv["max_queue"])
    from repro.runtime.router import TenantRouter, TenantSpec

    ten = mix["tenants"]
    tenants = [TenantSpec(f"tenant{i}", slo_class=ten["slo_class"],
                          domain=domains[i % len(domains)])
               for i in range(ten["n"])]
    return TenantRouter(dep.server, tenants, n_shards=srv["n_shards"],
                        max_batch=srv["max_batch"],
                        max_wait_ms=srv["max_wait_ms"],
                        max_queue=srv["max_queue"])


def orchestrators(front) -> list:
    return front.shard_list() if hasattr(front, "shard_list") else [front]


def admission_totals(front) -> dict:
    tot = {"batches": 0, "dispatched": 0}
    for o in orchestrators(front):
        st = o.stats()
        tot["batches"] += st["batches"]
        tot["dispatched"] += st["dispatched"]
    return tot


async def settle(records: list[Record], deadline: float) -> None:
    """Wait until every recorded ticket settles or ``deadline`` passes."""
    pending = [r.ticket._future for r in records if r.ticket is not None]
    if pending:
        await asyncio.wait(pending,
                           timeout=max(deadline - time.perf_counter(), 0.0))
    await asyncio.sleep(0)  # let the last done-callbacks copy their records


def warm_arrivals(kind, cfg: dict, mix: dict, pools: dict,
                  seed: int) -> list:
    """The ``warm_requests`` arrivals of a run, with the kind's fields."""
    n = int(cfg["warm_requests"])
    rng = np.random.default_rng([seed, 1])
    arrivals = [traffic_mod.Arrival(0.0, *r)
                for r in traffic_mod.requests(mix, pools, n, rng)]
    return kind.extend(cfg, mix, arrivals, seed)


async def warm(front, kind, dep, mix: dict, pools: dict, seed: int) -> None:
    """Serve ``warm_requests`` through the front door, all at once: fleet
    threads, executor caches and every bucket shape get used once."""
    arrivals = warm_arrivals(kind, dep.config, mix, pools, seed)
    tickets = [await front.submit(kind.make_request(dep, a))
               for a in arrivals]
    await asyncio.wait([t._future for t in tickets], timeout=SETTLE_GRACE_S)


async def open_window(front, arrivals, make_request, t0: float,
                      spans: Spans | None) -> list[Record]:
    import jax

    records = []
    for a in arrivals:
        due = t0 + a.due_s
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        req = make_request(a)
        sent = time.perf_counter()
        if spans is None:
            ticket = await front.submit(req)
        else:
            with jax.profiler.TraceAnnotation("bench.submit"):
                ticket = await front.submit(req)
        records.append(Record(a, due, sent, ticket, spans))
    return records


async def closed_window(front, arrivals, make_request, clients: int,
                        t_end: float, spans: Spans | None) -> list[Record]:
    import jax

    records: list[Record] = []
    nxt = itertools.cycle(arrivals)

    async def client():
        while time.perf_counter() < t_end:
            a = next(nxt)
            req = make_request(a)
            sent = time.perf_counter()
            if spans is None:
                ticket = await front.submit(req)
            else:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    ticket = await front.submit(req)
            records.append(Record(a, sent, sent, ticket, spans))
            # a failed request is counted by the end-to-end arithmetic; one
            # that never settles holds its client until the close
            await asyncio.wait([ticket._future],
                               timeout=max(t_end - time.perf_counter(), 0.0))

    await asyncio.gather(*(client() for _ in range(clients)))
    return records
