"""Asyncio-native serving front-end (paper §4: the always-on Runtime).

One ``Orchestrator`` replaces the three parallel blocking entrypoints that
had accreted around the server (``EcoLLMServer.handle``, ``handle_batch``,
``ReplicaFleet.submit_many``): callers ``submit()`` requests with per-request
SLO / priority / deadline and get an awaitable ``Ticket`` back.  A
micro-batching admission loop coalesces concurrent submissions — up to
``max_batch`` tickets or ``max_wait_ms`` after the first, whichever comes
first — and dispatches each bucket as ONE fused
``RuntimePathSelector.select_batch`` pass plus ONE non-blocking
``ReplicaFleet.submit_many_async`` fan-out, so open-world traffic rides the
amortized batch machinery by default instead of opt-in.  With the kernel
engine the whole bucket is handed to the composed
embed -> retrieve -> score -> argmax device program ONCE per admission
bucket (one jit trace per shape bucket — ``stats()['fused_traces']``); only
the rare OOD-fallback rows return to host Python.

Backpressure is explicit: the admission queue is bounded (``max_queue``) and
overflow is rejected immediately with a typed ``Overloaded`` result (load
shedding) instead of queueing without bound; a per-request ``deadline_s``
additionally sheds tickets whose admission deadline lapsed before dispatch.
Higher ``priority`` tickets are admitted first when a backlog forms.

Every ticket carries a lifecycle timeline (``Ticket.events``):
``admitted -> taken -> selected -> dispatched -> completed`` (or ``... ->
shed``), stamped with ``time.perf_counter()``; ``taken`` is when the
admission loop took it off the queue for a bucket.  Each bucket is numbered
(``Ticket.bucket``), and the spans of its work carry the number
(``runtime/tracing.py``).  Selection overheads ride on the
``Decision`` as before — amortized ``overhead_s`` plus the full
``batch_overhead_s`` of the bucket's selection pass.

Streaming contract: a ticket is also an async iterator — ``async for chunk
in ticket`` yields the response's ``GenChunk``s (split-inference drafts or
whole-model decode spans) in order, exactly once, whether the consumer
starts before the first chunk, midway, or after settle.  The fleet's
threads buffer each chunk on the ticket and wake the event loop only for a
consumer parked waiting for one (at most one wake per park), so a ticket
nobody iterates costs the loop nothing for its chunks.  ``first_chunk``
lands on the timeline between ``dispatched`` and ``completed``: stamped
with the first chunk's arrival in the buffer (no earlier than
``dispatched``), marked on the loop at the first consumer read or at
settle, whichever comes first.  The iterator terminates when the ticket
settles (completed, shed, or failed), so it is safe on non-streaming
outcomes too — it just yields nothing.  Chunks are a single-consumer side
channel; ``await ticket`` is unchanged and bit-for-bit identical to the
pre-streaming contract (the final Response comes from the same
non-streamed accounting).  ``stats()`` counts ``chunks_streamed`` (chunks
buffered on tickets) and ``chunk_wakes`` (loop wakes made to deliver them).

The synchronous ``EcoLLMServer.handle`` / ``handle_batch`` survive as thin
compatibility shims over ``dispatch_sync`` — the same bucket-dispatch
pipeline with the blocking fleet fan-out, bit-for-bit the pre-orchestrator
responses.
"""
from __future__ import annotations

import asyncio
import heapq
import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import TYPE_CHECKING, Optional, Union

import numpy as np

from repro.runtime import tracing

if TYPE_CHECKING:  # circular only for typing: server builds an Orchestrator
    from repro.runtime.server import EcoLLMServer, Request, Response


@dataclass(frozen=True)
class Overloaded:
    """Typed load-shed result: the orchestrator refused this request instead
    of queueing it without bound.  ``reason`` is ``"queue_full"`` (bounded
    admission queue overflowed), ``"deadline"`` (the per-request admission
    deadline lapsed before dispatch), ``"shutdown"``, or ``"stale_loop"``
    (submitted in a previous, now-closed event-loop session — nothing can
    await it anymore)."""

    reason: str
    queue_depth: int
    max_queue: int


def _wake(waiter: asyncio.Future) -> None:
    """Resume a parked chunk consumer (on its loop); the waiter of a
    consumer cancelled while parked is already done."""
    if not waiter.done():
        waiter.set_result(None)


class Ticket:
    """Awaitable handle for one admitted (or shed) request.

    ``await ticket`` / ``await ticket.wait()`` yields the ``Response`` — or
    an ``Overloaded`` marker if the request was shed.  ``events`` is the
    lifecycle timeline: ``[(name, perf_counter_ts), ...]`` through
    ``admitted -> taken -> selected -> dispatched -> completed`` (``shed``
    replaces the tail for rejected tickets; ``failed`` for a bucket whose
    dispatch raised — awaiting the ticket then re-raises that error).
    ``bucket`` is the id of the admission bucket that took it and ``row``
    its position among the bucket's dispatched tickets (None until then).

    ``async for chunk in ticket`` consumes the streamed partial results
    (module docstring): ordered, exactly-once, terminated when the ticket
    settles.  Chunks wait in a buffer on the ticket, filled from the
    fleet's threads; the loop is woken only for a consumer parked on an
    empty buffer.  ``first_chunk`` on the timeline is the first chunk's
    arrival in the buffer, no earlier than ``dispatched``.  Single
    consumer: chunks go to whichever iterator reads them first (a second
    ``async for`` after exhaustion terminates immediately).
    """

    __slots__ = ("request", "priority", "deadline_s", "deadline_at", "events",
                 "bucket", "row", "_future", "_chunks", "_chunk_lock",
                 "_waiter", "_first_chunk_at", "_n_chunks", "_n_wakes",
                 "_stream_done")

    def __init__(self, request: "Request", priority: int,
                 deadline_s: Optional[float], future: asyncio.Future):
        self.request = request
        self.priority = priority
        self.deadline_s = deadline_s
        self.deadline_at: Optional[float] = None  # set on admission
        self.events: list[tuple[str, float]] = []
        self.bucket: Optional[int] = None
        self.row: Optional[int] = None
        self._future = future
        # streaming side channel: the buffer, the parked consumer's waiter,
        # the end flag and the counters are guarded by _chunk_lock
        self._chunks: deque = deque()
        self._chunk_lock = threading.Lock()
        self._waiter: Optional[asyncio.Future] = None
        self._first_chunk_at: Optional[float] = None
        self._n_chunks = 0  # chunks buffered
        self._n_wakes = 0   # loop wakes scheduled to deliver them
        self._stream_done = False

    def mark(self, name: str) -> None:
        self.events.append((name, time.perf_counter()))

    def event(self, name: str) -> Optional[float]:
        """Timestamp of the first occurrence of ``name``, or None."""
        for n, ts in self.events:
            if n == name:
                return ts
        return None

    def done(self) -> bool:
        return self._future.done()

    @property
    def shed(self) -> bool:
        return (self._future.done() and not self._future.cancelled()
                and self._future.exception() is None
                and isinstance(self._future.result(), Overloaded))

    def __await__(self):
        return self._future.__await__()

    async def wait(self) -> Union["Response", Overloaded]:
        return await self._future

    # -- streaming side channel ---------------------------------------------

    def _put_chunk(self, chunk) -> None:
        """Buffer one streamed chunk; the fleet calls this from its threads
        (under the flight lock, so puts arrive in order).  The loop is
        touched only to wake a parked consumer, once per park."""
        with self._chunk_lock:
            if self._stream_done:
                return  # settled already (e.g. raced with an error) — drop
            if self._first_chunk_at is None:
                self._first_chunk_at = time.perf_counter()
            self._chunks.append(chunk)
            self._n_chunks += 1
            waiter, self._waiter = self._waiter, None
        if waiter is not None:
            try:
                waiter.get_loop().call_soon_threadsafe(_wake, waiter)
            except RuntimeError:
                return  # loop closed mid-stream: nothing can consume chunks
            with self._chunk_lock:
                self._n_wakes += 1

    def _mark_first_chunk(self) -> None:
        """Put the first chunk's arrival on the timeline (loop thread), once.
        A chunk can reach the buffer before the loop marks ``dispatched``,
        so the stamp is held to no earlier than that mark."""
        at = self._first_chunk_at
        if at is None or self.event("first_chunk") is not None:
            return
        dispatched = self.event("dispatched")
        if dispatched is not None:
            at = max(at, dispatched)
        self.events.append(("first_chunk", at))

    def _end_stream(self) -> None:
        """Terminate the chunk iterator and wake a parked consumer;
        idempotent, called at settle (loop thread)."""
        with self._chunk_lock:
            if self._stream_done:
                return
            self._stream_done = True
            waiter, self._waiter = self._waiter, None
        if waiter is not None:
            _wake(waiter)
        tracing.timeline(self.bucket, self.row, self.events)

    async def _iter_chunks(self):
        marked = False
        while True:
            waiter = None
            with self._chunk_lock:
                if self._chunks:
                    chunk = self._chunks.popleft()
                elif self._stream_done:
                    return
                else:
                    # park; a racing second consumer shares the waiter, so
                    # the one wake (or settle) resumes both
                    waiter = self._waiter
                    if waiter is None or waiter.done():
                        waiter = self._waiter = \
                            asyncio.get_running_loop().create_future()
            if waiter is not None:
                await waiter
                continue
            if not marked:
                self._mark_first_chunk()
                marked = True
            yield chunk

    def __aiter__(self):
        return self._iter_chunks()


_STOP_PRIO = float("inf")  # sorts after every real ticket in the heap


class BucketRows(list):
    """A bucket's requests, in order, with the bucket's id: what
    ``Orchestrator._select`` receives."""

    __slots__ = ("bucket",)


class Orchestrator:
    """Single async front-end over a trained ``EcoLLMServer``.

    Usage (async)::

        orch = Orchestrator(server, max_batch=32, max_wait_ms=2.0)
        await orch.start()
        ticket = await orch.submit(Request(...), priority=1, deadline_s=0.5)
        response = await ticket            # Response | Overloaded
        await orch.stop()                  # drains admitted tickets first

    or ``async with Orchestrator(server) as orch: ...``.  The synchronous
    ``dispatch_sync`` path (used by the ``handle``/``handle_batch`` shims)
    shares the same one-``select_batch``-one-fan-out pipeline without
    needing a running event loop.
    """

    def __init__(self, server: "EcoLLMServer", *, max_batch: int = 32,
                 max_wait_ms: float = 2.0, max_queue: int = 256,
                 hedge: bool = True, stream: bool = True,
                 shard_id: Optional[int] = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.server = server
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1e3
        self.max_queue = max_queue
        self.hedge = hedge
        self.stream = stream  # thread chunk delivery through to tickets
        # multi-tenant serving plane: an orchestrator can be one admission
        # shard of a TenantRouter (runtime/router.py); the id tags its fleet
        # dispatches so the ONE shared fleet attributes load per shard
        self.shard_id = shard_id
        # heap entries: (-priority, seq, ticket) — seq breaks ties FIFO and
        # keeps ticket objects out of the comparison
        self._queue: asyncio.PriorityQueue = asyncio.PriorityQueue(
            maxsize=max_queue)
        # stop sentinels currently enqueued: qsize() minus this is the real
        # backlog (a bare qsize() reported depth 1 on an empty stopping queue)
        self._stop_sentinels = 0
        self._seq = itertools.count()
        self._queue_loop: Optional[asyncio.AbstractEventLoop] = None
        self._task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._closed = False
        # admission telemetry; completions land from fleet worker threads,
        # shim dispatches from arbitrary caller threads — lock the counters
        self._stats_lock = threading.Lock()
        self.admitted = 0
        self.shed_count = 0
        self.deadline_shed_count = 0
        self.batches = 0
        self.dispatched = 0
        self.completed = 0  # executions that produced a Response
        self.failed = 0     # executions whose await re-raises
        self.select_passes = 0  # selection passes run (one per domain group)
        self.fallback_rows = 0  # rows decided by the host OOD fallback
        self.chunks_streamed = 0  # chunks buffered on settled tickets
        self.chunk_wakes = 0      # loop wakes made to deliver them
        # online adaptation observer (runtime/adaptation.py); None keeps the
        # settle/shed hooks at a single attribute load on the hot path
        self._adaptation = None

    def attach_adaptation(self, plane) -> None:
        """Attach an ``AdaptationPlane`` observer: every settled/shed
        outcome is appended (lock-free ring write, no table access) from
        the ``_note_*`` hooks.  Pass ``None`` to detach."""
        self._adaptation = plane

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "Orchestrator":
        """Start the micro-batching admission loop on the running loop."""
        if self._task is not None and not self._task.done():
            return self
        self._loop = asyncio.get_running_loop()
        # the asyncio queue loop-binds on its first awaited get(); a fresh
        # loop (a second asyncio.run session against the same orchestrator,
        # e.g. the server-singleton) needs a fresh queue, otherwise the
        # admission task dies instantly on a cross-loop get() and every
        # subsequently submitted ticket hangs forever.  put_nowait/get_nowait
        # are loop-free, so pending entries transfer safely.
        if self._queue_loop is not self._loop:
            # runs on the first start too (_queue_loop None): submits may
            # have happened under an earlier, since-closed loop even if no
            # admission loop ever ran there
            old, self._queue = self._queue, asyncio.PriorityQueue(
                maxsize=self.max_queue)
            while not old.empty():
                entry = old.get_nowait()
                ticket = entry[2]
                if ticket is None:
                    # stale stop sentinel from a torn-down session: carrying
                    # it over would make the fresh admission loop exit as
                    # soon as it drains to it
                    self._stop_sentinels = max(0, self._stop_sentinels - 1)
                    continue
                if ticket._future.get_loop() is not self._loop:
                    # the ticket's future is bound to a previous (dead)
                    # loop: nothing in this session can await it, and
                    # settling it could raise on the closed loop — shed it
                    try:
                        self._shed(ticket, "stale_loop")
                    except RuntimeError:  # dead-loop future had awaiters
                        pass
                    continue
                self._queue.put_nowait(entry)
        self._queue_loop = self._loop
        self._closed = False
        self._task = self._loop.create_task(self._admission_loop())
        return self

    async def stop(self) -> None:
        """Stop the admission loop, dispatching every already-admitted
        ticket first; subsequent submits are shed with reason 'shutdown'.
        Idempotent under concurrency: the task handle is claimed before the
        first suspension point, so racing stop() calls enqueue exactly one
        stop sentinel (a stale second sentinel would make the NEXT session's
        admission loop exit on arrival)."""
        task, self._task = self._task, None
        # flag first: stop() before (or without) start() must still flip the
        # orchestrator to shedding, else later submits enqueue onto a queue
        # with no consumer and hang forever
        self._closed = True
        if task is None:
            return
        if not task.done():
            await self._queue.put((_STOP_PRIO, next(self._seq), None))
            # counted after the put lands; both sides run on the loop
            # thread, so the admission loop can't pop it before this line
            self._stop_sentinels += 1
        await task

    async def __aenter__(self) -> "Orchestrator":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    def reconfigure(self, *, max_batch: Optional[int] = None,
                    max_wait_ms: Optional[float] = None,
                    max_queue: Optional[int] = None,
                    hedge: Optional[bool] = None,
                    stream: Optional[bool] = None) -> "Orchestrator":
        """Change the admission policy while the loop is NOT running (the
        synchronous ``dispatch_sync`` path is policy-free, so a shim-created
        orchestrator can be re-tuned before its first async ``start()``).
        Already-enqueued tickets are carried over; if a smaller ``max_queue``
        cannot hold them the overflow is shed (``queue_full``)."""
        if self._task is not None and not self._task.done():
            raise RuntimeError("cannot reconfigure a running admission loop")
        if max_batch is not None:
            if max_batch < 1:
                raise ValueError("max_batch must be >= 1")
            self.max_batch = max_batch
        if max_wait_ms is not None:
            self.max_wait_s = max_wait_ms / 1e3
        if hedge is not None:
            self.hedge = hedge
        if stream is not None:
            self.stream = stream
        if max_queue is not None and max_queue != self.max_queue:
            self.max_queue = max_queue
            old, self._queue = self._queue, asyncio.PriorityQueue(
                maxsize=max_queue)
            while not old.empty():
                entry = old.get_nowait()
                try:
                    self._queue.put_nowait(entry)
                except asyncio.QueueFull:
                    if entry[2] is not None:
                        self._shed(entry[2], "queue_full")
        return self

    # -- admission -----------------------------------------------------------

    async def submit(self, request: "Request", *, priority: int = 0,
                     deadline_s: Optional[float] = None) -> Ticket:
        """Admit one request; returns immediately with an awaitable Ticket.

        If the bounded admission queue is full (or the orchestrator is
        stopping) the ticket comes back already completed with a typed
        ``Overloaded`` result — explicit load shedding, never unbounded
        queueing.  ``priority`` orders admission under backlog (higher
        first); ``deadline_s`` sheds the ticket if it is still waiting for
        dispatch that many seconds after admission.
        """
        with tracing.span("eco.submit"):
            ticket = self._admit(request, priority, deadline_s)
        # yield once per admission: enqueueing itself never suspends, so a
        # tight submit loop would otherwise starve the admission loop and
        # spuriously shed a closed workload larger than max_queue
        await asyncio.sleep(0)
        return ticket

    def _admit(self, request: "Request", priority: int,
               deadline_s: Optional[float]) -> Ticket:
        """The synchronous part of ``submit``: enqueue or shed."""
        loop = asyncio.get_running_loop()
        ticket = Ticket(request, priority, deadline_s, loop.create_future())
        if self._closed:
            self._shed(ticket, "shutdown")
            return ticket
        try:
            self._queue.put_nowait((-float(priority), next(self._seq), ticket))
        except asyncio.QueueFull:
            # before shedding viable traffic, evict queue entries whose own
            # deadline already lapsed — they are shed either way, and they
            # must not squat on bounded-queue capacity
            if not self._purge_lapsed():
                self._shed(ticket, "queue_full")
                return ticket
            try:
                self._queue.put_nowait(
                    (-float(priority), next(self._seq), ticket))
            except asyncio.QueueFull:  # full of still-viable tickets
                self._shed(ticket, "queue_full")
                return ticket
        ticket.mark("admitted")
        if deadline_s is not None:
            ticket.deadline_at = ticket.events[-1][1] + deadline_s
        with self._stats_lock:
            self.admitted += 1
        return ticket

    def _queue_depth(self) -> int:
        """Real admission backlog: qsize() minus enqueued stop sentinels."""
        return max(0, self._queue.qsize() - self._stop_sentinels)

    def _fail(self, ticket: Ticket, err: Exception) -> None:
        ticket.mark("failed")
        with self._stats_lock:
            self.failed += 1
            self._note_settled(ticket, None, err)
        if not ticket._future.done():
            ticket._future.set_exception(err)
        ticket._end_stream()

    def _shed(self, ticket: Ticket, reason: str) -> None:
        ticket.mark("shed")
        with self._stats_lock:
            self.shed_count += 1
            if reason == "deadline":
                self.deadline_shed_count += 1
            self._note_shed(ticket, reason)
        if not ticket._future.done():
            ticket._future.set_result(
                Overloaded(reason, self._queue_depth(), self.max_queue))
        ticket._end_stream()

    # -- outcome hooks (AdmissionShard overrides add per-tenant accounting
    # and MUST call super() so adaptation observation still fires).  Both
    # run UNDER self._stats_lock so shard counters stay consistent with the
    # aggregate ones they refine; the adaptation observer is a bounded ring
    # append — producers are serialized by this very lock, and the fold work
    # happens on the plane's background thread, never here.

    def _note_shed(self, ticket: Ticket, reason: str) -> None:
        plane = self._adaptation
        if plane is not None:
            plane.observe_shed(self, ticket, reason)

    def _note_settled(self, ticket: Ticket, resp, err) -> None:
        plane = self._adaptation
        if plane is not None:
            plane.observe_settled(self, ticket, resp, err)

    def _purge_lapsed(self) -> int:
        """Shed queued tickets whose admission deadline already lapsed, so
        dead entries stop counting against ``max_queue`` capacity (they were
        previously only shed when popped into a bucket, squatting on slots
        and forcing ``queue_full`` sheds of viable traffic).  Runs on the
        loop thread; rebuilds the underlying heap in place."""
        now = time.perf_counter()
        heap = self._queue._queue

        def lapsed(entry) -> bool:
            t = entry[2]
            return (t is not None and t.deadline_at is not None
                    and now > t.deadline_at)

        dead = [e for e in heap if lapsed(e)]
        if not dead:
            return 0
        keep = [e for e in heap if not lapsed(e)]
        heap.clear()
        heap.extend(keep)
        heapq.heapify(heap)
        # the Queue's unfinished-task counter tracks puts, not the heap; the
        # orchestrator never calls task_done/join, so no rebalance is needed
        for e in dead:
            self._shed(e[2], "deadline")
        return len(dead)

    async def _admission_loop(self) -> None:
        """Accumulate concurrent submissions into buckets and dispatch each
        as one fused selection pass + one fleet fan-out."""
        while True:
            entry = await self._queue.get()
            if entry[2] is None:  # stop sentinel sorts last: queue is drained
                self._stop_sentinels = max(0, self._stop_sentinels - 1)
                return
            entry[2].mark("taken")
            bucket = [entry[2]]
            t0 = time.perf_counter()
            stop = False
            while len(bucket) < self.max_batch:
                remaining = self.max_wait_s - (time.perf_counter() - t0)
                if remaining <= 0:
                    break
                try:
                    nxt = await asyncio.wait_for(self._queue.get(), remaining)
                except asyncio.TimeoutError:
                    break  # deadline flush: dispatch the partial bucket
                if nxt[2] is None:
                    self._stop_sentinels = max(0, self._stop_sentinels - 1)
                    stop = True
                    break
                nxt[2].mark("taken")
                bucket.append(nxt[2])
            bid = tracing.new_bucket()
            with tracing.span("eco.bucket", bid) as sp:
                live = self._close_bucket(bucket, bid)
                sp.count("rows", len(live))
            if live:
                try:
                    await self._dispatch(live, bid)
                except Exception as e:  # noqa: BLE001 — fail the bucket,
                    # keep admitting: a dead admission loop would hang every
                    # pending ticket forever
                    for t in live:
                        self._fail(t, e)
            if stop:
                return

    def _close_bucket(self, bucket: list[Ticket], bid: int) -> list[Ticket]:
        """Shed the bucket's lapsed tickets, number the rest (``bucket``,
        ``row``) and count them dispatched; returns them."""
        now = time.perf_counter()
        live = []
        for t in bucket:
            t.bucket = bid
            if t.deadline_at is not None and now > t.deadline_at:
                self._shed(t, "deadline")
            else:
                t.row = len(live)
                live.append(t)
        if live:
            with self._stats_lock:
                self.batches += 1
                self.dispatched += len(live)
        return live

    # -- dispatch ------------------------------------------------------------

    def _select(self, reqs: list["Request"]):
        """One fused selection pass for a bucket: resolve -> ``select_batch``
        -> (query, path, domain) jobs.  Shared by the async admission loop
        and the synchronous shim path, so both produce identical decisions.

        Single-domain servers take EXACTLY the pre-multi-tenant path (same
        selector, same call); on a multi-domain server the bucket's rows are
        grouped by domain and each group runs through the domain-sharded
        fused program — one traced pass per group with the domain id as a
        carried scalar, no re-trace per tenant/domain.

        ``reqs`` is a ``BucketRows`` where the bucket is numbered; its spans
        (``eco.select`` and the selector's inside it) carry the number."""
        srv = self.server
        with tracing.span("eco.select", getattr(reqs, "bucket", None),
                          rows=len(reqs)):
            with tracing.span("eco.select.resolve"):
                resolved = [srv._resolve_query(r) for r in reqs]
                if not srv.is_multi_domain():
                    groups = {None: list(range(len(reqs)))}
                else:
                    groups = {}
                    for i, r in enumerate(reqs):
                        groups.setdefault(srv.canonical_domain(r.domain),
                                          []).append(i)
                embs = {dom: np.stack([resolved[i][1] for i in idxs])
                        for dom, idxs in groups.items()}
            decisions = [None] * len(reqs)
            for dom, idxs in groups.items():
                slos = [reqs[i].slo for i in idxs]
                if dom is None:
                    ds = srv.rps.select_batch(embs[dom], slos)
                else:
                    ds = srv.sharded_selector().select_batch(embs[dom], slos,
                                                             dom)
                for i, d in zip(idxs, ds):
                    decisions[i] = d
            with self._stats_lock:
                self.select_passes += len(groups)
                self.fallback_rows += sum(d.used_fallback for d in decisions)
            jobs = [(query, d.path, r.domain or srv.DEFAULT_DOMAIN)
                    for (query, _), d, r in zip(resolved, decisions, reqs)]
        return resolved, decisions, jobs

    def _fleet_tag(self) -> Optional[str]:
        """Fleet dispatch-attribution tag: ``shard<i>`` when this
        orchestrator is an admission shard, None (untagged) otherwise."""
        return None if self.shard_id is None else f"shard{self.shard_id}"

    async def _dispatch(self, tickets: list[Ticket], bid: int) -> None:
        """Dispatch one bucket without blocking the event loop: selection is
        CPU-bound so it runs on the default executor; the fleet fan-out is
        non-blocking and completes each ticket via callback."""
        reqs = BucketRows(t.request for t in tickets)
        reqs.bucket = bid
        resolved, decisions, jobs = await self._loop.run_in_executor(
            None, self._select, reqs)
        for t in tickets:
            t.mark("selected")
        futures = self.server.fleet.submit_many_async(jobs, hedge=self.hedge,
                                                      stream=self.stream,
                                                      tag=self._fleet_tag(),
                                                      bucket=bid)
        for t in tickets:
            t.mark("dispatched")
        for t, (query, _), dec, fut in zip(tickets, resolved, decisions,
                                           futures):
            if self.stream:
                # chunks are buffered on the ticket; the fleet replays any
                # that arrived before this subscription, in order
                fut.add_chunk_callback(t._put_chunk)
            fut.add_done_callback(self._completer(t, query, dec))

    def _completer(self, ticket: Ticket, query, decision):
        """Fleet-side completion callback: build the Response off-loop, then
        settle the ticket's future on the loop thread."""
        srv, loop = self.server, self._loop

        def cb(fut):
            with tracing.span("eco.fleet.respond", ticket.bucket,
                              row=ticket.row):
                try:
                    result, meta = fut.result(0)
                    resp = srv._respond(ticket.request, query, decision,
                                        result, meta)
                    err = None
                except Exception as e:  # noqa: BLE001 — surfaced on the
                    resp, err = None, e  # ticket

            def record():
                ticket._mark_first_chunk()
                ticket.mark("completed" if err is None else "failed")
                with self._stats_lock:
                    if err is None:
                        self.completed += 1
                    else:
                        self.failed += 1
                    # every chunk has arrived: the fleet refuses emits once
                    # a flight completes
                    self.chunks_streamed += ticket._n_chunks
                    self.chunk_wakes += ticket._n_wakes
                    self._note_settled(ticket, resp, err)

            def settle():
                with tracing.span("eco.settle", ticket.bucket,
                                  row=ticket.row):
                    record()
                    if not ticket._future.done():
                        if err is not None:
                            ticket._future.set_exception(err)
                        else:
                            ticket._future.set_result(resp)
                    ticket._end_stream()

            try:
                loop.call_soon_threadsafe(settle)
            except RuntimeError:
                # the loop already closed (the caller abandoned the session
                # without awaiting this ticket): nothing can observe the
                # future anymore — record the outcome for telemetry and let
                # the fleet worker finish cleanly instead of dying here
                record()

        return cb

    # -- synchronous shim path -----------------------------------------------

    def dispatch_sync(self, reqs) -> list["Response"]:
        """Dispatch one explicit bucket synchronously: the same
        one-``select_batch`` + one-fan-out pipeline as the admission loop,
        but over the blocking ``submit_many`` so callers get responses
        directly.  ``EcoLLMServer.handle`` / ``handle_batch`` are thin
        wrappers over this — a single request is simply a bucket of one."""
        reqs = BucketRows(reqs)
        if not reqs:
            return []
        reqs.bucket = tracing.new_bucket()
        with self._stats_lock:
            self.admitted += len(reqs)
            self.batches += 1
            self.dispatched += len(reqs)
        try:
            resolved, decisions, jobs = self._select(reqs)
            outcomes = self.server.fleet.submit_many(jobs, hedge=self.hedge,
                                                     tag=self._fleet_tag(),
                                                     bucket=reqs.bucket)
        except Exception:
            with self._stats_lock:  # keep completed + failed == dispatched
                self.failed += len(reqs)
            raise
        with self._stats_lock:
            self.completed += len(reqs)
        return [self.server._respond(req, query, d, result, meta)
                for req, (query, _), d, (result, meta)
                in zip(reqs, resolved, decisions, outcomes)]

    # -- telemetry -----------------------------------------------------------

    def stats(self) -> dict:
        """Admission counters + queue depth in one consistent observation."""
        with self._stats_lock:
            return {
                "admitted": self.admitted,
                "shed": self.shed_count,
                "deadline_shed": self.deadline_shed_count,
                "batches": self.batches,
                # (re)traces of the fused selection program: bounded by the
                # distinct shape buckets seen, 0 for the numpy engine (or a
                # serverless orchestrator, e.g. shed-path unit tests)
                "fused_traces": getattr(
                    getattr(self.server, "rps", None),
                    "kernel_trace_count", 0),
                "dispatched": self.dispatched,
                "completed": self.completed,
                "failed": self.failed,
                "select_passes": self.select_passes,
                "fallback_rows": self.fallback_rows,
                "chunks_streamed": self.chunks_streamed,
                "chunk_wakes": self.chunk_wakes,
                "queue_depth": self._queue_depth(),
                "max_batch": self.max_batch,
                "max_queue": self.max_queue,
                "shard_id": self.shard_id,
            }

    def adaptation_state(self) -> Optional[dict]:
        """This orchestrator's (shard's) adaptation-plane telemetry, or
        None when no plane is attached."""
        plane = self._adaptation
        return None if plane is None else plane.shard_state(self)
