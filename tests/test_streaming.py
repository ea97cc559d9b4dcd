"""Streaming delivery + PR-6 bugfix regressions.

Covers the streaming contract end to end — first-bytes-wins ownership under
hedged duplicates, exactly-once in-order chunk delivery, mid-stream
cancellation accounting (fleet counters == per-request meta), ``await
ticket`` vs ``async for`` equivalence, the ``first_chunk`` timeline event —
plus the satellite fixes: straggle double-count in ``Replica.call``, stop
sentinels inflating queue depth, and deadline-lapsed tickets squatting on
bounded admission-queue capacity.
"""
import asyncio
import random
import sys
import threading
import time
from collections import defaultdict

import numpy as np
import pytest

from repro.core.devices import EDGE_DEVICES
from repro.core.paths import MODEL_CATALOG, SPLIT_IMPL
from repro.core.splitgen import DraftState, generate_split
from repro.launch.serve import build_server
from repro.runtime.fleet import Replica, ReplicaFleet
from repro.runtime.orchestrator import Orchestrator, Overloaded, Ticket
from repro.runtime.server import Request


@pytest.fixture(scope="module")
def served():
    # split=True: the path space (and thus the trained RPS) includes the
    # CE-CoLLM edge-draft/cloud-verify configurations
    return build_server("smarthome", n_queries=30, budget=2.0, seed=1,
                        split=True)


def _quiesce(fleet, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        snap = fleet.snapshot()
        if snap["in_flight"] == 0 and snap["queue_depth"] == 0:
            return snap
        time.sleep(0.002)
    raise AssertionError("fleet did not quiesce")


# -- satellite: Replica.call straggle accounting -----------------------------


def test_straggle_latency_not_double_counted():
    """Regression: modeled latency is wall + only the UN-slept remainder of
    the injected straggle.  The old code added the whole ``straggle_s`` on
    top of a wall clock that already contained the bounded real sleep, so
    the rolling p95 driving hedge deadlines was inflated by the overlap."""
    rep = Replica(rid=0, execute=lambda job: "ok",
                  straggle_rate=1.0, straggle_s=0.5)
    out, lat = rep.call("job", random.Random(0))
    assert out == "ok"
    wall = rep.stats.wall_latencies[-1]
    assert rep.stats.latencies[-1] == lat
    assert lat == wall + (0.5 - 0.05)  # exact: same float expression
    assert wall < 0.25  # only the bounded 50 ms sleep was real


# -- fleet streaming: ownership, exactly-once, cancellation ------------------


def _streaming_fleet(n_chunks=3, chunk_delay=0.0, log=None, **kw):
    """Two replicas; rid 0 straggles before its stream starts (the bounded
    50 ms sleep happens in ``Replica.call`` ahead of ``execute_stream``)."""
    def make(rid):
        def execute(job):
            return ("full", job)

        def execute_stream(job, emit):
            for i in range(n_chunks):
                ok = emit((rid, i))
                if log is not None:
                    log.append((rid, i, ok))
                if not ok:
                    return None  # torn down: a rival owns the stream
                if chunk_delay:
                    time.sleep(chunk_delay)
            return ("full", job)

        return Replica(rid=rid, execute=execute,
                       execute_stream=execute_stream,
                       straggle_rate=1.0 if rid == 0 else 0.0,
                       straggle_s=1.0)
    return ReplicaFleet(make, n=2, seed=2, **kw)


def test_hedged_stream_delivers_chunks_exactly_once_in_order():
    """A straggling primary gets a hedge duplicate; whoever emits first owns
    the stream, every subscriber sees each chunk exactly once and in order,
    and the loser is cancelled with exact counter accounting.

    The work-stealing balancer may legitimately resolve the whole batch on
    the fast replica before the straggler claims anything — then no flight
    straggles and there is correctly nothing to hedge — so the scenario
    retries until a straggling primary actually existed (a hedge fired)."""
    for _ in range(10):
        log = []
        fleet = _streaming_fleet(log=log)
        # warm the backup's rolling wall-clock p95 so hedge deadlines are
        # armed
        fleet.replicas[0].straggle_rate = 0.0
        for _ in range(24):
            fleet.submit("warm")
        fleet.replicas[0].straggle_rate = 1.0

        got = defaultdict(list)
        futs = fleet.submit_many_async([f"j{i}" for i in range(6)],
                                       stream=True)
        for i, fut in enumerate(futs):
            fut.add_chunk_callback(lambda c, i=i: got[i].append(c))
        outs = [fut.result(timeout=10.0) for fut in futs]
        snap = _quiesce(fleet)
        if any(m["hedges"] for _, m in outs):
            break
        fleet.close()  # everything landed on the fast replica: re-roll
    else:
        raise AssertionError("no hedge fired in 10 attempts")
    for i, (out, meta) in enumerate(outs):
        assert out == ("full", f"j{i}")
        chunks = got[i]
        # exactly once, in order, single owner — and the owner is the winner
        assert [c[1] for c in chunks] == [0, 1, 2]
        assert {c[0] for c in chunks} == {meta["replica"]}
        assert meta["chunks"] == 3
        assert futs[i].chunks() == chunks  # snapshot matches live delivery
    # a refused emit stops the producer at its FIRST chunk: losers never
    # draft past the refusal
    refused = [(rid, i) for rid, i, ok in log if not ok]
    assert all(i == 0 for _, i in refused)
    # fleet counter == sum of per-flight meta, exact at quiescence (late
    # losers updated the published meta in place)
    assert snap["cancelled"] == sum(m["cancelled"] for _, m in outs)
    assert snap["hedges"] == sum(m["hedges"] for _, m in outs)
    fleet.close()


def test_midstream_duplicate_refused_and_accounted():
    """An eviction-driven duplicate lands while the stream is mid-flight:
    first-bytes-wins refuses the rival at its first emit, the flight settles
    with all chunks from one owner, and the loss is accounted through the
    same cancellation counters as a non-streaming race."""
    log = []
    fleet = _streaming_fleet(chunk_delay=0.03, log=log)
    fleet.scale_to(1)  # rid 1 drained: only the straggling rid 0 remains

    (fut,) = fleet.submit_many_async(["job"], stream=True)
    deadline = time.time() + 5.0
    while fleet.in_flight() == 0 and time.time() < deadline:
        time.sleep(0.001)
    assert fleet.in_flight() == 1  # parked in rid 0's pre-stream straggle

    fleet.scale_to(2)  # rid 2 joins; rid 0 then misses its beats
    for _ in range(fleet.max_missed):
        fleet.heartbeat(responding={r.rid for r in fleet.live()} - {0})

    out, meta = fut.result(timeout=10.0)
    snap = _quiesce(fleet)
    assert out == ("full", "job")
    chunks = fut.chunks()
    assert [c[1] for c in chunks] == [0, 1, 2]
    owner = {c[0] for c in chunks}
    assert len(owner) == 1  # one replica streamed every chunk
    assert meta["replica"] in owner and meta["chunks"] == 3
    assert meta["requeues"] == 1 and snap["requeues"] == 1
    # the rival attempted exactly one emit, was refused, and stopped
    refused = [(rid, i) for rid, i, ok in log if not ok]
    assert refused == [(({0, 2} - owner).pop(), 0)]
    assert meta["cancelled"] == 1 and snap["cancelled"] == 1
    fleet.close()


def test_sequential_stream_buffers_chunks_for_replay():
    """max_workers=1: futures come back complete with the chunk log already
    buffered; a late subscriber replays it in order.  Non-streaming submits
    on the same replicas still run plain ``execute`` (bit-for-bit result)."""
    fleet = _streaming_fleet(max_workers=1)
    fleet.replicas[0].straggle_rate = 0.0
    futs = fleet.submit_many_async(["a", "b"], stream=True)
    assert all(f.done() for f in futs)
    for fut, job in zip(futs, ["a", "b"]):
        out, meta = fut.result(0)
        assert out == ("full", job)
        replayed = []
        fut.add_chunk_callback(replayed.append)
        assert replayed == fut.chunks()
        assert [c[1] for c in replayed] == [0, 1, 2]
        assert {c[0] for c in replayed} == {meta["replica"]}
    (fut,) = fleet.submit_many_async(["c"], stream=False)
    out, _ = fut.result(0)
    assert out == ("full", "c") and fut.chunks() == []
    fleet.close()


# -- orchestrator streaming: tickets as async iterators ----------------------


def test_await_vs_async_for_equivalence(served):
    """``await ticket`` is unchanged by streaming: iterating the chunks and
    awaiting yield the same Response (path, accuracy, latency, cost), and
    the chunk timeline is ordered, cumulative, and stamped on the ticket."""
    server, test_idx = served
    qid = int(test_idx[0])

    async def run():
        orch = server.orchestrator(max_batch=8, max_wait_ms=1.0)
        await orch.start()
        t1 = await orch.submit(Request(prompt="", qid=qid))
        r1 = await t1
        t2 = await orch.submit(Request(prompt="", qid=qid))
        chunks = [c async for c in t2]
        r2 = await t2
        again = [c async for c in t2]  # exhausted: terminates immediately
        # t1 settled with nobody iterating: its iterator yields every chunk
        # that arrived, buffered
        late = [c async for c in t1]
        await orch.stop()
        return r1, r2, chunks, again, late, t1, t2

    r1, r2, chunks, again, late, t1, t2 = asyncio.run(run())
    assert (r1.path_key, r1.accuracy, r1.latency_s, r1.cost_usd) \
        == (r2.path_key, r2.accuracy, r2.latency_s, r2.cost_usd)
    assert chunks and chunks[-1].final and again == []
    assert [c.index for c in chunks] == list(range(len(chunks)))
    lats = [c.latency_s for c in chunks]
    assert lats == sorted(lats)  # cumulative along the chunk timeline
    assert len(late) == len(chunks) and late[-1].final
    for t in (t1, t2):  # t1 streamed too, even though nobody iterated it
        names = [n for n, _ in t.events]
        assert names.index("dispatched") < names.index("first_chunk") \
            < names.index("completed")
        stamps = [ts for _, ts in t.events]
        assert stamps == sorted(stamps)


def test_stream_off_preserves_response_and_skips_chunk_machinery(served):
    server, test_idx = served
    qid = int(test_idx[0])

    async def run(stream):
        orch = server.orchestrator(stream=stream)
        await orch.start()
        t = await orch.submit(Request(prompt="", qid=qid))
        r = await t
        chunks = [c async for c in t]
        await orch.stop()
        return r, chunks, t

    r_on, chunks_on, _ = asyncio.run(run(True))
    r_off, chunks_off, t_off = asyncio.run(run(False))
    server.orchestrator(stream=True)  # restore the module fixture's default
    assert chunks_on and chunks_off == []
    assert t_off.event("first_chunk") is None
    # the final Response does not depend on whether chunks were delivered
    assert (r_on.path_key, r_on.accuracy, r_on.latency_s, r_on.cost_usd) \
        == (r_off.path_key, r_off.accuracy, r_off.latency_s, r_off.cost_usd)


def _assert_first_chunk_between(ticket):
    names = [n for n, _ in ticket.events]
    assert names.index("dispatched") < names.index("first_chunk") \
        < names.index("completed")
    stamps = [ts for _, ts in ticket.events]
    assert stamps == sorted(stamps)


def test_unread_ticket_buffers_chunks_without_waking_the_loop(served):
    """A ticket nobody iterates settles with no loop wake for its chunks and
    still yields all of them, in order, when iterated after settle; a
    consumer iterating from before dispatch gets each chunk exactly once,
    in order.  Both tickets carry ``first_chunk`` between ``dispatched``
    and ``completed``, and ``stats()`` counts what the tickets buffered."""
    server, test_idx = served
    qid = int(test_idx[1])

    async def run():
        orch = server.orchestrator(max_batch=8, max_wait_ms=1.0)
        await orch.start()
        before = orch.stats()
        unread = await orch.submit(Request(prompt="", qid=qid))
        reader = await orch.submit(Request(prompt="", qid=qid))
        assert reader.event("dispatched") is None  # iterating from before
        read = [c async for c in reader]
        await unread
        assert unread._n_wakes == 0  # settled without a chunk wake
        late = [c async for c in unread]
        after = orch.stats()
        await orch.stop()
        return unread, reader, read, late, before, after

    unread, reader, read, late, before, after = asyncio.run(run())
    for chunks in (read, late):
        assert [c.index for c in chunks] == list(range(5))
        assert chunks[-1].final and sum(c.tokens for c in chunks) == 150
    assert 1 <= reader._n_wakes <= len(read)
    for t in (unread, reader):
        _assert_first_chunk_between(t)
    assert after["chunks_streamed"] - before["chunks_streamed"] == 10
    assert after["chunk_wakes"] - before["chunk_wakes"] == reader._n_wakes


async def _until_parked(ticket):
    """Yield to the loop until the ticket's consumer is parked on an empty
    buffer."""
    for _ in range(10_000):
        if ticket._waiter is not None:
            return
        await asyncio.sleep(0)
    raise AssertionError("consumer never parked")


def test_parked_consumer_costs_at_most_one_wake_per_park():
    """The producer wakes the loop only for a parked consumer, once per
    park: a burst of chunks while the consumer is parked costs one wake;
    settle ends the stream without a chunk wake."""
    async def run():
        loop = asyncio.get_running_loop()
        t = Ticket(None, 0, None, loop.create_future())
        got = []

        async def consume():
            async for c in t:
                got.append(c)

        task = asyncio.create_task(consume())
        await _until_parked(t)  # park 1: before dispatch, nothing buffered
        t.mark("dispatched")
        producer = threading.Thread(target=t._put_chunk, args=(0,))
        producer.start()
        producer.join(5.0)
        assert not producer.is_alive()
        await _until_parked(t)  # park 2, after taking chunk 0
        assert got == [0]
        for i in (1, 2, 3):  # one burst, no loop turn in between
            t._put_chunk(i)
        await _until_parked(t)  # park 3, after draining the burst
        assert got == [0, 1, 2, 3]
        t._end_stream()
        await asyncio.wait_for(task, 5.0)
        again = [c async for c in t]
        return t, got, again

    t, got, again = asyncio.run(run())
    assert got == [0, 1, 2, 3] and again == []
    assert t._n_chunks == 4
    assert t._n_wakes == 2  # parks 1 and 2 were woken by a chunk; 3 by settle
    assert [n for n, _ in t.events] == ["dispatched", "first_chunk"]


def test_first_chunk_buffered_before_dispatched_is_stamped_at_dispatched():
    """A chunk can reach the buffer before the loop marks ``dispatched``;
    the settle-time ``first_chunk`` mark is held to no earlier than it."""
    async def run():
        t = Ticket(None, 0, None, asyncio.get_running_loop().create_future())
        t.mark("selected")
        t._put_chunk("c0")
        t.mark("dispatched")
        t._mark_first_chunk()  # what settle does ahead of `completed`
        t.mark("completed")
        t._end_stream()
        return t, [c async for c in t]

    t, chunks = asyncio.run(run())
    assert chunks == ["c0"] and t._n_wakes == 0
    assert [n for n, _ in t.events] == ["selected", "dispatched",
                                        "first_chunk", "completed"]
    assert t.event("first_chunk") == t.event("dispatched")
    _assert_first_chunk_between(t)


def test_producer_after_loop_closed_does_not_raise():
    """A fleet thread still streaming after the session's loop closed (its
    consumer was parked at the close) buffers its chunks and never raises;
    the wake that cannot be scheduled is dropped and not counted."""
    held = {}

    async def run():
        t = Ticket(None, 0, None, asyncio.get_running_loop().create_future())
        held["ticket"] = t

        async def consume():
            async for _ in t:
                pass

        held["task"] = asyncio.create_task(consume())
        await _until_parked(t)

    asyncio.run(run())  # closes the loop with the consumer parked
    t, errors = held["ticket"], []

    def produce():
        try:
            for i in range(5):
                t._put_chunk(i)
        except Exception as e:  # noqa: BLE001 — the assertion reports it
            errors.append(e)

    producer = threading.Thread(target=produce)
    producer.start()
    producer.join(5.0)
    assert not producer.is_alive() and errors == []
    assert list(t._chunks) == [0, 1, 2, 3, 4]
    assert t._n_chunks == 5 and t._n_wakes == 0


def test_chunk_buffer_stress_no_lost_wake():
    """Many producer threads (more than cores) stream into tickets whose
    consumers park and wake on the loop, at a shortened switch interval:
    every consumer receives every chunk exactly once, in order, before
    settle — a lost wake would leave a consumer parked on a non-empty
    buffer and time out."""
    n_tickets, n_chunks, n_threads = 48, 40, 24

    async def run():
        loop = asyncio.get_running_loop()
        tickets = [Ticket(None, 0, None, loop.create_future())
                   for _ in range(n_tickets)]
        got = [[] for _ in tickets]

        async def consume(t, out):
            async for c in t:
                out.append(c)
                if len(out) == n_chunks:
                    return

        tasks = [asyncio.create_task(consume(t, out))
                 for t, out in zip(tickets, got)]

        def produce(mine):
            for i in range(n_chunks):
                for t in mine:
                    t._put_chunk(i)
                if i % 8 == 0:
                    time.sleep(0.0005)

        threads = [threading.Thread(target=produce,
                                    args=(tickets[k::n_threads],))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        await asyncio.wait_for(asyncio.gather(*tasks), 20.0)
        for th in threads:
            th.join(5.0)
        assert not any(th.is_alive() for th in threads)
        for t in tickets:
            t._end_stream()
        return tickets, got

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        tickets, got = asyncio.run(run())
    finally:
        sys.setswitchinterval(old)
    for t, out in zip(tickets, got):
        assert out == list(range(n_chunks))
        assert t._n_chunks == n_chunks
        assert t._n_wakes <= n_chunks


# -- satellite: stop sentinels must not inflate queue depth ------------------


def test_stop_sentinel_not_counted_in_queue_depth():
    async def run():
        orch = Orchestrator(None, max_queue=4)
        await orch.start()
        assert orch.stats()["queue_depth"] == 0
        stopper = asyncio.create_task(orch.stop())
        await asyncio.sleep(0)  # stop() has enqueued its sentinel by now
        d_stopping = orch.stats()["queue_depth"]
        await stopper
        d_stopped = orch.stats()["queue_depth"]
        late = await orch.submit("late")
        return d_stopping, d_stopped, await late

    d_stopping, d_stopped, shed = asyncio.run(run())
    # the enqueued sentinel is not backlog — before the fix this read 1
    assert d_stopping == 0 and d_stopped == 0
    assert isinstance(shed, Overloaded) and shed.reason == "shutdown"
    assert shed.queue_depth == 0  # Overloaded carries the corrected depth


# -- satellite: deadline-lapsed tickets must not squat on queue capacity -----


def test_full_queue_of_expired_tickets_admits_fresh_traffic():
    async def run():
        orch = Orchestrator(None, max_queue=4)  # loop not started: no drain
        stale = [await orch.submit(f"s{i}", deadline_s=0.005)
                 for i in range(4)]
        assert not any(t.done() for t in stale)  # queue now full of them
        await asyncio.sleep(0.02)  # every queued deadline lapses
        fresh = await orch.submit("fresh")
        outcomes = [await t for t in stale]
        return orch, outcomes, fresh

    orch, outcomes, fresh = asyncio.run(run())
    # the lapsed squatters were purged and shed with their own reason...
    assert all(isinstance(o, Overloaded) and o.reason == "deadline"
               for o in outcomes)
    # ...and the fresh ticket was ADMITTED, not queue_full-shed
    assert not fresh.done()
    assert [n for n, _ in fresh.events] == ["admitted"]
    stats = orch.stats()
    assert stats["admitted"] == 5 and stats["shed"] == 4
    assert stats["deadline_shed"] == 4 and stats["queue_depth"] == 1


def test_full_queue_of_viable_tickets_still_sheds_overflow():
    async def run():
        orch = Orchestrator(None, max_queue=2)
        for i in range(2):
            await orch.submit(f"v{i}")  # no deadline: nothing purgeable
        return await (await orch.submit("overflow"))

    shed = asyncio.run(run())
    assert isinstance(shed, Overloaded) and shed.reason == "queue_full"


# -- split inference: DraftState layout parity + deterministic traces --------


def test_draftstate_matches_decode_attention_oracle():
    """The draft KV cache is in the kernel's exact ``(B, W, Kv, hd)`` layout:
    the numpy readout, the jnp oracle, and the Pallas entry point agree on
    the identical buffers at every incremental cache length."""
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ref import decode_attention_ref

    ds = DraftState(seed=0, qid=3, edge=MODEL_CATALOG["internlm2-1.8b"],
                    n_chunks=5)
    for t in range(5):
        ds.append(t)
        o_np = ds.attend()
        o_ref = np.asarray(decode_attention_ref(
            jnp.asarray(ds._q), jnp.asarray(ds.k_cache),
            jnp.asarray(ds.v_cache), jnp.int32(ds.cache_len)))[0, 0, 0]
        np.testing.assert_allclose(o_np, o_ref, atol=1e-6)
        o_kernel = ds.attend(use_kernel=True)
        np.testing.assert_allclose(o_np, o_kernel, atol=1e-6)
    with pytest.raises(ValueError, match="out of order"):
        ds.append(7)


def test_generate_split_deterministic_stream_and_cancellation():
    common = dict(seed=3, qid=7, complexity=0.6,
                  edge=MODEL_CATALOG["recurrentgemma-2b"],
                  cloud=MODEL_CATALOG["kimi-k2-cloud"], tau=0.6,
                  device=EDGE_DEVICES["m4"], prompt_tokens=400,
                  out_tokens=150, grounding=0.3,
                  start_latency_s=0.1, start_cost_usd=0.001)
    chunks = []
    r = generate_split(**common, emit=lambda c: chunks.append(c) or True)
    assert generate_split(**common) == r  # emit cannot perturb the trace
    assert not r.cancelled and r.n_chunks == len(chunks) == 5
    assert [c.index for c in chunks] == list(range(5))
    assert sum(c.tokens for c in chunks) == 150 and chunks[-1].final
    assert {c.source for c in chunks} <= {"edge", "cloud"}
    assert sum(c.tokens for c in chunks if c.source == "cloud") \
        == r.cloud_tokens
    for a, b in zip(chunks, chunks[1:]):  # cumulative timeline
        assert b.latency_s >= a.latency_s and b.cost_usd >= a.cost_usd
    assert chunks[-1].cost_usd == r.cost_usd

    got = []
    r_c = generate_split(**common,
                         emit=lambda c: got.append(c) or len(got) < 2)
    assert r_c.cancelled and len(got) == 2
    assert got == chunks[:2]  # identical spans up to the teardown
    assert r_c.cost_usd == got[-1].cost_usd  # only generated spans billed


def test_split_paths_stream_through_executor(served):
    """Split paths ride the resolution-path machinery: ``run_stream`` emits
    edge/cloud spans and settles to the exact ``run`` result; whole-model
    paths stream decode spans with the same bit-for-bit settlement."""
    server, _ = served
    space = server.rps.space
    split_paths = [p for p in space.paths if p.model.impl == SPLIT_IMPL]
    assert split_paths, "split=True server lost its split configurations"
    q = server.domain.queries[5]
    for path in split_paths[:3]:
        base = server.executor.run(q, path)
        chunks = []
        out = server.executor.run_stream(
            q, path, lambda c: chunks.append(c) or True)
        assert out == base
        assert chunks[-1].final and sum(c.tokens for c in chunks) == 150
        assert {c.source for c in chunks} <= {"edge", "cloud"}

    whole = next(p for p in space.paths if p.model.impl != SPLIT_IMPL)
    base = server.executor.run(q, whole)
    chunks = []
    assert server.executor.run_stream(
        q, whole, lambda c: chunks.append(c) or True) == base
    assert {c.source for c in chunks} == {whole.model.impl}
    assert all(c.confidence == 1.0 for c in chunks)

    # mid-stream teardown: the emit gate returns False -> no settlement
    got = []
    assert server.executor.run_stream(
        q, split_paths[0], lambda c: got.append(c) or False) is None
    assert len(got) == 1
