"""Spans and per-request timelines of the serving path, on the profiler's
clock.  Every span the program makes goes through this module.

The profiler session is the switch: there is no flag.  While no session
is capturing (``jax.profiler.trace``, ``start_trace``, or a capture
through ``jax.profiler.start_server``), :func:`span` returns the shared
no-op :data:`OFF` after one ``TraceMe.is_enabled()`` check, with no
allocation and no clock read.  While one is capturing, a span enters
``jax.profiler.TraceAnnotation``, so it lands in the same trace as the
device operations and on their clock, and on exit appends one
:class:`Span` to the bounded in-memory :data:`store`.

Spans are for synchronous work on one thread; no span is ever held open
across an ``await`` (waits are ``Ticket.events`` marks).  A span without
a bucket id takes the one of the span it nests in on the same thread, so
the selector's pass spans carry the admission bucket that caused them.

Names, in the order one request meets them (thread in brackets):
``eco.submit`` [loop], ``eco.bucket`` [loop], ``eco.select`` and inside it
``eco.select.resolve``, ``eco.select.pass`` (``eco.select.launch``,
``eco.select.fetch``) and ``eco.select.decide`` [executor],
``eco.fleet.exec`` and ``eco.fleet.respond`` [fleet worker],
``eco.settle`` [loop].  Fleet and settle spans carry ``row``, the
request's position in its bucket; with the bucket id it names one request.
Of a request's executions (hedges, retries) the one that won counts
``won`` 1.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 19  # records kept; the oldest go first, and are counted

_enabled = TraceAnnotation.is_enabled

# admission bucket ids, unique in the process (shards share the counter)
new_bucket = itertools.count(1).__next__


class Span(NamedTuple):
    name: str
    start_ns: int  # time.perf_counter_ns(), the clock of Ticket.events
    end_ns: int
    thread: int    # threading.get_ident()
    bucket: Optional[int]
    counts: tuple  # ((name, value), ...)


class Timeline(NamedTuple):
    """A settled ticket's ``events`` (``(mark, perf_counter)`` pairs)."""
    bucket: Optional[int]
    row: Optional[int]
    events: tuple


class Store:
    """Bounded record of the spans and timelines made while a profiler
    session captured.  It outlives the session so that a reader can take
    the records after the capture stops; the oldest records give way to
    new ones past ``capacity``, and ``dropped`` counts them."""

    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=capacity)
        self.dropped = 0

    def add(self, record) -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(record)

    def snapshot(self) -> tuple[list[Span], list[Timeline]]:
        """(spans, timelines) held now, oldest first."""
        with self._lock:
            records = list(self._records)
        return ([r for r in records if isinstance(r, Span)],
                [r for r in records if isinstance(r, Timeline)])

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self.dropped = 0


# one store per process, as the profiler session it follows is one per
# process
store = Store()
_local = threading.local()  # .bucket: the bucket of the innermost span


class _Off:
    """The span returned while no session captures: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def count(self, name: str, value: int) -> None:
        pass


OFF = _Off()


class _On:
    __slots__ = ("name", "bucket", "counts", "start_ns", "_parent",
                 "_annotation")

    def __init__(self, name: str, bucket: Optional[int], counts: list):
        self.name = name
        self.bucket = bucket
        self.counts = counts

    def __enter__(self):
        self._parent = getattr(_local, "bucket", None)
        if self.bucket is None:
            self.bucket = self._parent
        _local.bucket = self.bucket
        meta = dict(self.counts)
        if self.bucket is not None:
            meta["bucket"] = self.bucket
        self._annotation = TraceAnnotation(self.name, **meta)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def count(self, name: str, value: int) -> None:
        """Add a count known only inside the span (to the trace too)."""
        self.counts.append((name, value))
        self._annotation.set_metadata(**{name: value})

    def __exit__(self, *exc) -> bool:
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _local.bucket = self._parent
        store.add(Span(self.name, self.start_ns, end_ns,
                       threading.get_ident(), self.bucket,
                       tuple(self.counts)))
        return False


def span(name: str, bucket: Optional[int] = None, *,
         row: Optional[int] = None, rows: Optional[int] = None,
         domain: Optional[int] = None):
    """Context manager around synchronous host work: :data:`OFF` while no
    profiler session captures, else a recorded span.  ``bucket`` defaults
    to the enclosing span's on this thread; ``row``, ``rows`` and
    ``domain`` are recorded where given, and ``count(name, value)`` adds
    one known only inside the span.  The counts are keywords, not
    ``**counts``, so that a call costs no dict while tracing is off."""
    if not _enabled():
        return OFF
    counts = [(k, v) for k, v in (("row", row), ("rows", rows),
                                  ("domain", domain)) if v is not None]
    return _On(name, bucket, counts)


def timeline(bucket: Optional[int], row: Optional[int], events) -> None:
    """Keep a settled ticket's timeline while a session captures."""
    if _enabled():
        store.add(Timeline(bucket, row, tuple(events)))
