"""The program's own spans and ticket timelines of a traced window
(``repro.runtime.tracing``), joined per admission bucket and per request
for the per-layer readers in ``bench/metrics``.

The window keeps the buckets that closed (the start of their
``eco.bucket`` span) between the first send and the last due time of the
window's requests.  A request is one settled ticket of such a bucket; its
fleet and settle spans are found by (bucket id, row); of its
``eco.fleet.exec`` spans (hedges, retries) the one that won the flight
counts ``won`` 1.

A program that makes no such spans (one from before them) leaves nothing
to read: every reader then returns None, and no note is written.
"""
from __future__ import annotations

from collections import defaultdict
from typing import NamedTuple, Optional

NS = 1e-9


class Times(NamedTuple):
    """One request's stages, in seconds (None where a piece is missing),
    and its ``admitted`` mark, which names it among the window's records."""
    admitted: Optional[float]
    queue: Optional[float]       # taken - admitted
    fill: Optional[float]        # bucket close - taken
    bucket: Optional[float]      # the eco.bucket span
    handoff: Optional[float]     # loop -> executor -> loop hops
    fleet_queue: Optional[float]  # winning exec start - dispatched
    fleet_exec: Optional[float]  # the winning eco.fleet.exec span
    respond: Optional[float]     # the eco.fleet.respond span
    settle_hop: Optional[float]  # eco.settle start - respond end


def _secs(span) -> tuple[float, float]:
    return span.start_ns * NS, span.end_ns * NS


def _diff(a: Optional[float], b: Optional[float]) -> Optional[float]:
    return None if a is None or b is None else a - b


class Window:
    """The joined records of the buckets that closed in ``[lo, hi]``
    (``perf_counter`` seconds)."""

    def __init__(self, spans: list, timelines: list, lo: float, hi: float):
        by_name: dict[str, list] = defaultdict(list)
        for s in spans:
            by_name[s.name].append(s)
        self.close = {s.bucket: s for s in by_name["eco.bucket"]
                      if lo <= s.start_ns * NS <= hi}
        keep = self.close.keys()

        def per_bucket(name: str) -> dict[int, list]:
            out: dict[int, list] = defaultdict(list)
            for s in by_name[name]:
                if s.bucket in keep:
                    out[s.bucket].append(s)
            return out

        def per_row(name: str) -> dict[tuple, list]:
            out: dict[tuple, list] = defaultdict(list)
            for s in by_name[name]:
                if s.bucket in keep:
                    out[s.bucket, dict(s.counts).get("row")].append(s)
            return out

        self.select = {b: v[0] for b, v in per_bucket("eco.select").items()}
        self.fetch = per_bucket("eco.select.fetch")
        self.decide = per_bucket("eco.select.decide")
        execs, respond = per_row("eco.fleet.exec"), per_row(
            "eco.fleet.respond")
        settle = per_row("eco.settle")
        self.requests: list[Times] = []
        for t in timelines:
            if t.bucket not in keep or t.row is None:
                continue
            ev: dict[str, float] = {}
            for name, ts in t.events:
                ev.setdefault(name, ts)
            key = (t.bucket, t.row)
            c0, c1 = _secs(self.close[t.bucket])
            sel = self.select.get(t.bucket)
            handoff = None
            if sel is not None and "selected" in ev:
                s0, s1 = _secs(sel)
                handoff = (s0 - c1) + (ev["selected"] - s1)
            r = respond.get(key, [None])[-1]
            r0, r1 = _secs(r) if r is not None else (None, None)
            won = [e for e in execs.get(key, [])
                   if ("won", 1) in e.counts]
            e0, e1 = _secs(won[0]) if won else (None, None)
            st = settle.get(key)
            self.requests.append(Times(
                admitted=ev.get("admitted"),
                queue=_diff(ev.get("taken"), ev.get("admitted")),
                fill=_diff(c0, ev.get("taken")),
                bucket=c1 - c0,
                handoff=handoff,
                fleet_queue=_diff(e0, ev.get("dispatched")),
                fleet_exec=_diff(e1, e0),
                respond=_diff(r1, r0),
                settle_hop=_diff(st[0].start_ns * NS if st else None, r1)))

    def mean_ms(self, stage: str) -> Optional[float]:
        """Mean of one of ``Times``' stages over the requests, in ms."""
        xs = [getattr(t, stage) for t in self.requests]
        xs = [x for x in xs if x is not None]
        return 1e3 * sum(xs) / len(xs) if xs else None

    def span_ms(self, spans: dict[int, list], per_bucket: bool) -> \
            Optional[float]:
        """Mean span time in ms: per span, or summed per bucket."""
        groups = [[(s.end_ns - s.start_ns) * NS for s in v]
                  for v in spans.values() if v]
        xs = ([sum(g) for g in groups] if per_bucket
              else [x for g in groups for x in g])
        return 1e3 * sum(xs) / len(xs) if xs else None


def window(ctx) -> Optional[Window]:
    """``ctx``'s window, built once per run and kept on ``ctx``; None
    where there is nothing to read.  The first build notes, in
    ``ctx.notes["eco_spans"]``, the terms of the two identities the split
    obeys and the mean select span inside and outside the program."""
    if not ctx.records:
        return None
    if hasattr(ctx, "eco_window"):
        return ctx.eco_window
    ctx.eco_window = None
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    spans, timelines = tracing.store.snapshot()
    lo = min(r.sent for r in ctx.records)
    hi = max(r.due for r in ctx.records)
    w = Window(spans, timelines, lo, hi)
    if not w.requests:
        return None
    ctx.eco_window = w
    outside = [b - a for a, b, _ in ctx.spans.select]
    ctx.notes["eco_spans"] = {
        "requests": len(w.requests), "buckets": len(w.close),
        "dropped": tracing.store.dropped,
        "bucket_ms": w.mean_ms("bucket"), "respond_ms": w.mean_ms("respond"),
        "select_ms": w.span_ms({b: [s] for b, s in w.select.items()}, False),
        "outside_select_ms": (1e3 * sum(outside) / len(outside)
                              if outside else None)}
    return w
