"""Reduce a profiler trace (``.xplane.pb``) of the measured window to what
the per-layer readers and the ``breakdown`` need.

* Device busy time is the union of the intervals of the operations on each
  device's ``XLA Ops`` line, averaged over the devices used.
* Each device module (a jitted program, ``XLA Modules`` line) and each
  operation is summed by name; the selection pass is the module whose name
  starts with ``jit__pass``.  An operation's event carries its whole HLO
  instruction; it is named by the instruction's own name (``%name.N``,
  the text before `` = ``), so that an operation is never counted under a
  name that only appears among its operands.
* Idle gaps are the holes in the device's busy union inside the window;
  each is named by the benchmark host span (``bench.*``) that covers most
  of it, or ``host:other`` where none does.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a0: int, a1: int, spans: list[tuple[int, int, str]]) -> dict:
    cover: dict[str, int] = defaultdict(int)
    for s0, s1, name in spans:
        lo, hi = max(a0, s0), min(a1, s1)
        if hi > lo:
            cover[name] += hi - lo
    return cover


def reduce_events(device_ops: list[list[tuple[int, int, str]]],
                  device_modules: list[list[tuple[int, int, str]]],
                  host_spans: list[tuple[int, int, str]],
                  window: tuple[int, int]) -> dict:
    """The reduction on plain (start_ns, end_ns, name) events, one list per
    device.  ``window`` bounds the traced window on the same clock."""
    w0, w1 = window
    busy_ns, gaps, op_ns, op_n = [], [], defaultdict(int), defaultdict(int)
    mod_ns, mod_n = defaultdict(int), defaultdict(int)
    for ops in device_ops:
        clipped = [(max(a, w0), min(b, w1)) for a, b, _ in ops
                   if min(b, w1) > max(a, w0)]
        union = _union(clipped)
        busy_ns.append(sum(b - a for a, b in union))
        edges = [w0] + [x for ab in union for x in ab] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for a, b, name in ops:
            op_ns[name] += b - a
            op_n[name] += 1
    for mods in device_modules:
        for a, b, name in mods:
            mod_ns[name] += b - a
            mod_n[name] += 1
    n_dev = max(len(device_ops), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = []
    for a, b in gaps[:TOP]:
        cover = _overlap(a, b, host_spans)
        name = max(cover, key=cover.get) if cover else "host:other"
        named_gaps.append([name, (b - a) / 1e9])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(device_ops),
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "op_n": dict(op_n),
        "module_s": {k: v / 1e9 for k, v in mod_ns.items()},
        "module_n": dict(mod_n),
        "breakdown": {
            "device_ops": [[k, v / n_dev / 1e9] for k, v in top_ops],
            "idle_gaps": named_gaps,
        },
    }


def xplane_path(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(found)}")
    return found[0]


def read_xplane(path: str, window_names=("bench.window",)) -> dict:
    """Events of a trace file, split into device operations, device modules
    and the benchmark's host spans; the window is the ``bench.window``
    span's extent."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, mods, spans = [], [], []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            p_ops, p_mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    p_ops = [(int(e.start_ns), int(e.end_ns),
                              e.name.split(" = ", 1)[0])
                             for e in line.events]
                elif line.name == MODULES_LINE:
                    p_mods = [(int(e.start_ns), int(e.end_ns), e.name)
                              for e in line.events]
            if p_ops:
                ops.append(p_ops)
                mods.append(p_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in window_names:
                        window = (int(e.start_ns), int(e.end_ns))
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append((int(e.start_ns), int(e.end_ns),
                                      e.name))
    if window is None:
        raise RuntimeError("the trace holds no bench.window span")
    return {"ops": ops, "modules": mods, "spans": spans, "window": window}


def reduce_file(path: str) -> dict:
    ev = read_xplane(path)
    return reduce_events(ev["ops"], ev["modules"], ev["spans"], ev["window"])
