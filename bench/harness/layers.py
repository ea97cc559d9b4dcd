"""Arithmetic shared by the per-layer readers in ``bench/metrics``."""
from __future__ import annotations

from bench.harness import flops as F

PASS_MODULE = "jit__pass"       # the jitted selection pass (core/rps.py)
RETRIEVE_OP = "%retrieval_topk_kernel"  # the Pallas retrieve kernel's op
MIN_BUCKET = 8                  # the program's smallest admission bucket


def bucket_of(rows: int) -> int:
    """The padded batch a pass of ``rows`` queries runs at (power of two,
    at least 8), as the program pads it."""
    return max(MIN_BUCKET, 1 << max(rows - 1, 0).bit_length())


def pass_device(trace: dict) -> tuple[float, int]:
    """(device seconds, count) of the selection-pass modules."""
    total, n = 0.0, 0
    for name, s in trace["module_s"].items():
        if name.startswith(PASS_MODULE):
            total += s
            n += trace["module_n"][name]
    return total, n


def pass_flops(ctx) -> float:
    cfg = ctx.config
    d = cfg["dsqe"]
    return sum(F.select_pass_flops(rows, cfg["d_in"], d["d_hidden"],
                                   d["n_layers"], ctx.shapes[dom]["n_log"],
                                   ctx.shapes[dom]["n_sets"], cfg["knn"])
               for rows, dom in ctx.pass_rows)


def retrieve_roofline(ctx):
    """(share %, bound) of the retrieve kernel, or None where the trace
    holds no such kernel."""
    t = ctx.trace
    names = [k for k in t["op_s"] if k.startswith(RETRIEVE_OP)]
    calls = sum(t["op_n"][k] for k in names)
    time_s = sum(t["op_s"][k] for k in names)
    if not calls or not time_s or not ctx.pass_rows:
        return None
    d = ctx.config["dsqe"]["d_hidden"]
    k = ctx.config["knn"]
    bounds, kinds = [], set()
    for rows, dom in ctx.pass_rows:
        bq, n = bucket_of(rows), ctx.shapes[dom]["n_log_padded"]
        b, kind = F.roofline_s(F.retrieve_flops(bq, n, d),
                               F.retrieve_bytes(bq, n, d, k),
                               ctx.peaks["bf16_flops_per_s"],
                               ctx.peaks["hbm_bytes_per_s"])
        bounds.append(b)
        kinds.add(kind)
    mean_bound = sum(bounds) / len(bounds)
    return 100.0 * mean_bound * calls / time_s, "+".join(sorted(kinds))
