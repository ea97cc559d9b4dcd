"""retrieval_topk_roofline: the Pallas retrieve kernel's share of its
roofline, the least time its operations (2 Bq N d) and the bytes of its
own arguments and outputs allow, over its device time in the trace.  The
bound that applies is named in ``ctx.notes``."""
from bench.harness.layers import retrieve_roofline


def read(ctx):
    out = retrieve_roofline(ctx)
    if out is None:
        return None
    share, bound = out
    ctx.notes["retrieval_topk_roofline_bound"] = bound
    return share
