"""bucket_fill: requests dispatched per admission bucket over the window,
as a share of ``max_batch`` (``Orchestrator.stats()`` before and after)."""


def read(ctx):
    b, d = ctx.admission["batches"], ctx.admission["dispatched"]
    return 100.0 * d / b / ctx.max_batch if b else None
