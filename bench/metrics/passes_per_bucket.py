"""passes_per_bucket: domain-sharded selection passes per admission
bucket (a mixed bucket runs one pass per domain), counted at the
benchmark's wraps of ``DomainShardedSelector.select_batch`` and
``Orchestrator._select``."""


def read(ctx):
    buckets = len(ctx.spans.select)
    if not ctx.spans.pass_rows or not buckets:
        return None
    return len(ctx.spans.pass_rows) / buckets
