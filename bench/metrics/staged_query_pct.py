"""Store search: share of queries answered on the host-staged path, from
the stores' ``staged_queries`` and ``fused_queries`` counters."""


def read(ctx):
    staged = ctx.counters["staged_queries"]
    total = staged + ctx.counters["fused_queries"]
    return 100.0 * staged / total if total else None
