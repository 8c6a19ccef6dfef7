"""Store search: host milliseconds per ``ReplicaEngine.query_reuse`` call
(one batched ``ReuseStore.query_batch`` per dispatched group)."""


def read(ctx):
    calls = ctx.spans.calls.get("search", [])
    return sum(calls) / len(calls) * 1e3 if calls else None
