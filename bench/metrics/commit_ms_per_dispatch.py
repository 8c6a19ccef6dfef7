"""Commit: host milliseconds per ``ReplicaEngine.commit_execution`` call
(store ``insert_batch``, device sync, Content Store inserts)."""


def read(ctx):
    calls = ctx.spans.calls.get("commit", [])
    return sum(calls) / len(calls) * 1e3 if calls else None
