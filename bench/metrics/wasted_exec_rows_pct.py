"""Execution: share of the rows sent to the model whose result was thrown
away because another execution had already answered the task: the engine's
``discarded_rows`` over its ``exec_rows`` in the window (None where the
counters were not read)."""


def read(ctx):
    rows = ctx.counters.get("exec_rows")
    if not rows:
        return None
    return 100.0 * ctx.counters["discarded_rows"] / rows
