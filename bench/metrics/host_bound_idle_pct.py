"""Host: share of the traced window in which the device runs nothing while
the host is inside one of the program's scoped ``reservoir/engine/*``
spans, on the device clock fitted to the host's
(``program_trace.program_reduce``, as ``ctx.program_trace``; None where the
trace has no program spans)."""


def read(ctx):
    prog = getattr(ctx, "program_trace", None)
    if prog is None or prog["window_s"] <= 0:
        return None
    return 100.0 * prog["host_bound_idle_s"] / prog["window_s"]
