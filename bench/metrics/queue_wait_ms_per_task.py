"""Engine batcher: mean host milliseconds a leader waits in its replica's
batcher queue, from admission to the start of the dispatch that carries it:
the program's ``engine/queue`` spans closed in the window, from the events
of its host-clock tracer (``ctx.program_events``; None where the run armed
none)."""


def read(ctx):
    events = getattr(ctx, "program_events", None)
    if events is None:
        return None
    waits = [e["dur"] for e in events
             if e["name"] == "engine/queue" and e["ph"] == "X"]
    return sum(waits) / len(waits) * 1e-3 if waits else None
