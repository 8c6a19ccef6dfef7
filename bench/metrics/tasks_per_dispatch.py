"""Engine batcher: tasks per pipeline dispatch, from the stores' query
counts over the engine's ``dispatches`` counter."""


def read(ctx):
    d = ctx.counters["dispatches"]
    return ctx.counters["queries"] / d if d else None
