"""Router: host microseconds per task in ``ReuseRouter.route`` (one hash
dispatch per admitted task), from the benchmark's spans."""


def read(ctx):
    calls = ctx.spans.calls.get("route", [])
    return sum(calls) / len(calls) * 1e6 if calls else None
