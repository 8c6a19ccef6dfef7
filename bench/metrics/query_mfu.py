"""Store search, whole query: the FLOPs the dispatched queries need (the
probe's cross-polytope projections plus the cosine against every unique
candidate) over the search calls' host time times the chip's bf16 peak."""

from bench import flops


def read(ctx):
    calls = ctx.spans.calls.get("search", [])
    queries = ctx.counters["queries"]
    if not calls or not queries or ctx.peaks is None:
        return None
    st = ctx.cfg["store"]
    need = queries * flops.hash_flops(st) + flops.cosine_flops(
        st, ctx.counters["candidates"])
    return 100.0 * need / (sum(calls) * ctx.peaks["bf16_flops"])
