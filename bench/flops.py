"""Operations and bytes that the served work needs, computed from shapes.

These are what the work requires, not what a given program happens to do:
causal attention counts the lower triangle only, padding rows count nothing,
and the logits are those of the last position (the served token).  A
model's prefill is counted by the model reference that its configuration
file names (``prefill_flops`` of ``bench/reference/<reference>.py``).
"""
from __future__ import annotations

from bench import reference


def hash_flops(store: dict) -> float:
    """Cross-polytope hash of one query: one D x D rotation per table."""
    d = store["dim"]
    return float(2 * store["num_tables"] * store["rotations_per_table"] * d * d)


def cosine_flops(store: dict, candidates: int) -> float:
    """Dot products of one query against its unique candidates."""
    return float(2 * store["dim"] * candidates)


def prefill_mfu(ctx):
    """FLOPs of the real (unpadded) rows of every execute call over those
    calls' host time times the chip's bf16 peak, in percent."""
    calls = ctx.spans.calls.get("execute", [])
    rows = sum(real for real, _ in ctx.exec_calls)
    if not calls or not rows or ctx.peaks is None:
        return None
    ref = reference.load(ctx.cfg["reference"])
    need = rows * ref.prefill_flops(ctx.cfg["model"], ctx.prompt_len)
    return 100.0 * need / (sum(calls) * ctx.peaks["bf16_flops"])
