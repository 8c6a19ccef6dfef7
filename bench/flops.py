"""Operations and bytes that the served work needs, computed from shapes.

These are what the work requires, not what a given program happens to do:
causal attention counts the lower triangle only, padding rows count nothing,
and the logits are those of the last position (the served token).
"""
from __future__ import annotations


def prefill_flops(model: dict, seq: int) -> float:
    """FLOPs of one prompt's prefill through ``model`` (a configuration
    file's ``model`` block) at ``seq`` positions, including image tokens."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    hd = model["head_dim"]
    ff = model["intermediate_size"]
    layers = model["num_hidden_layers"]
    proj = 2 * seq * d * (h + 2 * kv) * hd + 2 * seq * h * hd * d
    pairs = seq * (seq + 1) // 2                     # causal (q, k) pairs
    attn = 2 * 2 * pairs * h * hd                    # scores and weighted sum
    mlp = 2 * seq * d * 2 * ff + 2 * seq * ff * d    # gate+up, down
    head = 2 * d * model["vocab_size"]               # last position's logits
    return float(layers * (proj + attn + mlp) + head)


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
    need = rows * prefill_flops(ctx.cfg["model"], ctx.prompt_len)
    return 100.0 * need / (sum(calls) * ctx.peaks["bf16_flops"])
