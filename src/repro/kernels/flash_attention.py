"""Pallas TPU kernel: flash attention (online-softmax, KV-block streaming).

The prefill_32k cells are attention-dominated (O(S^2)); flash blocking keeps
the working set in VMEM: for each (batch*kv_head, group, q-block) the kernel
streams KV blocks, maintaining running max/denominator and a f32 accumulator
in VMEM scratch.  Supports causal masking, sliding windows (gemma2 local
layers), logit soft-capping, and GQA via the group grid axis.

Grid: (B * KV, G, S/bq, T/bk) — KV innermost so scratch carries across the
sequential TPU grid.  Causal + window tiles that are fully masked are skipped
by zeroing contributions (structural; Mosaic hoists the skipped DMA cost on
real hardware via grid pruning in the lowered loop bounds).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool,
                  window: Optional[int], softcap: Optional[float],
                  scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0].astype(jnp.float32)             # (bk, D)
    v = v_ref[0].astype(jnp.float32)             # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(kj == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale",
                     "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,                  # (B, S, H, D)
    k: jax.Array,                  # (B, T, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    bq, bk = min(block_q, S), min(block_k, T)
    # layout: fold (B, KV) into one grid axis; move head dims forward
    qr = jnp.moveaxis(q.reshape(B, S, KV, G, D), 1, 3).reshape(B * KV, G, S, D)
    kr = jnp.moveaxis(k, 1, 2).reshape(B * KV, T, D)
    vr = jnp.moveaxis(v, 1, 2).reshape(B * KV, T, D)
    grid = (B * KV, G, pl.cdiv(S, bq), pl.cdiv(T, bk))
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=bq, block_k=bk, causal=causal,
            window=window, softcap=softcap, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, g, i, j: (b, g, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, g, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, g, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, g, i, j: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return jnp.moveaxis(out.reshape(B, KV, G, S, D), 3, 1).reshape(B, S, H, D)
