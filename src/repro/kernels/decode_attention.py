"""Pallas TPU kernel: single-token decode attention (flash-decode).

decode_32k / long_500k cells: one query attends over a huge KV cache —
strictly memory-bound (arithmetic intensity ~1 FLOP/byte).  The kernel
streams KV blocks at HBM bandwidth while the online-softmax state (m, l,
acc) lives in VMEM; invalid ring-buffer slots are masked by ``kv_len``.

Grid: (B, KV, T/bk).  Queries for all G group heads of one kv head ride in a
single (G, D) block — G*D is tiny — so each KV byte is read exactly once.
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


def _decode_kernel(q_ref, k_ref, v_ref, kvlen_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_k: int,
                   softcap: Optional[float], scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0].astype(jnp.float32)             # (bk, D)
    v = v_ref[0].astype(jnp.float32)             # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = cols < kvlen_ref[b]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(j == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "scale", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,                  # (B, H, D)
    k: jax.Array,                  # (B, T, KV, D)
    v: jax.Array,
    kv_len: jax.Array,             # (B,)
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    B, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    bk = min(block_k, T)
    qr = q.reshape(B, KV, G, D)
    kr = jnp.moveaxis(k, 1, 2).reshape(B * KV, T, D)
    vr = jnp.moveaxis(v, 1, 2).reshape(B * KV, T, D)
    grid = (B, KV, pl.cdiv(T, bk))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=bk, softcap=softcap,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, h, j: (b * KV + h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, h, j: (b * KV + h, j, 0)),
            pl.BlockSpec(memory_space=pl.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, kv_len.astype(jnp.int32))
    return out.reshape(B, H, D)
