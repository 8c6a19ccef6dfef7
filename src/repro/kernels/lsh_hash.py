"""Pallas TPU kernel: fused cross-polytope LSH hashing.

The per-request hot spot of Reservoir at fleet scale (paper Table III: 0.4 to
3.3 ms *per task* on a CPU).  On TPU the whole hash is one fused pass:

    proj = x_tile @ R[t, k]           (MXU: bB x D times D x D)
    vid  = argmax(|proj|) with sign   (VPU, in VMEM)

Grid: (B / bB, T, K).  Each step loads one (D, D) rotation into VMEM, hits
the MXU once, and reduces in-register — no HBM round-trip for the projection.
Tile sizes are 128-aligned for the MXU; D itself is the embedding dim
(128/256 in deployments, zero-padded by ops.py otherwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _vertex_ids(proj):
    """Closest cross-polytope vertex of each row: argmax over [proj, -proj]
    (vertex v < D is +e_v), with core.lsh's first-index tie-break."""
    neg = jnp.max(-proj, axis=-1) > jnp.max(proj, axis=-1)
    return jnp.where(neg, jnp.argmax(-proj, axis=-1) + proj.shape[-1],
                     jnp.argmax(proj, axis=-1)).astype(jnp.int32)


def _lsh_hash_kernel(x_ref, rot_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)           # (bB, D)
    rot = rot_ref[0, 0].astype(jnp.float32)      # (D, D)
    # proj[b, d] = sum_e R[d, e] x[b, e]  (matches core.lsh / ref einsum)
    proj = jax.lax.dot_general(
        x, rot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (bB, D) on the MXU
    out_ref[...] = _vertex_ids(proj)[:, None]


def _lsh_hash_mix_kernel(x_ref, rot_ref, out_ref, *, radix: int, num_buckets: int):
    """Hash + modular-mixing epilogue: out revisited across the K grid steps.

    The K axis is innermost (sequential on TPU), and the out block's index map
    ignores it, so the (bB, 1) bucket accumulator of table t stays resident in
    VMEM while each rotation folds its vertex id in:
    acc = (acc * radix + vid) % NB.
    This removes the K host-side mixing steps `ops.lsh_buckets` used to run.
    """
    k = pl.program_id(2)
    x = x_ref[...].astype(jnp.float32)           # (bB, D)
    rot = rot_ref[0, 0].astype(jnp.float32)      # (D, D)
    proj = jax.lax.dot_general(
        x, rot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (bB, D)
    vid = _vertex_ids(proj)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref[...][:, 0]
    out_ref[...] = ((acc * radix + vid) % num_buckets)[:, None]


@functools.partial(
    jax.jit, static_argnames=("num_buckets", "block_b", "interpret"))
def lsh_hash_mix(x: jax.Array, rotations: jax.Array, *, num_buckets: int,
                 block_b: int = 128, interpret: bool) -> jax.Array:
    """x: (B, D); rotations: (T, K, D, D) -> (B, T) int32 mixed bucket ids.

    One dispatch for the whole cross-polytope hash including the bucket
    mixing that previously ran as K Python-level modular steps on host.
    """
    B, D = x.shape
    T, K = rotations.shape[:2]
    bB = min(block_b, B)
    grid = (pl.cdiv(B, bB), T, K)
    kernel = functools.partial(
        _lsh_hash_mix_kernel, radix=2 * D, num_buckets=num_buckets)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, D), lambda b, t, k: (b, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, t, k: (t, k, 0, 0)),
        ],
        # table-major (T, B, 1) output: a (bB, 1) block of a (B, T) array
        # breaks the TPU's (8, 128) block rule for T > 1
        out_specs=pl.BlockSpec((None, bB, 1), lambda b, t, k: (t, b, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, 1), jnp.int32),
        interpret=interpret,
    )(x, rotations)[..., 0].T


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def lsh_hash(x: jax.Array, rotations: jax.Array, *, block_b: int = 128,
             interpret: bool) -> jax.Array:
    """x: (B, D) f32/bf16; rotations: (T, K, D, D) -> (B, T, K) int32 ids."""
    B, D = x.shape
    T, K = rotations.shape[:2]
    bB = min(block_b, B)
    grid = (pl.cdiv(B, bB), T, K)
    return pl.pallas_call(
        _lsh_hash_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, D), lambda b, t, k: (b, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, t, k: (t, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bB, 1),
                               lambda b, t, k: (t, k, b, 0)),
        out_shape=jax.ShapeDtypeStruct((T, K, B, 1), jnp.int32),
        interpret=interpret,
    )(x, rotations)[..., 0].transpose(2, 0, 1)
