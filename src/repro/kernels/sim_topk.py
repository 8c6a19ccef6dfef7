"""Pallas TPU kernels: streaming nearest-neighbour search (cosine top-1).

The EN-side reuse query (paper Table IVb: 0.09-4.4 ms per search on CPU).
Inputs are L2-normalised (the reuse store normalises on insert), so cosine
similarity is a plain matmul.  Two kernels:

* ``sim_top1`` — brute-force streaming top-1 over the whole store.
  Grid: (Q / bQ, N / bN) with N innermost — TPU grids execute sequentially,
  so a VMEM scratch carries the running (best value, best index) across N
  tiles and the result is written once at the last tile.  This streams an
  arbitrarily large store through VMEM with O(bQ) state.  No served path
  calls it, and the v5e compiler refuses its load of the ``pl.ANY`` operand.

* ``reuse_top1`` — the store search's top-1 stage, shared by the
  one-dispatch query path (DESIGN.md §One-dispatch query path, candidates
  raw from the device slot tables) and the host-staged path (sorted unique
  candidates, ``ops.gathered_top1``).  Each query carries its own candidate
  list of store row ids (``-1`` = empty slot); the store is a flat
  ``(N, D)`` matrix or the reuse store's paged ``(num_pages, page_size, D)``
  device buffer.  XLA gathers the candidate rows, and the kernel streams
  (bQ, bC, D) tiles with candidates innermost, keeping a *lexicographic*
  (max similarity, then min store row id) running best: the lowest-id rule
  reproduces the host path's argmax-over-sorted-unique semantics without
  sorting.  Work is O(B * C * D) — the candidate set, not the store size.
  The data-dependent row gather stays in XLA because Mosaic reads an HBM
  (``pl.ANY``) operand only through DMA.

On CPU the kernels run in interpret mode (see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sim_top1_kernel(q_ref, s_ref, nvalid_ref, val_ref, idx_ref,
                     best_val, best_idx, *, block_n: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_val[...] = jnp.full_like(best_val, -jnp.inf)
        best_idx[...] = jnp.zeros_like(best_idx)

    q = q_ref[...].astype(jnp.float32)            # (bQ, D)
    s = s_ref[...].astype(jnp.float32)            # (bN, D)
    scores = jax.lax.dot_general(
        q, s, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (bQ, bN)
    base = j * block_n
    cols = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    valid = cols < nvalid_ref[0]
    scores = jnp.where(valid, scores, -jnp.inf)
    tile_val = jnp.max(scores, axis=-1)           # (bQ,)
    tile_arg = jnp.argmax(scores, axis=-1).astype(jnp.int32) + base
    better = tile_val > best_val[...]
    best_val[...] = jnp.where(better, tile_val, best_val[...])
    best_idx[...] = jnp.where(better, tile_arg, best_idx[...])

    @pl.when(j == nj - 1)
    def _done():
        val_ref[...] = best_val[...]
        idx_ref[...] = best_idx[...]


@functools.partial(jax.jit, static_argnames=("block_q", "block_n", "interpret"))
def sim_top1(q: jax.Array, store: jax.Array, n_valid: jax.Array | None = None,
             *, block_q: int = 128, block_n: int = 512,
             interpret: bool):
    """q: (Q, D); store: (N, D), rows L2-normalised. -> (best (Q,), idx (Q,)).

    ``n_valid`` masks the tail of a pre-allocated (ring-buffer) store.
    """
    Q, D = q.shape
    N = store.shape[0]
    bQ, bN = min(block_q, Q), min(block_n, N)
    nv = jnp.asarray([N if n_valid is None else n_valid], jnp.int32)
    grid = (pl.cdiv(Q, bQ), pl.cdiv(N, bN))
    val, idx = pl.pallas_call(
        functools.partial(_sim_top1_kernel, block_n=bN),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bQ, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bN, D), lambda i, j: (j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bQ,), lambda i, j: (i,)),
            pl.BlockSpec((bQ,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q,), jnp.float32),
            jax.ShapeDtypeStruct((Q,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bQ,), jnp.float32),
            pltpu.VMEM((bQ,), jnp.int32),
        ],
        interpret=interpret,
    )(q, store, nv)
    return val, idx


# Upper bound on the gathered candidate rows one kernel launch streams: the
# XLA gather materializes (rows, C, D) f32 in HBM ahead of the kernel, so a
# batch whose candidate tensor would exceed this is split into query chunks
# (1024 queries x 20480 candidates x 64 dims is 5.4 GB unchunked).
GATHER_BUDGET_BYTES = 128 << 20


def _reuse_top1_kernel(q_ref, ids_ref, cand_ref, val_ref, idx_ref):
    j = pl.program_id(1)
    imax = jnp.iinfo(jnp.int32).max

    # the (bQ, 1) outputs stay resident across the sequential candidate axis
    # and carry the running best
    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, -jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, imax)

    q = q_ref[...].astype(jnp.float32)                 # (bQ, D)
    ids = ids_ref[...]                                 # (bQ, bC)
    cand = cand_ref[...].astype(jnp.float32)           # (bQ, bC, D)
    # exact f32 multiply-add on the VPU: no MXU pass rounding, so the scores
    # agree with the host's numpy cosine to f32 accuracy
    scores = jnp.sum(cand * q[:, None, :], axis=-1)    # (bQ, bC)
    valid = ids >= 0
    scores = jnp.where(valid, scores, -jnp.inf)
    tile_val = jnp.max(scores, axis=-1, keepdims=True)  # (bQ, 1)
    # lexicographic running best: duplicate ids score bit-equal, so taking
    # the *minimum* id among this tile's maxima reproduces the host path's
    # argmax-over-sorted-unique tie-break without sorting candidates.
    elig = valid & (scores >= tile_val)
    tile_idx = jnp.min(jnp.where(elig, ids, imax), axis=-1, keepdims=True)
    bv, bi = val_ref[...], idx_ref[...]
    better = (tile_val > bv) | ((tile_val == bv) & (tile_idx < bi))
    val_ref[...] = jnp.where(better, tile_val, bv)
    idx_ref[...] = jnp.where(better, tile_idx, bi)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        idx_ref[...] = jnp.where(val_ref[...] > -jnp.inf, idx_ref[...], -1)


def _round_up(n: int, m: int) -> int:
    return max(-(-n // m) * m, m)


@functools.partial(jax.jit, static_argnames=("block_q", "block_c", "interpret"))
def reuse_top1(q: jax.Array, store: jax.Array, cand_ids: jax.Array,
               *, block_q: int = 8, block_c: int = 512, interpret: bool):
    """Masked cosine top-1 with lowest-id tie-break over candidate row ids.

    q: (Q, D) unit rows; store: flat (N, D) or paged
    (num_pages, page_size, D) device buffer (slot id = page * page_size +
    offset); cand_ids: (Q, C) int32 store row ids in any order, duplicates
    allowed, -1 = empty slot.  Returns (best (Q,), idx (Q,)): idx is the
    lowest store row id among the maximum-similarity candidates (-1 / -inf
    when a query has no valid candidate), matching the host path's
    sorted-unique argmax.

    The rows are gathered by XLA and the kernel streams blocked
    (bQ, bC, D) candidate tiles; the default blocks fit the v5e's scoped
    VMEM.  Queries are processed in chunks of at most
    ``GATHER_BUDGET_BYTES`` of gathered candidates.
    """
    Q, D = q.shape
    C = cand_ids.shape[1]
    bQ = min(block_q, _round_up(Q, 8))
    bC = min(block_c, _round_up(C, 128))
    Cp = _round_up(C, bC)
    rows = max(bQ, min(_round_up(Q, bQ), GATHER_BUDGET_BYTES // (Cp * D * 4))
               // bQ * bQ)
    n_chunks = -(-Q // rows)
    Qp = n_chunks * rows
    q = jnp.pad(q, ((0, Qp - Q), (0, 0)))
    ids = jnp.pad(cand_ids.astype(jnp.int32), ((0, Qp - Q), (0, Cp - C)),
                  constant_values=-1)
    flat = store.reshape(-1, D)
    top1 = pl.pallas_call(
        _reuse_top1_kernel,
        grid=(rows // bQ, Cp // bC),
        in_specs=[
            pl.BlockSpec((bQ, D), lambda i, j: (i, 0)),
            pl.BlockSpec((bQ, bC), lambda i, j: (i, j)),
            pl.BlockSpec((bQ, bC, D), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bQ, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bQ, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )

    def chunk(args):
        qc, idc = args
        cand = jnp.take(flat, jnp.maximum(idc, 0), axis=0, mode="clip")
        return top1(qc, idc, cand)

    val, idx = jax.lax.map(chunk, (q.reshape(n_chunks, rows, D),
                                   ids.reshape(n_chunks, rows, Cp)))
    return val.reshape(-1)[:Q], idx.reshape(-1)[:Q]
