"""One-dispatch fused reuse query (DESIGN.md §One-dispatch query path).

The whole batched reuse lookup — LSH rotation matmuls + cross-polytope
vertex ids + bucket mixing, multi-probe slot-table gather, and the masked
cosine top-1 over the paged device buffer — as a single jitted dispatch:

    embs (B, D) ──┐
    proj          ├─> multiprobe_buckets ─> (B, T, P) probe buckets
    slots (T*NB,cap) ─> table gather ─────> (B, T*P*cap) raw candidate ids
    pages (P, S, D) ──> reuse_top1 kernel ─> (best (B,), idx (B,))
                        sort + run-length ─> exact unique-candidate counts

Candidate ids go to the kernel *raw* (unsorted, duplicated, -1 for empty
slots); ``reuse_top1``'s lexicographic (max similarity, min id) running best
reproduces the host path's argmax-over-sorted-unique tie-break, and the
count epilogue reproduces its ``candidate_counts`` statistics bit-exactly.
The count epilogue is optional (``with_counts``): a device-side sort is the
right choice on TPU (keeps the pipeline one dispatch with no host work),
but XLA:CPU sorts are ~10x slower than numpy, so under interpret mode the
caller takes the raw candidate matrix back instead and counts on the host
(ops.unique_counts) — and skips counting entirely for ``peek`` reads,
which record no statistics.

Compile-cache design: the probe math is the *module-level*
``core.lsh.multiprobe_buckets`` with rotations/planes passed as traced
arguments, so one compilation serves every store whose static config
(family, probes, table/page shapes, blocks) matches — LSH seeds and store
contents never retrace.  Callers pad B to a multiple of 8 (ops.py); the
candidate width T*P*cap is static per store config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .sim_topk import reuse_top1

# Number of times the fused pipeline has been (re)traced this process —
# stable across repeated same-shape calls iff the jit cache persists.
# Tests assert on the delta to pin jit persistence.
FUSED_TRACE_COUNT = 0


@functools.partial(jax.jit, static_argnames=(
    "family", "num_probes", "block_q", "block_c", "interpret", "with_counts"))
def fused_query(embs: jax.Array, proj: jax.Array, slots_flat: jax.Array,
                pages: jax.Array, *, family: str, num_probes: int,
                block_q: int = 8, block_c: int = 512, interpret: bool,
                with_counts: bool = True):
    """hash -> probe -> gather -> top-1 in one dispatch.

    embs: (B, D) unit rows, B a multiple of 8; proj: (T, K, D, D) rotations
    (cross-polytope) or (T, bits, D) planes (hyperplane); slots_flat:
    (T * num_buckets, bucket_cap) int32 device slot tables; pages: the
    store's paged (num_pages, page_size, D) embedding mirror.

    Returns (best (B,) f32, idx (B,) int32 store row ids with -1 = no
    candidate, extra): extra is the (B,) int32 exact unique-candidate
    counts when ``with_counts``, else the raw (B, T*P*cap) candidate-id
    matrix (for host-side counting — see module docstring).
    """
    global FUSED_TRACE_COUNT
    FUSED_TRACE_COUNT += 1
    # lazy: kernels must stay importable without the core package loaded
    from repro.core.lsh import multiprobe_buckets

    b, d = embs.shape
    t = proj.shape[0]
    cap = slots_flat.shape[1]
    nb = slots_flat.shape[0] // t
    k = proj.shape[1] if family == "cross_polytope" else 1
    buckets, _ = multiprobe_buckets(
        embs, proj, family=family, dim=d, rotations_per_table=k,
        num_probes=num_probes, num_buckets=nb)          # (B, T, P)
    slots = slots_flat.reshape(t, nb, cap)
    t_idx = jnp.arange(t, dtype=jnp.int32)[None, :, None]
    ids = slots[t_idx, buckets].reshape(b, -1)          # (B, T*P*cap)
    val, idx = reuse_top1(embs, pages, ids, block_q=block_q, block_c=block_c,
                          interpret=interpret)
    if not with_counts:
        return val, idx, ids
    # exact unique-candidate counts: -1 pads sort to the front, a run-length
    # count of the ascending tail matches the host path's sorted-unique stats
    srt = jnp.sort(ids, axis=1)
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), srt[:, 1:] != srt[:, :-1]], axis=1)
    counts = jnp.sum((srt >= 0) & first, axis=1).astype(jnp.int32)
    return val, idx, counts
