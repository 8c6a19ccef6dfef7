"""Public jit'd wrappers around the Pallas kernels.

Handles padding to hardware-aligned tiles, platform dispatch (interpret=True
on CPU — the kernels target TPU; interpret mode executes the kernel body for
correctness), and dtype plumbing.  Every op has a pure-jnp oracle in
``ref.py``; tests sweep shapes/dtypes against it.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .decode_attention import decode_attention as _decode_kernel
from .flash_attention import flash_attention as _flash_kernel
from .fused_query import fused_query as _fused_query
from .lsh_hash import lsh_hash as _lsh_kernel
from .lsh_hash import lsh_hash_mix as _lsh_mix_kernel
from .sim_topk import reuse_top1 as _reuse_kernel
from .sim_topk import sim_top1 as _sim_kernel

# Device dispatches issued by the fused one-dispatch query path (one per
# ``reuse_query_top1`` call).  Paired with ``fused_query.FUSED_TRACE_COUNT``
# this lets tests assert "exactly one dispatch, zero retraces" on the hot
# path.
FUSED_DISPATCH_COUNT = 0


def _interpret() -> bool:
    """Interpret the kernels on the CPU (tests), compile them on the TPU."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels target the TPU (interpreted on the CPU); "
        f"backend {backend!r} has no path")


def _top1_blocks() -> dict:
    """reuse_top1 tiles: the kernel's v5e-sized defaults on the chip; under
    interpret mode larger tiles cut the interpreter's per-step overhead."""
    return {"block_q": 128, "block_c": 512} if _interpret() else {}


def _pad_to(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad), n


# ------------------------------------------------------------------- lsh_hash
def lsh_hash_ids(x: jax.Array, rotations: jax.Array) -> jax.Array:
    """(B, D) x (T, K, D, D) -> (B, T, K) cross-polytope vertex ids."""
    xp, b = _pad_to(x, 0, 8)
    out = _lsh_kernel(xp, rotations, interpret=_interpret())
    return out[:b]


def lsh_buckets(x: jax.Array, rotations: jax.Array, num_buckets: int) -> jax.Array:
    """Fused hash + per-table bucket mixing -> (B, T) int32, one dispatch.

    The K modular-mixing steps run inside the kernel epilogue (the bucket
    accumulator block stays VMEM-resident across the sequential K grid axis)
    instead of as host-side jnp ops after the hash kernel returned.
    """
    xp, b = _pad_to(x, 0, 8)
    out = _lsh_mix_kernel(xp, rotations, num_buckets=num_buckets,
                          interpret=_interpret())
    return out[:b]


# ------------------------------------------------------------------- sim_topk
def similarity_scores(q: jax.Array, store: jax.Array) -> jax.Array:
    """Dense cosine scores (small candidate sets); jnp path, kernels handle
    the streaming large-store case via ``nearest_neighbor``."""
    return ref.similarity_scores_ref(q, store)


def nearest_neighbor(q: jax.Array, store: jax.Array,
                     n_valid: Optional[jax.Array] = None):
    """Streaming top-1 over a (large, unit-normalised) store."""
    qp, nq = _pad_to(q, 0, 8)
    sp, ns = _pad_to(store, 0, 8)
    nv = jnp.asarray(ns if n_valid is None else n_valid, jnp.int32)
    val, idx = _sim_kernel(qp, sp, nv, interpret=_interpret())
    return val[:nq], idx[:nq]


def gathered_top1(q: jax.Array, store: jax.Array, cand_ids: jax.Array):
    """Candidate gather + masked cosine top-1 (the host-staged reuse query).

    q: (Q, D) unit rows; store: (N, D) unit rows, or the reuse store's paged
    (num_pages, page_size, D) device buffer — slot ids then address row
    ``page * page_size + offset``.  cand_ids: (Q, C) int32 store row ids,
    -1 = unused slot.  Returns (best (Q,) f32, idx (Q,) int32) where idx is a
    store row id and -1/-inf mark queries without candidates; equal
    similarities resolve to the lowest id, which for the staged path's sorted
    unique candidates is the first one.
    """
    q = jnp.atleast_2d(q)
    nq = q.shape[0]
    n_rows = int(np.prod(store.shape[:-1]))
    if n_rows == 0 or cand_ids.shape[1] == 0:
        return (jnp.full((nq,), -jnp.inf, jnp.float32),
                jnp.full((nq,), -1, jnp.int32))
    # queries pad to a multiple of 8 and candidates to one of 128 (the
    # kernel's lane tile), so drifting batch sizes and candidate counts
    # share a few compilations
    qp, _ = _pad_to(q, 0, 8)
    c = cand_ids.shape[1]
    ids = jnp.pad(jnp.asarray(cand_ids, jnp.int32),
                  ((0, qp.shape[0] - nq), (0, -(-c // 128) * 128 - c)),
                  constant_values=-1)
    val, idx = _reuse_kernel(qp, store, ids, interpret=_interpret(),
                             **_top1_blocks())
    return val[:nq], idx[:nq]


# --------------------------------------------------------- fused reuse query
def unique_counts(cand: "np.ndarray") -> "np.ndarray":
    """Exact unique-candidate counts from a raw (B, W) candidate-id matrix.

    Host-side twin of fused_query's device count epilogue: -1 pads sort to
    the front, a run-length count of the valid tail matches the scalar
    path's sorted-unique statistics bit-exactly.  numpy sorts ~10x faster
    than XLA:CPU, so the interpret-mode fused path counts here instead of
    in-jit (TPU keeps the device-side epilogue).
    """
    srt = np.sort(cand, axis=1)
    first = np.concatenate(
        [np.ones((srt.shape[0], 1), bool), srt[:, 1:] != srt[:, :-1]], axis=1)
    return ((srt >= 0) & first).sum(axis=1).astype(np.int32)


def reuse_query_top1(embs, lsh, slots_dev: jax.Array, pages_dev: jax.Array,
                     *, need_counts: bool = True):
    """One-dispatch batched reuse query over the device-resident store.

    embs: (B, D) unit rows (host or device); lsh: the store's ``core.lsh.LSH``
    instance (only its params + rotation/plane arrays are read); slots_dev:
    (T * num_buckets, bucket_cap) int32 device slot tables; pages_dev: paged
    (num_pages, page_size, D) device embedding mirror.

    Returns (best (B,) f32, idx (B,) int32, counts) — idx is a store row id
    (-1 = no candidate, lowest id wins similarity ties, matching the host
    path); counts are exact unique-candidate statistics, or None when the
    caller passes ``need_counts=False`` (peek reads record no statistics,
    and ``idx < 0`` already identifies the zero-candidate rows).  On TPU the
    counts come from the in-dispatch sort epilogue; under interpret mode
    they are counted host-side (``unique_counts``) from the returned raw
    candidate matrix — still a single device dispatch either way.

    B is padded to a multiple of 8; everything else in the signature is
    static per store config, so steady-state traffic reuses one compilation.
    """
    global FUSED_DISPATCH_COUNT
    p = lsh.params
    proj = lsh.rotations if p.family == "cross_polytope" else lsh.planes
    x = jnp.atleast_2d(jnp.asarray(embs, jnp.float32))
    nq = x.shape[0]
    xp, _ = _pad_to(x, 0, 8)
    interp = _interpret()
    val, idx, extra = _fused_query(
        xp, proj, slots_dev, pages_dev,
        family=p.family, num_probes=p.num_probes,
        interpret=interp, with_counts=not interp, **_top1_blocks())
    FUSED_DISPATCH_COUNT += 1
    if not need_counts:
        counts = None
    elif interp:
        counts = unique_counts(np.asarray(extra[:nq]))
    else:
        counts = extra[:nq]
    return val[:nq], idx[:nq], counts


# ------------------------------------------------------------ flash attention
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, block_q=128, block_k=128):
    """(B,S,H,D) x (B,T,KV,D)^2 -> (B,S,H,D); TPU flash, interpret on CPU."""
    return _flash_kernel(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=_interpret())


def decode_attention(q, k, v, kv_len, *, softcap=None, scale=None,
                     block_k=512):
    """(B,H,D) x (B,T,KV,D)^2 + (B,) -> (B,H,D)."""
    return _decode_kernel(
        q, k, v, kv_len, softcap=softcap, scale=scale, block_k=block_k,
        interpret=_interpret())
