"""Locality-Sensitive Hashing for Reservoir (paper §II, §IV).

Implements the two families used in practice by FALCONN [7] — the library the
paper builds on — adapted to be TPU/MXU friendly:

* ``cross_polytope``: project the (unit-normalised) input through K random
  rotations per table; the hash of one rotation is the index of the closest
  cross-polytope vertex, i.e. ``argmax |proj|`` with a sign bit.  Dense random
  rotations are used instead of FALCONN's fast-Hadamard pseudo-rotations: on
  TPU a dense (B,D)x(D,K*D) matmul maps straight onto the MXU, which is the
  hardware adaptation recorded in DESIGN.md §2.
* ``hyperplane``: classic sign-random-projection (SimHash); ``bits`` planes
  per table give a ``2**bits``-bucket table.

Both families support **multi-probe** (paper §II, [6]): for each table a
ranked sequence of alternative buckets likely to hold near neighbours, so few
tables suffice.  Probe sequences are generated vectorised (single-swap /
single-bit-flip perturbations ranked by score loss), which covers the bulk of
the perturbation probability mass and is batch/JIT friendly.

The batched hash path is the per-request hot spot at fleet scale; a fused
Pallas TPU kernel lives in ``repro.kernels.lsh_hash`` and is validated against
the pure-jnp math here.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LSHParams:
    """Static configuration of an LSH family.

    ``num_buckets`` is per-table; the paper's rFIB stores the per-table index
    size in bytes (Fig. 4), so ``index_size_bytes`` must satisfy
    ``num_buckets <= 256 ** index_size_bytes`` (FALCONN max: 4 bytes).
    """

    dim: int
    num_tables: int = 5
    rotations_per_table: int = 1
    num_buckets: int = 256
    num_probes: int = 8
    family: str = "cross_polytope"  # or "hyperplane"
    seed: int = 0

    @property
    def index_size_bytes(self) -> int:
        n, size = self.num_buckets - 1, 1
        while n >= 256:
            n >>= 8
            size += 1
        if size > 4:
            raise ValueError("FALCONN supports at most 4-byte bucket indices")
        return size

    @property
    def bits(self) -> int:
        """Hyperplane family: planes per table (log2 of buckets)."""
        b = int(np.log2(self.num_buckets))
        if 2 ** b != self.num_buckets:
            raise ValueError("hyperplane family needs power-of-two num_buckets")
        return b

    @property
    def effective_buckets(self) -> int:
        """Number of bucket indices that can actually occur.

        Cross-polytope with K rotations produces at most (2*dim)**K distinct
        mixed values; with K=1 and dim < num_buckets/2 the top of the bucket
        range is unreachable — rFIB partitions must cover only the live
        range or some ENs would never receive tasks.
        """
        if self.family == "cross_polytope":
            return min(self.num_buckets, (2 * self.dim) ** self.rotations_per_table)
        return self.num_buckets


# f32 projections at full precision: the TPU's default rounds matmul inputs
# to bf16, which moves near-tie buckets between the batched, fused and
# one-row probes of the same embedding.
_HI = jax.lax.Precision.HIGHEST


def _orthogonalize(m: np.ndarray) -> np.ndarray:
    q, r = np.linalg.qr(m)
    return (q * np.sign(np.diag(r))).astype(np.float32)


# ---------------------------------------------------------------------------
# Pure hash/probe math, shared verbatim by LSH and the fused one-dispatch
# query pipeline (repro.kernels.fused_query).  These are module-level so the
# fused jit can close over *traced* rotation/plane arguments and a hashable
# static config instead of a per-store bound method — one compilation serves
# every store whose table/probe shapes match, regardless of LSH seed.
# ---------------------------------------------------------------------------

def mix_vertex_ids(vids: Array, radix: int, num_buckets: int) -> Array:
    """Fold K per-rotation vertex ids (..., K) into one bucket id (...,)."""
    val = jnp.zeros(vids.shape[:-1], jnp.int32)
    for k in range(vids.shape[-1]):
        val = (val * radix + vids[..., k]) % num_buckets
    return val


def cp_vertex_scores(x: Array, rotations: Array) -> Array:
    """Cross-polytope vertex scores: (B, T, K, 2D); vertex v<D is +e_v."""
    proj = jnp.einsum("tkde,be->btkd", rotations, x, precision=_HI)
    return jnp.concatenate([proj, -proj], axis=-1)


def multiprobe_buckets(
    x: Array,
    proj: Array,
    *,
    family: str,
    dim: int,
    rotations_per_table: int,
    num_probes: int,
    num_buckets: int,
) -> Tuple[Array, Array]:
    """Ranked multi-probe buckets: (B, T, P) ids + (B, T, P) losses.

    ``proj`` is the family's projection parameter — ``(T, K, D, D)``
    rotations for cross-polytope, ``(T, bits, D)`` unit planes for
    hyperplane.  This is the body of ``LSH._probe_impl``; the class method
    delegates here so the fused query pipeline probes bit-identically.
    """
    x = x.astype(jnp.float32)
    if family == "cross_polytope":
        scores = cp_vertex_scores(x, proj)  # (B,T,K,2D)
        k = rotations_per_table
        m = min(max(2, num_probes // max(k, 1) + 1), 2 * dim)
        top_v, top_i = jax.lax.top_k(scores, m)  # (B,T,K,m)
        base_ids = top_i[..., 0]  # (B,T,K)
        radix = 2 * dim
        base_bucket = mix_vertex_ids(base_ids, radix, num_buckets)  # (B,T)
        # weight of rotation k in the mixing polynomial
        w = jnp.asarray(
            [pow(radix, k - 1 - i, num_buckets) for i in range(k)], jnp.int32
        )
        # single-swap candidates: rotation r -> its j-th best vertex
        alt_loss = top_v[..., :1] - top_v  # (B,T,K,m), loss_j = s_0 - s_j >= 0
        delta = (top_i - base_ids[..., None]) % num_buckets  # (B,T,K,m)
        cand = (base_bucket[..., None, None] + delta * w[:, None]) % num_buckets
        flat_loss = alt_loss[..., 1:].reshape(*alt_loss.shape[:2], -1)
        flat_cand = cand[..., 1:].reshape(*cand.shape[:2], -1)
        nprob = min(num_probes - 1, flat_loss.shape[-1])
        neg_loss, order = jax.lax.top_k(-flat_loss, nprob)
        picked = jnp.take_along_axis(flat_cand, order, axis=-1)
        buckets = jnp.concatenate([base_bucket[..., None], picked], axis=-1)
        losses = jnp.concatenate(
            [jnp.zeros_like(base_bucket, jnp.float32)[..., None], -neg_loss], axis=-1
        )
        return buckets.astype(jnp.int32), losses
    # hyperplane: flip bits ranked by |margin|
    margins = jnp.einsum("tbd,nd->ntb", proj, x, precision=_HI)  # (B,T,bits)
    bits = (margins > 0).astype(jnp.int32)
    base_bucket = mix_vertex_ids(bits, 2, num_buckets)
    nbits = margins.shape[-1]
    w = jnp.asarray([1 << (nbits - 1 - i) for i in range(nbits)], jnp.int32)
    flipped = (base_bucket[..., None] ^ w) % num_buckets  # (B,T,bits)
    loss = jnp.abs(margins)
    nprob = min(num_probes - 1, nbits)
    neg_loss, order = jax.lax.top_k(-loss, nprob)
    picked = jnp.take_along_axis(flipped, order, axis=-1)
    buckets = jnp.concatenate([base_bucket[..., None], picked], axis=-1)
    losses = jnp.concatenate(
        [jnp.zeros_like(base_bucket, jnp.float32)[..., None], -neg_loss], axis=-1
    )
    return buckets.astype(jnp.int32), losses


class LSH:
    """An instantiated LSH family: rotation/plane parameters + hash/probe ops."""

    def __init__(self, params: LSHParams):
        self.params = params
        rng = np.random.default_rng(params.seed)
        d, t, k = params.dim, params.num_tables, params.rotations_per_table
        if params.family == "cross_polytope":
            rots = rng.standard_normal((t, k, d, d)).astype(np.float32)
            rots = np.stack(
                [np.stack([_orthogonalize(rots[i, j]) for j in range(k)]) for i in range(t)]
            )
            self.rotations = jnp.asarray(rots)  # (T, K, D, D)
            self.planes = None
        elif params.family == "hyperplane":
            self.rotations = None
            planes = rng.standard_normal((t, params.bits, d)).astype(np.float32)
            self.planes = jnp.asarray(planes / np.linalg.norm(planes, axis=-1, keepdims=True))
        else:
            raise ValueError(f"unknown LSH family {params.family!r}")
        # lint: disable=J001(built once per LSH instance in __init__, cached)
        self._hash_jit = jax.jit(self._hash_impl)
        # lint: disable=J001(built once per LSH instance in __init__, cached)
        self._probe_jit = jax.jit(self._probe_impl)

    # ------------------------------------------------------------------ hash
    def _cp_scores(self, x: Array) -> Array:
        """Cross-polytope vertex scores: (B, T, K, 2D); vertex v<D is +e_v."""
        return cp_vertex_scores(x, self.rotations)

    def _mix(self, vids: Array) -> Array:
        """Fold K per-rotation vertex ids into one bucket id (mod num_buckets)."""
        p = self.params
        radix = 2 * p.dim if p.family == "cross_polytope" else 2
        return mix_vertex_ids(vids, radix, p.num_buckets)

    def _hash_impl(self, x: Array) -> Array:
        p = self.params
        x = x.astype(jnp.float32)
        if p.family == "cross_polytope":
            scores = self._cp_scores(x)  # (B,T,K,2D)
            vids = jnp.argmax(scores, axis=-1).astype(jnp.int32)
            return self._mix(vids)  # (B,T)
        margins = jnp.einsum("tbd,nd->ntb", self.planes, x,
                             precision=_HI)  # (B,T,bits)
        bits = (margins > 0).astype(jnp.int32)
        return self._mix(bits)

    def hash_batch(self, x: Array) -> Array:
        """(B, D) -> (B, T) int32 bucket ids in [0, num_buckets)."""
        return self._hash_jit(jnp.atleast_2d(x))

    # ----------------------------------------------------------------- probe
    def _probe_impl(self, x: Array) -> Tuple[Array, Array]:
        """Ranked multi-probe buckets: (B, T, P) ids + (B, T, P) losses."""
        p = self.params
        proj = self.rotations if p.family == "cross_polytope" else self.planes
        return multiprobe_buckets(
            x,
            proj,
            family=p.family,
            dim=p.dim,
            rotations_per_table=p.rotations_per_table,
            num_probes=p.num_probes,
            num_buckets=p.num_buckets,
        )

    def probe_batch(self, x: Array) -> Array:
        """(B, D) -> (B, T, P) ranked probe bucket ids (probe 0 == hash)."""
        return self._probe_jit(jnp.atleast_2d(x))[0]

    # ------------------------------------------------------------- utilities
    def hash_one(self, x: Array) -> np.ndarray:
        return np.asarray(self.hash_batch(x[None]))[0]

    def probe_one(self, x: Array) -> np.ndarray:
        return np.asarray(self.probe_batch(x[None]))[0]


@functools.lru_cache(maxsize=32)
def get_lsh(params: LSHParams) -> LSH:
    """Cached LSH instances (rotation sampling + jit are amortised)."""
    return LSH(params)


def normalize(x: np.ndarray) -> np.ndarray:
    """L2-normalise rows (cross-polytope LSH operates on the unit sphere)."""
    x = np.asarray(x, np.float32)
    n = np.linalg.norm(x, axis=-1, keepdims=True)
    return x / np.maximum(n, 1e-12)
