"""Plain reference of the edge node's reuse layers: LSH naming, the
bucket-range router and the reuse store (Reservoir, Sec. IV-D/E).

Written from the deployment's stated semantics, in numpy and float64, and
importing nothing of the program under test:

* Cross-polytope LSH (FALCONN): per table one random rotation, made from the
  store's LSH seed as ``numpy.random.default_rng(seed).standard_normal(
  (T, 1, D, D))`` in float32 and orthogonalised by QR with the signs of R's
  diagonal; a vector's bucket is the index of its closest cross-polytope
  vertex (``argmax`` over ``[Rx, -Rx]``).  Multi-probe visits the
  ``num_probes`` best vertices of each table.
* Router: the live bucket span ``[0, min(num_buckets, 2D))`` is cut into
  consecutive equal ranges, one per replica; a task goes to the replica that
  owns most of its T buckets, ties to the lowest id.
* Store: T tables of ``num_buckets`` buckets of ``bucket_cap`` slots; an
  insert appends to each of its buckets and, once a bucket is full,
  overwrites its oldest slot.  Entries get consecutive ids.  A query's
  candidates are the entries in its probed buckets; the answer is the
  candidate of highest cosine similarity (lowest id on a tie), a hit when
  that similarity reaches the task's threshold.

Every comparison allows ``TOL`` for float32 rounding: where two vertices,
two candidates, or a similarity and the threshold lie within it, either
answer is right.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

TOL = 1e-5


def rotations(store: dict) -> np.ndarray:
    """(T, D, D) float64 rotations of the LSH family (one per table)."""
    if store["family"] != "cross_polytope" or store["rotations_per_table"] != 1:
        raise ValueError("the reference covers cross-polytope LSH with one "
                         "rotation per table")
    rng = np.random.default_rng(store["seed"])
    t, d = store["num_tables"], store["dim"]
    raw = rng.standard_normal((t, 1, d, d)).astype(np.float32)
    out = []
    for i in range(t):
        q, r = np.linalg.qr(raw[i, 0])
        out.append((q * np.sign(np.diag(r))).astype(np.float32))
    return np.stack(out).astype(np.float64)


def normalize(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


class LSHRef:
    def __init__(self, store: dict):
        self.cfg = store
        self.rot = rotations(store)
        self.nb = store["num_buckets"]
        self.live = min(self.nb, 2 * store["dim"])

    def scores(self, x: np.ndarray) -> np.ndarray:
        """(N, D) -> (N, T, 2D) vertex scores."""
        p = np.einsum("tde,ne->ntd", self.rot, np.asarray(x, np.float64))
        return np.concatenate([p, -p], axis=-1)

    def hash(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """-> ((N, T) buckets, (N, T) runner-up buckets within TOL or -1)."""
        s = self.scores(x)
        order = np.argsort(-s, axis=-1, kind="stable")
        top = np.take_along_axis(s, order[..., :2], -1)
        tie = np.where(top[..., 0] - top[..., 1] < TOL, order[..., 1], -1)
        return order[..., 0] % self.nb, np.where(tie >= 0, tie % self.nb, -1)

    def probes(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """-> ((N, T, P) probed buckets, (N, T) ties at the last probe: the
        bucket that could stand in for the P-th one, or -1)."""
        p = self.cfg["num_probes"]
        s = self.scores(x)
        order = np.argsort(-s, axis=-1, kind="stable")
        edge = np.take_along_axis(s, order[..., p - 1:p + 1], -1)
        tie = np.where(edge[..., 0] - edge[..., 1] < TOL, order[..., p], -1)
        return order[..., :p] % self.nb, np.where(tie >= 0, tie % self.nb, -1)

    def owners(self, buckets: np.ndarray, n_replicas: int) -> np.ndarray:
        """(N, T) buckets -> (N,) majority owner, ties to the lowest id."""
        bounds = [round(i * self.live / n_replicas) for i in range(n_replicas + 1)]
        own = np.searchsorted(np.asarray(bounds[1:-1]), buckets, side="right")
        own = np.minimum(own, n_replicas - 1)
        votes = (own[..., None] == np.arange(n_replicas)).sum(axis=-2)
        return votes.argmax(axis=-1)


class StoreRef:
    """One replica's reuse store for one service."""

    def __init__(self, store: dict):
        t, nb, cap = store["num_tables"], store["num_buckets"], store["bucket_cap"]
        self.cap = cap
        self.capacity = store["capacity"]
        self.slots = np.full((t, nb, cap), -1, np.int64)
        self.fill = np.zeros((t, nb), np.int64)
        self.cursor = np.zeros((t, nb), np.int64)
        self.emb = np.zeros((self.capacity, store["dim"]), np.float64)
        self.results: List[object] = []

    def __len__(self) -> int:
        return len(self.results)

    def insert(self, emb: np.ndarray, result, buckets: np.ndarray) -> int:
        idx = len(self.results)
        if idx >= self.capacity:
            raise RuntimeError("the reference store does not model eviction: "
                               "the cell's stores must stay under capacity")
        self.emb[idx] = emb
        self.results.append(result)
        for t, b in enumerate(buckets):
            f = self.fill[t, b]
            if f < self.cap:
                self.slots[t, b, f] = idx
                self.fill[t, b] = f + 1
            else:
                c = self.cursor[t, b]
                self.slots[t, b, c] = idx
                self.cursor[t, b] = (c + 1) % self.cap
        return idx

    def candidates(self, probes: np.ndarray) -> np.ndarray:
        """Sorted unique ids in the (T, P) probed buckets."""
        cand = self.slots[np.arange(probes.shape[0])[:, None], probes]
        return np.unique(cand[cand >= 0])

    def answer_ok(self, q: np.ndarray, probes: np.ndarray, probe_tie: np.ndarray,
                  threshold: float, got_idx: Optional[int], got_sim: float
                  ) -> bool:
        """Is the program's answer (an id, or None for a miss, and its
        similarity) one that the reference allows?  Rounding ties count
        either way: at the last probe, between candidates, and at the
        threshold."""
        q = np.asarray(q, np.float64)
        sets = [probes]
        for t in np.flatnonzero(probe_tie >= 0):
            alt = probes.copy()
            alt[t, -1] = probe_tie[t]
            sets.append(alt)
        for pr in sets:
            cand = self.candidates(pr)
            if cand.size == 0:
                if got_idx is None and got_sim == -1.0:
                    return True
                continue
            sims = self.emb[cand] @ q
            best = float(sims.max())
            if got_idx is None:
                if best < threshold + TOL and abs(got_sim - best) <= TOL:
                    return True
                continue
            pos = np.searchsorted(cand, got_idx)
            if pos >= cand.size or cand[pos] != got_idx:
                continue
            got_ref = float(sims[pos])
            if (abs(got_ref - got_sim) <= TOL and best - got_ref <= TOL
                    and got_ref >= threshold - TOL):
                return True
        return False
