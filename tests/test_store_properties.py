"""Property-based ReuseStore parity vs a dict-of-lists reference model.

The safety net under the paged-device-buffer refactor (ISSUE 3): random
interleavings of ``insert`` / ``insert_batch`` / ``query`` / ``query_batch``
/ ``remove`` (plus capacity-driven LRU eviction) run side by side against
``RefStore`` — a deliberately naive model that keeps each LSH table as a
plain dict-of-lists with ring cursors, the LRU as an OrderedDict, and
embeddings in per-id dicts.  After every operation the harness asserts

  * hit/miss decisions, similarities, and winning slot ids match,
  * candidate-count statistics match,
  * LRU residency *order* (== eviction order) matches,
  * the array-native bucket tables (slots prefix, fill, ring cursor) are
    bit-identical to the model's lists, and ``overflows`` agrees,
  * paged-storage invariants hold: live rows equal the model's embeddings
    and released rows are tombstoned to zero.

Two drivers cover the same interleavings: a seed-parametrized sweep that
always runs (>= 200 interleavings on the exact numpy scoring path plus a
kernel-path subset through the paged device buffer), and a hypothesis
``@given`` sweep for CI depth (skipped when hypothesis is not installed —
see ``conftest.py``).

Also here: the ring-overflow recall regression (measured recall vs a
brute-force oracle above a pinned floor, ``overflows`` equal to the analytic
count) and the remove/evict tombstone regression.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import LSHParams, ReuseStore, get_lsh, normalize
from repro.core.similarity import get_similarity

DIM = 16
SIM_TOL = 1e-4   # kernel f32 accumulation vs numpy; also the tie/threshold
                 # margin below which decisions are adopted, not asserted


class RefStore:
    """Dict-of-lists reference model of ReuseStore semantics."""

    def __init__(self, params: LSHParams, capacity: int, bucket_cap: int,
                 similarity: str = "cosine"):
        self.lsh = get_lsh(params)
        self.params = params
        self.capacity = capacity
        self.cap = bucket_cap
        self.sim = get_similarity(similarity)
        t, nb = params.num_tables, params.num_buckets
        self.slots: List[List[List[int]]] = [
            [[] for _ in range(nb)] for _ in range(t)]
        self.cursor: List[List[int]] = [[0] * nb for _ in range(t)]
        self.emb: Dict[int, np.ndarray] = {}
        self.results: Dict[int, Any] = {}
        self.buckets_of: Dict[int, np.ndarray] = {}
        self.free: List[int] = []
        self.next_id = 0
        self.lru: "OrderedDict[int, None]" = OrderedDict()
        self.overflows = 0
        self.inserts = 0
        self.queries = 0
        self.candidate_counts: List[int] = []

    # ------------------------------------------------------------- mutation
    def _alloc(self) -> int:
        if self.free:
            return self.free.pop()
        self.next_id += 1
        return self.next_id - 1

    def _table_add(self, idx: int, buckets: np.ndarray) -> None:
        for t in range(self.params.num_tables):
            b = int(buckets[t])
            row = self.slots[t][b]
            if len(row) < self.cap:
                row.append(idx)
            else:
                row[self.cursor[t][b]] = idx
                self.cursor[t][b] = (self.cursor[t][b] + 1) % self.cap
                self.overflows += 1

    def _table_remove(self, idx: int, buckets: np.ndarray) -> None:
        for t in range(self.params.num_tables):
            b = int(buckets[t])
            row = self.slots[t][b]
            if idx in row:  # swap-with-last, mirroring the array tables
                p = row.index(idx)
                row[p] = row[-1]
                row.pop()

    def remove(self, idx: int) -> None:
        del self.lru[idx]
        self._table_remove(idx, self.buckets_of[idx])
        del self.emb[idx], self.results[idx], self.buckets_of[idx]
        self.free.append(idx)

    def _evict_lru(self) -> None:
        idx, _ = self.lru.popitem(last=False)
        self.lru[idx] = None  # transient re-add so remove() can pop it
        self.remove(idx)

    def _insert_hashed(self, emb: np.ndarray, result: Any,
                       buckets: np.ndarray) -> int:
        while len(self.lru) >= self.capacity > 0:
            self._evict_lru()
        idx = self._alloc()
        self.emb[idx] = emb
        self.results[idx] = result
        self.buckets_of[idx] = buckets
        self._table_add(idx, buckets)
        self.lru[idx] = None
        self.inserts += 1
        return idx

    def insert(self, embedding: np.ndarray, result: Any) -> int:
        emb = normalize(np.asarray(embedding, np.float32).reshape(-1))
        return self._insert_hashed(emb, result, self.lsh.hash_one(emb))

    def insert_batch(self, embeddings: np.ndarray, results: List[Any],
                     buckets: Optional[np.ndarray] = None) -> List[int]:
        embs = normalize(np.atleast_2d(np.asarray(embeddings, np.float32)))
        if buckets is None:  # preserved admission buckets (migration landing)
            buckets = np.asarray(self.lsh.hash_batch(embs))
        return [self._insert_hashed(e, r, np.asarray(b))
                for e, r, b in zip(embs, results, buckets)]

    # ------------------------------------------------------------- migration
    def ids_in_bucket_range(self, lo: int, hi: int) -> List[int]:
        t = self.params.num_tables
        return [i for i in self.lru
                if 2 * sum(1 for b in self.buckets_of[i]
                           if lo <= int(b) <= hi) > t]

    def extract(self, ids: List[int]
                ) -> Tuple[np.ndarray, List[Any], np.ndarray]:
        embs = np.stack([self.emb[i] for i in ids])
        res = [self.results[i] for i in ids]
        bks = np.stack([np.asarray(self.buckets_of[i], np.int64)
                        for i in ids])
        for i in ids:
            self.remove(i)
        return embs, res, bks

    # ---------------------------------------------------------------- query
    def best(self, embedding: np.ndarray
             ) -> Optional[Tuple[List[int], np.ndarray]]:
        """Candidates (ascending unique) + their similarities, or None."""
        emb = normalize(np.asarray(embedding, np.float32).reshape(-1))
        probes = self.lsh.probe_one(emb)  # (T, P)
        cand = sorted({i for t in range(self.params.num_tables)
                       for b in probes[t] for i in self.slots[t][int(b)]})
        if not cand:
            return None
        rows = np.stack([self.emb[i] for i in cand])
        return cand, self.sim(emb, rows)


def _assert_state(store: ReuseStore, model: RefStore) -> None:
    """Full structural parity: tables, LRU order, counters, page rows."""
    assert len(store) == len(model.lru)
    assert store.live_ids() == list(model.lru)
    assert store.overflows == model.overflows
    assert store.inserts == model.inserts
    assert store.queries == model.queries
    assert store.candidate_counts == model.candidate_counts
    t_n, nb = store.params.num_tables, store.params.num_buckets
    for t in range(t_n):
        for b in range(nb):
            row = model.slots[t][b]
            f = int(store._fill[t, b])
            assert f == len(row), (t, b)
            assert store._slots[t, b, :f].tolist() == row, (t, b)
            assert (store._slots[t, b, f:] == -1).all(), (t, b)
            if f == store.bucket_cap:  # cursor only meaningful at capacity
                assert int(store._cursor[t, b]) == model.cursor[t][b], (t, b)
    # paged rows: live slots hold the model's embeddings bit-exactly,
    # released slots are tombstoned to zero
    live = set(model.lru)
    for idx in range(store._n_slots):
        if idx in live:
            assert (store.embedding_of(idx) == model.emb[idx]).all()
        else:
            assert not store.embedding_of(idx).any(), idx


def _check_query(store: ReuseStore, model: RefStore, emb: np.ndarray,
                 thr: float, out: Tuple[Any, float, Optional[int]],
                 peek: bool = False) -> None:
    """One query's parity vs the model; adopts the store's decision inside
    the +-SIM_TOL tie/threshold margin so float noise can't cascade."""
    res, sim, idx = out
    m = model.best(emb)
    if not peek:
        model.queries += 1
        model.candidate_counts.append(0 if m is None else len(m[0]))
    if m is None:
        assert idx is None and sim == -1.0 and res is None
        return
    cand, sims = m
    best = int(np.argmax(sims))
    want_sim = float(sims[best])
    assert abs(sim - want_sim) < SIM_TOL, (sim, want_sim)
    tie = (np.sort(sims)[-2] > want_sim - SIM_TOL) if len(cand) > 1 else False
    if idx is not None:
        assert sim >= thr - SIM_TOL
        if not tie:
            assert idx == cand[best]
        assert res == model.results[idx]
        if not peek:
            model.lru.move_to_end(idx)
    else:
        assert want_sim < thr + SIM_TOL


def run_interleaving(seed: int, kernel: bool = False,
                     fused: bool = False) -> None:
    """One random op interleaving, store vs model, state-checked per op.

    ``kernel=True`` routes every batched score through the staged
    ``reuse_top1`` device path; ``fused=True`` routes every ``query_batch``
    through the one-dispatch ``reuse_query_top1`` pipeline (device slot
    tables + paged buffer), checking hit/miss, similarity, tie-break,
    tombstone, and LRU parity against the RefStore per op.
    """
    rng = np.random.default_rng(seed)
    params = LSHParams(dim=DIM, num_tables=int(rng.integers(2, 4)),
                       num_probes=4, num_buckets=32,
                       seed=int(rng.integers(1 << 16)))
    capacity = int(rng.integers(6, 24))
    bucket_cap = int(rng.integers(2, 5))
    page_size = int(rng.choice([4, 8, 16]))
    store = ReuseStore(
        params, capacity=capacity, bucket_cap=bucket_cap,
        page_size=page_size,
        use_kernel_threshold=1 if (kernel or fused) else 1 << 30,
        fused=fused, fused_min_batch=1 if fused else 64)
    model = RefStore(params, capacity, bucket_cap)
    inserted: List[np.ndarray] = []
    uid = 0

    def vec() -> np.ndarray:
        if inserted and rng.random() < 0.5:  # near-dup of a previous insert
            base = inserted[int(rng.integers(len(inserted)))]
            return normalize(base + 0.05 * rng.standard_normal(DIM)
                             .astype(np.float32))
        return normalize(rng.standard_normal(DIM).astype(np.float32))

    n_ops = 18 if (kernel or fused) else 30
    for _ in range(n_ops):
        op = rng.choice(["insert", "insert_batch", "query", "query_batch",
                         "remove", "migrate"],
                        p=[0.27, 0.18, 0.13, 0.22, 0.08, 0.12])
        if op == "insert":
            v = vec()
            inserted.append(v)
            got = store.insert(v, f"r{uid}")
            want = model.insert(v, f"r{uid}")
            assert got == want
            uid += 1
        elif op == "insert_batch":
            n = int(rng.integers(1, 6))
            vs = np.stack([vec() for _ in range(n)])
            inserted.extend(vs)
            res = [f"r{uid + i}" for i in range(n)]
            uid += n
            assert store.insert_batch(vs, res) == model.insert_batch(vs, res)
        elif op == "query":
            v, thr = vec(), float(rng.choice([0.0, 0.5, 0.9, 0.97]))
            _check_query(store, model, v, thr, store.query(v, thr))
        elif op == "query_batch":
            n = int(rng.integers(1, 6))
            vs = np.stack([vec() for _ in range(n)])
            thrs = rng.choice([0.0, 0.5, 0.9, 0.97], n).astype(np.float32)
            peek = bool(rng.random() < 0.2)
            outs = store.query_batch(vs, thrs, peek=peek)
            for v, t, out in zip(vs, thrs, outs):
                _check_query(store, model, v, float(t), out, peek=peek)
        elif op == "remove":
            live = store.live_ids()
            if live:
                idx = int(live[int(rng.integers(len(live)))])
                store.remove(idx)
                model.remove(idx)
        elif op == "migrate":
            # bucket-granular extract + preserved-bucket landing (ISSUE 8):
            # select a range by per-entry majority vote, tombstone it out,
            # then land the export back with its admission-time buckets —
            # the same op sequence a cross-EN migration runs, state-checked
            # at both the post-extract and post-landing instants
            nb = params.num_buckets
            lo = int(rng.integers(0, nb))
            hi = int(rng.integers(lo, nb))
            ids = store.ids_in_bucket_range(lo, hi)
            assert ids == model.ids_in_bucket_range(lo, hi)
            if ids:
                exp = store.extract(ids)
                m_embs, m_res, m_bks = model.extract(ids)
                assert exp.ids == ids
                assert (exp.embeddings == m_embs).all()
                assert exp.results == m_res
                assert (exp.buckets == m_bks).all()
                _assert_state(store, model)
                got = store.insert_batch(exp.embeddings, exp.results,
                                         buckets=exp.buckets)
                want = model.insert_batch(m_embs, m_res, buckets=m_bks)
                assert got == want
        _assert_state(store, model)


class TestStoreProperties:
    """>= 200 random interleavings on the exact numpy scoring path, plus a
    paged-device-kernel subset (acceptance: ISSUE 3)."""

    @pytest.mark.parametrize("seed", range(200))
    def test_interleaving_parity(self, seed):
        run_interleaving(seed)

    @pytest.mark.parametrize("seed", range(8))
    def test_interleaving_parity_kernel_path(self, seed):
        # use_kernel_threshold=1: every batched score runs the staged
        # reuse_top1 kernel against the paged device buffer
        run_interleaving(1000 + seed, kernel=True)

    @pytest.mark.parametrize("seed", range(200))
    def test_interleaving_parity_fused(self, seed):
        # fused_min_batch=1 + use_kernel_threshold=1: every query_batch is
        # one reuse_query_top1 dispatch over the device slot tables + paged
        # buffer (ISSUE 7 acceptance: hit/miss, similarity, tie-break,
        # tombstone and LRU parity on the 200-seed harness)
        run_interleaving(2000 + seed, fused=True)

    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_interleaving_parity_hypothesis(self, seed):
        run_interleaving(seed)

    def test_remove_unknown_raises(self):
        store = ReuseStore(LSHParams(dim=DIM, num_tables=2, num_buckets=32),
                           capacity=8)
        with pytest.raises(KeyError):
            store.remove(3)


class TestRingOverflowRecall:
    """First executable slice of the ROADMAP recall study: measured recall
    vs a brute-force oracle under ring overflow, with the ``overflows``
    counter pinned to the analytic displacement count."""

    def _overflowed_store(self, n=600, bucket_cap=4):
        params = LSHParams(dim=32, num_tables=4, num_probes=8,
                           num_buckets=64, seed=9)
        store = ReuseStore(params, capacity=10 * n, bucket_cap=bucket_cap)
        X = normalize(np.random.default_rng(17).standard_normal(
            (n, 32)).astype(np.float32))
        buckets = np.asarray(store.lsh.hash_batch(X))
        store.insert_batch(X, list(range(n)), buckets=buckets)
        return store, X, buckets

    def test_overflows_match_analytic_count(self):
        store, X, buckets = self._overflowed_store()
        want = 0
        for t in range(store.params.num_tables):
            _, counts = np.unique(buckets[:, t], return_counts=True)
            want += int(np.maximum(counts - store.bucket_cap, 0).sum())
        assert want > 0, "scenario must actually overflow"
        assert store.overflows == want

    def _recall(self, store, X):
        out = store.query_batch(X, 0.0, peek=True)
        # brute-force oracle over the full store (all rows live, normalized)
        rows = np.stack([store.embedding_of(i) for i in range(len(X))])
        oracle = np.argmax(X @ rows.T, axis=1)
        got = np.asarray([-1 if idx is None else idx for _, _, idx in out])
        return float((got == oracle).mean())

    def test_self_query_recall_above_pinned_floors(self):
        """Recall vs bucket_cap under ring overflow (ROADMAP recall study).

        Ring overflow drops one table pointer per displaced item; a
        displaced entry stays findable only through its other tables, so
        recall degrades as overflow pressure grows.  The seeded sweep
        measures 0.38 / 0.65 / 0.95 / 1.0 at caps 2/4/8/16 — the floors pin
        that curve so a stale-candidate or broken-ring regression (which
        craters recall) fails loudly.
        """
        recalls = {}
        for cap in (2, 4, 8, 16):
            store, X, _ = self._overflowed_store(bucket_cap=cap)
            recalls[cap] = self._recall(store, X)
        assert recalls[2] >= 0.30, recalls
        assert recalls[4] >= 0.60, recalls
        assert recalls[8] >= 0.90, recalls
        assert recalls[16] >= 0.98, recalls
        caps = sorted(recalls)
        assert all(recalls[a] <= recalls[b] + 0.02
                   for a, b in zip(caps, caps[1:])), recalls

    def test_scalar_batch_overflow_parity(self):
        """Grouped-scatter inserts overflow exactly like the scalar loop."""
        params = LSHParams(dim=32, num_tables=3, num_probes=4,
                           num_buckets=32, seed=4)
        a = ReuseStore(params, capacity=4096, bucket_cap=2)
        b = ReuseStore(params, capacity=4096, bucket_cap=2)
        X = normalize(np.random.default_rng(3).standard_normal(
            (300, 32)).astype(np.float32))
        for i, v in enumerate(X):
            a.insert(v, i)
        b.insert_batch(X, list(range(300)))
        assert a.overflows == b.overflows > 0
        assert (a._slots == b._slots).all()


class TestMigrationParity:
    """ISSUE 8 acceptance: a migrated bucket range answers queries
    bit-identically to a store built fresh at the destination from the same
    entries — including through the fused one-dispatch kernel path — and
    the tombstoned source pages sync clean."""

    P = LSHParams(dim=DIM, num_tables=3, num_probes=4, num_buckets=32,
                  seed=11)

    def _fresh(self, **kw):
        # bucket_cap sized so 300 entries do NOT ring-overflow: displacement
        # would evict entries from their own tables and the self-query
        # assertions below would measure overflow, not migration fidelity
        return ReuseStore(self.P, capacity=4096, bucket_cap=32, page_size=16,
                          **kw)

    def _warm_src(self, n=300, **kw):
        src = self._fresh(**kw)
        X = normalize(np.random.default_rng(21).standard_normal(
            (n, DIM)).astype(np.float32))
        src.insert_batch(X, [f"r{i}" for i in range(n)])
        return src, X

    def test_migrated_range_answers_bit_identically(self):
        src, X = self._warm_src()
        ids = src.ids_in_bucket_range(8, 23)
        assert len(ids) > 20, "range must select a real slice"
        exp = src.extract(ids)
        # destination that received the migrated slice...
        dst = self._fresh()
        dst.insert_batch(exp.embeddings, exp.results, buckets=exp.buckets)
        # ...vs a store built fresh at the destination from the same entries
        fresh = self._fresh()
        fresh.insert_batch(exp.embeddings, exp.results, buckets=exp.buckets)
        # table state, LRU order, and page rows are bit-identical
        assert (dst._slots == fresh._slots).all()
        assert (dst._fill == fresh._fill).all()
        assert dst.live_ids() == fresh.live_ids()
        for i in dst.live_ids():
            assert (dst.embedding_of(i) == fresh.embedding_of(i)).all()
            assert dst.result_of(i) == fresh.result_of(i)
        # and every query answers bit-identically (scalar staged path)
        for q in X[:64]:
            assert dst.query(q, 0.9) == fresh.query(q, 0.9)

    def test_migrated_range_fused_path_parity(self):
        src, X = self._warm_src()
        ids = src.ids_in_bucket_range(0, 15)
        exp = src.extract(ids)
        kw = dict(use_kernel_threshold=1, fused=True, fused_min_batch=1)
        dst = self._fresh(**kw)
        dst.insert_batch(exp.embeddings, exp.results, buckets=exp.buckets)
        fresh = self._fresh(**kw)
        fresh.insert_batch(exp.embeddings, exp.results, buckets=exp.buckets)
        got = dst.query_batch(X, 0.9)
        want = fresh.query_batch(X, 0.9)
        assert got == want
        assert any(idx is not None for _, _, idx in got), "slice must hit"

    def test_source_tombstones_survive_fused_requery(self):
        """After extract, the source's fused path (device mirrors synced
        O(dirty)) must stop answering the migrated entries."""
        src, X = self._warm_src(use_kernel_threshold=1, fused=True,
                                fused_min_batch=1)
        # make both mirrors device-resident BEFORE the extract, so the
        # post-extract sync exercises the dirty-page/slab tombstone path
        src.query_batch(X[:4], 0.99)
        ids = src.ids_in_bucket_range(8, 23)
        id_set = set(ids)
        exp = src.extract(ids)
        assert src.sync_device() >= 1  # tombstoned pages actually uploaded
        outs = src.query_batch(exp.embeddings, 0.999)
        for (_, _, idx), eid in zip(outs, exp.ids):
            assert idx != eid, "extracted slot still answering"
            assert idx is None or idx not in id_set
        # survivors outside the range still answer exactly
        rest = src.live_ids()
        if rest:
            q = np.stack([src.embedding_of(i) for i in rest[:8]])
            outs = src.query_batch(q, 0.999)
            assert all(idx == want for (_, _, idx), want
                       in zip(outs, rest[:8]))

    def test_export_is_pure_read(self):
        src, X = self._warm_src()
        before = src.live_ids()
        ids = src.ids_in_bucket_range(0, 31)
        exp = src.export(ids)
        assert src.live_ids() == before
        assert len(exp) == len(ids)
        # export copies: tombstoning the source later can't corrupt it
        row0 = exp.embeddings[0].copy()
        src.remove(exp.ids[0])
        assert (exp.embeddings[0] == row0).all()

    def test_export_dead_slot_raises(self):
        src, _ = self._warm_src(n=10)
        idx = src.live_ids()[0]
        src.remove(idx)
        with pytest.raises(KeyError):
            src.export([idx])
        with pytest.raises(KeyError):
            src.buckets_of(idx)


class TestTombstone:
    """remove()/evict must clear the entry's page rows (host + device) so a
    stale embedding can never win a top-1 tie after slot-id reuse."""

    P = LSHParams(dim=32, num_tables=3, num_probes=6, num_buckets=64, seed=5)

    def test_remove_zeroes_row_and_dirties_page(self):
        store = ReuseStore(self.P, capacity=64, page_size=8)
        v = normalize(np.random.default_rng(0).standard_normal(32)
                      .astype(np.float32))
        idx = store.insert(v, "r")
        store.sync_device(ensure=True)
        assert store.last_sync_pages == 1
        store.remove(idx)
        assert not store.embedding_of(idx).any()
        assert idx // store.page_size in store._dirty
        store.sync_device()
        page, off = idx // store.page_size, idx % store.page_size
        assert not np.asarray(store._emb_dev[page, off]).any()

    def test_eviction_tombstones_like_remove(self):
        store = ReuseStore(self.P, capacity=4, page_size=4)
        X = normalize(np.random.default_rng(1).standard_normal(
            (12, 32)).astype(np.float32))
        for i, v in enumerate(X):
            store.insert(v, i)
        live = set(store.live_ids())
        for idx in range(store._n_slots):
            if idx not in live:
                assert not store.embedding_of(idx).any(), idx

    def test_reused_slot_serves_new_embedding_through_kernel(self):
        """Device-resident regression: after remove + slot reuse, the kernel
        must score the new embedding, not the stale device row."""
        store = ReuseStore(self.P, capacity=64, page_size=8,
                           use_kernel_threshold=1)
        rng = np.random.default_rng(2)
        v = normalize(rng.standard_normal(32).astype(np.float32))
        idx = store.insert(v, "old")
        [out] = store.query_batch(v[None], 0.9)   # device-resident now
        assert out[2] == idx
        store.remove(idx)
        w = normalize(rng.standard_normal(32).astype(np.float32))
        idx2 = store.insert(w, "new")
        assert idx2 == idx  # slot id reused (LIFO free list)
        [out] = store.query_batch(w[None], 0.9)
        assert out[0] == "new" and out[1] > 0.999 and out[2] == idx2
        # the removed embedding no longer hits anywhere near sim 1.0
        [out] = store.query_batch(v[None], 0.9)
        assert out[2] is None
