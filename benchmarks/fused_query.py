"""One-dispatch fused reuse query vs the host-staged pipeline (ISSUE 7).

Sweeps store size x batch size and compares, on the *same* store,

  * ``staged`` — the PR-1 pipeline: one ``probe_batch`` dispatch, a host
    candidate-matrix build (two ``np.nonzero`` passes + per-row sort/unique),
    then the ``gathered_top1`` kernel dispatch, and
  * ``fused``  — ``ReuseStore._query_fused``: hash -> multi-probe -> device
    slot-table gather -> masked cosine top-1 -> candidate counting in a
    single jit dispatch over the device mirrors (``ops.reuse_query_top1``).

Arms are toggled via ``store.fused`` on one store and interleaved rep-by-rep
(best-of), with ``peek=True`` queries so neither arm perturbs LRU order or
statistics and both see bit-identical store state.  The derived column
records speedup, fused dispatch count per call, retrace count across the
timed reps (must be 0 on the hot path) and sync pages (must be 0 0: mirrors
are steady-state).

Acceptance (ISSUE 7): >= 3x per-task speedup at batch >= 1024 on a
>= 100k-entry store.

``python -m benchmarks.fused_query --smoke`` runs a fast self-check used by
CI: ~20k-entry store, one 512-task batch, asserts staged/fused result
parity, that the fused path actually engaged, and exactly one device
dispatch per ``query_batch`` call.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import Row
from repro.core import LSHParams, ReuseStore, normalize
from repro.kernels import fused_query as fused_mod
from repro.kernels import ops

STORE_SIZES = (10_000, 100_000, 250_000)
BATCH_SIZES = (256, 1024, 4096, 10_000)
DIM = 64
N_REPS = 5


def _time_us(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return (time.perf_counter() - t0) * 1e6


def _make_store(n_store: int, seed: int = 0) -> tuple[ReuseStore, np.ndarray]:
    # FALCONN convention (~N buckets) keeps per-bucket fill — and with it the
    # fused candidate width T*P*cap — small relative to the store.
    p = LSHParams(dim=DIM, num_tables=5, num_probes=8, num_buckets=16384,
                  family="hyperplane", seed=11)
    store = ReuseStore(p, capacity=n_store + 1)
    rng = np.random.default_rng(seed)
    X = normalize(rng.standard_normal((n_store, DIM)).astype(np.float32))
    for lo in range(0, n_store, 8192):
        store.insert_batch(X[lo:lo + 8192],
                           list(range(lo, min(lo + 8192, n_store))))
    return store, X


def _queries(X: np.ndarray, n: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return normalize(X[:n] + 0.05 * rng.standard_normal(
        (n, DIM)).astype(np.float32) / np.sqrt(DIM))


def run(n_reps: int = N_REPS) -> list:
    rows: list[Row] = []
    for n_store in STORE_SIZES:
        store, X = _make_store(n_store)
        queries = _queries(X, max(BATCH_SIZES))
        width = (store.params.num_tables * store.params.num_probes
                 * store.bucket_cap)
        # Warmup both arms at every batch size (jit compiles + both device
        # mirrors), then interleave staged/fused reps so bursty CPU
        # contention hits both sides of the ratio; best-of is the stable
        # capability measure.  peek=True freezes LRU/stats between arms.
        def arm(b: int, fused: bool):
            qb = queries[:b]

            def _fn():
                store.fused = fused
                store.query_batch(qb, 0.8, peek=True)
            return _fn

        fns = {(b, f): arm(b, f) for b in BATCH_SIZES for f in (False, True)}
        for fn in fns.values():
            fn()
        traces0 = fused_mod.FUSED_TRACE_COUNT
        best = {k: float("inf") for k in fns}
        for _ in range(n_reps):
            for k, fn in fns.items():
                best[k] = min(best[k], _time_us(fn))
        retraces = fused_mod.FUSED_TRACE_COUNT - traces0
        d0 = ops.FUSED_DISPATCH_COUNT
        fns[(BATCH_SIZES[0], True)]()
        dispatches = ops.FUSED_DISPATCH_COUNT - d0
        store.fused = True
        for b in BATCH_SIZES:
            us_s = best[(b, False)] / b
            us_f = best[(b, True)] / b
            rows.append((f"fused_query/staged/batch{b}/store{n_store}", us_s,
                         f"per-task best-of-{n_reps}, probe+host-matrix+"
                         f"gather kernel"))
            rows.append((f"fused_query/fused/batch{b}/store{n_store}", us_f,
                         f"per-task best-of-{n_reps}, speedup "
                         f"{us_s / us_f:.1f}x;dispatches_per_call="
                         f"{dispatches};retraces_timed={retraces};"
                         f"sync_pages={store.last_sync_pages} "
                         f"{store.last_table_sync_pages};"
                         f"cand_width={width}"))
    return rows


def smoke() -> None:
    """CI self-check: parity + one-dispatch on a small store (seconds)."""
    store, X = _make_store(20_000)
    q = _queries(X, 512)
    store.fused = False
    staged = store.query_batch(q, 0.8, peek=True)
    store.fused = True
    assert store._use_fused(len(q)), "fused path did not engage"
    store.query_batch(q, 0.8, peek=True)  # warm: compiles + mirror uploads
    d0, t0 = ops.FUSED_DISPATCH_COUNT, fused_mod.FUSED_TRACE_COUNT
    fused = store.query_batch(q, 0.8, peek=True)
    assert ops.FUSED_DISPATCH_COUNT - d0 == 1, "hot path must be 1 dispatch"
    assert fused_mod.FUSED_TRACE_COUNT == t0, "hot path must not retrace"
    assert store.last_sync_pages == 0 and store.last_table_sync_pages == 0
    mismatch = sum(a[2] != b[2] or abs(a[1] - b[1]) > 1e-4
                   for a, b in zip(staged, fused))
    assert mismatch == 0, f"{mismatch} staged/fused result mismatches"
    hits = sum(r[2] is not None for r in fused)
    print(f"fused_query smoke ok: 512 tasks, {hits} hits, parity exact, "
          f"1 dispatch, 0 retraces, 0 sync pages")


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        smoke()
    else:
        for name, us, derived in run():
            print(f"{name},{us:.2f},{derived}")
