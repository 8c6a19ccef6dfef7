"""Async serving sweep: offered load x batch window x straggler rate.

Drives the event-driven ``AsyncServingEngine`` on its virtual clock with
Poisson arrivals and measures, per configuration:

  * wall-clock processing throughput (requests / wall second — the batching
    win: one ``query_batch`` + one model batch per flush window), and
  * virtual-clock latency vs the per-request deadline (p99, miss fraction)
    with TTC-driven straggler re-dispatch repairing the injected tail.

The ``sync/submit_loop`` baseline runs the same trace one blocking
``ServingFleet.submit`` at a time (batches of 1 through the same pipeline).
Acceptance (ISSUE 2): async throughput >= the sync submit loop at batch
window >= 8 on the same trace.  Since PR 3 the sync/async reps are
*interleaved* (contention bursts hit both sides of the ratio); interleaved
recordings on the shared box measure ~1.0-1.3x — the original 2x recording
had the one-shot sync baseline land in a slow burst.  Per-request wall time
*improved* across the board in the same re-measurement.

The ``growth`` rows (ISSUE 3) run a miss-heavy trace over prefilled
40k-entry replica stores: every flush executes + commits, so the paged
store's O(dirty pages) commit-path sync is compared against the emulated
pre-paging full re-upload (``full_resync``) at identical virtual behaviour.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import Row
from repro.core.lsh import LSHParams, normalize
from repro.serving import AsyncServingEngine, ReplicaEngine, ServeRequest, ServingFleet
from repro.training.elastic import BackupPolicy

DIM = 32
N_REQUESTS = 600
N_REPLICAS = 3
DEADLINE_S = 0.25
BASE_EXEC_S = 0.08          # per-request execution cost (paper: 70-100 ms)
STRAGGLER_FACTOR = 8.0      # a straggling dispatch takes 8x the base time
LOADS_HZ = (200.0, 1000.0)
BATCH_SIZES = (1, 8, 32)
STRAGGLER_RATES = (0.0, 0.1)


def _max_wait_s(max_batch: int, load_hz: float) -> float:
    """Flush window sized to actually gather ~max_batch arrivals at the
    offered load, capped at a quarter of the deadline budget."""
    if max_batch == 1:
        return 0.001
    return min(DEADLINE_S / 4, max_batch / load_hz)


def _trace(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    base = normalize(rng.standard_normal((24, DIM)).astype(np.float32))
    embs = normalize(base[rng.integers(0, 24, n)]
                     + 0.04 * rng.standard_normal((n, DIM)).astype(np.float32)
                     / np.sqrt(DIM))
    return [ServeRequest(i, "svc", embs[i], threshold=0.9,
                         deadline_s=DEADLINE_S) for i in range(n)]


def _execute(reqs):
    return [round(float(np.sum(np.asarray(r.embedding))), 5) for r in reqs]


def _exec_time_fn(straggler_rate: float, seed: int):
    rng = np.random.default_rng(seed)

    def fn(rid, service, reqs):
        per_req = BASE_EXEC_S * (1 + 0.2 * rng.random())
        if straggler_rate > 0 and rng.random() < straggler_rate:
            per_req *= STRAGGLER_FACTOR
        # sub-linear batch scaling: the model batch amortizes
        return per_req * max(1.0, len(reqs)) ** 0.5

    return fn


def _replicas(params):
    """Warm fleet: replicas carry TTC statistics (production steady state),
    so straggler backup timers are armed from the first dispatch."""
    reps = [ReplicaEngine(i, params, _execute) for i in range(N_REPLICAS)]
    for r in reps:
        r.ttc.observe("svc", BASE_EXEC_S)
    return reps


N_REPS = 5  # best-of wall times: the box is noisy (~2x jitter), virtual
            # metrics are deterministic per seed, so only the wall measure
            # needs reps; sync/async reps are interleaved so contention
            # bursts hit both sides of the speedup ratio

GROWTH_PREFILL = 40_000   # per-replica store size at scenario start
GROWTH_REQS = 256         # unique arrivals: every flush executes + commits


def _growth_rows() -> list:
    """Store-growth scenario (ISSUE 3): production-size stores under a
    miss-heavy trace, so every batch window executes and commits inserts.

    With paged device residency the commit-path sync uploads only the dirty
    pages; the ``full`` rows flip ``full_resync`` to emulate the pre-paging
    whole-matrix re-upload on every commit.  Virtual p99 is sync-invariant
    (uploads are wall cost), so the win shows up as wall-clock time per
    request — the stall the async engine would otherwise surface as p99
    under real load.
    """
    rows: list[Row] = []
    params = LSHParams(dim=DIM, num_tables=5, num_probes=8, seed=7)
    rng = np.random.default_rng(9)
    prefill = normalize(rng.standard_normal(
        (GROWTH_PREFILL, DIM)).astype(np.float32))
    # unique, spread-out arrivals: near-zero reuse at threshold 0.99
    uniq = normalize(rng.standard_normal((GROWTH_REQS, DIM)).astype(np.float32))
    reqs = [ServeRequest(i, "svc", uniq[i], threshold=0.99,
                         deadline_s=DEADLINE_S) for i in range(GROWTH_REQS)]
    def _arm(mode: str, n: int):
        """One fresh fleet + prefill + drained trace of ``n`` requests."""
        reps = _replicas(params)
        for r in reps:
            st = r._store("svc")
            st.full_resync = mode == "full"
            for lo in range(0, GROWTH_PREFILL, 8192):
                st.insert_batch(prefill[lo:lo + 8192],
                                list(range(lo, min(lo + 8192, GROWTH_PREFILL))))
            st.sync_device(ensure=True)  # resident before the trace starts
        eng = AsyncServingEngine(
            params, reps, max_batch=16, max_wait_s=16 / 500.0,
            exec_time_fn=_exec_time_fn(0.0, seed=4))
        arrivals = np.cumsum(
            np.random.default_rng(6).exponential(1.0 / 500.0, n))
        futs = [eng.submit_at(t, r) for t, r in zip(arrivals, reqs[:n])]
        t0 = time.perf_counter()
        eng.drain()
        return (time.perf_counter() - t0,
                [r._store("svc") for r in reps], futs)

    # untimed warmup pass absorbs the one-time jit compiles (prefill hash
    # shapes, reuse_top1, page updater) shared by both arms; the timed
    # arms then run interleaved best-of-N so a contention burst hits both
    # sides of the speedup ratio (same idiom as the sweep above)
    _arm("paged", GROWTH_REQS // 4)
    best = {"full": float("inf"), "paged": float("inf")}
    last: dict = {}
    for _ in range(N_REPS):
        for mode in ("full", "paged"):
            wall, stores, futs = _arm(mode, GROWTH_REQS)
            best[mode] = min(best[mode], wall)
            last[mode] = (stores, futs)  # counters/latencies: same every rep
    for mode in ("full", "paged"):
        stores, futs = last[mode]
        pages = sum(s.sync_pages_total for s in stores)
        mb = sum(s.sync_bytes_total for s in stores) / 2**20
        p99 = float(np.percentile(
            [f.result.latency_s for f in futs], 99))
        rows.append((
            f"async_serving/growth/{mode}", best[mode] / GROWTH_REQS * 1e6,
            f"store{GROWTH_PREFILL}/replica miss-heavy trace, wall best-of-"
            f"{N_REPS} interleaved;sync_pages={pages};sync_mb={mb:.0f};"
            f"wall_speedup_vs_full={best['full'] / best[mode]:.2f}x;"
            f"p99_virtual_ms={p99 * 1e3:.1f}"))
    return rows


def run() -> list:
    rows: list[Row] = []
    params = LSHParams(dim=DIM, num_tables=5, num_probes=8, seed=7)
    reqs = _trace(N_REQUESTS)
    configs = [(load, mb, srate) for load in LOADS_HZ
               for mb in BATCH_SIZES for srate in STRAGGLER_RATES]

    # Sync baseline and async sweep run with *interleaved* reps (same idiom
    # as reuse_store_scale): bursty CPU contention on this shared box hits
    # both sides of every speedup ratio instead of whichever side happened
    # to run during the burst, and best-of-reps drops the jit-compile rep.
    sync_wall = float("inf")
    best = {cfg: float("inf") for cfg in configs}
    last: dict = {}
    for _ in range(N_REPS):
        fleet = ServingFleet(params, _replicas(params))
        fleet.engine.exec_time_fn = _exec_time_fn(0.0, seed=1)
        t0 = time.perf_counter()
        for r in reqs:
            fleet.submit(r)
        sync_wall = min(sync_wall, time.perf_counter() - t0)
        for cfg in configs:
            load, max_batch, srate = cfg
            eng = AsyncServingEngine(
                params, _replicas(params),
                backup=BackupPolicy(factor=1.5, max_backups=1),
                max_batch=max_batch,
                max_wait_s=_max_wait_s(max_batch, load),
                exec_time_fn=_exec_time_fn(srate, seed=2))
            rng = np.random.default_rng(3)
            arrivals = np.cumsum(rng.exponential(1.0 / load, N_REQUESTS))
            futs = [eng.submit_at(t, r) for t, r in zip(arrivals, reqs)]
            t0 = time.perf_counter()
            makespan = eng.drain()
            best[cfg] = min(best[cfg], time.perf_counter() - t0)
            last[cfg] = (eng, futs, makespan)  # virtual metrics: same every rep

    sync_tput = N_REQUESTS / sync_wall
    rows.append(("async_serving/sync/submit_loop", sync_wall / N_REQUESTS * 1e6,
                 f"best-of-{N_REPS}, throughput={sync_tput:.0f}req/s_wall"))
    for cfg in configs:
        load, max_batch, srate = cfg
        eng, futs, makespan = last[cfg]
        wall = best[cfg]
        lats = np.asarray([f.result.latency_s for f in futs])
        miss = float(np.mean(lats > DEADLINE_S))
        p99 = float(np.percentile(lats, 99))
        s = eng.stats()
        tput = N_REQUESTS / wall
        rows.append((
            f"async_serving/load{load:.0f}/batch{max_batch}/strag{srate}",
            wall / N_REQUESTS * 1e6,
            f"best-of-{N_REPS}, throughput={tput:.0f}req/s_wall;"
            f"speedup_vs_sync={tput / sync_tput:.2f}x;"
            f"makespan_s={makespan:.2f};"
            f"p99_ms={p99 * 1e3:.1f};deadline_miss_pct={miss * 100:.1f};"
            f"backups={s['backups']};backup_wins={s['backup_wins']};"
            f"executed={s['executed']};en={s['en']};cs={s['cs']};"
            f"aggregated={s['aggregated']}"))
    rows.extend(_growth_rows())
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.2f},{derived}")
