"""Async serving core: event loop, futures, deadline batching, stragglers.

ISSUE 2 acceptance: async-vs-sync parity on a >=500-request trace (same
hits, similarities, stats), virtual-clock straggler tests (backup fires,
first-result-wins, no double insert), Batcher deadline inheritance, and the
satellite fixes (vectorized insert scatter, forwarding-oracle peek,
follower latency accounting).
"""
import numpy as np
import pytest

from repro.core.lsh import LSHParams, normalize
from repro.core.reuse_store import ReuseStore
from repro.core.sim_clock import EventLoop, Future
from repro.serving import (
    AsyncServingEngine,
    Batcher,
    ReplicaEngine,
    ServeRequest,
    ServingFleet,
)
from repro.training.elastic import BackupPolicy

P = LSHParams(dim=32, num_tables=3, num_probes=6, seed=5)


def _vecs(n, seed=0, d=32):
    return normalize(np.random.default_rng(seed).standard_normal((n, d)))


def _execute(reqs):
    return [f"r{r.request_id}" for r in reqs]


def _clustered_trace(n, n_clusters=20, seed=3, noise=0.04):
    rng = np.random.default_rng(seed)
    base = _vecs(n_clusters, seed=seed + 1)
    embs = normalize(base[rng.integers(0, n_clusters, n)]
                     + noise * rng.standard_normal((n, 32)) / np.sqrt(32))
    return [ServeRequest(i, "svc", embs[i], threshold=0.9) for i in range(n)]


# --------------------------------------------------------------- event loop
class TestEventLoop:
    def test_ordering_and_clock(self):
        loop = EventLoop()
        seen = []
        loop.at(2.0, seen.append, "b")
        loop.at(1.0, seen.append, "a")
        loop.at(2.0, seen.append, "c")  # same time: insertion order
        assert loop.run() == 2.0
        assert seen == ["a", "b", "c"]

    def test_timer_cancel(self):
        loop = EventLoop()
        seen = []
        t = loop.at(1.0, seen.append, "x")
        loop.at(2.0, seen.append, "y")
        t.cancel()
        loop.run()
        assert seen == ["y"]

    def test_run_until(self):
        loop = EventLoop()
        seen = []
        loop.at(1.0, seen.append, 1)
        loop.at(5.0, seen.append, 5)
        loop.run(until=2.0)
        assert seen == [1] and len(loop) == 1

    def test_nested_scheduling(self):
        loop = EventLoop()
        seen = []
        loop.at(1.0, lambda: loop.call_later(0.5, seen.append, "late"))
        loop.run()
        assert seen == ["late"] and loop.now == 1.5

    def test_future_first_result_wins(self):
        fut = Future()
        got = []
        fut.add_done_callback(lambda f: got.append(f.result))
        assert fut.try_set_result("first", now=1.0)
        assert not fut.try_set_result("second", now=2.0)
        assert fut.result == "first" and fut.resolved_at == 1.0
        assert got == ["first"]
        with pytest.raises(RuntimeError):
            fut.set_result("third")
        fut.add_done_callback(lambda f: got.append("immediate"))
        assert got == ["first", "immediate"]


# ------------------------------------------------------------------ batcher
class TestBatcherDeadlines:
    def test_per_replica_keys_are_independent(self):
        b = Batcher(max_batch=2, max_wait_s=1.0)
        r = ServeRequest(0, "svc", _vecs(1)[0])
        assert b.add(r, 0.0, key=(0, "svc")) is None
        assert b.add(r, 0.0, key=(1, "svc")) is None  # other replica queue
        out = b.add(r, 0.0, key=(0, "svc"))
        assert out is not None and len(out) == 2
        assert b.pending((0, "svc")) == 0 and b.pending((1, "svc")) == 1

    def test_due_at_head_wait(self):
        b = Batcher(max_batch=8, max_wait_s=0.005)
        b.add(ServeRequest(0, "svc", _vecs(1)[0]), 1.0)
        assert b.due_at("svc") == pytest.approx(1.005)
        assert b.due_at("missing") is None

    def test_deadline_inheritance_tightens_flush(self):
        b = Batcher(max_batch=8, max_wait_s=0.1)
        b.add(ServeRequest(0, "svc", _vecs(1)[0]), 0.0)
        assert b.due_at("svc") == pytest.approx(0.1)
        # a deadline-carrying arrival pulls the whole queue's flush earlier:
        # arrival + deadline/2 - max_wait = 0.02 + 0.03 - 0.1 -> clamp 0.02
        b.add(ServeRequest(1, "svc", _vecs(1)[0], deadline_s=0.06), 0.02)
        assert b.due_at("svc") == pytest.approx(0.02)
        assert b.due("svc", 0.02) and not b.due("svc", 0.019)

    def test_deadline_leaves_half_budget(self):
        b = Batcher(max_batch=8, max_wait_s=0.005)
        b.add(ServeRequest(0, "svc", _vecs(1)[0], deadline_s=0.2), 1.0)
        # min(1 + 0.005, 1 + 0.1 - 0.005) -> head wait dominates
        assert b.due_at("svc") == pytest.approx(1.005)
        b2 = Batcher(max_batch=8, max_wait_s=0.08)
        b2.add(ServeRequest(0, "svc", _vecs(1)[0], deadline_s=0.2), 1.0)
        assert b2.due_at("svc") == pytest.approx(1.02)  # 1 + 0.1 - 0.08

    def test_flush_due_uses_keys(self):
        b = Batcher(max_batch=8, max_wait_s=0.005)
        b.add(ServeRequest(0, "svc", _vecs(1)[0]), 0.0, key=(2, "svc"))
        out = b.flush_due(0.02)
        assert list(out) == [(2, "svc")] and len(out[(2, "svc")]) == 1


# ------------------------------------------------------- async/sync parity
class TestAsyncSyncParity:
    def _run_pair(self, n=520, window=16, replicas=2):
        trace = _clustered_trace(n)
        sync_fleet = ServingFleet(
            P, [ReplicaEngine(i, P, _execute) for i in range(replicas)])
        async_eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(replicas)],
            backup=BackupPolicy(max_backups=0),
            max_batch=window + 1, max_wait_s=0.001,
            exec_time_fn=lambda rid, svc, reqs: 0.0)
        sync_out, async_out = [], []
        for lo in range(0, n, window):
            chunk = trace[lo:lo + window]
            sync_out.extend(sync_fleet.submit_batch_sync(chunk))
            futs = [async_eng.submit(r) for r in chunk]
            async_eng.drain()
            async_out.extend(f.result for f in futs)
        return sync_fleet, async_eng, sync_out, async_out

    def test_trace_parity_hits_similarities_stats(self):
        sync_fleet, async_eng, sync_out, async_out = self._run_pair()
        assert len(sync_out) == len(async_out) == 520
        for s, a in zip(sync_out, async_out):
            assert s.request_id == a.request_id
            assert s.reuse == a.reuse
            assert s.result == a.result
            assert s.replica == a.replica
            assert abs(s.similarity - a.similarity) < 1e-5
        # identical per-replica counters
        for rs, ra in zip(sync_fleet.replicas, async_eng.replicas):
            assert rs.stats == ra.stats
        # identical store contents
        for rs, ra in zip(sync_fleet.replicas, async_eng.replicas):
            assert set(rs.stores) == set(ra.stores)
            for svc in rs.stores:
                assert len(rs.stores[svc]) == len(ra.stores[svc])
                assert rs.stores[svc].live_ids() == ra.stores[svc].live_ids()

    def test_every_kind_exercised(self):
        _, async_eng, _, async_out = self._run_pair()
        kinds = {r.reuse for r in async_out}
        assert kinds == {None, "cs", "en"}
        s = async_eng.stats()
        assert s["aggregated"] > 0
        assert s["cs"] + s["en"] + s["executed"] + s["aggregated"] == 520


# ------------------------------------------------------------ async engine
class TestAsyncEngine:
    def _routed_to(self, eng, rid, seed0=100):
        for s in range(seed0, seed0 + 500):
            v = _vecs(1, seed=s)[0]
            if eng.router.route(v)[0] == rid:
                return v
        raise AssertionError("no embedding routed to replica")

    def test_cs_hit_resolves_immediately(self):
        eng = AsyncServingEngine(P, [ReplicaEngine(0, P, _execute)],
                                 max_wait_s=0.005)
        v = _vecs(1, seed=42)[0]
        f1 = eng.submit(ServeRequest(0, "svc", v))
        eng.drain()
        f2 = eng.submit(ServeRequest(1, "svc", v))
        assert f2.done and f2.result.reuse == "cs"
        assert f2.result.latency_s == 0.0
        assert f1.result.latency_s >= 0.005  # paid the batch window

    def test_followers_attach_and_record_wait(self):
        calls = {"n": 0}

        def execute(reqs):
            calls["n"] += len(reqs)
            return [f"r{r.request_id}" for r in reqs]

        eng = AsyncServingEngine(P, [ReplicaEngine(0, P, execute)],
                                 max_wait_s=0.005,
                                 exec_time_fn=lambda *a: 0.1)
        v = _vecs(1, seed=43)[0]
        f1 = eng.submit(ServeRequest(0, "svc", v))
        eng.drain(until=0.002)  # follower arrives mid-flight, pre-flush
        f2 = eng.submit(ServeRequest(1, "svc", v))
        eng.drain()
        assert calls["n"] == 1  # truly coalesced: no re-execution, no re-handle
        assert f1.result.reuse is None
        assert f2.result.reuse == "cs" and f2.result.similarity == 1.0
        assert f2.result.result == f1.result.result
        # leader resolved at 0.105 (flush 0.005 + exec 0.1); follower waited
        # from its 0.002 arrival and recorded that interval explicitly
        assert f2.result.agg_wait_s == pytest.approx(0.103)
        assert f2.result.latency_s == pytest.approx(0.103)
        assert eng.stats()["aggregated"] == 1

    @staticmethod
    def _prime_ttc(eng, svc="svc", t=0.05):
        # backup timers only arm once TTC statistics exist (a cold prior
        # must not duplicate first executions)
        for r in eng.replicas:
            r.ttc.observe(svc, t)

    def test_straggler_backup_first_result_wins(self):
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(3)],
            backup=BackupPolicy(factor=1.5, max_backups=1),
            max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: 10.0 if rid == 0 else 0.05)
        self._prime_ttc(eng)
        v = self._routed_to(eng, 0)
        fut = eng.submit(ServeRequest(0, "svc", v, threshold=0.9))
        eng.drain()
        res = fut.result
        assert res.backup and res.replica != 0
        assert res.latency_s < 1.0  # rescued from the 10s straggler
        s = eng.stats()
        assert s["backups"] == 1 and s["backup_wins"] == 1
        # no double insert: the loser's commit was skipped fleet-wide
        assert sum(len(st) for r in eng.replicas
                   for st in r.stores.values()) == 1
        assert s["executed"] == 1
        assert eng.pending() == 0 and eng.backup.active() == 0

    def test_backup_resolves_future_exactly_once(self):
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1),
            max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: 10.0 if rid == 0 else 0.05)
        self._prime_ttc(eng)
        v = self._routed_to(eng, 0)
        fut = eng.submit(ServeRequest(0, "svc", v, threshold=0.9))
        resolutions = []
        fut.add_done_callback(lambda f: resolutions.append(f.resolved_at))
        eng.drain()
        assert len(resolutions) == 1
        # the straggler's own completion event still pops (as a no-op)
        assert eng.loop.now == pytest.approx(10.005)

    def test_backup_win_backfills_primary_cs(self):
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1),
            max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: 10.0 if rid == 0 else 0.05)
        self._prime_ttc(eng)
        v = self._routed_to(eng, 0)
        eng.submit(ServeRequest(0, "svc", v, threshold=0.9))
        eng.drain()
        # an exact re-submit routes to the primary and must CS-hit there
        f = eng.submit(ServeRequest(1, "svc", v, threshold=0.9))
        assert f.done and f.result.reuse == "cs" and f.result.replica == 0

    def test_fast_primary_cancels_backup_timer(self):
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1),
            max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: 0.01)
        self._prime_ttc(eng)
        fut = eng.submit(ServeRequest(0, "svc", _vecs(1, seed=44)[0]))
        eng.drain()
        s = eng.stats()
        assert fut.result.reuse is None and not fut.result.backup
        assert s["backups"] == 0 and s["backup_wins"] == 0
        assert eng.backup.active() == 0  # timer torn down on resolution

    def test_max_backups_zero_never_redispatches(self):
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(max_backups=0), max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: 5.0)
        self._prime_ttc(eng)
        fut = eng.submit(ServeRequest(0, "svc", _vecs(1, seed=45)[0]))
        eng.drain()
        assert fut.result.latency_s == pytest.approx(5.005)
        assert eng.stats()["backups"] == 0

    def test_cold_ttc_arms_no_backup(self):
        # a first-ever execution (e.g. jit compile on the wall-time path)
        # must not be duplicated by the uninformed 85 ms TTC prior
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1),
            max_wait_s=0.005, exec_time_fn=lambda rid, svc, reqs: 5.0)
        fut = eng.submit(ServeRequest(0, "svc", _vecs(1, seed=48)[0]))
        eng.drain()
        assert fut.result.latency_s == pytest.approx(5.005)
        assert eng.stats()["backups"] == 0 and eng.backup.active() == 0

    def test_backup_en_hit_counts_win_and_backfills(self):
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1),
            max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: 10.0 if rid == 0 else 0.05)
        self._prime_ttc(eng)
        v = self._routed_to(eng, 0)
        # the backup replica's store already holds this embedding: the
        # re-dispatch resolves by cross-replica semantic rescue, not execute
        eng.replicas[1]._store("svc").insert(v, "cached-on-backup")
        fut = eng.submit(ServeRequest(0, "svc", v, threshold=0.9))
        eng.drain()
        res = fut.result
        assert res.backup and res.replica == 1 and res.reuse == "en"
        assert res.result == "cached-on-backup"
        s = eng.stats()
        assert s["backups"] == 1 and s["backup_wins"] == 1
        assert s["executed"] == 0  # straggler commit skipped, rescue was a hit
        # primary CS back-filled: exact retry hits locally on replica 0
        f2 = eng.submit(ServeRequest(1, "svc", v, threshold=0.9))
        assert f2.done and f2.result.reuse == "cs" and f2.result.replica == 0


    # ------------------------------------------- the group's backup deadline
    @staticmethod
    def _routed_group(eng, n, seed0):
        """``n`` fresh embeddings the router sends to replica 0, and the
        next unused seed."""
        out, s = [], seed0
        while len(out) < n:
            v = _vecs(1, seed=s)[0]
            s += 1
            if eng.router.route(v)[0] == 0:
                out.append(v)
        return out, s

    @staticmethod
    def _serve_group(eng, vecs, first_id):
        """Submit ``vecs`` at once (one flush, one group) and drain."""
        futs = [eng.submit(ServeRequest(first_id + i, "svc", v))
                for i, v in enumerate(vecs)]
        eng.drain()
        return futs

    @pytest.mark.parametrize("second_s, backed_up", [(0.05, False),
                                                     (0.2, True)])
    def test_group_backs_up_past_its_learned_time(self, second_s, backed_up):
        # the first two-row group teaches its size's time, 0.04 s; a later
        # one is a straggler past 1.5 x that (0.06 s), although the per-row
        # TTC would allow it 1.5 x 2 x 0.084 s
        times = iter([0.04, second_s])
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1), max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: (next(times) if rid == 0
                                                 else 0.02))
        self._prime_ttc(eng, t=0.1)
        first, s = self._routed_group(eng, 2, 300)
        self._serve_group(eng, first, 0)
        assert eng.stats()["backups"] == 0
        assert eng._expected_group_s(0, "svc", 2) == pytest.approx(0.04)
        second, _ = self._routed_group(eng, 2, s)
        futs = self._serve_group(eng, second, 2)
        st = eng.stats()
        assert all(f.result.backup == backed_up for f in futs)
        if backed_up:
            assert all(f.result.replica == 1 for f in futs)
            assert st["backups"] == 2 and st["backup_wins"] == 2
            assert (st["exec_rows"], st["discarded_rows"]) == (6, 2)
        else:
            assert st["backups"] == 0
            assert (st["exec_rows"], st["discarded_rows"]) == (4, 0)
        # the straggling group is learned too, its rows thrown away or not
        assert eng._expected_group_s(0, "svc", 2) == pytest.approx(
            0.8 * 0.04 + 0.2 * second_s)
        assert eng.pending() == 0 and eng.backup.active() == 0

    def test_one_row_group_after_full_groups_arms_no_backup(self):
        # a call's fixed cost makes one row slower than an eighth of a full
        # group; full groups pull the per-row TTC below two thirds of a
        # one-row group's time, but a one-row group is held to its own
        exec_s = lambda rid, svc, reqs: 0.015 + 0.017 * len(reqs)  # noqa: E731
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1), max_wait_s=0.005,
            max_batch=8, exec_time_fn=exec_s)
        s, rid = 400, 0
        for n in [1] * 3 + [8] * 10:
            vecs, s = self._routed_group(eng, n, s)
            self._serve_group(eng, vecs, rid)
            rid += n
        one = exec_s(0, "svc", [None])
        # the old deadline, 1.5 x the per-row TTC, falls before it completes
        assert 1.5 * eng.replicas[0].ttc.estimate("svc") < one
        vecs, s = self._routed_group(eng, 1, s)
        (fut,) = self._serve_group(eng, vecs, rid)
        st = eng.stats()
        assert not fut.result.backup and fut.result.replica == 0
        assert st["backups"] == 0 and st["discarded_rows"] == 0
        assert st["exec_rows"] == 3 + 80 + 1

    @pytest.mark.parametrize("group_s, backups", [(0.13, 0), (0.14, 3)])
    def test_unseen_group_size_falls_back_to_rows_times_ttc(self, group_s,
                                                            backups):
        # no three-row group has run: its deadline is 1.5 x 3 x 0.03 s
        eng = AsyncServingEngine(
            P, [ReplicaEngine(i, P, _execute) for i in range(2)],
            backup=BackupPolicy(factor=1.5, max_backups=1), max_wait_s=0.005,
            exec_time_fn=lambda rid, svc, reqs: (group_s if rid == 0
                                                 else 0.03))
        self._prime_ttc(eng, t=0.03)
        assert eng._expected_group_s(0, "svc", 3) == pytest.approx(0.09)
        vecs, _ = self._routed_group(eng, 3, 500)
        futs = self._serve_group(eng, vecs, 0)
        st = eng.stats()
        assert st["backups"] == backups
        # the primary finishes before any backup (0.135 + 0.03 s) can
        assert all(f.result.replica == 0 and not f.result.backup
                   for f in futs)
        assert (st["exec_rows"], st["discarded_rows"]) == (3 + backups,
                                                           backups)


# --------------------------------------------------- sync facade + stages
class TestSyncFacade:
    def test_submit_is_async_drained(self):
        fleet = ServingFleet(P, [ReplicaEngine(i, P, _execute)
                                 for i in range(2)])
        res = fleet.submit(ServeRequest(0, "svc", _vecs(1, seed=46)[0]))
        assert res.reuse is None
        assert fleet.engine.pending() == 0
        assert fleet.engine.loop.now > 0  # went through the virtual clock

    def test_mixed_apis_share_one_cs_clock(self):
        # async submit stamps the CS with virtual time; the sync parity path
        # must look up with the same clock or the entry appears expired
        fleet = ServingFleet(P, [ReplicaEngine(0, P, _execute)])
        v = _vecs(1, seed=49)[0]
        r1 = fleet.submit(ServeRequest(0, "svc", v))
        assert r1.reuse is None
        out = fleet.submit_batch_sync([ServeRequest(1, "svc", v)])
        assert out[0].reuse == "cs" and out[0].result == r1.result

    def test_stats_include_engine_counters(self):
        fleet = ServingFleet(P, [ReplicaEngine(0, P, _execute)])
        fleet.submit(ServeRequest(0, "svc", _vecs(1, seed=50)[0]))
        s = fleet.stats()
        assert {"backups", "backup_wins", "dispatches",
                "executed", "cs", "en", "aggregated"} <= set(s)
        assert s["dispatches"] == 1

    def test_follower_latency_inherits_leader_completion(self):
        eng = ReplicaEngine(0, P, _execute)
        v = _vecs(1, seed=47)[0]
        out = eng.handle_batch([ServeRequest(0, "svc", v),
                                ServeRequest(1, "svc", v)])
        assert out[1].reuse == "cs" and out[1].similarity == 1.0
        assert out[1].latency_s == out[0].latency_s  # not end-of-batch time
        assert out[1].agg_wait_s == out[0].latency_s
        assert out[0].agg_wait_s == 0.0


# ------------------------------------------------------- satellite: store
class TestInsertBatchScatter:
    @pytest.mark.parametrize("bucket_cap", [1, 2, 8])
    def test_bit_identical_to_scalar_loop(self, bucket_cap):
        a = ReuseStore(P, capacity=1024, bucket_cap=bucket_cap)
        b = ReuseStore(P, capacity=1024, bucket_cap=bucket_cap)
        X = _vecs(300, seed=6)
        for i, v in enumerate(X):
            a.insert(v, i)
        b.insert_batch(X, list(range(300)))
        assert (a._slots == b._slots).all()
        assert (a._fill == b._fill).all()
        assert (a._cursor == b._cursor).all()
        assert a.overflows == b.overflows
        assert list(a._lru) == list(b._lru)

    def test_chunked_equals_single_batch(self):
        a = ReuseStore(P, capacity=1024, bucket_cap=4)
        b = ReuseStore(P, capacity=1024, bucket_cap=4)
        X = _vecs(256, seed=7)
        a.insert_batch(X, list(range(256)))
        for lo in range(0, 256, 32):
            b.insert_batch(X[lo:lo + 32], list(range(lo, lo + 32)))
        assert (a._slots == b._slots).all() and a.overflows == b.overflows

    def test_eviction_keeps_invariants(self):
        store = ReuseStore(P, capacity=64)
        X = _vecs(200, seed=8)
        store.insert_batch(X[:50], list(range(50)))
        store.insert_batch(X[50:], list(range(50, 200)))
        assert len(store) == 64
        live = set(store.live_ids())
        assert set(store._slots[store._slots >= 0].tolist()) <= live
        assert ((store._slots >= 0).sum(axis=2) == store._fill).all()
        out = store.query_batch(X[-20:], -1.0)
        assert all(idx in live for _, _, idx in out if idx is not None)

    def test_evicting_batch_matches_scalar_exactly(self):
        # warm store at capacity: the insert must fall back to the scalar
        # interleaved-eviction order (upfront eviction reorders the free
        # list and displaces different ring victims)
        a = ReuseStore(P, capacity=20, bucket_cap=4)
        b = ReuseStore(P, capacity=20, bucket_cap=4)
        pre, batch = _vecs(18, seed=30), _vecs(15, seed=31)
        for s in (a, b):
            s.insert_batch(pre, [("pre", i) for i in range(18)])
        for i, v in enumerate(batch):
            a.insert(v, ("new", i))
        b.insert_batch(batch, [("new", i) for i in range(15)])
        assert (a._slots == b._slots).all()
        assert (a._fill == b._fill).all() and (a._cursor == b._cursor).all()
        assert a.overflows == b.overflows and list(a._lru) == list(b._lru)
        qa = a.query_batch(_vecs(30, seed=32), -1.0)
        qb = b.query_batch(_vecs(30, seed=32), -1.0)
        assert [(r, s, i) for r, s, i in qa] == [(r, s, i) for r, s, i in qb]

    def test_batch_larger_than_capacity_falls_back(self):
        store = ReuseStore(P, capacity=16)
        X = _vecs(64, seed=9)
        ids = store.insert_batch(X, list(range(64)))
        assert len(ids) == 64 and len(store) == 16
        assert set(store._slots[store._slots >= 0].tolist()) <= set(
            store.live_ids())


class TestQueryPeek:
    def test_peek_mutates_nothing(self):
        store = ReuseStore(P, capacity=256)
        X = _vecs(100, seed=10)
        store.insert_batch(X, list(range(100)))
        lru0 = list(store._lru)
        q0, cc0 = store.queries, len(store.candidate_counts)
        out_peek = store.query_batch(X[:8], 0.5, peek=True)
        assert list(store._lru) == lru0
        assert store.queries == q0 and len(store.candidate_counts) == cc0
        out = store.query_batch(X[:8], 0.5)
        assert [(s, i) for _, s, i in out_peek] == [(s, i) for _, s, i in out]

    def test_network_oracle_still_measures(self):
        from repro.core import ReservoirNetwork, Service
        from repro.core.topology import testbed_topology

        g, ens = testbed_topology()
        net = ReservoirNetwork(g, ens, P, seed=0, measure_fwd_errors=True)
        net.register_service(Service(
            "/svc", execute=lambda x: float(np.sum(x) > 0),
            exec_time_s=(0.07, 0.1), input_dim=32))
        net.add_user("u1", "fwd1")
        X = _vecs(80, seed=11)
        t = 0.0
        for i in range(80):
            net.submit_task("u1", "/svc", X[i % 20], 0.9, at_time=t)
            t += 0.01
        net.run()
        assert all(r.t_complete >= 0 for r in net.metrics.records)
        assert net.metrics.forwarding_error_rate() >= 0.0
