"""Observability layer tests (ISSUE 10): tracing, metrics registry, profiler.

Four contracts:

* **disarmed is the default and bit-identical** — a default-built
  ``EventLoop``/``ReservoirNetwork`` carries no tracer/profiler, and an
  ARMED run reproduces the seeded 500-task golden traces from
  tests/test_cosim.py bit-for-bit (the tracer observes the virtual
  timeline, never perturbs it);
* **span trees are well-formed** — no span left open once the loop drains
  to idle, even under chaos (loss + crash + retx), and every
  retx/drop/offload event carries its originating task id;
* **the registry is the one home for stats** — the legacy ``stats`` dicts
  are ``CounterGroup`` views adopted by ``net.registry`` (full Mapping
  compatibility preserved), and the per-phase latency decomposition comes
  from ``phase_summary()``;
* **lint rule O001** flags direct subscript mutation of those adopted
  mappings in sim paths (and only there).
"""
import json

import numpy as np
import pytest

from repro.analysis.lint import lint_source
from repro.core import LSHParams, ReservoirNetwork
from repro.core.edge_node import Service
from repro.core.sim_clock import EventLoop
from repro.faults import ChaosController, FaultPlan
from repro.faults.plan import CrashEvent, LinkFault
from repro.obs.registry import (Counter, CounterGroup, Gauge, Histogram,
                                MetricsRegistry)
from repro.obs.trace import TRACK_TID_BASE, Tracer

from test_cosim import GOLDEN, _key, _trace
from test_federation import _emb_routed_to, _star_topology


class TracedNet(ReservoirNetwork):
    """ReservoirNetwork with tracer + profiler force-armed: drop-in for the
    test_cosim ``_trace`` helper so armed runs replay the exact seeded
    golden workloads."""

    def __init__(self, *args, **kwargs):
        kwargs.setdefault("trace", True)
        kwargs.setdefault("profile", True)
        super().__init__(*args, **kwargs)


def _small_net(n_ens=2, policy=None, trace=True, profile=False,
               exec_time=(0.07, 0.1), **kw):
    params = LSHParams(dim=16, num_tables=5, num_probes=8)
    g, ens = _star_topology(n_ens)
    net = ReservoirNetwork(g, ens, params, seed=0, offload_policy=policy,
                           trace=trace, profile=profile, **kw)
    net.register_service(Service(
        "/svc", execute=lambda x: round(float(np.sum(x)), 5),
        exec_time_s=exec_time, input_dim=16))
    net.add_user("u1", "core")
    return net


# ------------------------------------------------------------------ registry
class TestRegistryPrimitives:
    def test_counter_and_gauge(self):
        c = Counter()
        c.inc()
        c.inc(4)
        assert c.value == 5
        g = Gauge()
        g.set(2.5)
        g.set(1)
        assert g.value == 1.0

    def test_histogram_observe_mean_quantile(self):
        h = Histogram(edges=(0.001, 0.01, 0.1))
        for v in (0.0005, 0.005, 0.005, 0.05, 3.0):
            h.observe(v)
        assert h.count == 5
        assert h.mean() == pytest.approx(3.0605 / 5)
        assert h.min == 0.0005 and h.max == 3.0
        assert h.counts == [1, 2, 1, 1]          # last = overflow bucket
        assert h.quantile(0.5) == 0.01           # bucket upper edge
        assert h.quantile(1.0) == 3.0            # overflow -> observed max
        d = h.to_dict()
        assert d["count"] == 5 and d["counts"] == [1, 2, 1, 1]
        empty = Histogram()
        assert np.isnan(empty.mean()) and np.isnan(empty.quantile(0.5))

    def test_countergroup_is_a_mapping(self):
        s = CounterGroup({"reused": 0, "executed": 0})
        # every legacy accessor the stats dicts supported must keep working
        s["reused"] += 1          # test-style subscript mutation
        s.inc("executed")         # src-style mutation
        s.inc("new_key", 3)       # inc creates missing keys
        assert s["reused"] == 1
        assert dict(s) == {"reused": 1, "executed": 1, "new_key": 3}
        assert s == {"reused": 1, "executed": 1, "new_key": 3}
        assert len(s) == 3 and "reused" in s
        assert list(s) == ["reused", "executed", "new_key"]  # insertion order
        assert s.get("missing", 7) == 7
        del s["new_key"]
        assert "new_key" not in s

    def test_registry_get_or_create_and_adopt(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        assert reg.gauge("g") is reg.gauge("g")
        assert reg.histogram("h") is reg.histogram("h")
        grp = CounterGroup({"x": 2})
        assert reg.adopt("legacy", grp) is grp
        reg.counter("a").inc(3)
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(0.02)
        d = reg.to_dict()
        assert d["counters"] == {"a": 3} and d["groups"] == {"legacy": {"x": 2}}
        assert d["gauges"] == {"g": 1.5} and d["histograms"]["h"]["count"] == 1

    def test_phase_summary_decomposition(self):
        reg = MetricsRegistry()
        ps = reg.phase_summary()
        assert ps["search_n"] == 0 and np.isnan(ps["search_ms"])
        reg.observe_phase("search", 0.002)
        reg.observe_phase("search", 0.004)
        ps = reg.phase_summary()
        assert ps["search_n"] == 2
        assert ps["search_ms"] == pytest.approx(3.0)
        assert np.isnan(ps["forward_ms"]) and ps["forward_n"] == 0


# -------------------------------------------------------------------- arming
class TestArming:
    def test_disarmed_by_default(self):
        loop = EventLoop()
        assert loop.tracer is None and loop.profiler is None
        net = _small_net(trace=None, profile=None)
        assert net.loop.tracer is None and net.loop.profiler is None
        assert isinstance(net.registry, MetricsRegistry)  # registry always on

    def test_kwarg_arming(self):
        loop = EventLoop(trace=True, profile=True)
        assert isinstance(loop.tracer, Tracer)
        assert loop.profiler is not None

    def test_env_arming_and_kwarg_override(self, monkeypatch):
        monkeypatch.setenv("RESERVOIR_TRACE", "1")
        monkeypatch.setenv("RESERVOIR_PROFILE", "yes")
        loop = EventLoop()
        assert loop.tracer is not None and loop.profiler is not None
        # explicit kwarg beats the environment, both directions
        off = EventLoop(trace=False, profile=False)
        assert off.tracer is None and off.profiler is None
        monkeypatch.setenv("RESERVOIR_TRACE", "0")
        assert EventLoop().tracer is None


# -------------------------------------------------------------------- tracer
class TestTracer:
    def test_span_lifecycle(self):
        tr = EventLoop(trace=True).tracer
        sid = tr.begin("task", "task", 7, t=1.0, user="u1")
        assert tr.open_spans() == [(sid, "task", "task", 7)]
        tr.end(sid, t=3.5, outcome="completed")
        assert tr.open_spans() == []
        tr.end(sid, t=9.0)  # double-close is a no-op, first close wins
        (ev,) = tr.events
        assert ev["ph"] == "X" and ev["ts"] == 1.0e6 and ev["dur"] == 2.5e6
        assert ev["tid"] == 7
        assert ev["args"] == {"user": "u1", "outcome": "completed"}

    def test_abandon_marks_outcome(self):
        tr = EventLoop(trace=True).tracer
        sid = tr.begin("offload", "federation", 3, t=0.0)
        tr.abandon(sid, t=1.0, why="peer-dead")
        assert not tr.open_spans()
        assert tr.events[-1]["args"]["outcome"] == "peer-dead"

    def test_tracks_and_export(self, tmp_path):
        tr = EventLoop(trace=True).tracer
        t1 = tr.track("gossip")
        assert t1 >= TRACK_TID_BASE
        assert tr.track("gossip") == t1            # stable
        assert tr.track("migrate") == t1 + 1       # distinct
        tr.name_task(5, "task u1/svc")
        tr.instant("gossip-round", "gossip", t1, t=0.5, round=1)
        path = tmp_path / "trace.json"
        doc = tr.export(str(path))
        loaded = json.loads(path.read_text())
        assert loaded == doc
        names = {e["args"]["name"] for e in loaded["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"gossip", "migrate", "task u1/svc"} <= names
        assert loaded["displayTimeUnit"] == "ms"


# ------------------------------------------------- bit-identical golden runs
class TestTracedBitIdentical:
    @pytest.mark.parametrize("protocol", ("direct", "ttc"))
    def test_traced_run_matches_seeded_goldens(self, protocol):
        """Arming tracer+profiler must not perturb the seeded 500-task
        acceptance trace: per-record bit-for-bit vs the untraced run AND
        the pinned cross-process goldens."""
        plain = _trace(ReservoirNetwork, protocol, 0.0)
        traced = _trace(TracedNet, protocol, 0.0)
        assert traced.loop.tracer is not None
        assert len(traced.metrics.records) == 500
        for a, b in zip(plain.metrics.records, traced.metrics.records):
            assert _key(a) == _key(b)
        assert plain.metrics.summary() == traced.metrics.summary()
        s = traced.metrics.summary()
        for k, v in GOLDEN[protocol].items():
            assert s[k] == pytest.approx(v, rel=1e-9), k
        # the trace itself is complete: one closed span per task, none open
        tr = traced.loop.tracer
        assert not tr.open_spans()
        tasks = [e for e in tr.events
                 if e["ph"] == "X" and e["name"] == "task"]
        assert len(tasks) == 500
        assert all(e["args"]["outcome"] == "completed" for e in tasks)
        # phase decomposition populated from the same run (forward is
        # observed at EN arrival: CS hits and PIT-coalesced tasks skip it)
        ps = traced.registry.phase_summary()
        assert 0 < ps["forward_n"] <= 500 and ps["search_n"] > 0
        assert ps["execute_n"] > 0

    def test_registry_adopts_all_stats_families(self):
        net = _small_net(policy="least-loaded")
        ChaosController(net, FaultPlan(seed=1))
        groups = net.registry.groups
        assert "fault" in groups and "chaos" in groups
        assert "federation" in groups
        assert any(k.startswith("en/") for k in groups)
        # adopted views ARE the live objects, not copies
        assert groups["federation"] is net.federator.stats


def _chaos_net(n_tasks=150):
    params = LSHParams(dim=16, num_tables=5, num_probes=8)
    g, ens = _star_topology(3)
    net = ReservoirNetwork(g, ens, params, seed=0,
                           offload_policy="least-loaded",
                           retx_timeout_s=0.25, pit_lifetime_s=2.0,
                           trace=True)
    ChaosController(net, FaultPlan(
        seed=3,
        links=[LinkFault(loss=0.08)],
        crashes=[CrashEvent(node=ens[-1], at=0.8)]))
    net.register_service(Service(
        "/svc", execute=lambda x: round(float(np.sum(x)), 5),
        exec_time_s=(0.01, 0.015), input_dim=16))
    net.add_user("u1", "core")
    rng = np.random.default_rng(7)
    from repro.core.lsh import normalize
    X = normalize(rng.standard_normal((n_tasks, 16)).astype(np.float32))
    t = 0.0
    for i, x in enumerate(X):
        net.submit_task("u1", "svc", x, 0.9, at_time=t)
        t += 0.02
    net.run()
    return net


# ------------------------------------------------------ span well-formedness
class TestSpanTreeUnderChaos:
    def test_no_open_spans_and_task_attribution(self):
        net = _chaos_net()
        tr = net.loop.tracer
        assert not tr.open_spans(), tr.open_spans()
        tasks = [e for e in tr.events
                 if e["ph"] == "X" and e["name"] == "task"]
        assert len(tasks) == 150          # one closed span per submission
        outcomes = {e["args"]["outcome"] for e in tasks}
        assert outcomes <= {"completed", "failed", "unresolved-at-drain"}
        task_tids = {e["tid"] for e in tasks}
        # chaos actually exercised the fault machinery
        retx = [e for e in tr.events if e["name"] == "retx"]
        drops = [e for e in tr.events if e["name"] == "drop"]
        assert retx and drops
        # every retx carries its originating task, on that task's track
        for e in retx:
            assert e["args"]["task"] == e["tid"] and e["tid"] in task_tids
        # drops of task-attributable packets parent to the task; control
        # traffic (no name-map entry) lands on the shared fault track
        for e in drops:
            if e["args"]["task"] is not None:
                assert e["args"]["task"] in task_tids
            else:
                assert e["tid"] >= TRACK_TID_BASE

    def test_offload_span_closes_with_outcome(self):
        net = _small_net(policy="least-loaded", n_ens=2)
        src = net.en_nodes[0]
        emb = _emb_routed_to(net, src)
        net._en_busy_until[src] = 5.0     # local queue >> remote
        rec = net.submit_task("u1", "svc", emb, 0.9, at_time=0.0)
        net.run()
        assert net.federator.stats["offloads"] == 1
        tr = net.loop.tracer
        assert not tr.open_spans()
        (off,) = [e for e in tr.events if e["name"] == "offload"]
        assert off["ph"] == "X" and off["dur"] > 0
        assert off["args"]["outcome"] in ("remote-hit", "remote-exec")
        assert off["args"]["task"] == rec.task_id == off["tid"]
        # the fed-name alias was cleaned up with the span
        assert not net._task_meta


# ------------------------------------------------------------------ profiler
class TestProfiler:
    def test_ranked_sites_and_report(self):
        net = _small_net(trace=False, profile=True)
        emb = _emb_routed_to(net, net.en_nodes[0])
        for i in range(20):
            net.submit_task("u1", "svc", emb, 0.9, at_time=0.01 * i)
        net.run()
        prof = net.loop.profiler
        rows = prof.rows()
        assert rows and all(r["count"] > 0 for r in rows)
        walls = [r["wall_s"] for r in rows]
        assert walls == sorted(walls, reverse=True)
        assert any("ReservoirNetwork" in r["site"] for r in rows)
        totals = prof.totals()
        assert totals["events"] == sum(r["count"] for r in rows)
        assert "store_sync_pages" in totals
        rep = prof.report(top=5)
        assert "EventLoop profile" in rep and rows[0]["site"] in rep
        d = prof.to_dict()
        assert d["sites"] == rows and d["totals"]["events"] == totals["events"]


# ---------------------------------------------------------------- lint O001
class TestLintO001:
    SRC = (
        "class F:\n"
        "    def run(self):\n"
        "        self.stats['offloads'] += 1\n"
        "        self.engine_stats['dispatches'] = 5\n"
        "        peer.fault_stats['drops'] += 2\n"
        "        self.other['x'] += 1\n"
    )

    def test_flags_sim_path_mutations(self):
        vs = lint_source(self.SRC, "src/repro/federation/fake.py")
        o = [v for v in vs if v.rule == "O001"]
        assert [v.line for v in o] == [3, 4, 5]
        assert all(v.severity == "error" for v in o)

    def test_tests_and_benchmarks_exempt(self):
        for path in ("tests/test_fake.py", "benchmarks/fake.py",
                     "src/repro/analysis/fake.py"):
            vs = lint_source(self.SRC, path)
            assert not [v for v in vs if v.rule == "O001"], path

    def test_waiver_suppresses_with_reason(self):
        src = ("class F:\n"
               "    def run(self):\n"
               "        self.stats['x'] += 1"
               "  # lint: disable=O001(legacy shim)\n")
        vs = lint_source(src, "src/repro/core/fake.py")
        (v,) = [v for v in vs if v.rule == "O001"]
        assert v.waived and v.waive_reason == "legacy shim"
