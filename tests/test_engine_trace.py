"""The serving engine's stage spans and counters (DESIGN.md §Observability).

Contracts, all on the CPU with a stub ``execute_fn``:

* a host-clock tracer armed on the engine's loop gives one ``engine/queue``
  span per leader, closed with the reason of the flush that carried it, and
  none for Content Store hits or followers;
* scoped spans nest as the engine's stages do (admit > route, dispatch >
  search + execute; commit apart) and nothing is left open after a drain;
* ``exec_rows`` / ``discarded_rows`` count every row sent to the model and
  every row whose result a faster execution made useless, traced or not;
* disarmed, no tracer object is touched and results and counters are
  bit-identical to an armed run on either clock;
* a host-clock tracer records backend compiles as ``engine/compile`` spans
  inside the stage that compiled, and stops at disarm.
"""
import numpy as np
import pytest

from repro.core.lsh import LSHParams, normalize
from repro.core.sim_clock import EventLoop
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer
from repro.serving import AsyncServingEngine, ReplicaEngine, ServeRequest
from repro.serving.async_engine import _UNTRACED, _scope
from repro.training.elastic import BackupPolicy

P = LSHParams(dim=32, num_tables=3, num_probes=6, seed=5)


def _vecs(n, seed=0, d=32):
    return normalize(np.random.default_rng(seed).standard_normal((n, d)))


def _execute(reqs):
    return [f"r{r.request_id}" for r in reqs]


def _engine(n_replicas=1, clock=None, **kw):
    kw.setdefault("max_wait_s", 0.005)
    kw.setdefault("exec_time_fn", lambda rid, svc, reqs: 0.01)
    eng = AsyncServingEngine(
        P, [ReplicaEngine(i, P, kw.pop("execute", _execute))
            for i in range(n_replicas)], **kw)
    if clock is not None:
        eng.loop.arm_tracer(clock)
    return eng


def _spans(tr, name):
    return [e for e in tr.events if e["ph"] == "X" and e["name"] == name]


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def _prime_ttc(eng, t=0.03, svc="svc"):
    for r in eng.replicas:
        r.ttc.observe(svc, t)


# ---------------------------------------------------------------- queue
def test_one_queue_span_per_leader_with_its_flush_reason():
    eng = _engine(max_batch=2, clock="host")
    v = _vecs(4, seed=11)
    eng.submit_at(0.000, ServeRequest(0, "svc", v[0]))   # leader, full
    eng.submit_at(0.001, ServeRequest(1, "svc", v[0]))   # follower of 0
    eng.submit_at(0.002, ServeRequest(2, "svc", v[1]))   # leader, full
    eng.submit_at(0.003, ServeRequest(3, "svc", v[2]))   # leader, timer
    eng.drain()
    cs = eng.submit(ServeRequest(4, "svc", v[0]))        # Content Store hit
    eng.drain()
    assert cs.result.reuse == "cs"
    tr = eng.loop.tracer
    queue = {e["tid"]: e for e in _spans(tr, "engine/queue")}
    assert sorted(queue) == [0, 2, 3]
    assert [queue[i]["args"]["reason"] for i in (0, 2, 3)] == \
        ["full", "full", "timer"]
    assert all(e["dur"] >= 0 and e["args"]["replica"] == 0
               for e in queue.values())
    admits = {e["tid"]: e["args"]["outcome"] for e in _spans(tr, "engine/admit")}
    assert admits == {0: "leader", 1: "follower", 2: "leader", 3: "leader",
                      4: "cs"}
    assert not tr.open_spans()


def test_scoped_spans_nest_as_the_stages_do():
    eng = _engine(n_replicas=2, clock="host", max_batch=4,
                  backup=BackupPolicy(max_backups=0))
    for i, v in enumerate(_vecs(24, seed=12)):
        eng.submit_at(0.002 * i, ServeRequest(i, "svc", v))
    eng.drain()
    tr = eng.loop.tracer
    assert not tr.open_spans()
    admits = _spans(tr, "engine/admit")
    routes = _spans(tr, "engine/route")
    assert len(admits) == len(routes) == 24
    by_tid = {e["tid"]: e for e in admits}
    assert all(_inside(r, by_tid[r["tid"]]) for r in routes)
    dispatches = _spans(tr, "engine/dispatch")
    assert sum(d["args"]["rows"] for d in dispatches) == 24
    assert {d["args"]["reason"] for d in dispatches} <= {"full", "timer"}
    tracks = {tr.track("engine/r0"), tr.track("engine/r1")}
    for name in ("engine/search", "engine/execute"):
        children = _spans(tr, name)
        assert children and all(c["tid"] in tracks for c in children)
        assert all(any(c["tid"] == d["tid"] and _inside(c, d)
                       for d in dispatches) for c in children)
    assert all(s["args"]["path"] == "staged"
               for s in _spans(tr, "engine/search"))
    commits = _spans(tr, "engine/commit")
    # a commit runs at its group's completion event, outside any dispatch
    assert commits and not any(_inside(c, d) for c in commits
                               for d in dispatches)
    assert sum(c["args"]["rows"] for c in commits) == 24
    assert all(c["args"]["sync_pages"] == 0 for c in commits)


def test_abort_closes_queued_leaders():
    eng = _engine(clock="host", max_batch=8)
    eng.submit(ServeRequest(0, "svc", _vecs(1, seed=13)[0]))
    tr = eng.loop.tracer
    assert [n for _, n, _, _ in tr.open_spans()] == ["engine/queue"]
    eng.abort_all()
    assert not tr.open_spans()
    (q,) = _spans(tr, "engine/queue")
    assert q["args"]["outcome"] == "aborted"


# ------------------------------------------------------------- counters
@pytest.mark.parametrize("clock", [None, "virtual", "host"])
def test_forced_backup_counts_the_discarded_row(clock):
    eng = _engine(n_replicas=2, clock=clock,
                  backup=BackupPolicy(factor=1.5, max_backups=1),
                  exec_time_fn=lambda rid, svc, reqs: 10.0 if rid == 0
                  else 0.05)
    _prime_ttc(eng, 0.05)
    for s in range(100, 600):   # an embedding the router sends to replica 0
        v = _vecs(1, seed=s)[0]
        if eng.router.route(v)[0] == 0:
            break
    fut = eng.submit(ServeRequest(0, "svc", v))
    eng.drain()
    assert fut.result.backup
    s = eng.stats()
    # the straggler and its backup each ran the row; the straggler's
    # result came second and was thrown away
    assert (s["exec_rows"], s["discarded_rows"]) == (2, 1)
    if clock is not None:
        reasons = [d["args"]["reason"]
                   for d in _spans(eng.loop.tracer, "engine/dispatch")]
        assert reasons == ["timer", "backup"]
        assert _spans(eng.loop.tracer, "engine/dispatch")[1]["args"]["backup"]


def test_group_at_its_expected_time_arms_no_backup():
    # a group of two takes twice the per-row time the TTC learns; its
    # deadline is 1.5 x the expected time of a two-row group, not of one
    # row, so neither row is run again
    eng = _engine(n_replicas=2, max_batch=2,
                  backup=BackupPolicy(factor=1.5, max_backups=1),
                  exec_time_fn=lambda rid, svc, reqs: 0.03 * len(reqs))
    _prime_ttc(eng, 0.03)
    picked, s = [], 200
    while len(picked) < 2:
        v = _vecs(1, seed=s)[0]
        s += 1
        if eng.router.route(v)[0] == 0:
            picked.append(v)
    futs = [eng.submit(ServeRequest(i, "svc", v)) for i, v in enumerate(picked)]
    eng.drain()
    assert all(not f.result.backup for f in futs)
    st = eng.stats()
    assert st["backups"] == 0 and st["backup_wins"] == 0
    assert (st["exec_rows"], st["discarded_rows"]) == (2, 0)


# --------------------------------------------------------------- disarmed
def _workload(clock):
    eng = _engine(n_replicas=2, clock=clock, max_batch=4,
                  backup=BackupPolicy(factor=1.5, max_backups=1),
                  # replica 1 straggles, so some rows are run twice
                  exec_time_fn=lambda rid, svc, reqs: 0.03 * len(reqs) * (
                      4 if rid == 1 else 1))
    _prime_ttc(eng, 0.03)
    rng = np.random.default_rng(14)
    base = _vecs(12, seed=15)
    futs = []
    for i in range(120):
        v = normalize(base[rng.integers(0, 12)]
                      + 0.04 * rng.standard_normal(32) / np.sqrt(32))
        futs.append(eng.submit_at(0.004 * i, ServeRequest(i, "svc", v)))
    eng.drain()
    res = [(f.result.request_id, f.result.reuse, f.result.result,
            f.result.replica, f.result.latency_s, f.result.similarity,
            f.result.backup) for f in futs]
    return eng, res


def test_disarmed_is_bit_identical_to_armed_on_either_clock():
    plain, res = _workload(None)
    assert plain.loop.tracer is None
    assert plain.stats()["discarded_rows"] > 0
    for clock in ("virtual", "host"):
        eng, armed = _workload(clock)
        assert armed == res, clock
        assert eng.stats() == plain.stats(), clock
        assert eng.loop.now == plain.loop.now


def test_disarmed_touches_no_tracer(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("tracer touched while disarmed")

    for name in ("__init__", "span", "begin", "end", "instant", "complete",
                 "track", "abandon", "now"):
        monkeypatch.setattr(Tracer, name, boom)
    assert _scope(None, "engine/x", 0, rows=1) is _UNTRACED
    _workload(None)
    eng = _engine(max_batch=8)
    eng.submit(ServeRequest(0, "svc", _vecs(1, seed=16)[0]))
    eng.abort_all()


# ------------------------------------------------------------ the tracer
def test_arm_and_disarm_on_a_running_loop():
    loop = EventLoop()
    assert loop.tracer is None and loop.disarm_tracer() is None
    tr = loop.arm_tracer("host")
    assert loop.tracer is tr and tr.clock == "host"
    with tr.span("engine/x", "engine", 3, rows=2) as args:
        args["outcome"] = "done"
    (ev,) = tr.events
    assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["tid"] == 3
    assert ev["args"] == {"rows": 2, "outcome": "done"}
    assert loop.arm_tracer("virtual").clock == "virtual"   # replaces
    assert loop.disarm_tracer() is not tr and loop.tracer is None
    with pytest.raises(ValueError):
        Tracer(loop, clock="wall")


def test_host_tracer_records_compiles_inside_the_stage_until_disarmed():
    import jax
    import jax.numpy as jnp

    def execute(reqs):
        # a shape no earlier call compiled: one backend compile per call
        n = 7 + reqs[0].request_id
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((n, 3))).block_until_ready()
        return _execute(reqs)

    eng = _engine(execute=execute, clock="host", max_batch=1)
    eng.submit(ServeRequest(0, "svc", _vecs(1, seed=17)[0]))
    eng.drain()
    tr = eng.loop.tracer
    compiles = _spans(tr, "engine/compile")
    assert compiles and all(c["dur"] > 0 for c in compiles)
    (ex,) = _spans(tr, "engine/execute")
    assert all(_inside(c, ex) for c in compiles)
    assert eng.loop.disarm_tracer() is tr
    n = len(tr.events)
    jax.jit(lambda x: x - 5)(jnp.ones((3, 11))).block_until_ready()
    assert len(tr.events) == n      # no longer listening
    # the event name JAX reports compiles under, so a rename shows here
    from jax._src import dispatch
    assert obs_trace.COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT
