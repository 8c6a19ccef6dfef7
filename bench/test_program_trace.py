"""CPU tests of the reduction of the program's own spans against the
device trace (``program_trace.py``) and of the readers built on it: on
synthesised traces, with a planted offset between the device and host
clocks, and on a tiny run through the harness's engine and open loop with
the program's host-clock tracer armed."""
from __future__ import annotations

import types

import pytest

from bench import generator, harness
from bench import program_trace as pt
from bench import trace as tr
from bench.test_bench import TINY, _config, _tiny

MS = 1e6
NEW_READERS = ("queue_wait_ms_per_task", "wasted_exec_rows_pct",
               "host_bound_idle_pct")


def _program_trace(shift_ms=0.0):
    """Three calls of the service, each filling its engine/execute span
    inside a dispatch, then a commit, and one search kernel; device ops
    moved by ``shift_ms`` as if the device clock ran that far ahead."""
    program, device = [], []
    for k, t in enumerate((10.0, 40.0, 70.0)):
        program += [("reservoir/engine/dispatch", t * MS, 14 * MS),
                    ("reservoir/engine/search", t * MS, 2 * MS),
                    ("reservoir/engine/execute", (t + 2) * MS, 12 * MS),
                    ("reservoir/engine/commit", (t + 16) * MS, 2 * MS)]
        device += [(f"jit_serve:%fusion.{k}", (t + 2 + shift_ms) * MS, 6 * MS),
                   (f"jit_serve:%while.{k}", (t + 8 + shift_ms) * MS, 6 * MS)]
    device.append(("jit_reuse_top1:%closed_call", (41 + shift_ms) * MS, MS))
    return {"devices": [device], "host": [("bench/window", 0.0, 100 * MS)]}, \
        program


def test_program_reduction_on_one_clock():
    raw, program = _program_trace()
    prog = pt.program_reduce(raw, program)
    assert prog["raw"] == pytest.approx(1.0)
    assert prog["contained"] == pytest.approx(1.0)
    assert prog["offset_ns"] == 0.0
    assert prog["serve_s"] == pytest.approx(0.036)
    red = tr.reduce(raw)
    # 100 ms less 36 ms of service and 1 ms of search kernel: per call 2 ms
    # of search (1 ms in the second), 2 ms of commit, the rest in no span
    assert sum(prog["idle"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert prog["idle"] == {"engine/search": pytest.approx(0.005),
                            "engine/commit": pytest.approx(0.006),
                            "loop": pytest.approx(0.052)}
    assert prog["host_bound_idle_s"] == pytest.approx(0.011)


def test_program_reduction_finds_and_removes_a_planted_offset():
    raw, program = _program_trace(shift_ms=5.0)
    prog = pt.program_reduce(raw, program)
    # 5 ms late: the last 5 ms of each 12 ms call leave its span
    assert prog["raw"] == pytest.approx(7 / 12)
    assert prog["offset_ns"] == pytest.approx(-5e6)
    assert prog["contained"] == pytest.approx(1.0)
    assert prog["idle"]["engine/search"] == pytest.approx(0.005)
    assert "engine/execute" not in prog["idle"]
    # on the raw clock the same trace puts the first 5 ms of each execute
    # span down as idle, less the search kernel moved into the second one
    raw_split = pt.stage_idle(
        tr.gaps(tr.union(raw["devices"][0]), 0.0, 100 * MS), program)
    assert raw_split["engine/execute"] == pytest.approx(0.014)


def test_program_reduction_ignores_device_planes_without_ops():
    # the chip's trace also holds /device:CUSTOM:Megascale Trace, which
    # ``trace.load`` returns as a device with no events
    raw, program = _program_trace(shift_ms=5.0)
    alone = pt.program_reduce(raw, program)
    both = pt.program_reduce({**raw, "devices": raw["devices"] + [[]]},
                             program)
    assert both == alone


def test_program_reduction_is_none_without_program_spans():
    raw, _ = _program_trace()
    assert pt.program_reduce(raw, []) is None
    assert pt.containment(raw["devices"][0], []) == (0.0, 0.0)


def test_stage_idle_takes_the_innermost_span_at_each_instant():
    spans = [("reservoir/engine/admit", 0.0, 10 * MS),
             ("reservoir/engine/route", 2 * MS, 3 * MS),
             ("reservoir/engine/dispatch", 12 * MS, 4 * MS)]
    split = pt.stage_idle([(1 * MS, 4 * MS), (9 * MS, 13 * MS)], spans)
    assert split == {"engine/admit": pytest.approx(0.002),
                     "engine/route": pytest.approx(0.002),
                     "loop": pytest.approx(0.002),
                     "engine/dispatch": pytest.approx(0.001)}


def _ctx(**program):
    ctx = harness.Context(cfg={}, peaks=None, spans=harness.Spans(),
                          trace={}, counters=program.pop("counters", {}),
                          exec_calls=[], prompt_len=0)
    for k, v in program.items():
        setattr(ctx, k, v)
    return ctx


def test_new_readers_on_a_synthesised_trace():
    raw, program = _program_trace(shift_ms=5.0)
    events = [{"name": "engine/queue", "ph": "X", "ts": 0.0, "dur": d,
               "tid": i, "args": {}} for i, d in enumerate((4000.0, 12000.0))]
    events.append({"name": "engine/admit", "ph": "X", "ts": 0.0,
                   "dur": 9e6, "tid": 0, "args": {}})
    ctx = _ctx(counters={"exec_rows": 40, "discarded_rows": 14},
               program_events=events,
               program_trace=pt.program_reduce(raw, program))
    read = {n: harness.load_reader(n)(ctx) for n in NEW_READERS}
    assert read == {"queue_wait_ms_per_task": pytest.approx(8.0),
                    "wasted_exec_rows_pct": pytest.approx(35.0),
                    "host_bound_idle_pct": pytest.approx(11.0)}


def test_new_readers_read_nothing_from_a_run_without_program_spans():
    ctx = _ctx(counters={"dispatches": 3, "queries": 6})
    assert [harness.load_reader(n)(ctx) for n in NEW_READERS] == [None] * 3


@pytest.fixture(scope="module")
def tiny_run():
    """A tiny run through the harness's engine, instrumentation and open
    loop, the program's host-clock tracer armed for the window and a
    profiler session around it."""
    import tempfile

    import jax

    from bench import service

    cfg_name, traffic = TINY["phi"]
    cfg, arch = _tiny(_config(cfg_name))
    svc = service.Service(cfg, jax.random.PRNGKey(0), model_override=arch)
    svc.warm()
    engine = harness.build_engine(cfg, svc)
    seg = generator.segment(traffic, 40, 0.6, 2**31 + 17, "window",
                            svc.prompt, cfg["model"]["vocab_size"],
                            svc.pool_size)
    reqs = harness.make_requests(seg, "/pandaset", 0.9, 0)
    spans = harness.Spans()
    harness.instrument(engine, "/pandaset", harness.RunLog(), spans,
                       lambda e: -1, None)
    harness.drive(engine, reqs[:4], seg.due[:4], 0.05, 30.0)   # warm
    spans.on = True
    tracer = engine.loop.arm_tracer("host")
    d = tempfile.mkdtemp(prefix="bench-test-trace-")
    jax.profiler.start_trace(d)
    harness.drive(engine, reqs, seg.due, 0.6, 30.0)
    jax.profiler.stop_trace()
    assert engine.loop.disarm_tracer() is tracer
    return tracer, spans, pt.load_program(d)


def test_program_spans_agree_with_the_bench_spans_beside_them(tiny_run):
    """Each engine/search and engine/commit span holds exactly one bench
    span of the same call; their totals differ only by the engine's own
    bookkeeping around the call."""
    tracer, spans, _ = tiny_run
    assert not tracer.open_spans()
    for stage in ("search", "commit"):
        prog = [e["dur"] * 1e-6 for e in tracer.events
                if e["name"] == "engine/" + stage]
        bench = spans.calls[stage]
        assert len(prog) == len(bench) > 0
        assert sum(bench) <= sum(prog) <= sum(bench) + 0.002 * len(prog)


def test_program_spans_land_in_the_profiler_trace(tiny_run):
    tracer, _, program = tiny_run
    names = {n for n, _, _ in program}
    assert {"reservoir/engine/admit", "reservoir/engine/route",
            "reservoir/engine/dispatch", "reservoir/engine/search",
            "reservoir/engine/execute",
            "reservoir/engine/commit"} <= names
    scoped = [e for e in tracer.events if e["ph"] == "X"
              and e["name"] != "engine/queue"
              and e["name"] != "engine/compile"]
    assert len(program) == len(scoped)
    execute = [e for e in program if e[0] == pt.EXECUTE_SPAN]
    dispatch = [e for e in program if e[0] == "reservoir/engine/dispatch"]
    assert all(any(ds <= es and es + ed <= ds + dd for _, ds, dd in dispatch)
               for _, es, ed in execute)
