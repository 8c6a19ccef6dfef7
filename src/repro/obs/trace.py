"""Per-task distributed tracing on the virtual or the host timeline.

One span per hop of a task's life: consumer submit -> forwarder hops
(PIT/CS) -> EN window/admission -> reuse query (staged vs fused, with
dispatch + sync-page counts) -> federation offload / migration / retx +
backup events -> backend execute -> Data return.  Events are stamped with
VIRTUAL time and exported as Chrome trace-event JSON (the ``traceEvents``
array format), openable directly in Perfetto / ``chrome://tracing``.

Arming follows the sanitizer pattern (DESIGN.md §Observability):
``RESERVOIR_TRACE=1`` at EventLoop construction, or
``EventLoop(trace=True)``.  Disarmed, every hook site is a single
``tracer is None`` test and the simulation is bit-identical to a build
without the tracer (asserted by tests/test_obs.py against the seeded
goldens).

Host-clock mode (``EventLoop.arm_tracer("host")``) stamps events with the
host's monotonic clock instead, for a loop driven in real time on the
accelerator: each scoped ``span`` also opens a
``jax.profiler.TraceAnnotation("reservoir/<name>")``, so the program's
stages land in a profiler trace on the same host plane as the device ops,
and every backend compile JAX reports while the tracer is armed becomes an
``engine/compile`` span.  This package is the one place allowed to read the
wall clock (lint rule D002), so the serving code never reads one itself.

Track model: each task gets its own ``tid`` (= task id) so its spans nest
on one timeline row; shared infrastructure (per-EN windows, migration,
gossip) lives on named tracks with reserved large tids.  Cross-track
parenting is by ``args={"task": <tid>}`` — the well-formedness contract
(tests): every offload/retx/backup/migration event carries its originating
task, and no span is left open once the loop drains to idle.
"""
from __future__ import annotations

import itertools
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

_ENV = "RESERVOIR_TRACE"
PID = 1
#: First tid handed to named (non-task) tracks; task ids stay far below.
TRACK_TID_BASE = 1_000_000_000
#: Prefix of the profiler annotations that host-mode spans open.
ANNOTATION_PREFIX = "reservoir/"
#: The event JAX reports once per backend compile, with its duration.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CLOCKS = ("virtual", "host")


def env_enabled() -> bool:
    """True when RESERVOIR_TRACE asks for an armed tracer."""
    return os.environ.get(_ENV, "").strip().lower() in ("1", "true", "yes", "on")


class Tracer:
    """Chrome-trace-event recorder bound to one EventLoop's clock.

    ``clock="virtual"`` (the default) stamps the loop's virtual ``now``;
    ``clock="host"`` stamps the host's monotonic clock (seconds).

    Spans that cross async hops use explicit handles: ``begin`` returns a
    span id, ``end`` closes it (emitting one complete "X" event); they stay
    in memory only.  Spans that open and close inside one call use the
    scoped ``span`` context manager, which in host mode also writes the span
    into the profiler trace.  Point events use ``instant``; spans whose
    duration is known up front use ``complete``.  ``open_spans`` exposes
    what is still unclosed — empty at drain-to-idle is the well-formedness
    invariant.  A host-mode tracer listens to JAX's compile events until
    ``close``.
    """

    def __init__(self, loop: Any, clock: str = "virtual"):
        if clock not in CLOCKS:
            raise ValueError(f"clock must be one of {CLOCKS}, not {clock!r}")
        self.loop = loop
        self.clock = clock
        self.events: List[Dict[str, Any]] = []
        self._open: Dict[int, Tuple[str, str, int, float, Dict[str, Any]]] = {}
        self._sids = itertools.count(1)
        self._tracks: Dict[str, int] = {}
        self._thread_names: Dict[int, str] = {}
        self._annotation: Any = None
        self._listener: Any = None
        if clock == "host":
            import jax

            self._annotation = jax.profiler.TraceAnnotation
            self._listener = self._on_duration
            jax.monitoring.register_event_duration_secs_listener(
                self._listener)

    def now(self) -> float:
        """The tracer's clock in seconds."""
        return time.perf_counter() if self.clock == "host" else self.loop.now

    def close(self) -> None:
        """Stop listening to JAX's compile events (host mode); recorded
        events stay readable."""
        if self._listener is not None:
            import jax

            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None

    def _on_duration(self, event: str, secs: float, **kwargs: Any) -> None:
        if event == COMPILE_EVENT:
            t1 = self.now()
            self.complete("engine/compile", "compile", self.track("compile"),
                          t0=t1 - secs, dur=secs,
                          fun=str(kwargs.get("fun_name", "")))

    # ---------------------------------------------------------------- tracks
    def track(self, name: str) -> int:
        """Stable tid for a named (non-task) track, e.g. ``en/fwd1``."""
        tid = self._tracks.get(name)
        if tid is None:
            tid = TRACK_TID_BASE + len(self._tracks)
            self._tracks[name] = tid
            self._thread_names[tid] = name
        return tid

    def name_task(self, tid: int, name: str) -> None:
        if tid not in self._thread_names:
            self._thread_names[tid] = name

    # ----------------------------------------------------------------- spans
    def span(self, name: str, cat: str, tid: int, **args: Any) -> "_Span":
        """Scoped span: ``with tracer.span(...) as args:`` records one
        complete event when the block exits; the block may add to ``args``.
        In host mode the block also runs inside a profiler annotation named
        ``reservoir/<name>``."""
        return _Span(self, name, cat, tid, args)

    def begin(self, name: str, cat: str, tid: int,
              t: Optional[float] = None, **args: Any) -> int:
        sid = next(self._sids)
        self._open[sid] = (name, cat, tid,
                           self.now() if t is None else t, args)
        return sid

    def end(self, sid: Optional[int], t: Optional[float] = None,
            **args: Any) -> None:
        if sid is None:
            return
        entry = self._open.pop(sid, None)
        if entry is None:  # already closed (racing completions): keep first
            return
        name, cat, tid, t0, a0 = entry
        t1 = self.now() if t is None else t
        if args:
            a0 = {**a0, **args}
        self.events.append({"name": name, "cat": cat, "ph": "X",
                            "ts": t0 * 1e6, "dur": max(t1 - t0, 0.0) * 1e6,
                            "pid": PID, "tid": tid, "args": a0})

    def complete(self, name: str, cat: str, tid: int, t0: float,
                 dur: float, **args: Any) -> None:
        self.events.append({"name": name, "cat": cat, "ph": "X",
                            "ts": t0 * 1e6, "dur": max(dur, 0.0) * 1e6,
                            "pid": PID, "tid": tid, "args": args})

    def instant(self, name: str, cat: str, tid: int,
                t: Optional[float] = None, **args: Any) -> None:
        self.events.append({"name": name, "cat": cat, "ph": "i",
                            "ts": (self.now() if t is None else t) * 1e6,
                            "s": "t", "pid": PID, "tid": tid, "args": args})

    def open_spans(self) -> List[Tuple[int, str, str, int]]:
        """Unclosed spans as (sid, name, cat, tid) — must be empty once the
        simulation has drained to idle."""
        return [(sid, name, cat, tid)
                for sid, (name, cat, tid, _, _) in self._open.items()]

    def abandon(self, sid: Optional[int], t: Optional[float] = None,
                why: str = "abandoned") -> None:
        """Close a span whose task will never complete (lost past the retx
        budget, stranded at a crashed EN, ...) — the tracing analogue of
        ``Sanitizer.note_loss``."""
        self.end(sid, t, outcome=why)

    # ---------------------------------------------------------------- export
    def to_chrome(self) -> Dict[str, Any]:
        meta: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": PID, "tid": 0,
            "args": {"name": "reservoir-sim"}}]
        for tid, name in sorted(self._thread_names.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": PID,
                         "tid": tid, "args": {"name": name}})
        return {"traceEvents": meta + self.events,
                "displayTimeUnit": "ms"}

    def export(self, path: Optional[str] = None) -> Dict[str, Any]:
        doc = self.to_chrome()
        if path:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


class _Span:
    """One scoped span of a ``Tracer`` (see ``Tracer.span``)."""

    __slots__ = ("tracer", "name", "cat", "tid", "args", "t0", "annotation")

    def __init__(self, tracer: Tracer, name: str, cat: str, tid: int,
                 args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self.t0 = 0.0
        self.annotation: Any = None

    def __enter__(self) -> Dict[str, Any]:
        make = self.tracer._annotation
        if make is not None:
            self.annotation = make(ANNOTATION_PREFIX + self.name)
            self.annotation.__enter__()
        self.t0 = self.tracer.now()
        return self.args

    def __exit__(self, *exc: Any) -> None:
        t1 = self.tracer.now()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        self.tracer.complete(self.name, self.cat, self.tid, self.t0,
                             t1 - self.t0, **self.args)
