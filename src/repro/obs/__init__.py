"""Observability layer (ISSUE 10): tracing, metrics registry, profiling.

Three independent pieces sharing the sanitizer's arming discipline
(DESIGN.md §Observability):

* ``trace``    — per-task distributed tracing on the virtual timeline,
                 exported as Chrome trace-event / Perfetto JSON.  Armed via
                 ``RESERVOIR_TRACE=1`` or ``EventLoop(trace=True)``, or on
                 the host clock with ``EventLoop.arm_tracer("host")``,
                 whose scoped spans also land in the JAX profiler trace;
                 disarmed it is a ``None`` attribute and costs one attribute
                 test per hook site.
* ``registry`` — unified counters/gauges/histograms.  ALWAYS ON: purely
                 observational (no RNG draws, no event scheduling), so it
                 cannot perturb the seeded goldens.  The legacy stats dicts
                 (``EdgeNode.stats``, ``Federator.stats``, ...) are
                 ``CounterGroup``s adopted into one ``MetricsRegistry``
                 without breaking their Mapping accessors.
* ``profiler`` — wall-time + kernel-counter accounting per EventLoop
                 callback site.  Armed via ``RESERVOIR_PROFILE=1`` or
                 ``EventLoop(profile=True)``.

This package is intentionally outside the sim-path lint packages: it is the
one place allowed to read the host's wall clock (the profiler measures the
simulator itself, never the virtual timeline).
"""
from .profiler import Profiler
from .registry import Counter, CounterGroup, Gauge, Histogram, MetricsRegistry
from .trace import Tracer

__all__ = [
    "Counter", "CounterGroup", "Gauge", "Histogram", "MetricsRegistry",
    "Tracer", "Profiler",
]
