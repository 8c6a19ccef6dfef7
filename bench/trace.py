"""Reduction of a profiler trace to the numbers the per-layer readers use.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
returns plain lists; everything after that is arithmetic on
``(name, start_ns, dur_ns)`` tuples, so a test can feed it a synthesised
trace.  Device events are the operations on the ``/device:TPU:*`` planes
(their "XLA Ops" line where there is one); host spans are the benchmark's
own ``jax.profiler.TraceAnnotation`` events, named ``bench/<layer>``.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "window"


def load(trace_dir: str) -> dict:
    """-> {"devices": [[Event, ...] per device], "host": [Event, ...]}."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((float(e.start_ns), float(e.duration_ns),
                           e.name.split("(")[0])
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else []))
            ops = [lines["XLA Ops"]] if "XLA Ops" in lines else list(lines.values())
            devices.append([(op_name(e.name, float(e.start_ns), mods),
                             float(e.start_ns), float(e.duration_ns))
                            for ln in ops for e in ln.events])
        elif plane.name.startswith("/host:"):
            host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                        for ln in plane.lines for e in ln.events
                        if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def op_name(text: str, start: float, modules: List[tuple]) -> str:
    """``<module>:<op>`` from an op's HLO text and the device's module
    events, e.g. ``jit_reuse_top1:%closed_call.7[tpu_custom_call]``: the
    module names the jitted function, the tag marks a Pallas kernel."""
    op = text.split(" = ")[0]
    if 'custom_call_target="tpu_custom_call"' in text:
        op += "[tpu_custom_call]"
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if i >= 0 and start <= modules[i][0] + modules[i][1]:
        return f"{modules[i][2]}:{op}"
    return op


def window(host: List[Event]) -> Tuple[float, float]:
    """(start_ns, end_ns) of the measured window's span."""
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no window span")
    _, s, d = max(spans, key=lambda e: e[2])
    return s, s + d


def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals [(start, end), ...] of the events."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(events: List[Event]) -> float:
    return sum(b - a for a, b in union(events)) * 1e-9


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of the window between busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_seconds(events: List[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, d in events:
        out[name] = out.get(name, 0.0) + d * 1e-9
    return out


def attribute_gaps(idle: List[Tuple[float, float]], host: List[Event]
                   ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: the innermost benchmark span
    that covers the middle of each gap, or ``loop`` (the generator and the
    event loop between the program's calls)."""
    spans = sorted((s, s + d, n[len(SPAN_PREFIX):]) for n, s, d in host
                   if n != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    out: Dict[str, float] = {}
    for a, b in idle:
        mid = 0.5 * (a + b)
        best = None
        # spans nest only a few deep, so the covering ones start among the
        # last few that start before the middle
        for s, e, n in reversed(spans[max(0, bisect.bisect_right(starts, mid)
                                           - 64):bisect.bisect_right(starts, mid)]):
            if e >= mid and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        key = best[2] if best else "loop"
        out[key] = out.get(key, 0.0) + (b - a) * 1e-9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(raw: dict) -> dict:
    """Window, busy seconds (mean over devices), per-op device seconds and
    idle gaps by host activity."""
    lo, hi = window(raw["host"])
    per_dev = [clip(ev, lo, hi) for ev in raw["devices"]]
    if not per_dev:
        raise ValueError("trace has no device plane")
    busy = [busy_seconds(ev) for ev in per_dev]
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for ev in per_dev:
        for k, v in op_seconds(ev).items():
            ops[k] = ops.get(k, 0.0) + v / len(per_dev)
        for k, v in attribute_gaps(gaps(union(ev), lo, hi),
                                   clip(raw["host"], lo, hi)).items():
            idle[k] = idle.get(k, 0.0) + v / len(per_dev)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / len(busy),
            "ops": ops, "idle": idle, "device_events": per_dev}
