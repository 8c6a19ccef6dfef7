"""The program's own spans against the device trace.

The program's tracer, armed on the host clock (``EventLoop.arm_tracer``),
writes its scoped stages into the profiler trace as
``reservoir/engine/<stage>`` annotations, on the host plane beside the
device ops.  The service's jitted module is ``jit_serve`` and
``ReplicaEngine.execute_batch`` blocks on its result, so on one clock every
one of its device ops lies inside a ``reservoir/engine/execute`` span: the
share that does is the check of that clock, and where it is short the
device clock is moved by a fitted offset before the idle time is split by
program stage.

Like ``trace.py``, everything after ``load_program`` is arithmetic on
``(name, start_ns, dur_ns)`` tuples, so a test can feed it a synthesised
trace; ``raw`` is what ``trace.load`` returns.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.trace import Event, clip, gaps, union, window

PROGRAM_PREFIX = "reservoir/"
EXECUTE_SPAN = PROGRAM_PREFIX + "engine/execute"
SERVE_MODULE = "jit_serve:"
#: Containment under which the device clock is moved to fit the host's.
CONTAINED = 0.99
#: Device ops of the service closer than this belong to one call, for the
#: fit of the offset.
CALL_GAP_NS = 0.5e6


def load_program(trace_dir: str) -> List[Event]:
    """The program's spans (``reservoir/...``) on the host planes of the
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for plane in ProfileData.from_file(paths[-1]).planes
            if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events
            if e.name.startswith(PROGRAM_PREFIX)]


def _covered(spans: List[Tuple[float, float]]):
    """-> f(t): the length of the disjoint sorted ``spans`` that lies
    before ``t`` (vectorised over an array of times)."""
    a = np.asarray([s for s, _ in spans], np.float64)
    b = np.asarray([e for _, e in spans], np.float64)
    cum = np.concatenate(([0.0], np.cumsum(b - a)))

    def f(t: np.ndarray) -> np.ndarray:
        k = np.searchsorted(a, t, side="right")
        over = np.where(k > 0, b[np.maximum(k - 1, 0)] - t, 0.0)
        return cum[k] - np.maximum(over, 0.0)

    return f


def containment(device: List[Event], spans: List[Event]
                ) -> Tuple[float, float]:
    """(share, seconds) of the service's device time that lies inside the
    ``spans``."""
    ops = union([e for e in device if e[0].startswith(SERVE_MODULE)])
    if not ops or not spans:
        return 0.0, 0.0
    iv = np.asarray(ops, np.float64)
    f = _covered(union(spans))
    inside = float(np.sum(f(iv[:, 1]) - f(iv[:, 0])))
    total = float(np.sum(iv[:, 1] - iv[:, 0]))
    return inside / total, total * 1e-9


def fit_offset(device: List[Event], spans: List[Event]) -> float:
    """The shift of the device clock (ns) that puts the most of the
    service's device time inside the ``spans``; of equals, the one nearest
    0.  Containment is piecewise linear in the shift, so its maximum lies
    where an edge of a call of the service meets an edge of a span; the
    shifts tried are those that meet each call with the spans that start
    next to it, which finds any offset shorter than the time between two
    calls."""
    calls: List[List[float]] = []
    for a, b in union([e for e in device if e[0].startswith(SERVE_MODULE)]):
        if calls and a - calls[-1][1] < CALL_GAP_NS:
            calls[-1][1] = b
        else:
            calls.append([a, b])
    merged = union(spans)
    if not calls or not merged:
        return 0.0
    c = np.asarray(calls, np.float64)
    s = np.asarray(merged, np.float64)
    near = np.searchsorted(s[:, 0], c[:, 0])
    idx = np.clip(near[:, None] + np.arange(-2, 2)[None, :], 0, len(s) - 1)
    cand = np.unique(np.concatenate(
        [(s[idx, i] - c[:, j][:, None]).ravel()
         for i in (0, 1) for j in (0, 1)]))
    f = _covered(merged)
    scores = np.concatenate([
        np.sum(f(c[None, :, 1] + d[:, None]) - f(c[None, :, 0] + d[:, None]),
               axis=1)
        for d in np.array_split(cand, max(1, len(cand) // 2048))])
    best = np.flatnonzero(scores >= scores.max() - 1.0)   # within 1 ns
    return float(cand[best[np.argmin(np.abs(cand[best]))]])


def stage_idle(idle: List[Tuple[float, float]], spans: List[Event]
               ) -> Dict[str, float]:
    """Idle seconds by the innermost program span open at each instant of
    each idle interval (its name without ``reservoir/``), ``loop`` where
    none is open: an exact split, so the parts sum to the idle time.  The
    spans come from one thread, so they nest."""
    pieces: List[Tuple[float, float, str]] = []   # (start, end, label)
    stack: List[Tuple[float, float, str]] = []
    t = -np.inf

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][1] <= limit:
            _, e, n = stack.pop()
            pieces.append((t, e, n))
            t = e

    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        close_until(s)
        pieces.append((t, s, stack[-1][2] if stack else "loop"))
        t = s
        stack.append((s, s + d, name[len(PROGRAM_PREFIX):]))
    close_until(np.inf)
    pieces.append((t, np.inf, "loop"))
    out: Dict[str, float] = {}
    i = 0
    for a, b in idle:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            lo, hi = max(a, pieces[j][0]), min(b, pieces[j][1])
            if hi > lo:
                label = pieces[j][2]
                out[label] = out.get(label, 0.0) + (hi - lo) * 1e-9
            j += 1
    return out


def program_reduce(raw: dict, program: List[Event]) -> Optional[dict]:
    """The program's spans against the device trace, averaged over the
    device planes that hold ops (``trace.load`` also returns planes that
    hold none, such as ``/device:CUSTOM:Megascale Trace``): the raw and the
    corrected share of the service's device time
    inside ``engine/execute`` spans, the offset added to device times (ns;
    0 unless the raw share is under ``CONTAINED``), the service's device
    seconds, the idle split by program stage on the corrected clock, and
    the idle seconds in which the host was inside a program span.  None
    where the trace has no ``engine/execute`` span, as from a program
    without host-clock spans."""
    execute = [e for e in program if e[0] == EXECUTE_SPAN]
    devices = [ev for ev in raw["devices"] if ev]
    if not execute or not devices:
        return None
    lo, hi = window(raw["host"])
    scoped = clip(program, lo, hi)
    out: dict = {"window_s": (hi - lo) * 1e-9, "raw": 0.0, "contained": 0.0,
                 "offset_ns": 0.0, "serve_s": 0.0, "idle": {}}
    n = len(devices)
    for ev in devices:
        share, serve_s = containment(clip(ev, lo, hi), execute)
        shift = fit_offset(ev, execute) if share < CONTAINED else 0.0
        moved = clip([(name, s + shift, d) for name, s, d in ev], lo, hi)
        fixed, _ = containment(moved, execute)
        out["raw"] += share / n
        out["contained"] += fixed / n
        out["offset_ns"] += shift / n
        out["serve_s"] += serve_s / n
        for k, v in stage_idle(gaps(union(moved), lo, hi), scoped).items():
            out["idle"][k] = out["idle"].get(k, 0.0) + v / n
    out["host_bound_idle_s"] = sum(v for k, v in out["idle"].items()
                                   if k != "loop")
    return out
