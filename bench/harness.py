"""One run of one benchmark cell, from set-up to the result line.

A cell is a configuration (``configs/<name>.json``) under a traffic mix
(``traffic/<name>.json``); ``BENCHMARK.json`` names both.  The run:

1. set-up: device check, compile cache, weights and the program's prefill,
   the serving engine with the deployment's replicas, history inserted
   through the program's insert path, a warm-up segment of the same mix, and
   the candidate-width and batch shapes of the staged search;
2. the window: an open-loop generator submits each task to
   ``AsyncServingEngine`` at its due time and runs the engine's event loop on
   the host clock in between; a task completes when its future resolves;
3. the drain: the loop runs on, without arrivals, until every task is
   answered or the mix's ``drain_s`` has passed;
4. the check (``check.py``) of everything the run logged against the plain
   references, once the program's state is freed;
5. the metrics: end-to-end with ``--trace 0``, per-layer readers
   (``metrics/<name>.py``) over the profiler trace with ``--trace 1``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import generator

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))


class NoChip(RuntimeError):
    """The measurement path found no accelerator, or too few chips."""


def load_cell(workload: str, bench_file: Optional[str] = None) -> dict:
    with open(bench_file or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": generator.load_traffic(cell["traffic"])}


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event.endswith("/cache_hits"):
            self.cache_hits += 1

    def mark(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits


def compile_cache(min_compile_s: float) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where the
    environment sets it, else ``<checkout>/.jax_cache``.  Set-up's shapes are
    written whatever their compile time; the window's are written only past
    JAX's default second, so that a window never finds what an earlier
    window compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_s)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ logging
@dataclasses.dataclass
class RunLog:
    """What the program did, in order, as the check needs it."""

    route: List[tuple] = dataclasses.field(default_factory=list)
    events: List[tuple] = dataclasses.field(default_factory=list)


class Spans:
    """Host spans around the program's calls (``--trace 1``): written into
    the profiler trace as ``bench/<name>`` and kept as host-clock
    durations."""

    def __init__(self):
        self.on = False
        self.calls: Dict[str, List[float]] = {}

    def wrap(self, name: str, fn: Callable) -> Callable:
        import jax

        label = "bench/" + name

        def wrapped(*args, **kwargs):
            if not self.on:
                return fn(*args, **kwargs)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*args, **kwargs)
            self.calls.setdefault(name, []).append(time.perf_counter() - t)
            return out

        return wrapped


def instrument(engine, service: str, log: RunLog, spans: Spans, task_of_emb,
               fault: Optional[str]) -> None:
    """Wrap the program's router and replica stages on these instances: the
    run log for the check, host spans for the readers, and (tests only) a
    fault planted in the timed path."""
    router = engine.router
    route = router.route

    def logged_route(embedding):
        rid, buckets = route(embedding)
        log.route.append((task_of_emb(embedding), int(rid),
                          np.asarray(buckets).copy()))
        return rid, buckets

    router.route = spans.wrap("route", logged_route)
    for rep in engine.replicas:
        rid = rep.replica_id

        def query(svc, embs, thrs, _q=rep.query_reuse, _rid=rid):
            out = _q(svc, embs, thrs)
            if fault == "store":   # every hit answered as a miss
                out = [(r, s, None) for r, s, _ in out]
            log.events.append(("query", _rid, np.array(embs), np.array(thrs),
                               [(r, float(s), i) for r, s, i in out]))
            return out

        def execute(reqs, _x=rep.execute_batch):
            if fault == "half" and len(reqs) > 1:
                h = (len(reqs) + 1) // 2
                outs, wall = _x(reqs[:h])
                outs = outs + [outs[-1]] * (len(reqs) - h)
            else:
                outs, wall = _x(reqs)
            if fault == "token":
                outs = [o + 1 for o in outs]
            return outs, wall

        def commit(svc, embs, names, outs, now, exec_s, buckets=None,
                   _c=rep.commit_execution, _rid=rid):
            log.events.append(("commit", _rid, np.array(embs), list(outs)))
            return _c(svc, embs, names, outs, now, exec_s, buckets=buckets)

        rep.query_reuse = spans.wrap("search", query)
        rep.execute_batch = spans.wrap("execute", execute)
        rep.commit_execution = spans.wrap("commit", commit)


# --------------------------------------------------------------- the engine
def build_engine(cfg: dict, svc):
    from repro.core.lsh import LSHParams
    from repro.core.sim_clock import EventLoop
    from repro.serving import AsyncServingEngine, ReplicaEngine

    st, eng = cfg["store"], cfg["engine"]
    params = LSHParams(dim=st["dim"], num_tables=st["num_tables"],
                       rotations_per_table=st["rotations_per_table"],
                       num_buckets=st["num_buckets"],
                       num_probes=st["num_probes"], family=st["family"],
                       seed=st["seed"])
    replicas = [ReplicaEngine(i, params, svc.execute,
                              store_capacity=st["capacity"])
                for i in range(eng["replicas"])]
    engine = AsyncServingEngine(params, replicas, loop=EventLoop(),
                                max_batch=eng["max_batch"],
                                max_wait_s=eng["max_wait_ms"] * 1e-3)
    return engine


def fill_history(engine, service: str, hist: np.ndarray) -> np.ndarray:
    """Route the history through the program's router and insert each
    replica's share through the store's insert path; -> (N, T) buckets."""
    if not len(hist):
        return np.zeros((0, engine.router.params.num_tables), np.int64)
    owners, buckets = engine.router.route_batch(hist)
    owners, buckets = np.asarray(owners), np.asarray(buckets)
    for rep in engine.replicas:
        idx = np.flatnonzero(owners == rep.replica_id)
        store = rep._store(service)
        for lo in range(0, len(idx), 8192):
            part = idx[lo:lo + 8192]
            store.insert_batch(hist[part], [-(int(i) + 1) for i in part],
                               buckets=buckets[part])
        store.sync_device(ensure=True)
    return buckets


def warm_search(engine, service: str, n_more: int, max_batch: int) -> int:
    """Compile the staged search's shapes: the probe for every group size,
    and the candidate gather/top-1 for every group size at the narrowest
    width and at every width (in the kernel's 128 steps) that the stores can
    reach in this run.  Returns the number of widths."""
    import jax.numpy as jnp

    from repro.kernels import ops

    widths = 0
    for rep in engine.replicas:
        store = rep._store(service)
        p = store.params
        dim = p.dim
        for n in range(1, max_batch + 1):
            store.lsh.probe_batch(np.zeros((n, dim), np.float32))
        entries = len(store) + n_more
        pages = -(-max(entries, 1) // store.page_size)
        alloc = 1
        while alloc < pages:
            alloc *= 2
        if store.device_pages and store.device_pages != alloc:
            alloc = store.device_pages
        buf = store._emb_dev if store.device_pages == alloc else jnp.zeros(
            (alloc, store.page_size, dim), jnp.float32)
        top = min(p.num_tables * p.num_probes * store.bucket_cap,
                  p.num_tables * entries)
        q = np.zeros((max_batch, dim), np.float32)
        for n in range(1, max_batch + 1):
            ops.gathered_top1(q[:n], buf, np.full((n, 128), -1, np.int32))
        for c in range(128, -(-top // 128) * 128 + 1, 128):
            ids = np.full((max_batch, c), -1, np.int32)
            ids[:, 0] = 0
            val, _ = ops.gathered_top1(q, buf, ids)
            widths += 1
        np.asarray(val)
    return widths


# ---------------------------------------------------------------- the loop
@dataclasses.dataclass
class Segment:
    reqs: list
    due: np.ndarray
    submitted: np.ndarray
    resolved: np.ndarray
    futures: list


def make_requests(seg, service: str, threshold: float, first_id: int):
    from repro.serving import ServeRequest

    return [ServeRequest(first_id + i, service, seg.emb[i],
                         payload={"tokens": seg.tokens[i],
                                  "image": int(seg.image[i])},
                         threshold=threshold)
            for i in range(len(seg.due))]


def drive(engine, reqs, due: np.ndarray, seconds: float, drain_s: float,
          at_close: Optional[Callable[[], None]] = None, span=None,
          clock=time.perf_counter) -> Segment:
    """Open loop: submit each request at its due time (host seconds from the
    start), run the engine's event loop on the host clock between arrivals,
    stop arrivals after ``seconds``, call ``at_close`` and drain for at most
    ``drain_s`` more."""
    loop = engine.loop
    n = len(reqs)
    submitted = np.full(n, np.nan)
    resolved = np.full(n, np.nan)
    futures = [None] * n
    base = loop.now
    t0 = clock()

    def done(i):
        def cb(_fut):
            resolved[i] = clock() - t0
        return cb

    i = 0
    with span or contextlib.nullcontext():
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            loop.run(until=base + now)
            while i < n and due[i] <= now:
                submitted[i] = clock() - t0
                fut = engine.submit(reqs[i])
                futures[i] = fut
                fut.add_done_callback(done(i))
                i += 1
    if at_close is not None:
        at_close()
    end = seconds + drain_s
    while any(f is not None and not f.done for f in futures):
        now = clock() - t0
        if now >= end:
            break
        loop.run(until=base + now)
    return Segment(reqs, due, submitted, resolved, futures)


# ------------------------------------------------------------------ metrics
def percentile(times: np.ndarray, q: float) -> float:
    """Nearest-rank percentile; an unanswered task counts as infinitely
    late."""
    x = np.sort(np.where(np.isnan(times), np.inf, times))
    return float(x[max(int(np.ceil(q / 100.0 * len(x))) - 1, 0)])


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(devices, peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ------------------------------------------------------------------ the run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, fault: Optional[str] = None,
        bench_file: Optional[str] = None, config_override: Optional[dict] = None,
        traffic_override: Optional[dict] = None, model_override=None,
        stats: Optional[dict] = None, control: bool = False,
        err=sys.stderr) -> dict:
    import jax

    from bench import check, service
    from bench.peaks import peaks_for

    gc.collect()   # a previous run's weights, where one process runs several
    spec = load_cell(workload, bench_file)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    cfg = config_override or cfg
    traffic = traffic_override or traffic
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        raise NoChip(f"the cell needs {cell['chips']} chips, JAX found "
                     f"{len(devices)}")
    devices = devices[:cell["chips"]]
    peaks = peaks_for(devices[0].device_kind) if require_tpu else None
    cache = compile_cache if require_tpu else (lambda _s: None)
    cache(0.0)
    clog = CompileLog()
    name = "/" + traffic["stream"]

    key = jax.random.fold_in(jax.random.PRNGKey(
        generator.derive_seed(seed, "weights") % (1 << 32)), 0)
    svc = service.Service(cfg, key, model_override=model_override)
    svc.warm()
    engine = build_engine(cfg, svc)
    hist = generator.history(traffic, seed)
    hist_buckets = fill_history(engine, name, hist)

    m = cfg["model"]
    n_win, n_warm, warm_s = generator.window_counts(traffic, seconds)
    warm = generator.segment(traffic, n_warm, warm_s, seed, "warmup",
                             svc.prompt, m["vocab_size"], svc.pool_size)
    win = generator.segment(traffic, n_win, seconds, seed, "window",
                            svc.prompt, m["vocab_size"], svc.pool_size)
    tasks = np.concatenate([warm.emb, win.emb])
    threshold = float(traffic["threshold"])
    reqs_warm = make_requests(warm, name, threshold, 0)
    reqs_win = make_requests(win, name, threshold, n_warm)
    emb_ids = {id(r.embedding): r.request_id for r in reqs_warm + reqs_win}
    log, spans = RunLog(), Spans()
    instrument(engine, name, log, spans, lambda e: emb_ids.get(id(e), -1),
               fault)
    # warm-up: the same mix through the same loop, then the search shapes
    cache(1.0)
    seg_warm = drive(engine, reqs_warm, warm.due, warm_s, traffic["drain_s"])
    cache(0.0)
    widths = warm_search(engine, name, n_win, cfg["engine"]["max_batch"])
    for rep in engine.replicas:
        rep._store(name).sync_device(ensure=True)
    jax.effects_barrier()
    cache(1.0)
    gc.collect()

    # the window
    stores = [rep._store(name) for rep in engine.replicas]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    closed: dict = {}

    def close():
        closed["compiles"] = clog.mark()
        closed["counters"] = _counters(engine, stores)
        closed["exec_calls"] = list(svc.calls)
        if trace:
            jax.profiler.stop_trace()
            spans.on = False

    c0 = clog.mark()
    counters0 = _counters(engine, stores)
    if trace:
        spans.on = True
        jax.profiler.start_trace(trace_dir)
    t_setup = time.perf_counter() - t_start
    svc.calls.clear()
    seg = drive(engine, reqs_win, win.due, seconds, traffic["drain_s"],
                at_close=close, span=jax.profiler.TraceAnnotation(
                    "bench/window") if trace else None)
    c1, counters1, exec_calls = (closed["compiles"], closed["counters"],
                                 closed["exec_calls"])
    found = check.found_again(engine, name, log, tasks, seed)
    peak = memory_peak(devices)
    served = _served(seg_warm, 0) + _served(seg, 1)

    # free the program's state before the reference runs on the chip
    del engine, stores
    gc.collect()
    result = check.run_checks(
        cfg=cfg, seed=seed, log=log, tasks=tasks,
        tokens=np.concatenate([warm.tokens, win.tokens]),
        images=np.concatenate([warm.image, win.image]),
        hist=hist, hist_buckets=hist_buckets, served=served, found=found,
        weights=svc.weights, image_pool=svc.images, control=control)

    attempted = int(np.sum(~np.isnan(seg.submitted)))
    lat = (seg.resolved - seg.due)[~np.isnan(seg.submitted)]
    late = (seg.submitted - seg.due)[~np.isnan(seg.submitted)]
    failed = int(np.sum(np.isnan(lat)))
    result["checks"]["unanswered"] = (failed, 0)
    done_in_window = int(np.sum(seg.resolved <= seconds))
    reuse = [s[1] for s in served[n_warm:] if s is not None]
    print(f"generator: {attempted} of {n_win} tasks submitted, lateness "
          f"p50 {np.median(late) * 1e3:.3f} ms, p99 "
          f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
          f"{late.max() * 1e3:.3f} ms", file=err)
    print(f"answers: cs {reuse.count('cs')}, store {reuse.count('en')}, "
          f"executed {reuse.count(None)}, unanswered {failed}; "
          f"{done_in_window} done inside the window", file=err)
    print(f"compiles in the window: {c1[0] - c0[0]} ({c1[1] - c0[1]:.3f} s), "
          f"persistent-cache hits {c1[2] - c0[2]}; set-up warmed {widths} "
          f"candidate widths", file=err)
    print(f"peak device memory {peak} bytes; set-up {t_setup:.3f} s",
          file=err)

    if stats is not None:
        stats.update(attempted=attempted, done_in_window=done_in_window,
                     p50_ms=percentile(lat, 50) * 1e3,
                     p95_ms=percentile(lat, 95) * 1e3,
                     late_p99_ms=float(np.percentile(late, 99)) * 1e3,
                     compiles=c1[0] - c0[0], compile_s=c1[1] - c0[1],
                     setup_s=t_setup, peak=peak, served=[
                         s[1] for s in served[n_warm:] if s is not None],
                     checks=result["checks"],
                     program_gap=result["program_gap"])
    metrics: Dict[str, dict] = {}
    bench = spec["bench"]
    extra = {}
    if not trace:
        values = {
            "setup_s": t_setup,
            "completion_p50_ms": percentile(lat, 50) * 1e3,
            "completion_p95_ms": percentile(lat, 95) * 1e3,
            "tasks_per_s": done_in_window / seconds,
        }
        for mdef in bench["end_to_end"]:
            if _applies(mdef, workload) and np.isfinite(values[mdef["name"]]):
                metrics[mdef["name"]] = {"value": values[mdef["name"]],
                                         "unit": mdef["unit"]}
    else:
        from bench import trace as tr

        raw = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tr.reduce(raw)
        ctx = Context(cfg=cfg, peaks=peaks, spans=spans, trace=red,
                      counters={k: counters1[k] - counters0[k]
                                for k in counters0},
                      exec_calls=exec_calls, prompt_len=svc.prompt + svc.front)
        for mdef in bench["per_layer"]:
            if not _applies(mdef, workload):
                continue
            v = load_reader(mdef["name"])(ctx)
            if v is not None:
                metrics[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}
        extra["breakdown"] = {"device_ops": tr.top(red["ops"]),
                              "idle_gaps": tr.top(red["idle"])}
        extra["busy_s"], extra["window_s"] = red["busy_s"], red["window_s"]
    for name_, (v, lim) in result["checks"].items():
        print(f"check {name_}: {v} (limit {lim})", file=err)
    dev = device_info(devices, peak)
    if trace:
        dev["busy_s"], dev["window_s"] = extra["busy_s"], extra["window_s"]
    out = {"correct": all(v is not None and v <= lim
                          for v, lim in result["checks"].values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": dev}
    if "breakdown" in extra:
        out["breakdown"] = extra["breakdown"]
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in result["checks"].items()}
    return out


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read: the configuration, the chip's
    peaks, the host spans, the program's counters over the window, the
    execute calls' (real, padded) rows and the reduced trace."""

    cfg: dict
    peaks: Optional[dict]
    spans: Spans
    trace: dict
    counters: Dict[str, float]
    exec_calls: List[tuple]
    prompt_len: int


def _applies(mdef: dict, workload: str) -> bool:
    return "workloads" not in mdef or workload in mdef["workloads"]


def _counters(engine, stores) -> Dict[str, float]:
    return {"dispatches": engine.stats()["dispatches"],
            "staged_queries": sum(s.staged_queries for s in stores),
            "fused_queries": sum(s.fused_queries for s in stores),
            "queries": sum(s.queries for s in stores),
            "candidates": float(sum(sum(s.candidate_counts) for s in stores))}


def _served(seg: Segment, part: int) -> list:
    """Per task: (result, reuse kind, replica, (part, resolve time)) or
    None; parts are the run's segments in order."""
    out = []
    for i, f in enumerate(seg.futures):
        if f is None or not f.done or f.exception is not None:
            out.append(None)
            continue
        r = f.result
        out.append((r.result, r.reuse, r.replica,
                    (part, float(seg.resolved[i]))))
    return out
