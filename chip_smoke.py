"""Smoke test of the served path on one TPU chip.

    python chip_smoke.py

Runs in one process, on the first device JAX finds, and exits non-zero
(printing no result line) unless that device is a TPU and every phase passes:

  (a) device   — platform, kind and count as JAX reports them;
  (b) store    — an edge node's ``ReuseStore`` (100k entries kept on the
                 device, 5 hyperplane tables of 16384 buckets) answers one
                 1024-row batch through the fused one-dispatch search and
                 through the staged search, and 64 of its rows through the
                 scalar numpy path; all three must return the same ids, with
                 similarities within 1e-4 of numpy;
  (c) service  — the ``--engine cosim`` path of ``repro.launch.serve`` with
                 full-width qwen3-1.7b (random weights from seed 0) serves 32
                 cctv1 tasks; every task completes, some execute, some are
                 reused, and one prompt's last-position logits keep the
                 argmax of a float32 run of the same weights.

Timings, compile seconds and peak device memory are printed as readings of
this run; the last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

SEED = 0
STORE_ROWS = 100_000       # ReuseStore's default capacity
QUERY_ROWS = 1024
SCALAR_ROWS = 64
SIM_TOL = 1e-4             # f32 cosine, kernel vs numpy summation order
SERVE_ARGV = ["--engine", "cosim", "--requests", "32", "--dataset", "cctv1"]


class _CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, read from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        name = event.rsplit("/", 1)[-1]
        if name in self.events:
            self.events[name] += 1

    def __str__(self):
        return (f"compile {self.seconds:.2f}s, persistent cache "
                f"hits={self.events['cache_hits']} "
                f"misses={self.events['cache_misses']}")


def _require(ok, message: str) -> None:
    if not ok:
        raise RuntimeError(message)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def store_phase(n_rows: int = STORE_ROWS, n_query: int = QUERY_ROWS,
                n_scalar: int = SCALAR_ROWS) -> None:
    """(b): fused, staged and scalar numpy answers of one store agree."""
    from repro.core import LSHParams, ReuseStore, normalize
    from repro.kernels import ops

    p = LSHParams(dim=64, num_tables=5, num_probes=8, num_buckets=16384,
                  family="hyperplane", seed=11)
    store = ReuseStore(p, capacity=n_rows)
    rng = np.random.default_rng(SEED)
    X = normalize(rng.standard_normal((n_rows, p.dim)).astype(np.float32))

    def fill():
        for lo in range(0, n_rows, 8192):
            hi = min(lo + 8192, n_rows)
            store.insert_batch(X[lo:hi], list(range(lo, hi)))
        store.sync_device(ensure=True)

    _, t_fill = _timed(fill)
    Q = normalize(X[:n_query] + 0.05 * rng.standard_normal(
        (n_query, p.dim)).astype(np.float32) / np.sqrt(p.dim))
    width = p.num_tables * p.num_probes * store.bucket_cap
    print(f"store: {len(store)} entries, {store.device_pages} device pages of "
          f"{store.page_size} rows, bucket_cap {store.bucket_cap}, "
          f"candidate width {width}, filled in {t_fill:.2f}s")

    _require(store._use_fused(n_query), "fused path does not engage")
    d0 = ops.FUSED_DISPATCH_COUNT
    fused, t_first = _timed(lambda: store.query_batch(Q, 0.0, peek=True))
    _require(store.last_query_fused, "query_batch did not take the fused path")
    _require(ops.FUSED_DISPATCH_COUNT - d0 == 1, "fused query is not 1 dispatch")
    _, t_fused = _timed(lambda: store.query_batch(Q, 0.0, peek=True))
    store.fused = False
    staged, t_staged_first = _timed(lambda: store.query_batch(Q, 0.0, peek=True))
    _require(not store.last_query_fused, "staged query took the fused path")
    _, t_staged = _timed(lambda: store.query_batch(Q, 0.0, peek=True))
    scalar, t_scalar = _timed(
        lambda: [store.query(q, 0.0) for q in Q[:n_scalar]])
    print(f"store: {n_query}-row batch fused {t_first:.3f}s first call, "
          f"{t_fused:.4f}s next; staged {t_staged_first:.3f}s first, "
          f"{t_staged:.4f}s next; scalar numpy {t_scalar / n_scalar * 1e3:.3f} "
          f"ms/row")

    f_ids = [r[2] for r in fused]
    _require(f_ids == [r[2] for r in staged], "fused and staged ids differ")
    _require(f_ids[:n_scalar] == [r[2] for r in scalar],
             "fused and scalar numpy ids differ")
    f_sim = np.array([r[1] for r in fused])
    s_sim = np.array([r[1] for r in staged])
    n_sim = np.array([r[1] for r in scalar])
    err = max(np.abs(f_sim[:n_scalar] - n_sim).max(),
              np.abs(s_sim[:n_scalar] - n_sim).max())
    _require(err <= SIM_TOL, f"similarity off numpy by {err}")
    _require(np.abs(f_sim - s_sim).max() <= SIM_TOL,
             "fused and staged similarities differ")
    own = np.mean(np.asarray(f_ids) == np.arange(n_query))
    print(f"store: ids identical across fused/staged/scalar, max |sim - "
          f"numpy| {err:.2e}, {own:.1%} of queries find their source row")


def service_phase(argv=SERVE_ARGV) -> None:
    """(c): full-width cosim service plus the float32 logit check."""
    import jax

    from repro.data import DATASETS, make_stream
    from repro.launch import serve
    from repro.models import build_model

    args = serve.parse_args(argv)

    def build():
        svc = serve.load_service(args)
        jax.block_until_ready(svc.params)
        return svc

    svc, t_init = _timed(build)
    n_params = sum(x.size for x in jax.tree.leaves(svc.params))
    print(f"service: {svc.cfg.name} d_model {svc.cfg.d_model} x "
          f"{svc.cfg.n_layers} layers, {n_params / 1e9:.3f}B params "
          f"({svc.cfg.dtype} compute), built in {t_init:.1f}s")

    X, _ = make_stream(DATASETS[args.dataset], args.requests, seed=SEED)
    run = serve.run_cosim(args, svc, X)
    recs = run.net.metrics.records
    done = sum(r.t_complete >= 0 for r in recs)
    executed = sum(r.t_complete >= 0 and r.reuse is None for r in recs)
    reused = done - executed
    s = run.net.metrics.summary()
    print(f"service: {done}/{len(recs)} tasks complete, {executed} executed, "
          f"{reused} reused, reuse accuracy {s['accuracy_pct']:.1f}%, "
          f"{run.wall:.2f}s wall for {run.makespan:.3f}s virtual")
    _require(done == len(recs), "not every task completed")
    _require(executed >= 1 and reused >= 1,
             f"need executed and reused tasks, got {executed}, {reused}")

    batch = {"tokens": svc.tokens(X[0])}
    served = np.asarray(svc.prefill(svc.params, batch))[0, -1]
    ref_model = build_model(dataclasses.replace(svc.cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, b: ref_model.prefill(
            p, b, args.seq_len + 8)[0])(svc.params, batch))[0, -1]
    diff = float(np.abs(served - ref).max())
    print(f"service: last-position logits vs float32: max |diff| {diff:.4f} "
          f"(largest |logit| {np.abs(ref).max():.3f}), argmax "
          f"{int(served.argmax())} vs {int(ref.argmax())}")
    _require(np.isfinite(served).all(), "served logits are not finite")
    _require(served.argmax() == ref.argmax(), "argmax differs from float32")


def main() -> int:
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}")
    if d.platform != "tpu":
        print("chip_smoke: no TPU; nothing run", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    log = _CompileLog()
    _, t = _timed(store_phase)
    print(f"phase store passed in {t:.1f}s ({log})")
    _, t = _timed(service_phase)
    print(f"phase service passed in {t:.1f}s ({log})")
    peak = d.memory_stats().get("peak_bytes_in_use", 0)
    print(f"first chip readings, not benchmark numbers: peak_bytes_in_use "
          f"{peak / 2**30:.2f} GiB, {log}")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
