"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs a reuse-aware serving fleet over a real model at its published widths
with seeded random weights (``--reduced`` selects the tiny CPU smoke size):
requests with correlated input embeddings stream in, the ReuseRouter sends
similar requests to the same replica (rFIB semantics), replicas answer from
the semantic cache when possible and run model prefill otherwise.  Prints
the reuse/latency summary — the serving analogue of the paper's Figure 8.

``--engine cosim`` runs the full edge-to-TPU co-simulation instead: the NDN
testbed topology (``ReservoirNetwork``) forwards the same request stream to
ENs whose execute path is an ``EngineBackend`` replica set running *this
model's* prefill — forwarding, reuse-store search, engine batching, and
wall-measured model execution share one virtual timeline.
"""
from __future__ import annotations

import argparse
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.lsh import LSHParams
from repro.data import DATASETS, make_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import AsyncServingEngine, ReplicaEngine, ServeRequest, ServingFleet


# Edge-node LSH of the served path: 5 cross-polytope tables of 256 buckets
# (a 100k-entry store then gets bucket_cap 512)
SERVE_LSH = LSHParams(dim=64, num_tables=5, num_probes=8)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true",
                    help="run the arch's tiny same-family config (CPU smoke "
                         "tests) instead of its published widths")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--threshold", type=float, default=0.9)
    ap.add_argument("--dataset", default="cctv1", choices=sorted(DATASETS))
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--engine", default="sync",
                    choices=("sync", "async", "cosim"),
                    help="sync: one submit per request; async: event-driven "
                         "engine with Poisson arrivals + deadline batching; "
                         "cosim: NDN network in front of engine-backed ENs")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="async/cosim offered load (requests/s, virtual clock)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--window-ms", type=float, default=8.0,
                    help="cosim EN-side batch window (milliseconds)")
    ap.add_argument("--offload-policy", default=None,
                    choices=("local-only", "least-loaded", "reuse-affinity"),
                    help="cosim federation policy: forward reuse-store "
                         "misses to a remote EN's engine (DESIGN.md "
                         "§Federation); default keeps execution local")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="cosim only: arm per-task tracing and write the "
                         "Chrome trace-event / Perfetto JSON here")
    args = ap.parse_args(argv)
    if args.offload_policy is not None and args.engine != "cosim":
        ap.error("--offload-policy requires --engine cosim (federation "
                 "runs between the co-simulated ENs)")
    if args.trace_out is not None and args.engine != "cosim":
        ap.error("--trace-out requires --engine cosim (spans live on the "
                 "network's virtual timeline)")
    return args


def load_service(args) -> SimpleNamespace:
    """Build the model with seeded random weights and its jitted prefill.

    Returns ``cfg``, ``params``, ``prefill(params, batch) -> (B, 1, V)
    last-position logits`` and ``tokens(emb) -> (1, seq_len)`` (the prompt
    is derived deterministically from the request's embedding)."""
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    # one compiled init instead of an eager dispatch per parameter
    params = jax.jit(model.init)(  # lint: disable=J001(once per process)
        jax.random.PRNGKey(0))
    max_len = args.seq_len + 8

    @jax.jit
    def prefill(p, batch):  # lint: disable=J001(built once per CLI process)
        logits, _ = model.prefill(p, batch, max_len)
        return logits

    def tokens(emb):
        emb = np.asarray(emb, np.float32)
        return jnp.asarray(
            (np.abs(emb[: args.seq_len]) * 1e4).astype(np.int64)
            % cfg.vocab_size, jnp.int32)[None, :]

    return SimpleNamespace(cfg=cfg, params=params, prefill=prefill,
                           tokens=tokens)


def run_cosim(args, svc, X) -> SimpleNamespace:
    """Drive ``X`` through the NDN testbed whose ENs execute misses on an
    ``EngineBackend`` running ``svc``'s prefill (wall-time charged)."""
    from repro.core import ReservoirNetwork
    from repro.core.edge_node import Service
    from repro.core.topology import testbed_topology
    from repro.serving import EngineBackend

    def svc_execute(emb):
        logits = svc.prefill(svc.params, {"tokens": svc.tokens(emb)})
        return int(jnp.argmax(logits[0, -1]))

    g, ens = testbed_topology()
    backend = EngineBackend(
        n_replicas=args.replicas, max_batch=args.max_batch,
        max_wait_s=args.max_wait_ms * 1e-3, wall_time=True)
    net = ReservoirNetwork(
        g, ens, SERVE_LSH, seed=0, en_batch_window_s=args.window_ms * 1e-3,
        backend=backend, offload_policy=args.offload_policy,
        trace=True if args.trace_out else None)
    net.register_service(Service(
        f"/{args.dataset}", execute=svc_execute, input_dim=64))
    net.add_user("u0", "fwd1")
    net.add_user("u1", "fwd2")
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, len(X)))
    # submit_task runs one untimed oracle prefill per request here (the
    # from-scratch answer reuse accuracy is measured against); the timed
    # region below covers only the co-simulation itself
    for i, (t, emb) in enumerate(zip(arrivals, X)):
        net.submit_task(f"u{i % 2}", args.dataset, emb, args.threshold,
                        at_time=float(t))
    t_all = time.time()
    makespan = net.run()
    return SimpleNamespace(net=net, backend=backend, makespan=makespan,
                           wall=time.time() - t_all)


def main(argv=None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    svc = load_service(args)

    def execute(reqs):
        out = []
        for r in reqs:
            logits = svc.prefill(svc.params, r.payload)
            out.append(int(jnp.argmax(logits[0, -1])))
        return out

    replicas = [ReplicaEngine(i, SERVE_LSH, execute)
                for i in range(args.replicas)]

    spec = DATASETS[args.dataset]
    X, _ = make_stream(spec, args.requests, seed=0)

    def make_req(i, emb):
        return ServeRequest(i, args.dataset, emb,
                            payload={"tokens": svc.tokens(emb)},
                            threshold=args.threshold)

    if args.engine == "cosim":
        run = run_cosim(args, svc, X)
        net, makespan, wall = run.net, run.makespan, run.wall
        # consumed by the engine-agnostic reuse/latency report further down
        lat = [(r.completion_time, r.reuse) for r in net.metrics.records
               if r.t_complete >= 0]
        stats = run.backend.stats()
        s = net.metrics.summary()
        if args.trace_out:
            net.loop.tracer.export(args.trace_out)
            print(f"trace: {len(net.loop.tracer.events)} events -> "
                  f"{args.trace_out}")
        if net.loop.profiler is not None:
            print(net.loop.profiler.report())
        print(f"\n{len(lat)} tasks through the co-sim in {wall:.1f}s wall "
              f"({makespan:.2f}s virtual, offered {args.rate:.0f} req/s, "
              f"EN window {args.window_ms:.0f} ms, {args.replicas} replicas/EN)")
        print(f"  network reuse: {s['reuse_pct']:.1f}% "
              f"(cs {s['reuse_pct_cs']:.1f}%, en {s['reuse_pct_en']:.1f}%), "
              f"accuracy {s['accuracy_pct']:.1f}%")
        ph = net.registry.phase_summary()
        print("  phases: " + "  ".join(
            f"{p}={ph[p + '_ms']:.2f}ms/n={ph[p + '_n']}"
            for p in ("forward", "search", "execute", "aggregate")))
        if net.federator is not None:
            fs = net.federator.stats
            print(f"  federation[{args.offload_policy}]: "
                  f"offloads={fs['offloads']} "
                  f"remote_hits={fs['remote_hits']} "
                  f"remote_execs={fs['remote_execs']} "
                  f"rebalances={fs['rebalances']}")
    elif args.engine == "async":
        engine = AsyncServingEngine(
            SERVE_LSH, replicas, max_batch=args.max_batch,
            max_wait_s=args.max_wait_ms * 1e-3)
        rng = np.random.default_rng(0)
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
        futs = [engine.submit_at(t, make_req(i, emb))
                for i, (t, emb) in enumerate(zip(arrivals, X))]
        t_all = time.time()
        makespan = engine.drain()
        wall = time.time() - t_all
        lat = [(f.result.latency_s, f.result.reuse) for f in futs]
        stats = engine.stats()
        print(f"\n{len(futs)} requests drained in {wall:.1f}s wall "
              f"({makespan:.2f}s virtual, offered {args.rate:.0f} req/s, "
              f"window {args.max_wait_ms:.0f} ms x {args.max_batch})")
    else:
        fleet = ServingFleet(SERVE_LSH, replicas)
        lat = []
        t_all = time.time()
        for i, emb in enumerate(X):
            req = make_req(i, emb)
            t0 = time.perf_counter()
            res = fleet.submit(req)
            lat.append((time.perf_counter() - t0, res.reuse))
        wall = time.time() - t_all
        stats = fleet.stats()
        print(f"\n{len(lat)} requests in {wall:.1f}s over {args.replicas} replicas")
    by = lambda k: [l for l, r in lat if r == k]  # noqa: E731
    print(f"  reuse: cs={stats['cs']} en={stats['en']} "
          f"executed={stats['executed']} aggregated={stats['aggregated']}")
    if args.engine == "async":
        p99 = float(np.percentile([l for l, _ in lat], 99))
        print(f"  backups={stats['backups']} backup_wins={stats['backup_wins']} "
              f"dispatches={stats['dispatches']}  p99 latency {p99 * 1e3:.2f} ms")
    for kind in ("cs", "en", None):
        ls = by(kind)
        if ls:
            print(f"  latency[{kind or 'scratch':7s}] "
                  f"mean={np.mean(ls) * 1e3:7.2f} ms  n={len(ls)}")
    scratch, cs = by(None), by("cs")
    if scratch and cs:
        print(f"  speedup cs vs scratch: {np.mean(scratch) / np.mean(cs):.1f}x")


if __name__ == "__main__":
    main()
