"""Benchmark driver: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see DESIGN.md §8 for the
benchmark <-> paper artifact mapping).  Select subsets with
``python -m benchmarks.run [names...]``; pass ``--json <path>`` to also emit
a machine-readable ``BENCH_*.json`` so the perf trajectory can be tracked
across PRs.
"""
from __future__ import annotations

import json
import sys
import time

BENCHES = (
    "hashing_time",       # Table III
    "search_accuracy",    # Table IV (a) + (b)
    "rfib_lookup",        # Fig. 6 + rFIB size
    "completion_time",    # Figs. 8a/8b + 9a/9b
    "reuse_accuracy",     # Figs. 8c + 9c
    "percent_reuse",      # Figs. 8d + 9d
    "cache_sweep",        # §V-C cache-size study
    "forwarding_error",   # Fig. 10
    "icedge_compare",     # Fig. 11
    "serving_reuse",      # beyond-paper: reuse-aware LM serving
    "multiprobe",         # beyond-paper: probe depth vs recall vs cost
    "reuse_store_scale",  # beyond-paper: batched vs scalar reuse pipeline
    "fused_query",        # beyond-paper: one-dispatch fused vs staged query
    "async_serving",      # beyond-paper: event-driven serving core sweep
    "cosim",              # beyond-paper: edge-to-TPU co-simulation sweep
    "federation",         # beyond-paper: cross-EN offload policy sweep
    "fault_recovery",     # beyond-paper: fault injection + recovery under loss
    "migration",          # beyond-paper: store migration under fleet churn
    "sanitizer_overhead",  # armed vs disarmed invariant-sanitizer cost
    "obs_overhead",       # armed vs disarmed tracing/profiling cost
    "roofline",           # §Roofline (reads dry-run artifacts)
)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        try:
            json_path = args[i + 1]
        except IndexError:
            raise SystemExit("--json requires a path argument")
        del args[i:i + 2]
    selected = args or BENCHES
    print("name,us_per_call,derived")
    failures = []
    records = []
    for bench in selected:
        mod = __import__(f"benchmarks.{bench}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001 — report, keep the suite going
            failures.append((bench, repr(e)))
            print(f"{bench}/ERROR,0,{e!r}")
            records.append({"bench": bench, "name": f"{bench}/ERROR",
                            "us_per_call": 0.0, "derived": repr(e)})
            continue
        for name, us, derived in rows:
            print(f'{name},{us:.2f},"{derived}"')
            records.append({"bench": bench, "name": name,
                            "us_per_call": round(float(us), 2),
                            "derived": str(derived)})
        print(f"# {bench} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if json_path is not None:
        with open(json_path, "w") as f:
            json.dump({"benches": list(selected), "rows": records}, f, indent=1)
        print(f"# wrote {json_path}", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
