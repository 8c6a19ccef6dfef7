"""Find a cell's knee once, on the chip: the highest offered rate whose
backlog does not grow through the window.

    python3 bench/sweep.py --workload <name> --rates 60 80 100

Runs the cell once per rate, each in a process of its own as the benchmark's
runs are (a process that has compiled the search's widths for one rate would
serve the next one faster), and prints per rate the tasks completed per
second inside the window, the backlog left at its close, the completion-time
median and 95th percentile, the compiles inside the window and the check.
The benchmark's own runs do not run this; the rate chosen goes into the
traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def one(workload: str, rate: float, seconds: float, seed: int) -> dict:
    from bench import harness

    traffic = dict(harness.load_cell(workload)["traffic"], rate_per_s=rate)
    stats: dict = {}
    out = harness.run(workload, seed, seconds, False, t_start=time.perf_counter(),
                      traffic_override=traffic, stats=stats)
    return {"rate": rate, "done_per_s": stats["done_in_window"] / seconds,
            "backlog": stats["attempted"] - stats["done_in_window"],
            "p50_ms": stats["p50_ms"], "p95_ms": stats["p95_ms"],
            "late_p99_ms": stats["late_p99_ms"], "compiles": stats["compiles"],
            "compile_s": stats["compile_s"], "correct": out["correct"],
            "reuse": {str(k): stats["served"].count(k) for k in ("cs", "en", None)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        row = one(args.workload, args.rates[0], args.seconds, args.seed)
        print("SWEEP " + json.dumps(row), flush=True)
        return 0
    for rate in args.rates:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", "--workload",
             args.workload, "--seconds", str(args.seconds), "--seed",
             str(args.seed), "--rates", str(rate)],
            cwd=ROOT, stdout=subprocess.PIPE, text=True)
        rows = [ln for ln in p.stdout.splitlines() if ln.startswith("SWEEP ")]
        print(rows[-1] if rows else f"SWEEP {json.dumps({'rate': rate, 'rc': p.returncode})}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
