"""The control of the ``logit_gap`` check, and the program's own readings,
over many seeds in one process, on the chip.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ...

For each seed it runs the cell (set-up, a window at the cell's own load and
length, so that it compares as many tasks as a run does, the checks) with
the control in the program's place at the ``logit_gap`` check: the tokens
that a float8 (e4m3, per-tensor scaled) run of the float32 reference puts
first, at the same prompts, stand in for the served tokens.  It prints the
run's ``correct``, which has to read false, the control's widest gap and,
beside it, the program's own gap over the same tasks.  The limit in the
configuration file lies between the largest program reading and the
smallest control reading.  The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per seed (default: the benchmark's run_seconds)")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    seconds = args.seconds or harness.load_cell(args.workload)["bench"]["run_seconds"]
    for seed in args.seeds:
        stats: dict = {}
        out = harness.run(args.workload, seed, seconds, False,
                          t_start=time.perf_counter(), stats=stats,
                          control=True)
        print("CONTROL " + json.dumps({
            "seed": seed, "correct": out["correct"],
            "control_gap": stats["checks"]["logit_gap"][0],
            "program_gap": stats["program_gap"],
            "checks": {k: v[0] for k, v in stats["checks"].items()},
            "executed": stats["served"].count(None),
            "compiles_in_window": stats["compiles"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
