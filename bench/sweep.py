#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
waiting queue does not grow across the window.

    python3 bench/sweep.py --workload <name> --rates 1,1.5,2 [--seconds 51]

Each rate is one run of the cell (warm-up, window) at that rate, in one
process. The queue grows when its mean over the window's last quarter
exceeds its mean over the first quarter by more than one request. Prints
one JSON line per rate and writes them to ``.bench_cache/sweep-<name>.json``.
The benchmark's runs never sweep: the cell's rate is fixed in its mix.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def queue_growth(run) -> float:
    steps = run.window_steps()
    if len(steps) < 8:
        return float("nan")
    q = len(steps) // 4
    first = sum(s.waiting for s in steps[:q]) / q
    last = sum(s.waiting for s in steps[-q:]) / q
    return last - first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    harness.prepare_process()
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    seconds = args.seconds or float(bench["run_seconds"])
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        res, run = harness.run_cell(cell, args.seed, seconds, False,
                                    t_process=t0, rate=rate,
                                    log=lambda m: print(m, file=sys.stderr))
        row = {"rate_per_s": rate, "queue_growth": queue_growth(run),
               "steps": len(run.window_steps()),
               "attempted": res["attempted"], "failed": res["failed"],
               "correct": res["correct"],
               **{k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, ".bench_cache"), exist_ok=True)
    with open(os.path.join(ROOT, ".bench_cache",
                           f"sweep-{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
