#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/limits.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--seconds 51]

For every seed, one run of the cell as the benchmark makes it, without the
warm-up, and its widest logit gap against the reference; for every
control seed, the same with the configuration's control weights (one step
lower in precision than it states), checked against the stated ones. The
benchmark's own runs never run the control. Prints one JSON line, and
writes it to ``.bench_cache/limits-<workload>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    harness.prepare_process()
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    seconds = args.seconds or float(bench["run_seconds"])
    runs = [(int(s), None) for s in args.seeds.split(",") if s] + [
        (int(s), cell.model["control"]["weights"])
        for s in args.control_seeds.split(",") if s]
    out = {"workload": args.workload, "seconds": seconds, "program": [],
           "control": []}
    for seed, qmode in runs:
        t0 = time.perf_counter()
        res, _ = harness.run_cell(cell, seed, seconds, False, t_process=t0,
                                  log=lambda m: print(m, file=sys.stderr),
                                  qmode=qmode, warmup=False)
        row = {"seed": seed, "weights": qmode or
               cell.model["serving"]["weights"],
               **{k: v["value"] for k, v in res["checked"].items()}}
        out["control" if qmode else "program"].append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    for side in ("program", "control"):
        gaps = [r["max_logit_gap"] for r in out[side]]
        if gaps:
            out[side + "_range"] = [min(gaps), max(gaps)]
    os.makedirs(os.path.join(ROOT, ".bench_cache"), exist_ok=True)
    with open(os.path.join(ROOT, ".bench_cache",
                           f"limits-{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
