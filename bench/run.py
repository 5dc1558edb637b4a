#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. ``--seed`` makes
the weights and the traffic; ``--trace 1`` runs the profiler over the
window and reports the per-layer metrics instead of the end-to-end ones.

Everything else is printed first, to standard error; the numbers compared
for ``correct`` come last there, each beside its limit. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and with ``--trace 1`` ``breakdown``), then
``checked``. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    harness.prepare_process()
    cell = harness.find_cell(harness.load_benchmark(ROOT), args.workload)
    try:
        out, _ = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               log=log)
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 2
    for name, c in out["checked"].items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    log(f"[check] correct {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
