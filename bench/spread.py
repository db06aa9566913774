#!/usr/bin/env python3
"""Run a cell in two sets of runs and print the spread of its metrics.

    python bench/spread.py <cell> --seconds S [--runs 6] [--first-seed N]

Each run is a process of its own (``bench/run.py``); this parent never
touches JAX, so the children get the chips. Both sets use the same seeds.
It prints every run's result line, then for each end-to-end metric and
set the median and the spread: the distance between the first and third
quartile of ``statistics.quantiles(values, n=4)``, as a share of the
median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def one_run(cell: str, seed: int, seconds: float):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"],
        cwd=os.path.dirname(BENCH), capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        print(proc.stderr[-4000:], file=sys.stderr)
        return None
    return json.loads(lines[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + k for k in range(args.runs)]
    sets = []
    for s in range(2):
        sets.append([one_run(args.cell, seed, args.seconds) for seed in seeds])
        for seed, res in zip(seeds, sets[-1]):
            print(json.dumps({"set": s, "seed": seed, "result": res}),
                  flush=True)
    for name in sorted({m for rows in sets for r in rows if r
                        for m in r["metrics"]}):
        for s, rows in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in rows
                    if r and name in r["metrics"]]
            if len(vals) >= 2:
                q1, _, q3 = statistics.quantiles(vals, n=4)
                med = statistics.median(vals)
                print(json.dumps({"cell": args.cell, "metric": name,
                                  "set": s, "median": med,
                                  "spread": (q3 - q1) / med}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
