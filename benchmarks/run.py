"""Benchmark harness: one module per paper table/figure + the roofline
report. Prints ``name,us_per_call,derived`` CSV per row.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,...]
    PYTHONPATH=src python benchmarks/run.py [--quick]   # same, script form
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# suite name -> module under benchmarks/ exposing run(quick=...); the
# children start from the repo root, where repro_bootstrap adds src/ when
# repro isn't pip-installed
SUITES = {
    "fig1": "fig1_single_worker",
    "fig2": "fig2_distributed",
    "fig3": "fig3_large",
    "table1": "table1_accounting",
    "theory": "theory_rates",
    "tau": "tau_sweep",
    "variance": "variance",
    "drivers": "driver_throughput",
    "spmd": "spmd_scaling",
    "train": "train_throughput",
    "serve": "serve_throughput",
    "roofline": "roofline_report",
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-speed)")
    ap.add_argument("--only", default="",
                    help="comma list: " + ",".join(SUITES))
    args = ap.parse_args(argv)

    only = [s for s in args.only.split(",") if s]
    failures = []
    print("name,us_per_call,derived", flush=True)
    for name, module in SUITES.items():
        if only and name not in only:
            continue
        # every suite runs in its own interpreter, one after another, and
        # this process never touches jax: a chip belongs to one process at
        # a time, and the spmd/train/serve suites force a host-device
        # count that must precede backend start-up
        code = (f"import repro_bootstrap; from benchmarks import {module}; "
                f"{module}.run(quick={args.quick})")
        rc = subprocess.run([sys.executable, "-c", code], cwd=ROOT).returncode
        if rc:
            failures.append((name, rc))
    if failures:
        print(f"FAILED suites (name, exit code): {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
