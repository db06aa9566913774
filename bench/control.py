#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, at a cell's own size.

    python bench/control.py <cell> [--seeds 12] [--first-seed 5000]
        [--fault <fault>]

For each seed, in one process: the cell's dataset; one solve of the
cell's spec through ``repro.solve`` (the program, as a run's window calls
it); the plain reference; and the control, the same reference at bf16x3
precision in the program's place. Both answers are compared with the
reference as ``bench/compare.py`` compares a run's. One JSON line per
seed, then a summary: each number's lower reading (the largest the
program gives) and upper reading (the smallest the control gives).
It runs on the chip. With ``--fault`` the program runs with that fault
of ``bench/tests/plant.py`` planted, and its readings are the fault's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def readings(name: str, seeds, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.core import convex
    from repro.launch.compile_cache import enable_compile_cache

    from bench import cells, compare, data, reference

    enable_compile_cache()
    cell = cells.load_cell(name)
    config, fields = cell["config_data"], cell["traffic_data"]["spec"]
    rounds = int(cell["rounds"])
    prog, ctrl = [], []
    for seed in seeds:
        t0 = time.perf_counter()
        with jax.default_matmul_precision(config["matmul_precision"]):
            ds = data.make(config, seed)
            problem = convex.Problem(ds.A, ds.b, jnp.float32(ds.lam),
                                     ds.loss)
            sd = data.solve_seed(seed, 1)
            res = repro.solve(repro.RunSpec(seed=sd, rounds=rounds,
                                            **fields), problem)
            x = np.asarray(res.x)
            table = np.asarray(compare.table_of(res.state))
            del res
        A64 = np.asarray(ds.A, np.float64)
        b64 = np.asarray(ds.b, np.float64)
        eta = reference.step_size(A64, ds.lam, ds.loss)

        def ref(precision):
            return reference.solve(ds.A, ds.b, ds.lam, ds.loss,
                                   algo=fields["algo"], p=int(fields["p"]),
                                   rounds=rounds, seed=sd, eta=eta,
                                   precision=precision)

        def rel64(v):
            return reference.rel_grad_norm64(A64, b64, ds.lam, ds.loss, v)

        x_hist, _, table_ref = ref("highest")
        want = (x_hist, table_ref)
        xc_hist, _, table_c = ref("bf16x3")
        prog.append(compare.numbers_of(x, table, want, rel64(x)))
        ctrl.append(compare.numbers_of(xc_hist[-1], table_c, want,
                                       rel64(xc_hist[-1])))
        log(json.dumps({"cell": name, "seed": seed, "program": prog[-1],
                        "control": ctrl[-1],
                        "seconds": time.perf_counter() - t0}))
    summary = {"cell": name, "seeds": len(prog),
               "lower": compare.worst(prog),
               "upper": {k: min(c[k] for c in ctrl)
                         for k in compare.NUMBERS}}
    log(json.dumps(summary))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=5000)
    ap.add_argument("--fault", default="none")
    args = ap.parse_args(argv)
    from bench.tests import plant
    plant.plant(args.fault)
    seeds = range(args.first_seed, args.first_seed + args.seeds)
    for name in args.cells:
        readings(name, seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
