#!/usr/bin/env python3
"""Derive a cell's target from the float32 reference.

    python bench/derive_targets.py ijcnn1.sync-p4 [--seeds 6] [--rounds R]

For each seed it makes the cell's dataset, runs the plain reference
(``bench/reference.py``, float32 at ``highest`` precision) for the cell's
rounds, and evaluates the rel-grad-norm of every round's iterate in
float64. It prints one JSON line per seed and a last line with the
target: twice the largest final rel-grad-norm over the seeds. Seeds are
those of a run: data from seed ``s``, the solve from ``solve_seed(s, 1)``.
It runs wherever JAX runs; a configuration of hundreds of MB belongs on
the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TARGET_FACTOR = 2.0


def derive(cell_name: str, seeds, rounds=None, log=print) -> float:
    import jax
    import numpy as np

    from bench import cells, data, reference

    cell = cells.load_cell(cell_name)
    config, traffic = cell["config_data"], cell["traffic_data"]["spec"]
    rounds = int(rounds or cell["rounds"])
    finals = []
    for s in seeds:
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            d = data.make(config, s)
        A64 = np.asarray(d.A, np.float64)
        b64 = np.asarray(d.b, np.float64)
        eta = reference.step_size(A64, d.lam, d.loss)
        x_hist, _, _ = reference.solve(
            d.A, d.b, d.lam, d.loss, algo=traffic["algo"], p=traffic["p"],
            rounds=rounds, seed=data.solve_seed(s, 1), eta=eta)
        rels = [reference.rel_grad_norm64(A64, b64, d.lam, d.loss, x)
                for x in x_hist]
        finals.append(rels[-1])
        log(json.dumps({"cell": cell_name, "seed": s, "rounds": rounds,
                        "eta": eta, "rels64": rels,
                        "seconds": time.perf_counter() - t0}))
    target = TARGET_FACTOR * max(finals)
    log(json.dumps({"cell": cell_name, "rounds": rounds,
                    "max_final_rel64": max(finals), "target": target}))
    return target


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args(argv)
    seeds = range(args.first_seed, args.first_seed + args.seeds)
    for name in args.cells:
        derive(name, seeds, args.rounds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
