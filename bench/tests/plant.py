"""Drive one run of a cell at a small size, with a fault planted in the
program or the control put in its place; print the result line.

    python bench/tests/plant.py <cell> <fault> [--rows N]

Run in a process of its own (the faults patch the program's modules
before anything is traced). It skips the harness's look for a chip; the
rest of the run is the benchmark's own.

Faults, each in the layer where it would arise:

* ``none``: the program as it is;
* ``state_unchanged``: the CentralVR step returns its state unchanged;
* ``half_batch``: each epoch visits half of its rows, and the running
  mean is taken over that half;
* ``no_exchange``: the cross-chip ``pmean`` is left out (spmd only);
* ``answer_altered``: the driver's final iterate is altered by 1e-3 of
  its norm in one coordinate;
* ``control``: the plain reference at bf16x3 precision in the program's
  place (``bench/reference.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FAULTS = ("none", "state_unchanged", "half_batch", "no_exchange",
          "answer_altered", "control")


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import centralvr, distributed, solver

    if fault == "state_unchanged":
        def unchanged(A, b, lam, kind, x, table, gbar, eta, perm, **_):
            return x, table, gbar
        distributed._local_centralvr_epoch = unchanged
        centralvr.epoch = lambda prob, state, eta, order, **_: (state, None)
    elif fault == "half_batch":
        local = distributed._local_centralvr_epoch
        single = centralvr.epoch

        def half_local(A, b, lam, kind, x, table, gbar, eta, perm, **kw):
            x, table, acc = local(A, b, lam, kind, x, table, gbar, eta,
                                  perm[: perm.shape[0] // 2], **kw)
            return x, table, 2.0 * acc

        def half_single(prob, state, eta, order, **kw):
            st, traj = single(prob, state, eta, order[: order.shape[0] // 2],
                              **kw)
            return st._replace(gbar=2.0 * st.gbar), traj
        distributed._local_centralvr_epoch = half_local
        centralvr.epoch = half_single
    elif fault == "no_exchange":
        jax.lax.pmean = lambda x, axis_name, **_: x
    elif fault == "answer_altered":
        for name, entry in list(solver.REGISTRY.items()):
            def altered(spec, prob, eta, key, mesh, _call=entry.call):
                state, x, rels, evals = _call(spec, prob, eta, key, mesh)
                x = jnp.asarray(x)
                return state, x.at[0].add(1e-3 * jnp.linalg.norm(x)), rels, \
                    evals
            solver.REGISTRY[name] = entry._replace(call=altered)
    elif fault == "control":
        _control_in_place()
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


def _control_in_place() -> None:
    """``repro.solve`` answered by the reference at bf16x3 precision."""
    import time
    from types import SimpleNamespace

    import numpy as np

    import repro

    from bench import reference

    def control_solve(spec, problem):
        t0 = time.perf_counter()
        A64 = np.asarray(problem.A, np.float64)
        eta = reference.step_size(A64, float(problem.lam), problem.kind)
        x_hist, rels, table = reference.solve(
            problem.A, problem.b, float(problem.lam), problem.kind,
            algo=spec.algo, p=spec.p, rounds=spec.rounds, seed=spec.seed,
            eta=eta, precision="bf16x3")
        return SimpleNamespace(rels=rels, x=x_hist[-1],
                               state=SimpleNamespace(table=table),
                               wall_s=time.perf_counter() - t0)
    repro.solve = control_solve


def small_cell(name: str, rows: int) -> dict:
    from bench import cells

    cell = cells.load_cell(name)
    cell["config_data"] = dict(cell["config_data"], rows=rows)
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("fault", choices=FAULTS)
    ap.add_argument("--rows", type=int, default=4000)
    ap.add_argument("--seed", type=int, default=2**31 + 17)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--target", type=float, default=None,
                    help="the stated accuracy at this size")
    args = ap.parse_args(argv)
    plant(args.fault)
    from bench import run

    run.VERIFY_SAMPLES = 1
    cell = small_cell(args.cell, args.rows)
    if args.target is not None:
        cell["target"] = args.target
    out = run.run_cell(args.cell, args.seed, args.seconds, False, cell=cell,
                       require_chip=False)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
