"""The comparison that decides ``correct``.

A solve answers with its final iterate ``x``, the rel-grad-norm it
reports for every round, and its final table of scalar residuals (the
driver state ``RunResult.state`` holds). For a sample of the window's
solves, drawn from the run's seed once the window has closed, the plain
reference (``bench/reference.py``) redoes the solve from the same data,
step size and seed. The numbers, each the largest over the sample:

* ``table_gap``: ``|table - table_ref| / |table_ref|`` over every row:
  each entry is ``l'(a_i . x)`` at the step that last visited row ``i``,
  so this follows every inner step of the last epoch (the drivers, and
  under spmd the exchange that fed them);
* ``x_gap``: ``|x - x_ref| / |x_ref|``, the final iterate;
* ``rel_final``: ``rel64(x)``, the float64 rel-grad-norm at the returned
  iterate, against the cell's target (the accuracy the cell states).

The cell's file gives the limits of ``table_gap`` and ``x_gap``, set from
readings (``bench/control.py``). The run is correct when every compared
number is finite and within its limit.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("table_gap", "x_gap", "rel_final")


def table_of(state):
    """The final table of scalar residuals in a driver's state: ``tables``
    (p, ns) for the distributed drivers, ``table`` (n,) for Algorithm 1."""
    return state.tables if hasattr(state, "tables") else state.table


def numbers_of(x, table, ref, rel_final64) -> dict:
    """The numbers of one solve; ``ref`` is the reference's
    ``(x_hist, table)``."""
    x_hist, table_ref = ref
    table = np.asarray(table, np.float64).ravel()
    table_ref = np.asarray(table_ref, np.float64)
    x_ref = np.asarray(x_hist[-1], np.float64)
    x = np.asarray(x, np.float64)

    def gap(a, b):
        if a.shape != b.shape:
            return math.inf
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    return {"table_gap": gap(table, table_ref), "x_gap": gap(x, x_ref),
            "rel_final": float(rel_final64)}


def worst(per_solve: list) -> dict:
    """Each number's largest value over the sample (NaN counts as
    infinite)."""
    out = {}
    for k in NUMBERS:
        vals = [math.inf if not math.isfinite(n[k]) else n[k]
                for n in per_solve]
        out[k] = max(vals) if vals else math.inf
    return out


def limits_of(cell: dict) -> dict:
    """The limits a cell's run is held to: those set for the cell from
    readings (``limits``), and the target for ``rel_final``."""
    lim = dict(cell["limits"])
    lim["rel_final"] = float(cell["target"])
    return lim


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, checks): each compared number beside its limit."""
    checks = {k: {"value": values[k], "limit": limits[k]}
              for k in NUMBERS if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
