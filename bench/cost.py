"""Operations and bytes a solve needs, from its shapes.

The count follows the algorithm, not the program's code:

* every sequential inner step (one per row per epoch, the SGD
  initialization epoch included) reads a row of A (4 d bytes), its label
  (4), and reads and writes one table entry (8); it computes the margin
  ``a . x`` (2 d operations), the correction and step
  ``x - eta ((s_new - s_old) a + gbar + 2 lam x)`` (6 d) and the
  accumulator ``acc + s a / n`` (3 d);
* every full-gradient pass reads A and b once and computes ``A x`` and
  ``A^T s`` (4 n d operations): one for the step size, one for
  ``|grad f(0)|`` and one per round for the reported rel-grad-norm.

The iterate, gbar and the accumulator (d floats each) stay in registers
and are not counted. float32 throughout.
"""
from __future__ import annotations

FLOAT = 4
STEP_FLOPS_PER_FEATURE = 2 + 6 + 3


def solve_cost(rows: int, features: int, rounds: int) -> tuple[float, float]:
    """(operations, bytes) of one solve with ``rounds`` rounds."""
    n, d = rows, features
    steps = (rounds + 1) * n
    passes = rounds + 2
    flops = steps * STEP_FLOPS_PER_FEATURE * d + passes * 4 * n * d
    nbytes = steps * (FLOAT * d + FLOAT + 2 * FLOAT) + passes * FLOAT * (n * d + n)
    return float(flops), float(nbytes)
