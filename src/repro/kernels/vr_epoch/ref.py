"""Pure-jnp oracle for the local-epoch kernel: the drivers' unfused scan
body, one worker at a time under ``vmap``."""
from __future__ import annotations

import jax


def vr_epoch_ref(A, b, table, perm, x, gbar, eta, lam, *, kind: str,
                 prox=None):
    """``ops.local_epochs`` with a table and a shared ``x`` / ``gbar``, as
    ``distributed._local_centralvr_epoch`` computes it: (x, table, acc),
    each with the worker axis first."""
    from repro.core.distributed import _local_centralvr_epoch

    return jax.vmap(lambda A, b, table, perm: _local_centralvr_epoch(
        A, b, lam, kind, x, table, gbar, eta, perm, prox=prox))(
        A, b, table, perm)
