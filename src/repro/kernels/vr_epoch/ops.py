"""Un-jitted wrapper of the local-epoch kernel, for call sites already
inside a jit (the drivers' ``sync_init`` / ``_sync_scan``, ``init_state``
/ ``_run_scan`` and the spmd runner), and the path chooser they share.

Around the kernel, in the same program: each row is widened to whole
128-lane tiles, with its label in lane d and its old table entry in lane
d+1; the rows are gathered into visit order, ``rows_v[t, w] = rows[w,
perm[w, t]]``; and the new residuals are scattered back, ``table[w,
perm[w, t]] = s_v[w, t]``. With permutation sampling each row is visited
once and its old entry is read before it is written, so this is exact.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.vr_epoch import kernel
from repro.prox import operators as proxops


def engaged(*, sampling: str = "permutation", track_iterates: bool = False,
            fused=None, prox=None) -> bool:
    """Whether a local epoch runs as the kernel: on a backend that
    compiles Pallas natively, with permutation sampling, no iterate
    trajectory, no per-step ``fused`` params and no prox or an
    elementwise one. Everything else keeps the XLA scan, which is also
    the CPU path and the tests' oracle."""
    return (kernels.has_pallas_support() and sampling == "permutation"
            and not track_iterates and fused is None
            and proxops.is_elementwise(prox))


def path(**what) -> str:
    """``"kernel"`` or ``"scan"``, as :func:`engaged` decides for
    ``what``: the ``epoch`` field of the drivers' ``driver/init`` and
    ``driver/run`` spans."""
    return "kernel" if engaged(**what) else "scan"


def local_epochs(A, b, perm, x, eta, lam, *, kind: str, table=None,
                 gbar=None, prox=None, interpret: bool | None = None):
    """One local epoch on each of the p stacked workers at once.

    ``A`` (p, ns, d), ``b`` and ``perm`` (p, ns); ``x`` and ``gbar`` (d,)
    shared or (p, d) per worker; ``table`` (p, ns). ``table=None`` and
    ``gbar=None`` give the init epoch (plain SGD from a zero table).
    ``eta`` and ``lam`` may be traced. Returns ``(x, table, acc)``, each
    with the worker axis first: the arithmetic of
    ``distributed._local_centralvr_epoch`` / ``_local_sgd_epoch``, with
    ``a . x`` added in the order XLA's TPU reduce adds it there."""
    p, ns, d = A.shape
    dt = A.dtype
    dp = -(-(d + 2) // kernel.LANES) * kernel.LANES
    block = kernel.BLOCK
    steps = -(-ns // block) * block
    told = jnp.zeros((p, ns), dt) if table is None else table.astype(dt)
    rows = jnp.concatenate([A, b.astype(dt)[..., None], told[..., None],
                            jnp.zeros((p, ns, dp - d - 2), dt)], axis=2)
    # visit order, padded to whole blocks with row 0 (masked in-kernel);
    # a permutation's indices are in bounds, so no gather or scatter
    # below masks them
    order = jnp.pad(perm, ((0, 0), (0, steps - ns)))
    rows_v = jnp.swapaxes(jnp.take_along_axis(
        rows, order[..., None], axis=1, mode="promise_in_bounds"), 0, 1)

    def vec(v):
        v = jnp.zeros((d,), dt) if v is None else jnp.asarray(v, dt)
        return jnp.pad(jnp.broadcast_to(v, (p, d)), ((0, 0), (0, dp - d)))

    scalars = jnp.stack([jnp.asarray(eta, dt), jnp.asarray(lam, dt)])
    x, s_b, acc = kernel.vr_epoch(
        scalars, rows_v, vec(x), vec(gbar), kind=kind, prox=prox, ns=ns,
        d=d, block=block,
        interpret=(kernels.default_interpret() if interpret is None
                   else interpret))
    s_v = jnp.swapaxes(s_b, 0, 1).reshape(p, steps)[:, :ns]
    table = jnp.zeros((p, ns), dt).at[jnp.arange(p)[:, None], perm].set(
        s_v, unique_indices=True, mode="promise_in_bounds")
    return x[:, :d], table, acc[:, :d]
