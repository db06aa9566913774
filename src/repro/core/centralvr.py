"""CentralVR, single-worker case (Algorithm 1 of the paper).

The update (Eqs. 5-6):

    x <- x - eta * ( grad f_i(x) - grad f_i(xtilde_i) + gbar )

with gbar = (1/n) sum_j grad f_j(xtilde_j) frozen over the epoch and
refreshed at epoch end from the running accumulator gtilde (line 11).

Storage uses the GLM scalar-residual structure (one scalar per sample, the
paper's own observation in §2.3); the regularizer gradient 2*lam*x is added
exactly outside the correction (see core/convex.py docstring).

Both sampling modes of the paper are implemented:
  * permutation sampling (§2.2, the practical default) — the accumulator
    identity makes one epoch an exact full-gradient step in aggregate
    (Eq. 7), which ``tests/test_paper_invariants.py`` checks bit-for-bit;
  * uniform-with-replacement (§3) — the regime of Theorem 1.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import convex, runtime
from repro.core.convex import Problem
from repro.kernels.vr_epoch import ops as vr_epoch
from repro.obs import recorder as obs_recorder
from repro.obs import stage as obs_stage
from repro.obs import stream as obs_stream
from repro.prox import operators as proxops


class VRState(NamedTuple):
    x: jax.Array        # (d,) iterate
    table: jax.Array    # (n,) stored scalar residuals s_j = l'(a_j^T xtilde_j)
    gbar: jax.Array     # (d,) data term of the epoch-frozen mean gradient


# ---------------------------------------------------------------------------
# Initialization (Algorithm 1, line 2: one epoch of plain SGD)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("prox",))
def init_state(prob: Problem, eta: float, key: jax.Array,
               x0: Optional[jax.Array] = None, prox=None) -> VRState:
    """The init epoch of plain SGD in the order ``permutation(key, n)``;
    on a TPU (``vr_epoch.engaged``) it runs as the ``vr_epoch`` kernel
    from a zero table, the same floats as the scan below."""
    x0 = jnp.zeros((prob.d,)) if x0 is None else x0
    perm = jax.random.permutation(key, prob.n)
    if vr_epoch.engaged(prox=prox):
        with jax.named_scope("vr/init_epoch"):
            x, table, acc = vr_epoch.local_epochs(
                prob.A[None], prob.b[None], perm[None], x0, eta, prob.lam,
                kind=prob.kind, prox=prox)
        return VRState(x=x[0], table=table[0], gbar=acc[0])

    def body(carry, i):
        x, table, acc = carry
        s = convex.scalar_residual(prob, x, i)
        g = s * prob.A[i] + 2.0 * prob.lam * x
        table = table.at[i].set(s)
        acc = acc + s * prob.A[i] / prob.n
        x_next = proxops.apply_prox(prox, x - eta * g, eta)
        return (x_next, table, acc), None

    init = (x0, jnp.zeros((prob.n,)), jnp.zeros((prob.d,)))
    with jax.named_scope("vr/init_epoch"):
        (x, table, acc), _ = jax.lax.scan(body, init, perm)
    return VRState(x=x, table=table, gbar=acc)


# ---------------------------------------------------------------------------
# One epoch
# ---------------------------------------------------------------------------

def epoch(prob: Problem, state: VRState, eta: float, order: jax.Array,
          *, track_iterates: bool = False, fused=None, prox=None):
    """Run n CentralVR updates visiting ``order``, a permutation (the
    practical variant; ``epoch_uniform`` is the Theorem-1 variant).

    Returns the new state (gbar <- gtilde per line 11) and, optionally, the
    iterate trajectory for Lyapunov-function measurements.

    ``fused``: static kernel params from :func:`fused.make_params`, or
    ``None`` for the unfused oracle body.  The fused path runs the
    correction + step + accumulator write as one ``vr_update`` launch per
    step (DESIGN.md §Fused kernels hot-path); eta — and the prox epilogue,
    when one is configured — ride in the params, so ``prox`` here only
    shapes the unfused body.

    ``fused=None`` on a TPU, with no iterate trajectory and no prox or an
    elementwise one (``vr_epoch.engaged``), runs this unfused algebra as
    the ``vr_epoch`` kernel, the step loop inside one launch; elsewhere
    as the scan below, which is the kernel's oracle.

    The epoch's device ops are named ``vr/local_epoch``.
    """
    if vr_epoch.engaged(track_iterates=track_iterates, fused=fused,
                        prox=prox):
        with jax.named_scope("vr/local_epoch"):
            x, table, acc = vr_epoch.local_epochs(
                prob.A[None], prob.b[None], order[None], state.x, eta,
                prob.lam, kind=prob.kind, table=state.table[None],
                gbar=state.gbar, prox=prox)
        return VRState(x=x[0], table=table[0], gbar=acc[0]), None
    if fused is not None:
        from repro.core import fused as fusedmod
        with jax.named_scope("vr/local_epoch"):
            x, table, acc, traj = fusedmod.centralvr_epoch(
                prob.A, prob.b, prob.kind, state.x, state.table, state.gbar,
                order, fused, track=track_iterates)
        return VRState(x=x, table=table, gbar=acc), traj

    def body(carry, i):
        x, table, acc = carry
        s_new = convex.scalar_residual(prob, x, i)
        # v = (s_new - s_old) a_i + gbar + 2 lam x   (Eq. 6, scalar form)
        v = (s_new - table[i]) * prob.A[i] + state.gbar + 2.0 * prob.lam * x
        x_next = proxops.apply_prox(prox, x - eta * v, eta)
        table = table.at[i].set(s_new)
        acc = acc + s_new * prob.A[i] / prob.n
        return (x_next, table, acc), (x if track_iterates else None)

    init = (state.x, state.table, jnp.zeros((prob.d,)))
    with jax.named_scope("vr/local_epoch"):
        (x, table, acc), traj = jax.lax.scan(body, init, order)
    # permutation sampling: every index is visited exactly once, so the
    # running accumulator IS the table mean (line 11: gbar <- gtilde)
    gbar_next = acc
    return VRState(x=x, table=table, gbar=gbar_next), traj


def epoch_uniform(prob: Problem, state: VRState, eta: float, key: jax.Array,
                  *, track_iterates: bool = False, fused=None, prox=None):
    """Theorem-1 regime: i.i.d. uniform sampling, gbar refreshed from table."""
    idx = jax.random.randint(key, (prob.n,), 0, prob.n)
    if fused is not None:
        from repro.core import fused as fusedmod
        x, table, _, traj = fusedmod.centralvr_epoch(
            prob.A, prob.b, prob.kind, state.x, state.table, state.gbar,
            idx, fused, track=track_iterates)
        gbar_next = convex.data_grad_from_scalars(prob, table)
        return VRState(x=x, table=table, gbar=gbar_next), traj

    def body(carry, i):
        x, table = carry
        s_new = convex.scalar_residual(prob, x, i)
        v = (s_new - table[i]) * prob.A[i] + state.gbar + 2.0 * prob.lam * x
        x_next = proxops.apply_prox(prox, x - eta * v, eta)
        table = table.at[i].set(s_new)
        return (x_next, table), (x if track_iterates else None)

    with jax.named_scope("vr/local_epoch"):
        (x, table), traj = jax.lax.scan(body, (state.x, state.table), idx)
    gbar_next = convex.data_grad_from_scalars(prob, table)
    return VRState(x=x, table=table, gbar=gbar_next), traj


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("sampling", "fused", "stream", "prox"),
                   donate_argnames=("state",))
def _run_scan(prob: Problem, state: VRState, eta, g0, keys, sampling: str,
              fused=None, stream: bool = False, prox=None):
    """The whole Algorithm-1 run as one executable: a scan over epochs with
    the relative-grad-norm metric computed on device.  ``state`` is donated
    so the (n,) table and (d,) iterate/gbar update in place.  The metric's
    device ops are named ``vr/rel_grad_norm``."""

    def one_epoch(state, xs):
        i, k = xs if stream else (None, xs)
        runtime.TRACES.inc("centralvr_epoch")
        if sampling == "permutation":
            order = jax.random.permutation(k, prob.n)
            new_state, _ = epoch(prob, state, eta, order, fused=fused,
                                 prox=prox)
        else:
            new_state, _ = epoch_uniform(prob, state, eta, k, fused=fused,
                                         prox=prox)
        with jax.named_scope("vr/rel_grad_norm"):
            rel = convex.rel_grad_norm(prob, new_state.x, g0, prox=prox,
                                       eta=eta)
        if stream:
            obs_stream.scan_metric("rel", i, rel)
        return new_state, rel

    # `stream` is STATIC: telemetry off traces the exact pre-telemetry
    # program (DESIGN.md §Observability)
    xs = (jnp.arange(keys.shape[0]), keys) if stream else keys
    return jax.lax.scan(one_epoch, state, xs)


def run(prob: Problem, *, eta: float, epochs: int, key: jax.Array,
        sampling: str = "permutation", x0: Optional[jax.Array] = None,
        backend: str = "vmap", mesh=None, fused=False, prox=None):
    """Full Algorithm 1. Returns (final state, per-epoch relative grad norms,
    gradient-evaluation counts). 1 gradient evaluation per iteration
    (Table 1 row 'CentralVR'), plus the n initialization evaluations.

    Device-resident: the epoch loop is a single jitted ``lax.scan``; the
    per-epoch metric trajectory comes back in one transfer (DESIGN.md §3).

    ``backend``: Algorithm 1 is single-worker, so ``"spmd"`` simply places
    the run on the mesh's first device — the parameter exists so launchers
    can address every driver through one switch (DESIGN.md §2).

    Validation is a ``solver.RunSpec`` build (DESIGN.md §Solver API).

    ``fused=False`` on a TPU with permutation sampling runs the init
    epoch and every epoch as the ``vr_epoch`` kernel; the ``driver/init``
    and ``driver/run`` spans say which path ran in their ``epoch`` field.
    """
    from repro.core import fused as fusedmod
    from repro.core import solver
    spec = solver.RunSpec(algo="centralvr", eta=float(eta), rounds=epochs,
                          backend=backend, sampling=sampling, fused=fused,
                          prox=proxops.canonical(prox))
    px = proxops.parse(spec.prox) if spec.prox is not None else None
    if spec.sampling == "sparse":
        from repro.prox import lazy
        return lazy.run_sparse(prob, eta=eta, epochs=epochs, key=key,
                               x0=x0, prox=px)
    if spec.backend == "spmd":
        from repro.core import spmd
        return spmd.run_centralvr(prob, eta=eta, epochs=epochs, key=key,
                                  sampling=sampling, x0=x0, mesh=mesh,
                                  fused=fused, prox=spec.prox)
    # the fused tuple carries its own copy of the (elementwise) prox for
    # the kernel epilogue; ``px`` still shapes the init epoch, the metric,
    # and the unfused body — the epoch dispatcher ignores it when fused
    fused_t = fusedmod.make_params(spec.fused, eta, prob.lam, prox=px)
    with obs_recorder.span("driver/keys"):
        k_init, k_run = jax.random.split(key)
        keys = jax.random.split(k_run, epochs)
    with obs_recorder.span("driver/init", epoch=vr_epoch.path(prox=px)):
        state = init_state(prob, eta, k_init, x0=x0, prox=px)
    with obs_recorder.span("driver/grad_norm0"):
        g0 = convex.grad_norm0(prob, prox=px, eta=eta)
    with obs_recorder.span("driver/run", epoch=vr_epoch.path(
            sampling=sampling, fused=fused_t, prox=px)):
        state, rels = obs_stage.staged_call(
            _run_scan, prob, state, eta, g0, keys, _label="solve/centralvr",
            sampling=sampling, fused=fused_t, prox=px,
            stream=obs_stream.stream_active())
    grad_evals = prob.n * jnp.arange(2, epochs + 2)
    return state, rels, grad_evals
