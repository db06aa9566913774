"""Distributed algorithms of the paper, on the convex substrate:

  * CentralVR-Sync   (Algorithm 2)
  * CentralVR-Async  (Algorithm 3) — delta algebra + staleness simulator
  * Distributed SVRG (Algorithm 4)
  * Distributed SAGA (Algorithm 5)

Workers are simulated SPMD-style: the p local shards are stacked along a
leading axis and local epochs run under ``jax.vmap`` (numerically identical
to p independent processes; on the real mesh the same code runs under
``shard_map`` — see ``repro/train`` for the LM-scale version). The central
server of the paper is realized as an average across the worker axis — on
a TPU pod this is the epoch-boundary ``pmean`` (DESIGN.md §2).

Asynchrony: TPUs are SPMD, so CentralVR-Async's lock-free arrival order is
modelled by a deterministic staleness schedule: at event t (round-robin
over workers, optionally with heterogeneous speeds), worker s runs its
epoch from the central state it fetched at its *previous* event — i.e.
effective staleness p-1 events, the natural value for a round-robin
server. The *delta* form of the central update (x += dx/p) is kept exactly
as in Algorithm 3; the paper argues this is what makes fast workers unable
to bias the average.

Because every event depends only on the central state its worker fetched
at its OWN previous event, the schedule also admits a device-parallel
execution: ``backend="spmd"`` partitions it into concurrency waves
(``runtime.wave_partition``) and runs each wave under ``shard_map`` with
one worker per device, the delta pushes applied at the wave boundary in
event order — same algebra, same trajectories to float32 tolerance
(``core/spmd.py``, DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import convex, runtime
from repro.core.convex import Problem
from repro.obs import recorder as obs_recorder
from repro.obs import stage as obs_stage
from repro.obs import stream as obs_stream
from repro.prox import operators as proxops


class ShardedProblem(NamedTuple):
    """p stacked local shards; the global objective is the mean over all
    p * ns samples (§4 of the paper)."""

    A: jax.Array    # (p, ns, d)
    b: jax.Array    # (p, ns)
    lam: jnp.float32
    kind: str

    @property
    def p(self):
        return self.A.shape[0]

    @property
    def ns(self):
        return self.A.shape[1]

    @property
    def d(self):
        return self.A.shape[2]

    def local(self, s) -> Problem:
        return Problem(self.A[s], self.b[s], self.lam, self.kind)

    def merged(self) -> Problem:
        return Problem(self.A.reshape(-1, self.d), self.b.reshape(-1),
                       self.lam, self.kind)


jax.tree_util.register_pytree_node(
    ShardedProblem,
    lambda p: ((p.A, p.b, p.lam), p.kind),
    lambda kind, leaves: ShardedProblem(*leaves, kind=kind),
)


def check_backend(backend: str, *, spmd_ok: bool = True, algo: str = ""):
    """Validate a driver ``backend=`` argument (DESIGN.md §2).

    ``vmap`` is the stacked-axis single-device simulation and the default
    everywhere; ``spmd`` is the one-worker-per-device shard_map backend in
    ``core/spmd.py``.  Every driver has an SPMD program now — the async
    drivers run their event schedule as concurrency waves
    (``runtime.wave_partition``) — EXCEPT instant-fetch D-SAGA, whose
    events form a serial dependency chain (each event reads the central
    state as updated by the previous one): that mode passes
    ``spmd_ok=False`` and gets a clear error instead of a silent
    fallback."""
    if backend not in ("vmap", "spmd"):
        raise ValueError(
            f"unknown backend {backend!r}: expected 'vmap' or 'spmd'")
    if backend == "spmd" and not spmd_ok:
        raise NotImplementedError(
            f"{algo} is event-serial (every event reads the central state "
            "written by the previous event), so it has no worker-parallel "
            "SPMD execution; use backend='vmap', or fetch='stale' for the "
            "wave-parallel staleness construction (DESIGN.md §2)")
    return backend


def shard_problem(prob: Problem, p: int) -> ShardedProblem:
    n = (prob.n // p) * p
    return ShardedProblem(prob.A[:n].reshape(p, -1, prob.d),
                          prob.b[:n].reshape(p, -1), prob.lam, prob.kind)


def make_distributed(key, cfg) -> ShardedProblem:
    """Paper §6.2: each worker gets its OWN toy dataset of size cfg.n
    (total data scales linearly with workers — the weak-scaling setup)."""
    keys = jax.random.split(key, cfg.workers)
    probs = [convex.make_problem(k, cfg) for k in keys]
    return ShardedProblem(jnp.stack([q.A for q in probs]),
                          jnp.stack([q.b for q in probs]),
                          jnp.float32(cfg.lam), probs[0].kind)


# ---------------------------------------------------------------------------
# Local epoch primitives (vmapped over the worker axis)
# ---------------------------------------------------------------------------

def _local_centralvr_epoch(A, b, lam, kind, x, table, gbar, eta, perm,
                           fused=None, prox=None):
    """One CentralVR epoch on one worker's shard (Alg 2 lines 6-12).

    ``fused``: static kernel params from ``fused.make_params`` — routes
    the per-step update through the ``vr_update`` Pallas kernel (one
    launch per step) instead of the unfused oracle body.  ``prox``: a
    static ProxSpec (or None) — the proximal step is applied per local
    step, ``x <- prox_{eta*g}(x - eta*v)`` (DESIGN.md §Composite
    objectives); when ``fused`` is set the prox rides inside the kernel
    params and this argument is ignored (the tuple carries its own copy).

    Its device ops are named ``vr/local_epoch`` (``jax.named_scope``)."""
    if fused is not None:
        from repro.core import fused as fusedmod
        with jax.named_scope("vr/local_epoch"):
            x, table, acc, _ = fusedmod.centralvr_epoch(
                A, b, kind, x, table, gbar, perm, fused)
        return x, table, acc
    prob = Problem(A, b, lam, kind)
    ns = A.shape[0]

    def body(carry, i):
        x, table, acc = carry
        s_new = convex.scalar_residual(prob, x, i)
        v = (s_new - table[i]) * A[i] + gbar + 2.0 * lam * x
        table = table.at[i].set(s_new)
        acc = acc + s_new * A[i] / ns
        return (proxops.apply_prox(prox, x - eta * v, eta), table, acc), None

    with jax.named_scope("vr/local_epoch"):
        (x, table, acc), _ = jax.lax.scan(body, (x, table, jnp.zeros_like(x)),
                                          perm)
    return x, table, acc   # acc = local gtilde (data term)


def _local_sgd_epoch(A, b, lam, kind, x, eta, perm, prox=None):
    """The init epoch of plain SGD on one worker's shard (Alg 2 line 2);
    its device ops are named ``vr/init_epoch``."""
    prob = Problem(A, b, lam, kind)
    ns = A.shape[0]

    def body(carry, i):
        x, table, acc = carry
        s = convex.scalar_residual(prob, x, i)
        g = s * A[i] + 2.0 * lam * x
        table = table.at[i].set(s)
        acc = acc + s * A[i] / ns
        return (proxops.apply_prox(prox, x - eta * g, eta), table, acc), None

    init = (x, jnp.zeros((ns,)), jnp.zeros_like(x))
    with jax.named_scope("vr/init_epoch"):
        (x, table, acc), _ = jax.lax.scan(body, init, perm)
    return x, table, acc


class SyncState(NamedTuple):
    x: jax.Array        # (d,) shared iterate
    tables: jax.Array   # (p, ns) per-worker scalar tables
    gbar: jax.Array     # (d,) shared epoch-frozen mean gradient (data term)


# ---------------------------------------------------------------------------
# CentralVR-Sync (Algorithm 2)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("prox",))
def sync_init(sp: ShardedProblem, eta: float, key: jax.Array,
              prox=None) -> SyncState:
    """Init with one plain-SGD epoch per worker, then average (line 2).
    With a prox, locals take prox'd SGD steps and the central average gets
    one more prox (the wave-boundary ordering, DESIGN.md §2)."""
    keys = jax.random.split(key, sp.p)
    perms = jax.vmap(lambda k: jax.random.permutation(k, sp.ns))(keys)
    x0 = jnp.zeros((sp.d,))
    xs, tables, accs = jax.vmap(
        lambda A, b, perm: _local_sgd_epoch(A, b, sp.lam, sp.kind, x0, eta,
                                            perm, prox=prox)
    )(sp.A, sp.b, perms)
    return _average(xs, tables, accs, eta, prox)


def sync_round(sp: ShardedProblem, st: SyncState, eta: float, key: jax.Array,
               fused=None, prox=None) -> SyncState:
    """One communication round: a full local epoch everywhere, then the
    central average of (x, gbar) — Algorithm 2 lines 4-18.  Composite
    objectives apply the prox per local step AND once more after the
    central average: the averaged iterate of prox'd locals is not itself
    a prox output (mean of sparse vectors is dense), so the wave boundary
    re-projects it (DESIGN.md §2 ordering note)."""
    keys = jax.random.split(key, sp.p)
    perms = jax.vmap(lambda k: jax.random.permutation(k, sp.ns))(keys)
    xs, tables, accs = jax.vmap(
        lambda A, b, table, perm: _local_centralvr_epoch(
            A, b, sp.lam, sp.kind, st.x, table, st.gbar, eta, perm,
            fused=fused, prox=prox)
    )(sp.A, sp.b, st.tables, perms)
    # central node: average x and gbar (lines 16-18); on a pod: pmean
    return _average(xs, tables, accs, eta, prox)


def _average(xs, tables, accs, eta, prox) -> SyncState:
    """The central node's average of the workers' x and gbar, its device
    ops named ``vr/average``."""
    with jax.named_scope("vr/average"):
        return SyncState(x=proxops.apply_prox(prox, xs.mean(0), eta),
                         tables=tables, gbar=accs.mean(0))


@functools.partial(jax.jit, static_argnames=("fused", "stream", "prox"),
                   donate_argnames=("st",))
def _sync_scan(sp: ShardedProblem, st: SyncState, eta, g0, keys, fused=None,
               stream: bool = False, prox=None):
    merged = sp.merged()

    def step(st, xs):
        i, k = xs if stream else (None, xs)
        runtime.TRACES.inc("sync_round")
        st = sync_round(sp, st, eta, k, fused=fused, prox=prox)
        with jax.named_scope("vr/rel_grad_norm"):
            rel = convex.rel_grad_norm(merged, st.x, g0, prox=prox, eta=eta)
        if stream:
            obs_stream.scan_metric("rel", i, rel)
        return st, rel

    # `stream` is STATIC: the telemetry-off trace below is byte-identical
    # to the pre-telemetry program (DESIGN.md §Observability)
    xs = (jnp.arange(keys.shape[0]), keys) if stream else keys
    return jax.lax.scan(step, st, xs)


def run_sync(sp: ShardedProblem, *, eta: float, rounds: int, key: jax.Array,
             backend: str = "vmap", mesh=None, fused=False, prox=None):
    """Algorithm 2 end to end: one jitted scan over communication rounds,
    metric on device, state donated (DESIGN.md §3).

    ``backend="spmd"`` runs the same rounds under ``shard_map`` with one
    worker per device of ``mesh`` (default: a mesh over the first p
    devices); the central average becomes a ``pmean`` (DESIGN.md §2).

    Thin wrapper contract (DESIGN.md §Solver API): argument validation is
    a ``solver.RunSpec`` build, so this signature and ``solve()`` fail
    identically on invalid combinations."""
    from repro.core import fused as fusedmod
    from repro.core import solver
    spec = solver.RunSpec(algo="centralvr_sync", p=sp.p, eta=float(eta),
                          rounds=rounds, backend=backend, fused=fused,
                          prox=proxops.canonical(prox))
    if spec.backend == "spmd":
        from repro.core import spmd
        return spmd.run_sync(sp, eta=eta, rounds=rounds, key=key, mesh=mesh,
                             fused=fused, prox=spec.prox)
    px = proxops.parse(spec.prox) if spec.prox is not None else None
    fused_t = fusedmod.make_params(spec.fused, eta, sp.lam, prox=px)
    with obs_recorder.span("driver/keys"):
        k_init, k_run = jax.random.split(key)
        keys = jax.random.split(k_run, rounds)
    with obs_recorder.span("driver/init"):
        st = sync_init(sp, eta, k_init, prox=px)
    with obs_recorder.span("driver/grad_norm0"):
        g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    with obs_recorder.span("driver/run"):
        return obs_stage.staged_call(
            _sync_scan, sp, st, eta, g0, keys,
            _label="solve/centralvr_sync",
            fused=fused_t, stream=obs_stream.stream_active(), prox=px)


# ---------------------------------------------------------------------------
# CentralVR-Async (Algorithm 3)
# ---------------------------------------------------------------------------

class AsyncState(NamedTuple):
    x_c: jax.Array        # central iterate
    gbar_c: jax.Array     # central mean gradient (data term)
    tables: jax.Array     # (p, ns)
    x_old: jax.Array      # (p, d) each worker's previous sent x
    gbar_old: jax.Array   # (p, d) each worker's previous sent gbar
    x_fetch: jax.Array    # (p, d) central x as of each worker's last fetch
    gbar_fetch: jax.Array # (p, d)


def async_init(sp: ShardedProblem, eta: float, key: jax.Array,
               prox=None) -> AsyncState:
    st = sync_init(sp, eta, key, prox=prox)
    p = sp.p
    # Algorithm 3 line 2 sets x_old = gbar_old = 0 with x_c = x0; starting
    # instead from the SGD-init iterate requires the workers' "previous
    # contribution" to equal that iterate, otherwise the first p events
    # add the init point a second time (x_c <- x_init + mean(x_s) instead
    # of mean(x_s)). Same algebra, transient removed.
    return AsyncState(
        x_c=st.x, gbar_c=st.gbar, tables=st.tables,
        x_old=jnp.tile(st.x, (p, 1)), gbar_old=jnp.tile(st.gbar, (p, 1)),
        x_fetch=jnp.tile(st.x, (p, 1)), gbar_fetch=jnp.tile(st.gbar, (p, 1)),
    )


def async_event(sp: ShardedProblem, st: AsyncState, s, eta: float,
                key: jax.Array, fused=None, prox=None) -> AsyncState:
    """Worker s completes one local epoch computed from its stale fetch,
    sends (dx, dgbar); the central node applies x += dx/p (Alg 3 l.18-21);
    the worker then fetches the fresh central state.

    Composite objectives: the central accumulator x_c must stay LINEAR in
    the pushed deltas (the spmd wave backend reconstructs fetches by
    prefix sums over them), so the prox is never applied to x_c itself —
    each worker prox's its FETCHED copy at epoch start instead, and the
    metric/final iterate evaluate at ``prox(x_c)`` (DESIGN.md §Composite
    objectives).

    ``s`` may be a concrete int or a TRACED index: the stacked (p, ns)
    tables are read with dynamic gathers (``sp.A[s]``) and written with
    ``.at[s].set``, so one compiled executable serves every worker — the
    event schedule becomes data, not code (DESIGN.md §3)."""
    p = sp.p
    alpha = 1.0 / p
    perm = jax.random.permutation(key, sp.ns)
    x_new, table, gtilde = _local_centralvr_epoch(
        sp.A[s], sp.b[s], sp.lam, sp.kind,
        proxops.apply_prox(prox, st.x_fetch[s], eta), st.tables[s],
        st.gbar_fetch[s], eta, perm, fused=fused, prox=prox)
    dx = x_new - st.x_old[s]
    dg = gtilde - st.gbar_old[s]
    x_c = st.x_c + alpha * dx
    gbar_c = st.gbar_c + alpha * dg
    return AsyncState(
        x_c=x_c, gbar_c=gbar_c,
        tables=st.tables.at[s].set(table),
        x_old=st.x_old.at[s].set(x_new),
        gbar_old=st.gbar_old.at[s].set(gtilde),
        x_fetch=st.x_fetch.at[s].set(x_c),        # receive updated x
        gbar_fetch=st.gbar_fetch.at[s].set(gbar_c),
    )


@functools.partial(jax.jit, static_argnames=("fused", "stream", "prox"),
                   donate_argnames=("st",))
def _async_scan(sp: ShardedProblem, st: AsyncState, eta, g0, schedule, keys,
                fused=None, stream: bool = False, prox=None):
    """The full event schedule in one executable: an outer scan over rounds
    (emitting the metric every p events, as the host loop did) nests an
    inner scan over each round's p events.  The worker index is TRACED —
    exactly one trace/compile of ``async_event`` regardless of p."""
    merged = sp.merged()

    def one_round(st, xs):
        if stream:
            i, sched_row, key_row = xs
        else:
            sched_row, key_row = xs

        def one_event(st, sk):
            runtime.TRACES.inc("async_event")
            s, k = sk
            return async_event(sp, st, s, eta, k, fused=fused,
                               prox=prox), None

        st, _ = jax.lax.scan(one_event, st, (sched_row, key_row))
        # metric at the feasible point prox(x_c) — x_c itself stays linear
        rel = convex.rel_grad_norm(
            merged, proxops.apply_prox(prox, st.x_c, eta), g0,
            prox=prox, eta=eta)
        if stream:
            obs_stream.scan_metric("rel", i, rel)
        return st, rel

    xs = ((jnp.arange(schedule.shape[0]), schedule, keys) if stream
          else (schedule, keys))
    return jax.lax.scan(one_round, st, xs)


def run_async(sp: ShardedProblem, *, eta: float, rounds: int, key: jax.Array,
              speeds=None, backend: str = "vmap", mesh=None, fused=False,
              prox=None):
    """``rounds`` epochs per worker. ``speeds``: optional per-worker relative
    speeds; faster workers fire proportionally more events (heterogeneous
    cluster simulation). Default: round-robin (staleness p-1).

    The speed-weighted schedule is precomputed on the host, shipped as a
    (rounds, p) int32 array, and scanned on device in a single compile.

    ``backend="spmd"`` executes the SAME schedule as rounds of concurrent
    events: each worker's epoch starts from the central state it fetched
    at its previous event — a per-worker stale snapshot already carried by
    the delta algebra — so all events of a concurrency wave
    (``runtime.wave_partition``) run in parallel under ``shard_map``, one
    worker per device of ``mesh``, and the ``x += dx/p`` delta pushes are
    applied at the wave boundary in the schedule's event order
    (DESIGN.md §2).  Trajectories match this event-serial path within
    float32 tolerance (pinned by ``tests/test_spmd_backend.py``).

    Validation is a ``solver.RunSpec`` build (DESIGN.md §Solver API)."""
    from repro.core import fused as fusedmod
    from repro.core import solver
    spec = solver.RunSpec(
        algo="centralvr_async", p=sp.p, eta=float(eta), rounds=rounds,
        backend=backend, fused=fused,
        speeds=None if speeds is None else tuple(float(s) for s in speeds),
        prox=proxops.canonical(prox))
    if spec.backend == "spmd":
        from repro.core import spmd
        return spmd.run_async(sp, eta=eta, rounds=rounds, key=key,
                              speeds=spec.speeds, mesh=mesh, fused=fused,
                              prox=spec.prox)
    px = proxops.parse(spec.prox) if spec.prox is not None else None
    fused_t = fusedmod.make_params(spec.fused, eta, sp.lam, prox=px)
    k_init, k_run = jax.random.split(key)
    st = async_init(sp, eta, k_init, prox=px)
    g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    schedule = runtime.event_schedule(sp.p, rounds, spec.speeds)
    keys = jax.random.split(k_run, schedule.size)
    sched, keys = runtime.per_round(schedule, keys, sp.p)
    return obs_stage.staged_call(
        _async_scan, sp, st, eta, g0, jnp.asarray(sched), keys,
        _label="solve/centralvr_async",
        fused=fused_t, stream=obs_stream.stream_active(), prox=px)


# ---------------------------------------------------------------------------
# Distributed SVRG (Algorithm 4)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("tau", "fused", "stream", "prox",
                                    "snapshot"),
                   donate_argnames=("x",))
def _dsvrg_scan(sp: ShardedProblem, x, eta, g0, keys, tau: int, fused=None,
                stream: bool = False, prox=None, snapshot: str = "last",
                snap_idx=None):
    """``snapshot`` selects the next-round anchor each worker contributes
    (then averaged across workers): ``last`` = final inner iterate (the
    historical program, byte-identical), ``avg`` = mean of the tau inner
    iterates, ``rand`` = the inner iterate at a host-precomputed uniform
    index (``snap_idx``, one shared draw per round so vmap and spmd pick
    the same one) — the SVRG options of Johnson & Zhang.  ``prox`` applies
    per inner step and once more after the cross-worker average."""
    merged = sp.merged()

    def round_(x, xs):
        if snapshot == "rand":
            xs, r = xs[:-1], xs[-1]
            xs = xs[0] if len(xs) == 1 else xs
        step_i, k = xs if stream else (None, xs)
        runtime.TRACES.inc("dsvrg_round")
        xbar = x
        gbar = convex.full_grad(merged, xbar)   # sync step (line 5)

        def local(A, b, kk):
            prob = Problem(A, b, sp.lam, sp.kind)
            idx = jax.random.randint(kk, (tau,), 0, sp.ns)

            if fused is not None:
                # snapshot=="last" here: run_dsvrg falls back to unfused
                # for avg/rand (and RunSpec refuses an explicit fused=True)
                from repro.core import fused as fusedmod
                sbar = convex.scalar_residual_all(prob, xbar)
                return fusedmod.svrg_steps(A, b, sp.kind, xbar, sbar, gbar,
                                           idx, fused)

            def body(xl, i):
                g = (convex.scalar_residual(prob, xl, i) * A[i]
                     - convex.scalar_residual(prob, xbar, i) * A[i]
                     + gbar + 2.0 * sp.lam * (xl - xbar))
                xl = proxops.apply_prox(prox, xl - eta * g, eta)
                return xl, (xl if snapshot != "last" else None)

            xl, traj = jax.lax.scan(body, xbar, idx)
            if snapshot == "avg":
                return traj.mean(0)
            if snapshot == "rand":
                return traj[r]
            return xl

        xl_all = jax.vmap(local)(sp.A, sp.b, jax.random.split(k, sp.p))
        x = proxops.apply_prox(prox, xl_all.mean(0), eta)
        rel = convex.rel_grad_norm(merged, x, g0, prox=prox, eta=eta)
        if stream:
            obs_stream.scan_metric("rel", step_i, rel)
        return x, rel

    xs = (jnp.arange(keys.shape[0]), keys) if stream else keys
    if snapshot == "rand":
        xs = (xs + (snap_idx,)) if isinstance(xs, tuple) else (xs, snap_idx)
    return jax.lax.scan(round_, x, xs)


def run_dsvrg(sp: ShardedProblem, *, eta: float, rounds: int, key: jax.Array,
              tau: int = 0, backend: str = "vmap", mesh=None, fused=False,
              prox=None, snapshot: str = "last"):
    """tau local steps from the shared snapshot (default tau = 2*ns, the
    paper's recommendation from [17]); gbar = full gradient at the snapshot
    (the synchronization step); then average x across workers.
    2 gradient evaluations per iteration (Table 1).  One jitted scan over
    rounds (DESIGN.md §3); ``backend="spmd"`` places one worker per mesh
    device and the averages/sync gradient become collectives.

    ``snapshot`` in {"last", "avg", "rand"} picks the anchor each worker
    feeds the cross-worker average (see ``_dsvrg_scan``); avg/rand need
    the inner trajectory, which the fused kernel does not materialize, so
    they run unfused (``fused="auto"`` silently falls back here,
    ``fused=True`` is refused by RunSpec pre-JAX).

    Validation is a ``solver.RunSpec`` build (DESIGN.md §Solver API)."""
    from repro.core import fused as fusedmod
    from repro.core import solver
    spec = solver.RunSpec(algo="dsvrg", p=sp.p, eta=float(eta),
                          rounds=rounds, backend=backend, tau=tau or None,
                          fused=fused, prox=proxops.canonical(prox),
                          snapshot=snapshot)
    if spec.backend == "spmd":
        from repro.core import spmd
        return spmd.run_dsvrg(sp, eta=eta, rounds=rounds, key=key, tau=tau,
                              mesh=mesh, fused=fused, prox=spec.prox,
                              snapshot=snapshot)
    px = proxops.parse(spec.prox) if spec.prox is not None else None
    fused_t = (fusedmod.make_params(spec.fused, eta, sp.lam, prox=px)
               if snapshot == "last" else None)
    tau = tau or 2 * sp.ns
    x = jnp.zeros((sp.d,))
    g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    keys = jax.random.split(key, rounds)
    # one shared uniform anchor index per round, drawn off the main key
    # stream (fold_in) so last/avg trajectories are unaffected
    snap_idx = (jax.random.randint(jax.random.fold_in(key, 1), (rounds,),
                                   0, tau)
                if snapshot == "rand" else None)
    return obs_stage.staged_call(
        _dsvrg_scan, sp, x, eta, g0, keys, _label="solve/dsvrg",
        tau=tau, fused=fused_t, stream=obs_stream.stream_active(),
        prox=px, snapshot=snapshot, snap_idx=snap_idx)


# ---------------------------------------------------------------------------
# Distributed SAGA (Algorithm 5)
# ---------------------------------------------------------------------------

class DSagaState(NamedTuple):
    x_c: jax.Array
    gbar_c: jax.Array
    tables: jax.Array     # (p, ns) scalar residuals
    x_old: jax.Array      # (p, d)
    gbar_old: jax.Array   # (p, d) — literal mode: previous local final gbar


def _local_saga_steps(A, b, lam, kind, x, table, gbar, eta, n_global, idx,
                      fused=None, prox=None):
    """tau local SAGA steps on one worker's shard (Alg 5 lines 5-11): VR
    step from the scalar table, running-mean gbar update with the GLOBAL
    1/n scaling (line 9, §5.2).  The single spelling shared by both fetch
    disciplines and the spmd wave runner — the vmap-vs-spmd agreement
    pins rely on these being the same arithmetic (and, when ``fused`` is
    set, the same single-launch kernel step — the fused tuple carries its
    own prox copy)."""
    if fused is not None:
        from repro.core import fused as fusedmod
        return fusedmod.saga_steps(A, b, kind, x, table, gbar, n_global,
                                   idx, fused)
    prob = Problem(A, b, lam, kind)

    def body(carry, i):
        x, table, gbar = carry
        s_new = convex.scalar_residual(prob, x, i)
        v = (s_new - table[i]) * A[i] + gbar + 2.0 * lam * x
        gbar = gbar + (s_new - table[i]) * A[i] / n_global
        table = table.at[i].set(s_new)
        return (proxops.apply_prox(prox, x - eta * v, eta), table, gbar), None

    (x, table, gbar), _ = jax.lax.scan(body, (x, table, gbar), idx)
    return x, table, gbar


def dsaga_event(sp: ShardedProblem, st: DSagaState, s, eta: float, tau: int,
                key, literal_scaling: bool = False,
                fused=None, prox=None) -> DSagaState:
    """Worker s: tau local SAGA steps from its fetched central state, then
    the delta push (Alg 5 lines 12-20). Events interleave round-robin — the
    async arrival order, one at a time (the paper's implementation is
    'locked': one worker updates the server at a time, §6.2).  ``s`` may be
    a traced index (dynamic gathers on the stacked tables), so one compiled
    event function serves all p workers."""
    alpha = 1.0 / sp.p
    alpha_g = alpha if literal_scaling else 1.0
    idx = jax.random.randint(key, (tau,), 0, sp.ns)
    # prox the FETCHED copy at block start; x_c itself stays linear in the
    # pushed deltas (same rationale as async_event)
    x, table, gbar = _local_saga_steps(
        sp.A[s], sp.b[s], sp.lam, sp.kind,
        proxops.apply_prox(prox, st.x_c, eta), st.tables[s], st.gbar_c,
        eta, sp.p * sp.ns, idx, fused=fused, prox=prox)
    dx = x - st.x_old[s]
    if literal_scaling:
        dg = gbar - st.gbar_old[s]       # printed line 13
    else:
        dg = gbar - st.gbar_c            # own contribution only
    return DSagaState(
        x_c=st.x_c + alpha * dx,
        gbar_c=st.gbar_c + alpha_g * dg,
        tables=st.tables.at[s].set(table),
        x_old=st.x_old.at[s].set(x),
        gbar_old=st.gbar_old.at[s].set(gbar),
    )


@jax.jit
def dsaga_init(sp: ShardedProblem) -> DSagaState:
    """Tables at x0 (Alg 5 lines 2-3), central gbar = global table mean."""
    x0 = jnp.zeros((sp.d,))
    s_all = jax.vmap(lambda A, b: convex.scalar_residual_all(
        Problem(A, b, sp.lam, sp.kind), x0))(sp.A, sp.b)
    gbar0 = jnp.einsum("psd,ps->d", sp.A, s_all) / (sp.p * sp.ns)
    return DSagaState(x_c=x0, gbar_c=gbar0, tables=s_all,
                      x_old=jnp.tile(x0, (sp.p, 1)),
                      gbar_old=jnp.tile(gbar0, (sp.p, 1)))


def dsaga_init_stale(sp: ShardedProblem) -> AsyncState:
    """Stale-fetch D-SAGA start state: ``dsaga_init`` plus per-worker fetch
    snapshots initialized to the central values (every worker's first event
    starts from the true t=0 state, exactly like ``async_init``)."""
    st = dsaga_init(sp)
    return AsyncState(
        x_c=st.x_c, gbar_c=st.gbar_c, tables=st.tables,
        x_old=st.x_old, gbar_old=st.gbar_old,
        x_fetch=jnp.tile(st.x_c, (sp.p, 1)),
        gbar_fetch=jnp.tile(st.gbar_c, (sp.p, 1)),
    )


def dsaga_event_stale(sp: ShardedProblem, st: AsyncState, s, eta: float,
                      tau: int, key, literal_scaling: bool = False,
                      fused=None, prox=None) -> AsyncState:
    """Algorithm 5 with Algorithm 3's fetch discipline: worker s runs its
    tau local SAGA steps from the central state it fetched at its PREVIOUS
    event (``st.x_fetch[s]``/``st.gbar_fetch[s]``) instead of the
    instantaneous central state ``dsaga_event`` reads.  This is the
    event-serial reference for the spmd-async backend (DESIGN.md §2): the
    stale snapshot removes the event-to-event serial dependency, so all
    events of a concurrency wave commute and can run under ``shard_map``.
    The delta algebra is unchanged — dx against the worker's previous sent
    x, dgbar against its fetched gbar (its own table-update contribution,
    the §5.2 semantics), server coefficients exactly as ``dsaga_event``.
    ``s`` may be a traced index, as everywhere in this runtime."""
    alpha = 1.0 / sp.p
    alpha_g = alpha if literal_scaling else 1.0
    idx = jax.random.randint(key, (tau,), 0, sp.ns)
    x, table, gbar = _local_saga_steps(
        sp.A[s], sp.b[s], sp.lam, sp.kind,
        proxops.apply_prox(prox, st.x_fetch[s], eta), st.tables[s],
        st.gbar_fetch[s], eta, sp.p * sp.ns, idx, fused=fused, prox=prox)
    dx = x - st.x_old[s]
    if literal_scaling:
        dg = gbar - st.gbar_old[s]       # printed line 13
    else:
        dg = gbar - st.gbar_fetch[s]     # own contribution only
    x_c = st.x_c + alpha * dx
    gbar_c = st.gbar_c + alpha_g * dg
    return AsyncState(
        x_c=x_c, gbar_c=gbar_c,
        tables=st.tables.at[s].set(table),
        x_old=st.x_old.at[s].set(x),
        gbar_old=st.gbar_old.at[s].set(gbar),
        x_fetch=st.x_fetch.at[s].set(x_c),
        gbar_fetch=st.gbar_fetch.at[s].set(gbar_c),
    )


@functools.partial(jax.jit,
                   static_argnames=("tau", "literal_scaling", "stale",
                                    "fused", "stream", "prox"),
                   donate_argnames=("st",))
def _dsaga_scan(sp: ShardedProblem, st, eta, g0, schedule, keys,
                tau: int, literal_scaling: bool, stale: bool, fused=None,
                stream: bool = False, prox=None):
    """One scan runner for both fetch disciplines: ``stale`` selects the
    event function (and the matching state type — DSagaState for instant,
    AsyncState for stale) at trace time."""
    merged = sp.merged()
    event = dsaga_event_stale if stale else dsaga_event
    trace_key = "dsaga_event_stale" if stale else "dsaga_event"

    def one_round(st, xs):
        if stream:
            i, sched_row, key_row = xs
        else:
            sched_row, key_row = xs

        def one_event(st, sk):
            runtime.TRACES.inc(trace_key)
            s, k = sk
            return event(sp, st, s, eta, tau, k, literal_scaling,
                         fused=fused, prox=prox), None

        st, _ = jax.lax.scan(one_event, st, (sched_row, key_row))
        rel = convex.rel_grad_norm(
            merged, proxops.apply_prox(prox, st.x_c, eta), g0,
            prox=prox, eta=eta)
        if stream:
            obs_stream.scan_metric("rel", i, rel)
        return st, rel

    xs = ((jnp.arange(schedule.shape[0]), schedule, keys) if stream
          else (schedule, keys))
    return jax.lax.scan(one_round, st, xs)


def run_dsaga(sp: ShardedProblem, *, eta: float, rounds: int, key: jax.Array,
              tau: int = 100, literal_scaling: bool = False,
              backend: str = "vmap", fetch: str | None = None,
              speeds=None, mesh=None, fused=False, prox=None):
    """Algorithm 5. Each worker runs tau SAGA steps with its local table;
    the running mean gbar is updated with the GLOBAL 1/n scaling (§5.2);
    deltas (dx, dgbar) are pushed with server coefficient alpha.

    Delta semantics for gbar: Algorithm 5 as literally printed computes
    dgbar against the worker's own previous *final* local gbar and applies
    server coefficient alpha=1/p. That delta embeds the central drift
    caused by OTHER workers between the two events (the local gbar starts
    from the fetched central value), so with alpha=1 it echoes and
    diverges, and with alpha=1/p the server's gbar lags the true table
    mean by a factor ~p and convergence plateaus (we measured both; see
    EXPERIMENTS.md §D-SAGA delta semantics). The §5.2 prose — "the previous
    contribution to the average from that local worker is just replaced by
    the new contribution ... gbar is built from the most recent gradient
    computations at each index" — pins down the intended semantics:
    the delta must isolate the worker's OWN table-update contribution,
    i.e. dgbar = gbar_local_final - gbar_fetched (the sum of its 1/n-scaled
    table updates this block), applied with coefficient 1 (indices are
    disjoint across workers, so the sum keeps the server gbar exactly equal
    to the global table mean at every event). That is the default here;
    ``literal_scaling=True`` reproduces the printed lines for comparison.

    Fetch discipline: the default ``fetch="instant"`` is the locked serial
    model (each event reads the central state left by the previous event —
    the seed semantics, pinned against ``host_loop.run_dsaga``);
    ``fetch="stale"`` is Algorithm 3's discipline applied to Algorithm 5
    (each worker starts from the central state fetched at its own previous
    event), which removes the event-to-event serial dependency and is
    therefore what the wave-parallel spmd backend executes.
    ``backend="spmd"`` defaults to (and requires) ``fetch="stale"``:
    instant fetch has no worker-parallel program and raises.  ``speeds``
    weights the event schedule exactly as in :func:`run_async`.

    Like CentralVR-Async, the whole event schedule runs as one jitted scan
    with a traced worker index — one executable regardless of p.

    Validation — including the fetch-default resolution and the
    fetch='instant'+spmd refusal — is a ``solver.RunSpec`` build
    (DESIGN.md §Solver API).
    """
    from repro.core import fused as fusedmod
    from repro.core import solver
    spec = solver.RunSpec(
        algo="dsaga", p=sp.p, eta=float(eta), rounds=rounds,
        backend=backend, fetch=fetch,
        speeds=None if speeds is None else tuple(float(s) for s in speeds),
        tau=tau, fused=fused, prox=proxops.canonical(prox))
    fetch = spec.fetch
    if spec.backend == "spmd":
        from repro.core import spmd
        return spmd.run_dsaga(sp, eta=eta, rounds=rounds, key=key, tau=tau,
                              literal_scaling=literal_scaling,
                              speeds=spec.speeds, mesh=mesh, fused=fused,
                              prox=spec.prox)
    px = proxops.parse(spec.prox) if spec.prox is not None else None
    fused_t = fusedmod.make_params(spec.fused, eta, sp.lam, prox=px)
    g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    schedule = runtime.event_schedule(sp.p, rounds, spec.speeds)
    keys = jax.random.split(key, schedule.size)
    sched, keys = runtime.per_round(schedule, keys, sp.p)
    st = dsaga_init_stale(sp) if fetch == "stale" else dsaga_init(sp)
    return obs_stage.staged_call(
        _dsaga_scan, sp, st, eta, g0, jnp.asarray(sched), keys,
        _label="solve/dsaga", tau=tau, literal_scaling=literal_scaling,
        stale=(fetch == "stale"), fused=fused_t,
        stream=obs_stream.stream_active(), prox=px)
