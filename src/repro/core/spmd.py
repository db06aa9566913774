"""SPMD multi-device execution backend for the convex driver runtime.

The default backend simulates the p workers with a stacked leading axis
under ``jax.vmap`` — numerically identical to p processes, but every shard
lives on ONE device.  This module is the second backend (DESIGN.md §2):
the same local-epoch primitives run under ``jax.shard_map`` over a real
``jax.sharding.Mesh`` with one worker per device, so each worker's
``(ns, d)`` shard, VR table, and gradient accumulator are resident on its
own device and the paper's central server becomes collective communication
(``jax.lax.pmean`` over the worker axis) instead of a ``mean(axis=0)``.

On the chip, the mesh is the chip's devices: ``worker_mesh(p)`` takes
the first p and fails, naming the count, when there are fewer.  On the
CPU (tests), ``force_host_devices(n)`` (shared by ``launch/mesh.py`` and
the tests) makes the host platform present n devices via XLA_FLAGS — it
must run before the jax backend initializes, but after ``import jax`` is
fine (device state is lazy).

Sampling is data, not code (the async event schedule's rule, DESIGN.md §3,
extended to RNG): every permutation/index draw is precomputed on the host
with EXACTLY the key splits the vmap drivers perform, then shipped to the
mesh sharded along the worker axis.  Shipping the draws guarantees both
backends consume identical randomness by construction, so the only
numerical divergence left is collective reduction order (the spmd/vmap
agreement is pinned by ``tests/test_spmd_backend.py``).  Every runner
passes ``check_vma=False``: the round loops carry state that enters
unreplicated and leaves pmean-replicated, and correctness is pinned by
the vmap-agreement tests instead.

The asynchronous drivers (CentralVR-Async, D-SAGA) run their deterministic
event schedule as ROUNDS OF CONCURRENT EVENTS: ``runtime.wave_partition``
groups the flat schedule into waves containing each worker at most once
(byte-identical event order), every worker of a wave runs its local epoch
from the central state it fetched at its previous event — a stale snapshot
carried per worker on its own device — and the Algorithm-3 delta pushes
``x += dx/p`` are applied at the wave boundary in the schedule's event
order (each worker's fresh fetch is the central state immediately after
its own event, reconstructed as a rank-prefix over the wave's
all-gathered deltas).  Same delta algebra, so the trajectories match the
event-serial scan within float32 tolerance.  D-SAGA requires the
``fetch="stale"`` discipline for this (see ``distributed.run_dsaga``);
instant-fetch D-SAGA remains event-serial and refuses ``backend="spmd"``.

Backend contract (pinned by ``tests/test_spmd_backend.py``):

  * trajectories agree with the event-equivalent vmap driver within
    float32 tolerance — including the async drivers, round-robin and
    heterogeneous-speed schedules alike;
  * worker state is genuinely placed: each shard of the ``(p, ns)`` tables
    maps to a distinct device;
  * instant-fetch D-SAGA (a serial dependency chain between events) raises
    ``NotImplementedError`` from ``distributed.py`` rather than silently
    falling back.
"""
from __future__ import annotations

import functools
import os
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import convex, runtime
from repro.core.convex import Problem
from repro.obs import recorder as obs_recorder
from repro.obs import stage as obs_stage
from repro.prox import operators as proxops

WORKER_AXIS = "workers"

_COUNT_FLAG = "--xla_force_host_platform_device_count"


# ---------------------------------------------------------------------------
# Host-device simulation + mesh construction
# ---------------------------------------------------------------------------

def force_host_devices(n: int) -> None:
    """Make the CPU host platform present ``n`` devices (XLA_FLAGS).

    Safe to call after ``import jax`` but only before the backend
    initializes (first ``jax.devices()`` / first op); afterwards it is a
    no-op if enough devices already exist or the devices are an
    accelerator's (``worker_mesh`` then names the count it found), and an
    error otherwise.  Both ``launch/mesh.py`` and the spmd tests go
    through here so the flag is spelled in exactly one place.
    """
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.device_count() >= n or jax.default_backend() != "cpu":
            return
        raise RuntimeError(
            f"jax already initialized with {jax.device_count()} device(s); "
            f"force_host_devices({n}) must run before the first jax "
            "operation (importing jax is fine — touching devices is not)")
    flags = os.environ.get("XLA_FLAGS", "")
    existing = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if existing:
        # at-least-n semantics, same as the post-init branch: never lower
        # a count someone already forced (e.g. a user-exported XLA_FLAGS)
        if int(existing.group(1)) < n:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n}",
                           flags)
    else:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    os.environ["XLA_FLAGS"] = flags


def worker_mesh(p: int) -> Mesh:
    """A 1-D mesh of p devices, one CentralVR worker per device."""
    devs = jax.devices()
    if len(devs) < p:
        platform = devs[0].platform
        hint = (f"call repro.core.spmd.force_host_devices({p}) before the "
                f'first jax operation (or set XLA_FLAGS="{_COUNT_FLAG}={p}")'
                if platform == "cpu" else
                "each worker takes one chip, so use at most that many "
                "workers or run on a host with more chips")
        raise RuntimeError(
            f"spmd backend needs {p} devices, found {len(devs)} "
            f"{platform} device(s); {hint}")
    return Mesh(np.asarray(devs[:p]), (WORKER_AXIS,))


def process_worker_mesh(p: int) -> Mesh:
    """A GLOBAL 1-D worker mesh spanning every process of a
    ``jax.distributed`` world (DESIGN.md §Multi-host & elasticity).

    The execution model has three tiers: single-process vmap (the
    event-serial reference), single-process spmd (this module's
    ``worker_mesh`` over local simulated host devices), and the
    multi-process tier, where each process owns a contiguous block of the
    p workers (``procmesh.worker_blocks``).  On accelerator backends the
    block maps onto this global mesh and the runners here execute it
    under ``shard_map``; on CPU, XLA cannot compile cross-process
    computations, so ``core/procmesh.py`` runs the blocks as local jitted
    programs and exchanges wave-boundary deltas through the coordination
    service instead — this helper then only validates the world shape.
    """
    devs = jax.devices()
    if len(devs) < p:
        raise RuntimeError(
            f"process mesh needs {p} devices across the world, found "
            f"{len(devs)} over {jax.process_count()} process(es); grow "
            "the world or lower p")
    if jax.process_count() > 1 and p % jax.process_count():
        raise RuntimeError(
            f"process mesh: p={p} workers do not divide evenly over "
            f"{jax.process_count()} processes; shard_map needs equal "
            "per-process blocks (the KV-store engines in core/procmesh.py "
            "accept uneven blocks)")
    return Mesh(np.asarray(devs[:p]), (WORKER_AXIS,))


def _check_mesh(mesh: Optional[Mesh], p: int) -> Mesh:
    mesh = mesh if mesh is not None else worker_mesh(p)
    if mesh.devices.size != p:
        raise ValueError(
            f"mesh has {mesh.devices.size} devices but the problem has "
            f"{p} workers; the spmd backend places exactly one worker "
            "per mesh device")
    return mesh


def _put(mesh: Mesh, sharded_tree, replicated_tree, worker_dim=0):
    """Place worker-stacked leaves sharded along ``worker_dim`` and
    everything else replicated, so the jitted runners see consistent input
    shardings (mixing mesh-sharded and single-device-committed args is an
    error).  Inside an ``spmd/place`` span whose ``bytes`` field counts
    what the placement moves."""
    spec = P(*([None] * worker_dim + [WORKER_AXIS]))
    shard = NamedSharding(mesh, spec)
    repl = NamedSharding(mesh, P())
    moved = _moved_bytes(sharded_tree, shard) + _moved_bytes(replicated_tree,
                                                             repl)
    with obs_recorder.span("spmd/place", bytes=moved):
        return (jax.device_put(sharded_tree, shard),
                jax.device_put(replicated_tree, repl))


def _moved_bytes(tree, sharding) -> int:
    """Bytes of the leaves of ``tree`` not already laid out as
    ``sharding``: those ``jax.device_put`` has to move.  Reads metadata
    only, never the values."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            if not leaf.sharding.is_equivalent_to(sharding, leaf.ndim):
                total += leaf.nbytes
        else:
            total += np.asarray(leaf).nbytes
    return total


# ---------------------------------------------------------------------------
# Host-side RNG precompute — bit-identical to the vmap drivers' draws
# ---------------------------------------------------------------------------

def _round_perms(keys: jax.Array, p: int, ns: int) -> jax.Array:
    """(rounds, p, ns) permutations: per round, split the round key into p
    and draw each worker's epoch permutation — exactly ``sync_round``."""
    return jax.vmap(lambda k: jax.vmap(
        lambda kk: jax.random.permutation(kk, ns))(jax.random.split(k, p))
    )(keys)


def _round_indices(keys: jax.Array, p: int, ns: int, tau: int) -> jax.Array:
    """(rounds, p, tau) uniform index draws — exactly the vmapped
    ``jax.random.randint(kk, (tau,), 0, ns)`` of the local-loop drivers."""
    return jax.vmap(lambda k: jax.vmap(
        lambda kk: jax.random.randint(kk, (tau,), 0, ns))(
        jax.random.split(k, p)))(keys)


# ---------------------------------------------------------------------------
# In-shard metric helpers
# ---------------------------------------------------------------------------

def _rel_grad_norm(local: Problem, x: jax.Array, g0: jax.Array,
                   prox=None, eta=None) -> jax.Array:
    """The paper's y-axis on the GLOBAL objective, from inside a shard:
    per-shard data-term means are equal-weighted (every worker holds ns
    samples), so their pmean is the merged problem's data gradient.  With
    a prox, the smooth norm becomes the composite gradient-mapping norm —
    the same metric ``convex.rel_grad_norm(..., prox=)`` reports, so the
    vmap/spmd agreement pins cover the prox'd trajectories too."""
    s = convex.scalar_residual_all(local, x)
    data = jax.lax.pmean(convex.data_grad_from_scalars(local, s), WORKER_AXIS)
    full = data + 2.0 * local.lam * x
    if prox is None:
        return jnp.linalg.norm(full) / g0
    return jnp.linalg.norm(proxops.grad_map(prox, x, full, eta)) / g0


def _full_grad(local: Problem, x: jax.Array) -> jax.Array:
    """Global full gradient via collective: pmean of per-shard full
    gradients (the replicated 2·lam·x term averages to itself)."""
    return jax.lax.pmean(convex.full_grad(local, x), WORKER_AXIS)


# ---------------------------------------------------------------------------
# CentralVR-Sync (Algorithm 2) under shard_map
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sync_runner(mesh: Mesh, kind: str, fused=None, prox=None):
    """One compiled executable per (mesh, problem kind, fused params, prox
    spec): init epoch + the whole round scan inside a single jitted
    shard_map.  Cached so warm calls skip shard_map re-construction and
    hit the jit cache.  ``fused`` is the static kernel-params tuple from
    ``fused.make_params`` and ``prox`` a static ProxSpec-or-None
    (both hashable, so they extend the cache key).  Prox placement mirrors
    ``distributed.sync_round`` exactly: per local step, then once more
    after the central pmean (the wave-boundary ordering, DESIGN.md §2)."""
    from repro.core.distributed import _local_centralvr_epoch, _local_sgd_epoch

    def body(A, b, lam, eta, g0, perm0, perms):
        A, b, perm0 = A[0], b[0], perm0[0]    # this worker's shard
        local = Problem(A, b, lam, kind)

        def average(x_w, acc):
            # the central node as collectives, named ``vr/average``
            with jax.named_scope("vr/average"):
                x = jax.lax.pmean(x_w, WORKER_AXIS)
                return (proxops.apply_prox(prox, x, eta),
                        jax.lax.pmean(acc, WORKER_AXIS))

        # --- init: one plain-SGD epoch per worker, then average (line 2)
        x0 = jnp.zeros((A.shape[1],), dtype=A.dtype)
        x_w, table, acc = _local_sgd_epoch(A, b, lam, kind, x0, eta, perm0,
                                           prox=prox)
        x, gbar = average(x_w, acc)

        # --- communication rounds (lines 4-18): local epoch, then the
        # central average of (x, gbar) as a collective pmean
        def one_round(carry, perm):
            x, table, gbar = carry
            x_w, table, acc = _local_centralvr_epoch(
                A, b, lam, kind, x, table, gbar, eta, perm[0], fused=fused,
                prox=prox)
            x, gbar = average(x_w, acc)
            with jax.named_scope("vr/rel_grad_norm"):
                rel = _rel_grad_norm(local, x, g0, prox=prox, eta=eta)
            return (x, table, gbar), rel

        (x, table, gbar), rels = jax.lax.scan(one_round, (x, table, gbar),
                                              perms)
        return x, table[None], gbar, rels

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(), P(),
                  P(WORKER_AXIS), P(None, WORKER_AXIS)),
        out_specs=(P(), P(WORKER_AXIS), P(), P()), check_vma=False))


def run_sync(sp, *, eta: float, rounds: int, key: jax.Array,
             mesh: Optional[Mesh] = None, fused=False, prox=None):
    """Algorithm 2 with one worker per device (DESIGN.md §2, spmd backend).
    Same RNG draws as the vmap driver (precomputed on host), so the
    trajectories agree within reduction-order float noise."""
    from repro.core import fused as fusedmod
    from repro.core.distributed import SyncState

    px = proxops.parse(prox) if prox is not None else None
    fused_t = fusedmod.make_params(fused, eta, sp.lam, prox=px)
    mesh = _check_mesh(mesh, sp.p)
    with obs_recorder.span("driver/keys"):
        k_init, k_run = jax.random.split(key)
        perms = _round_perms(jax.random.split(k_run, rounds), sp.p, sp.ns)
    with obs_recorder.span("driver/grad_norm0"):
        g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    with obs_recorder.span("driver/init"):
        perm0 = jax.vmap(lambda kk: jax.random.permutation(kk, sp.ns))(
            jax.random.split(k_init, sp.p))
    (A, b, perm0), (lam, eta, g0) = _put(
        mesh, (sp.A, sp.b, perm0), (sp.lam, jnp.asarray(eta), g0))
    (perms,), () = _put(mesh, (perms,), (), worker_dim=1)
    with obs_recorder.span("driver/run"):
        x, tables, gbar, rels = obs_stage.staged_call(
            _sync_runner(mesh, sp.kind, fused_t, px),
            A, b, lam, eta, g0, perm0, perms, _label="spmd/centralvr_sync")
    return SyncState(x=x, tables=tables, gbar=gbar), rels


# ---------------------------------------------------------------------------
# Distributed SVRG (Algorithm 4) under shard_map
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _dsvrg_runner(mesh: Mesh, kind: str, fused=None, prox=None,
                  snapshot: str = "last"):
    """Prox placement and snapshot selection mirror
    ``distributed._dsvrg_scan`` exactly: prox per inner step and once more
    after the cross-worker pmean; snapshot anchors last/avg/rand with the
    rand index host-precomputed and shipped replicated (``snap``), so both
    backends pick the same inner iterate."""
    def body(A, b, lam, eta, g0, idx, snap):
        A, b = A[0], b[0]
        local = Problem(A, b, lam, kind)
        x0 = jnp.zeros((A.shape[1],), dtype=A.dtype)

        def round_(x, ins):
            idx_r, r = ins
            xbar = x
            gbar = _full_grad(local, xbar)   # sync step (line 5)

            if fused is not None:
                # snapshot=="last" here (run_dsvrg falls back otherwise)
                from repro.core import fused as fusedmod
                sbar = convex.scalar_residual_all(local, xbar)
                xl = fusedmod.svrg_steps(A, b, kind, xbar, sbar, gbar,
                                         idx_r[0], fused)
            else:
                def step(xl, i):
                    g = (convex.scalar_residual(local, xl, i) * A[i]
                         - convex.scalar_residual(local, xbar, i) * A[i]
                         + gbar + 2.0 * lam * (xl - xbar))
                    xl = proxops.apply_prox(prox, xl - eta * g, eta)
                    return xl, (xl if snapshot != "last" else None)

                xl, traj = jax.lax.scan(step, xbar, idx_r[0])
                if snapshot == "avg":
                    xl = traj.mean(0)
                elif snapshot == "rand":
                    xl = traj[r]
            x = proxops.apply_prox(prox, jax.lax.pmean(xl, WORKER_AXIS),
                                   eta)
            rel = _rel_grad_norm(local, x, g0, prox=prox, eta=eta)
            return x, rel

        return jax.lax.scan(round_, x0, (idx, snap))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(), P(),
                  P(None, WORKER_AXIS), P()),
        out_specs=(P(), P()), check_vma=False))


def run_dsvrg(sp, *, eta: float, rounds: int, key: jax.Array, tau: int = 0,
              mesh: Optional[Mesh] = None, fused=False, prox=None,
              snapshot: str = "last"):
    from repro.core import fused as fusedmod

    px = proxops.parse(prox) if prox is not None else None
    fused_t = (fusedmod.make_params(fused, eta, sp.lam, prox=px)
               if snapshot == "last" else None)
    tau = tau or 2 * sp.ns
    mesh = _check_mesh(mesh, sp.p)
    g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    idx = _round_indices(jax.random.split(key, rounds), sp.p, sp.ns, tau)
    # same draw as distributed.run_dsvrg (fold_in off the main key stream)
    snap = (jax.random.randint(jax.random.fold_in(key, 1), (rounds,),
                               0, tau)
            if snapshot == "rand" else jnp.zeros((rounds,), jnp.int32))
    (A, b), (lam, eta, g0, snap) = _put(
        mesh, (sp.A, sp.b), (sp.lam, jnp.asarray(eta), g0, snap))
    (idx,), () = _put(mesh, (idx,), (), worker_dim=1)
    return obs_stage.staged_call(
        _dsvrg_runner(mesh, sp.kind, fused_t, px, snapshot),
        A, b, lam, eta, g0, idx, snap, _label="spmd/dsvrg")


# ---------------------------------------------------------------------------
# Minibatch baselines under shard_map
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _dist_sgd_runner(mesh: Mesh, kind: str):
    def body(A, b, lam, g0, idx, etas):
        A, b = A[0], b[0]
        local = Problem(A, b, lam, kind)
        x0 = jnp.zeros((A.shape[1],), dtype=A.dtype)

        def round_(x, ins):
            idx_r, eta_l = ins

            def step(xl, i):
                g = (convex.scalar_residual(local, xl, i) * A[i]
                     + 2.0 * lam * xl)
                return xl - eta_l * g, None

            xl, _ = jax.lax.scan(step, x, idx_r[0])
            x_new = jax.lax.pmean(xl, WORKER_AXIS)
            return x_new, _rel_grad_norm(local, x_new, g0)

        return jax.lax.scan(round_, x0, (idx, etas))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(),
                  P(None, WORKER_AXIS), P()),
        out_specs=(P(), P()), check_vma=False))


def run_dist_sgd(sp, *, eta: float, rounds: int, key: jax.Array,
                 tau: int = 0, decay: float = 0.0,
                 mesh: Optional[Mesh] = None):
    tau = tau or sp.ns
    mesh = _check_mesh(mesh, sp.p)
    g0 = convex.grad_norm0(sp.merged())
    idx = _round_indices(jax.random.split(key, rounds), sp.p, sp.ns, tau)
    etas = eta / (1.0 + decay * jnp.arange(rounds) * tau) ** 0.5
    (A, b), (lam, g0, etas) = _put(
        mesh, (sp.A, sp.b), (sp.lam, g0, etas))
    (idx,), () = _put(mesh, (idx,), (), worker_dim=1)
    return obs_stage.staged_call(_dist_sgd_runner(mesh, sp.kind),
                                 A, b, lam, g0, idx, etas,
                                 _label="spmd/dist_sgd")


@functools.lru_cache(maxsize=None)
def _easgd_runner(mesh: Mesh, kind: str):
    def body(A, b, lam, alpha, g0, idx, etas):
        A, b = A[0], b[0]
        local = Problem(A, b, lam, kind)
        d = A.shape[1]
        xc0 = jnp.zeros((d,), dtype=A.dtype)
        xl0 = jnp.zeros((d,), dtype=A.dtype)

        def round_(carry, ins):
            xc, xl = carry
            idx_r, eta_l = ins

            def comm_block(carry, idx_tau):
                xl, xc_view = carry

                def step(x, i):
                    g = (convex.scalar_residual(local, x, i) * A[i]
                         + 2.0 * lam * x)
                    return x - eta_l * g, None

                xl, _ = jax.lax.scan(step, xl, idx_tau)
                diff = xl - xc_view
                return (xl - alpha * diff, xc_view + alpha * diff), diff

            (xl, _), diffs = jax.lax.scan(comm_block, (xl, xc), idx_r[0])
            # center update: sum of worker contributions / p == pmean
            xc = xc + alpha * jax.lax.pmean(diffs.sum(0), WORKER_AXIS)
            rel = _rel_grad_norm(local, xc, g0)
            return (xc, xl), rel

        (xc, xl), rels = jax.lax.scan(round_, (xc0, xl0), (idx, etas))
        return xc, xl[None], rels

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(), P(),
                  P(None, WORKER_AXIS), P()),
        out_specs=(P(), P(WORKER_AXIS), P()), check_vma=False))


def run_easgd(sp, *, eta: float, rounds: int, key: jax.Array, tau: int = 16,
              rho: float = 1.0, decay: float = 0.0,
              mesh: Optional[Mesh] = None):
    mesh = _check_mesh(mesh, sp.p)
    alpha = min(0.9 / sp.p, eta * rho * tau)
    steps_per_round = max(sp.ns // tau, 1)
    g0 = convex.grad_norm0(sp.merged())
    idx = _round_indices(jax.random.split(key, rounds), sp.p, sp.ns,
                         steps_per_round * tau)
    idx = idx.reshape(rounds, sp.p, steps_per_round, tau)
    etas = eta / (1.0 + decay * jnp.arange(rounds) * sp.ns) ** 0.5
    (A, b), (lam, alpha, g0, etas) = _put(
        mesh, (sp.A, sp.b), (sp.lam, jnp.asarray(alpha), g0, etas))
    (idx,), () = _put(mesh, (idx,), (), worker_dim=1)
    xc, _, rels = obs_stage.staged_call(
        _easgd_runner(mesh, sp.kind), A, b, lam, alpha, g0, idx, etas,
        _label="spmd/easgd")
    return xc, rels


@functools.lru_cache(maxsize=None)
def _ps_svrg_runner(mesh: Mesh, kind: str):
    def body(A, b, lam, eta, g0, idx):
        A, b = A[0], b[0]
        local = Problem(A, b, lam, kind)
        x0 = jnp.zeros((A.shape[1],), dtype=A.dtype)

        def round_(x, idx_r):
            xbar = x
            gbar = _full_grad(local, xbar)

            def step(x, ii):
                # this worker's index of the server step's (p,) draw
                i = ii[0]
                g_w = ((convex.scalar_residual(local, x, i)
                        - convex.scalar_residual(local, xbar, i)) * A[i]
                       + gbar + 2.0 * lam * (x - xbar))
                g = jax.lax.pmean(g_w, WORKER_AXIS)
                return x - eta * g, None

            x, _ = jax.lax.scan(step, x, idx_r)
            return x, _rel_grad_norm(local, x, g0)

        return jax.lax.scan(round_, x0, idx)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(), P(),
                  P(None, None, WORKER_AXIS)),
        out_specs=(P(), P()), check_vma=False))


def run_ps_svrg(sp, *, eta: float, rounds: int, key: jax.Array,
                epoch_mult: int = 2, mesh: Optional[Mesh] = None):
    mesh = _check_mesh(mesh, sp.p)
    g0 = convex.grad_norm0(sp.merged())
    inner = epoch_mult * sp.ns
    # (rounds, inner, p): per server step, one index per worker — exactly
    # the vmap driver's randint(ks, (p,)) stream
    idx = jax.vmap(lambda k: jax.vmap(
        lambda ks: jax.random.randint(ks, (sp.p,), 0, sp.ns))(
        jax.random.split(k, inner)))(jax.random.split(key, rounds))
    (A, b), (lam, eta, g0) = _put(
        mesh, (sp.A, sp.b), (sp.lam, jnp.asarray(eta), g0))
    (idx,), () = _put(mesh, (idx,), (), worker_dim=2)
    return obs_stage.staged_call(_ps_svrg_runner(mesh, sp.kind),
                                 A, b, lam, eta, g0, idx,
                                 _label="spmd/ps_svrg")


# ---------------------------------------------------------------------------
# Async drivers (Algorithms 3 & 5) as concurrency waves under shard_map
# ---------------------------------------------------------------------------

def _scatter_events(draws, schedule, slot, shape):
    """Arrange per-event host-precomputed draws ``(total, ...)`` — computed
    in flat schedule order with EXACTLY the event-serial drivers' key
    splits — into the ``(rounds, W, p, ...)`` wave layout of
    ``runtime.wave_partition``.  Inactive (padding) slots keep a zeros
    filler: index 0 is valid everywhere and the runner masks those
    workers' results out."""
    rounds, width, p = shape
    draws = np.asarray(draws)
    out = np.zeros((rounds * width, p) + draws.shape[1:], dtype=draws.dtype)
    out[slot, schedule] = draws
    return out.reshape((rounds, width, p) + draws.shape[1:])


def _wave_push(x_c, gbar_c, dxs, dgs, rk, my_rank, alpha, alpha_g):
    """Apply a wave's delta pushes to the central state and reconstruct
    this worker's fresh fetch.  ``dxs``/``dgs`` are the all-gathered
    (p, d) per-worker deltas (zero where inactive); the serial scan adds
    them one event at a time, so worker w's fetch — the central state
    immediately after ITS event — is the rank-prefix sum ``rk <= my_rank``
    over the wave (inactive workers carry the rank sentinel p and a zero
    delta, so they never contribute).  Returns (x_c', gbar_c', x_f, g_f)."""
    pre = (rk <= my_rank)[:, None]
    x_f = x_c + alpha * jnp.where(pre, dxs, 0.0).sum(0)
    g_f = gbar_c + alpha_g * jnp.where(pre, dgs, 0.0).sum(0)
    x_c = x_c + alpha * dxs.sum(0)
    gbar_c = gbar_c + alpha_g * dgs.sum(0)
    return x_c, gbar_c, x_f, g_f


@functools.lru_cache(maxsize=None)
def _async_runner(mesh: Mesh, kind: str, fused=None, prox=None):
    """CentralVR-Async (Algorithm 3) with one worker per device: the whole
    wave schedule in one jitted shard_map.  Each worker's stale snapshot
    (x_fetch, gbar_fetch), previous contribution (x_old, gbar_old), and
    scalar table live on its own device; the central (x_c, gbar_c) are
    replicated and advanced at wave boundaries.  Prox placement mirrors
    ``distributed.async_event``: the central accumulator stays linear in
    the deltas (the wave prefix-sum reconstruction requires it) and each
    worker prox's its fetched copy at epoch start; the metric evaluates
    at ``prox(x_c)``."""
    from repro.core.distributed import _local_centralvr_epoch, _local_sgd_epoch

    p = int(mesh.devices.size)
    alpha = 1.0 / p

    def body(A, b, lam, eta, g0, perm0, active, rank, perms):
        A, b, perm0 = A[0], b[0], perm0[0]    # this worker's shard
        local = Problem(A, b, lam, kind)
        w_idx = jax.lax.axis_index(WORKER_AXIS)

        # --- init == async_init: one SGD epoch per worker, average, and
        # every worker's previous contribution / fetch set to that iterate
        x0 = jnp.zeros((A.shape[1],), dtype=A.dtype)
        x_w, table, acc = _local_sgd_epoch(A, b, lam, kind, x0, eta, perm0,
                                           prox=prox)
        x_c = proxops.apply_prox(prox, jax.lax.pmean(x_w, WORKER_AXIS), eta)
        gbar_c = jax.lax.pmean(acc, WORKER_AXIS)
        carry0 = (x_c, gbar_c, table, x_c, gbar_c, x_c, gbar_c)

        def one_round(carry, xs):
            act_r, rank_r, perm_r = xs

            def one_wave(carry, wv):
                (x_c, gbar_c, table, x_old, gbar_old,
                 x_fetch, gbar_fetch) = carry
                act, rk, perm = wv
                # every worker traces the epoch; inactive results are
                # masked (round-robin schedules have no inactive slots)
                x_new, table_new, gtilde = _local_centralvr_epoch(
                    A, b, lam, kind,
                    proxops.apply_prox(prox, x_fetch, eta), table,
                    gbar_fetch, eta, perm[0], fused=fused, prox=prox)
                on = act[w_idx]
                dx = jnp.where(on, x_new - x_old, 0.0)
                dg = jnp.where(on, gtilde - gbar_old, 0.0)
                dxs = jax.lax.all_gather(dx, WORKER_AXIS)
                dgs = jax.lax.all_gather(dg, WORKER_AXIS)
                x_c, gbar_c, x_f, g_f = _wave_push(
                    x_c, gbar_c, dxs, dgs, rk, rk[w_idx], alpha, alpha)
                table = jnp.where(on, table_new, table)
                x_old = jnp.where(on, x_new, x_old)
                gbar_old = jnp.where(on, gtilde, gbar_old)
                x_fetch = jnp.where(on, x_f, x_fetch)
                gbar_fetch = jnp.where(on, g_f, gbar_fetch)
                return (x_c, gbar_c, table, x_old, gbar_old,
                        x_fetch, gbar_fetch), None

            carry, _ = jax.lax.scan(one_wave, carry, (act_r, rank_r, perm_r))
            rel = _rel_grad_norm(local,
                                 proxops.apply_prox(prox, carry[0], eta),
                                 g0, prox=prox, eta=eta)
            return carry, rel

        carry, rels = jax.lax.scan(one_round, carry0, (active, rank, perms))
        x_c, gbar_c, table, x_old, gbar_old, x_fetch, gbar_fetch = carry
        return (x_c, gbar_c, table[None], x_old[None], gbar_old[None],
                x_fetch[None], gbar_fetch[None], rels)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(), P(),
                  P(WORKER_AXIS), P(), P(), P(None, None, WORKER_AXIS)),
        out_specs=(P(), P(), P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                   P(WORKER_AXIS), P(WORKER_AXIS), P()), check_vma=False))


def _wave_inputs(mesh, sp, schedule, draws):
    """Common wave-layout plumbing: partition the schedule, scatter the
    per-event draws into (rounds, W, p, ...), and place everything —
    active/rank replicated, draws sharded along the worker axis."""
    active, rank, slot = runtime.wave_partition(schedule, sp.p)
    waved = _scatter_events(draws, schedule, slot, active.shape)
    (), (active, rank) = _put(mesh, (), (jnp.asarray(active),
                                         jnp.asarray(rank)))
    (waved,), () = _put(mesh, (waved,), (), worker_dim=2)
    return active, rank, waved


def run_async(sp, *, eta: float, rounds: int, key: jax.Array, speeds=None,
              mesh: Optional[Mesh] = None, fused=False, prox=None):
    """Algorithm 3 as concurrency waves (DESIGN.md §2, spmd-async mode).
    Identical schedule, identical RNG draws, and identical delta algebra
    as ``distributed.run_async`` — the event-serial reference it is pinned
    against."""
    from repro.core import fused as fusedmod
    from repro.core.distributed import AsyncState

    px = proxops.parse(prox) if prox is not None else None
    fused_t = fusedmod.make_params(fused, eta, sp.lam, prox=px)
    mesh = _check_mesh(mesh, sp.p)
    k_init, k_run = jax.random.split(key)
    g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    # init draws: exactly sync_init's splits (async_init delegates to it)
    perm0 = jax.vmap(lambda kk: jax.random.permutation(kk, sp.ns))(
        jax.random.split(k_init, sp.p))
    schedule = runtime.event_schedule(sp.p, rounds, speeds)
    # per-event draws: exactly async_event's permutation(keys[t], ns)
    perms = jax.vmap(lambda k: jax.random.permutation(k, sp.ns))(
        jax.random.split(k_run, schedule.size))
    (A, b, perm0), (lam, eta, g0) = _put(
        mesh, (sp.A, sp.b, perm0), (sp.lam, jnp.asarray(eta), g0))
    active, rank, perms = _wave_inputs(mesh, sp, schedule, perms)
    (x_c, gbar_c, tables, x_old, gbar_old, x_fetch, gbar_fetch,
     rels) = obs_stage.staged_call(
        _async_runner(mesh, sp.kind, fused_t, px),
        A, b, lam, eta, g0, perm0, active, rank, perms,
        _label="spmd/centralvr_async")
    return AsyncState(x_c=x_c, gbar_c=gbar_c, tables=tables, x_old=x_old,
                      gbar_old=gbar_old, x_fetch=x_fetch,
                      gbar_fetch=gbar_fetch), rels


@functools.lru_cache(maxsize=None)
def _dsaga_runner(mesh: Mesh, kind: str, literal_scaling: bool, fused=None,
                  prox=None):
    """Stale-fetch D-SAGA (Algorithm 5 with Algorithm 3's fetch
    discipline) as concurrency waves — the spmd execution of
    ``distributed.dsaga_event_stale`` (prox'd fetch, linear central
    accumulator, metric at ``prox(x_c)``)."""
    from repro.core.distributed import _local_saga_steps

    p = int(mesh.devices.size)
    alpha = 1.0 / p
    alpha_g = alpha if literal_scaling else 1.0

    def body(A, b, lam, eta, g0, active, rank, idx):
        A, b = A[0], b[0]
        local = Problem(A, b, lam, kind)
        n_global = p * A.shape[0]
        w_idx = jax.lax.axis_index(WORKER_AXIS)

        # --- init == dsaga_init: tables at x0, central gbar = table mean
        x0 = jnp.zeros((A.shape[1],), dtype=A.dtype)
        table = convex.scalar_residual_all(local, x0)
        gbar_c = jax.lax.pmean(
            convex.data_grad_from_scalars(local, table), WORKER_AXIS)
        carry0 = (x0, gbar_c, table, x0, gbar_c, x0, gbar_c)

        def one_round(carry, xs):
            act_r, rank_r, idx_r = xs

            def one_wave(carry, wv):
                (x_c, gbar_c, table, x_old, gbar_old,
                 x_fetch, gbar_fetch) = carry
                act, rk, idx_w = wv
                x_new, table_new, gb = _local_saga_steps(
                    A, b, lam, kind,
                    proxops.apply_prox(prox, x_fetch, eta), table,
                    gbar_fetch, eta, n_global, idx_w[0], fused=fused,
                    prox=prox)
                on = act[w_idx]
                dx = jnp.where(on, x_new - x_old, 0.0)
                if literal_scaling:
                    dg = jnp.where(on, gb - gbar_old, 0.0)
                else:
                    dg = jnp.where(on, gb - gbar_fetch, 0.0)
                dxs = jax.lax.all_gather(dx, WORKER_AXIS)
                dgs = jax.lax.all_gather(dg, WORKER_AXIS)
                x_c, gbar_c, x_f, g_f = _wave_push(
                    x_c, gbar_c, dxs, dgs, rk, rk[w_idx], alpha, alpha_g)
                table = jnp.where(on, table_new, table)
                x_old = jnp.where(on, x_new, x_old)
                gbar_old = jnp.where(on, gb, gbar_old)
                x_fetch = jnp.where(on, x_f, x_fetch)
                gbar_fetch = jnp.where(on, g_f, gbar_fetch)
                return (x_c, gbar_c, table, x_old, gbar_old,
                        x_fetch, gbar_fetch), None

            carry, _ = jax.lax.scan(one_wave, carry, (act_r, rank_r, idx_r))
            rel = _rel_grad_norm(local,
                                 proxops.apply_prox(prox, carry[0], eta),
                                 g0, prox=prox, eta=eta)
            return carry, rel

        carry, rels = jax.lax.scan(one_round, carry0, (active, rank, idx))
        x_c, gbar_c, table, x_old, gbar_old, x_fetch, gbar_fetch = carry
        return (x_c, gbar_c, table[None], x_old[None], gbar_old[None],
                x_fetch[None], gbar_fetch[None], rels)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(), P(), P(),
                  P(), P(), P(None, None, WORKER_AXIS)),
        out_specs=(P(), P(), P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                   P(WORKER_AXIS), P(WORKER_AXIS), P()), check_vma=False))


def run_dsaga(sp, *, eta: float, rounds: int, key: jax.Array, tau: int = 100,
              literal_scaling: bool = False, speeds=None,
              mesh: Optional[Mesh] = None, fused=False, prox=None):
    """Stale-fetch Algorithm 5 as concurrency waves (DESIGN.md §2).
    Pinned against ``distributed.run_dsaga(fetch="stale")``, the
    event-serial scan with the same fetch discipline, schedule, and RNG."""
    from repro.core import fused as fusedmod
    from repro.core.distributed import AsyncState

    px = proxops.parse(prox) if prox is not None else None
    fused_t = fusedmod.make_params(fused, eta, sp.lam, prox=px)
    mesh = _check_mesh(mesh, sp.p)
    g0 = convex.grad_norm0(sp.merged(), prox=px, eta=eta)
    schedule = runtime.event_schedule(sp.p, rounds, speeds)
    # per-event draws: exactly dsaga_event's randint(keys[t], (tau,), 0, ns)
    idx = jax.vmap(lambda k: jax.random.randint(k, (tau,), 0, sp.ns))(
        jax.random.split(key, schedule.size))
    (A, b), (lam, eta, g0) = _put(
        mesh, (sp.A, sp.b), (sp.lam, jnp.asarray(eta), g0))
    active, rank, idx = _wave_inputs(mesh, sp, schedule, idx)
    (x_c, gbar_c, tables, x_old, gbar_old, x_fetch, gbar_fetch,
     rels) = obs_stage.staged_call(
        _dsaga_runner(mesh, sp.kind, bool(literal_scaling), fused_t, px),
        A, b, lam, eta, g0, active, rank, idx, _label="spmd/dsaga")
    return AsyncState(x_c=x_c, gbar_c=gbar_c, tables=tables, x_old=x_old,
                      gbar_old=gbar_old, x_fetch=x_fetch,
                      gbar_fetch=gbar_fetch), rels


# ---------------------------------------------------------------------------
# Algorithm 1 (single worker) on a mesh device
# ---------------------------------------------------------------------------

def run_centralvr(prob: Problem, *, eta: float, epochs: int, key: jax.Array,
                  sampling: str = "permutation", x0=None,
                  mesh: Optional[Mesh] = None, fused=False, prox=None):
    """Algorithm 1 has no worker axis to shard — ``backend="spmd"`` means
    "execute on the mesh": the problem is placed on the mesh's first
    device and the standard device-resident scan runs there, so a launcher
    can address one API regardless of backend."""
    from repro.core import centralvr

    mesh = mesh if mesh is not None else worker_mesh(1)
    dev = mesh.devices.ravel()[0]
    prob = jax.device_put(prob, dev)
    key = jax.device_put(key, dev)
    if x0 is not None:
        x0 = jax.device_put(x0, dev)
    return centralvr.run(prob, eta=eta, epochs=epochs, key=key,
                         sampling=sampling, x0=x0, fused=fused, prox=prox)
