"""Unified solver API: declarative ``RunSpec`` -> ``solve`` -> ``RunResult``.

The paper's experiments (§6) are head-to-head sweeps of one algorithm
family — CentralVR-Sync/Async vs D-SVRG, D-SAGA, EASGD and SGD baselines —
parameterized by a few axes (table form, fetch discipline, topology,
speeds).  This module exposes that family *as data* instead of 11 drifting
``run_*`` keyword surfaces:

  * :class:`RunSpec` — a frozen, validated description of one run (algo,
    p, eta, rounds, backend, fetch, speeds, tau, seed, metric cadence).
    Every backend/fetch/speeds combination check lives in spec
    construction, so an invalid combination fails *before* any JAX work,
    with an error naming the offending spec field.
  * the algorithm **registry** — name -> driver + :class:`AlgoCaps`
    capability record (distributed? spmd program? async? accepts
    fetch/speeds/tau?).  New workloads are one registry entry, not a new
    bespoke driver signature.
  * :class:`RunResult` — the uniform return: rels trajectory, final
    iterate + full driver state, wall clock, trace-count stats, and the
    resolved spec for provenance (``RunResult.provenance()`` is what the
    benchmark artifacts embed).
  * :func:`solve` — runs a spec against a problem/config: acquires
    simulated host devices before the first jax op when
    ``backend="spmd"``, shards or merges the data to match the algorithm's
    topology, derives the RNG key from ``spec.seed``, and normalizes every
    driver's return tuple.

The ``run_*`` drivers keep their exact signatures and trajectories; they
now build a spec internally for validation (DESIGN.md §Solver API), so
existing call sites — and all vmap/spmd/host-loop trajectory pins — are
untouched.
"""
from __future__ import annotations

import dataclasses
import importlib
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import numpy as np

from repro.core import runtime

__all__ = ["RunSpec", "RunResult", "AlgoCaps", "Algorithm", "REGISTRY",
           "algorithms", "runner", "solve"]


# ---------------------------------------------------------------------------
# Capability records + registry
# ---------------------------------------------------------------------------

class AlgoCaps(NamedTuple):
    """What a registry algorithm supports — the validation contract
    :class:`RunSpec` enforces at construction, pinned against observed
    driver behavior by ``tests/test_solver_api.py``."""

    distributed: bool          # runs on a ShardedProblem (p workers)?
    spmd_ok: bool              # has a backend="spmd" program?
    is_async: bool             # event-scheduled (vs bulk-synchronous)?
    accepts_fetch: bool = False   # fetch="instant"|"stale" discipline?
    accepts_speeds: bool = False  # heterogeneous-speed event schedule?
    accepts_tau: bool = False     # local-step count (inner loop length)?
    accepts_fused: bool = False   # fused vr_update kernel hot path?
    accepts_prox: bool = False    # composite objectives (prox= axis)?
    snapshots: Tuple[str, ...] = ()   # supported snapshot= anchors; the
                                  # table-based VR algorithms pin their
                                  # anchor to the running table ("last"
                                  # only), the SVRG family re-anchors per
                                  # round ("last" | "avg" | "rand")


class Algorithm(NamedTuple):
    name: str
    module: str                # dotted module of the public run_* driver
    func: str                  # driver attribute within ``module``
    caps: AlgoCaps
    call: Callable             # (spec, problem, eta, key, mesh) ->
                               #   (state, x, rels, grad_evals | None)
    doc: str


REGISTRY: dict[str, Algorithm] = {}


def register(name: str, module: str, func: str, caps: AlgoCaps,
             call: Callable, doc: str) -> None:
    if name in REGISTRY:
        raise ValueError(f"algorithm {name!r} already registered")
    REGISTRY[name] = Algorithm(name, module, func, caps, call, doc)


def algorithms() -> Tuple[str, ...]:
    """Registered algorithm names, in registration (paper) order."""
    return tuple(REGISTRY)


def runner(name: str) -> Callable:
    """Resolve a registry entry to its public ``run_*`` driver."""
    entry = REGISTRY[name]
    return getattr(importlib.import_module(entry.module), entry.func)


# ---------------------------------------------------------------------------
# RunSpec — declarative, frozen, validated at construction
# ---------------------------------------------------------------------------

_SAMPLINGS = ("permutation", "uniform", "sparse")
_DECAY_ALGOS = ("sgd", "dist_sgd", "easgd")
_SNAPSHOTS = ("last", "avg", "rand")


@dataclass(frozen=True)
class RunSpec:
    """One solver run, as data.

    Fields:
      algo          registry name (see :func:`algorithms`)
      p             worker count (must be 1 for single-worker algorithms)
      eta           step size; None -> ``convex.auto_eta`` on the (merged)
                    problem at solve time
      rounds        communication rounds (epochs for the single-worker
                    algorithms; ``spec.epochs`` is an alias)
      backend       "vmap" (stacked single-device simulation, default) or
                    "spmd" (one worker per mesh device, DESIGN.md §2)
      fetch         D-SAGA fetch discipline "instant"|"stale"; None
                    resolves to the driver default ("stale" under spmd,
                    else "instant")
      speeds        per-worker relative speeds for the async event
                    schedule (len p); None -> round-robin
      tau           local steps per event/round where the algorithm takes
                    them (D-SVRG/D-SAGA/dist-SGD/EASGD; SVRG's inner loop);
                    None -> the driver's documented default
      seed          PRNGKey seed used by :func:`solve` when no explicit
                    key is passed
      metric_every  metric cadence: keep every k-th round's rel-grad-norm
                    (plus the final round) in ``RunResult.rels``.  The
                    drivers still compute the metric on device each round
                    inside their jitted scan; this controls what the
                    result records.
      sampling      CentralVR sampling mode ("permutation"|"uniform",
                    Algorithm 1 only); "sparse" routes Algorithm 1
                    through the lazy CSR driver (``prox/lazy.py``) —
                    per-coordinate just-in-time catch-up of the skipped
                    ``eta*gbar`` (+ L1 prox) updates
      prox          composite objective: apply ``prox_{eta*g}`` at every
                    update site (``"l1:0.01"``, ``"elasticnet:a:b"``,
                    ``"box:lo:hi"``, ``"group_l2:lam:size"`` — see
                    ``repro.prox.operators``).  VR family only; stored
                    normalized (params resolved) so asdict round-trips.
                    ``fused=True`` + a non-elementwise prox (group_l2)
                    is refused here, pre-JAX; ``fused="auto"`` falls
                    back to the unfused oracle path instead
      snapshot      VR anchor strategy: "last" (default — the anchor the
                    table algorithms maintain implicitly), "avg"/"rand"
                    re-anchor the SVRG family's round snapshot at the
                    inner-iterate average / a uniformly drawn inner
                    iterate (svrg, dsvrg only; refused with fused=True,
                    whose kernel path anchors at the last iterate)
      decay         step-size decay for the SGD-family baselines
      fused         route the VR inner loop through the Pallas
                    ``vr_update`` kernel (DESIGN.md §Fused kernels
                    hot-path): False (unfused oracle, default), True
                    (force; interpret mode off-TPU), or "auto" (fused
                    iff a compiled Pallas backend is present)
      topology      "local" (one process, default) or "process" (a
                    ``jax.distributed`` process mesh — the run must be
                    launched through ``repro.launch.distributed``;
                    CentralVR-Sync/Async only, DESIGN.md §Multi-host &
                    elasticity)
      elastic       tolerate worker dropout/rejoin at wave boundaries
                    (CentralVR-Async only): under topology="process" the
                    heartbeat/membership protocol runs at every round
                    boundary; under topology="local" the run replays a
                    deterministic ``membership=`` plan passed to
                    :func:`solve`

    All cross-field validation happens here: asking for an impossible
    combination (spmd on a serial algorithm, speeds on a synchronous one,
    fetch="instant" under spmd, elastic on a synchronous algorithm, ...)
    raises at construction with the offending field named, before any JAX
    work.
    """

    algo: str
    p: int = 1
    eta: Optional[float] = None
    rounds: int = 10
    backend: str = "vmap"
    fetch: Optional[str] = None
    speeds: Optional[Tuple[float, ...]] = None
    tau: Optional[int] = None
    seed: int = 0
    metric_every: int = 1
    sampling: str = "permutation"
    decay: float = 0.0
    fused: Any = False
    topology: str = "local"
    elastic: bool = False
    prox: Optional[str] = None
    snapshot: Optional[str] = None

    def __post_init__(self):
        if self.algo not in REGISTRY:
            raise ValueError(
                f"RunSpec.algo: unknown algorithm {self.algo!r}; registry "
                f"has {', '.join(REGISTRY)}")
        caps = REGISTRY[self.algo].caps
        _set = lambda k, v: object.__setattr__(self, k, v)  # noqa: E731

        # normalize scalar fields so asdict() round-trips exactly
        _set("p", int(self.p))
        _set("rounds", int(self.rounds))
        _set("seed", int(self.seed))
        _set("metric_every", int(self.metric_every))
        if self.eta is not None:
            _set("eta", float(self.eta))
        if self.tau is not None:
            _set("tau", int(self.tau))
        _set("decay", float(self.decay))

        if self.p < 1:
            raise ValueError(f"RunSpec.p: need at least 1 worker, got "
                             f"{self.p}")
        if not caps.distributed and self.p != 1:
            raise ValueError(
                f"RunSpec.p: algorithm {self.algo!r} is single-worker; "
                f"got p={self.p} (use the distributed variants for p>1)")
        if self.rounds < 1:
            raise ValueError(f"RunSpec.rounds: need >= 1, got {self.rounds}")
        if self.metric_every < 1:
            raise ValueError(
                f"RunSpec.metric_every: need >= 1, got {self.metric_every}")
        if self.eta is not None and not self.eta > 0.0:
            raise ValueError(f"RunSpec.eta: need > 0, got {self.eta}")
        if self.tau is not None and self.tau < 1:
            raise ValueError(f"RunSpec.tau: need >= 1, got {self.tau}")

        # fetch discipline (resolved BEFORE the backend check: whether an
        # spmd program exists for D-SAGA depends on the discipline)
        if self.fetch is not None and not caps.accepts_fetch:
            raise ValueError(
                f"RunSpec.fetch: algorithm {self.algo!r} has a single "
                "fetch discipline; only D-SAGA exposes fetch=")
        if caps.accepts_fetch:
            if self.fetch is None:
                _set("fetch",
                     "stale" if self.backend == "spmd" else "instant")
            if self.fetch not in ("instant", "stale"):
                raise ValueError(
                    f"RunSpec.fetch: unknown fetch {self.fetch!r}: "
                    "expected 'instant' or 'stale'")

        # backend — reuse check_backend so the error contracts ("unknown
        # backend", "event-serial") stay the single spelling everywhere
        from repro.core.distributed import check_backend
        try:
            check_backend(self.backend)
        except ValueError as e:
            raise ValueError(f"RunSpec.backend: {e}") from None
        if self.backend == "spmd":
            if not caps.spmd_ok:
                raise NotImplementedError(
                    f"RunSpec.backend: algorithm {self.algo!r} has no SPMD "
                    "program (single-device driver); use backend='vmap'")
            if caps.accepts_fetch and self.fetch == "instant":
                try:
                    check_backend(
                        "spmd", spmd_ok=False,
                        algo=f"{self.algo} with fetch='instant'")
                except NotImplementedError as e:
                    raise NotImplementedError(
                        f"RunSpec.backend: {e}") from None

        # speeds — async event schedules only
        if self.speeds is not None:
            if not caps.accepts_speeds:
                raise ValueError(
                    f"RunSpec.speeds: algorithm {self.algo!r} is "
                    "synchronous — per-worker speeds only weight the "
                    "asynchronous event schedules (centralvr_async, dsaga)")
            speeds = tuple(float(s) for s in self.speeds)
            if len(speeds) != self.p:
                raise ValueError(
                    f"RunSpec.speeds: need one entry per worker "
                    f"(p={self.p}), got {len(speeds)}")
            if any(s <= 0.0 for s in speeds):
                raise ValueError("RunSpec.speeds: speeds must be > 0, got "
                                 f"{speeds}")
            _set("speeds", speeds)

        if self.tau is not None and not caps.accepts_tau:
            raise ValueError(
                f"RunSpec.tau: algorithm {self.algo!r} has no local-step "
                "count (its inner loop is a full epoch)")
        if self.sampling not in _SAMPLINGS:
            raise ValueError(
                f"RunSpec.sampling: unknown sampling {self.sampling!r}: "
                f"expected one of {_SAMPLINGS}")
        if self.sampling != "permutation" and self.algo != "centralvr":
            raise ValueError(
                "RunSpec.sampling: only 'centralvr' (Algorithm 1) exposes "
                "the sampling mode")

        # composite objective (prox=) — parse eagerly so a bad operator
        # string fails here, pre-JAX, naming the field
        if self.prox is not None:
            from repro.prox import operators as proxops
            if not caps.accepts_prox:
                raise ValueError(
                    f"RunSpec.prox: algorithm {self.algo!r} has no VR "
                    "update site to compose a prox into; only the VR "
                    "family (centralvr, centralvr_sync, centralvr_async, "
                    "dsvrg, dsaga, svrg, saga) exposes prox=")
            try:
                _set("prox", proxops.canonical(self.prox))
            except ValueError as e:
                raise ValueError(f"RunSpec.prox: {e}") from None
            if self.fused is True and not proxops.is_elementwise(self.prox):
                raise ValueError(
                    f"RunSpec.fused: prox "
                    f"{proxops.parse(self.prox).name!r} couples "
                    "coordinates, but the fused vr_update epilogue is "
                    "elementwise; use fused=False (or 'auto', which falls "
                    "back to the unfused oracle)")

        # snapshot anchor strategy — capability-gated per algorithm
        if self.snapshot is not None:
            if self.snapshot not in _SNAPSHOTS:
                raise ValueError(
                    f"RunSpec.snapshot: unknown snapshot "
                    f"{self.snapshot!r}: expected one of {_SNAPSHOTS}")
            if not caps.snapshots:
                raise ValueError(
                    f"RunSpec.snapshot: algorithm {self.algo!r} has no VR "
                    "anchor to re-snapshot; only the VR family exposes "
                    "snapshot=")
            if self.snapshot not in caps.snapshots:
                raise ValueError(
                    f"RunSpec.snapshot: algorithm {self.algo!r} supports "
                    f"snapshot in {caps.snapshots}, got {self.snapshot!r} "
                    "(the table-based algorithms maintain their anchor "
                    "incrementally — 'last' only)")
            if self.fused and self.snapshot != "last":
                raise ValueError(
                    "RunSpec.fused: the fused SVRG kernel path anchors at "
                    f"the last iterate; snapshot={self.snapshot!r} "
                    "requires fused=False")

        # sparse lazy driver (Algorithm 1 only; sampling rule above)
        if self.sampling == "sparse":
            if self.backend != "vmap":
                raise ValueError(
                    "RunSpec.backend: sampling='sparse' is the lazy "
                    "host-CSR driver (prox/lazy.py); it has no spmd "
                    "program — use backend='vmap'")
            if self.fused:
                raise ValueError(
                    "RunSpec.fused: sampling='sparse' already skips the "
                    "dense update (lazy catch-up); fused= does not apply")
            if self.prox is not None:
                from repro.prox import operators as proxops
                if proxops.parse(self.prox).name != "l1":
                    raise ValueError(
                        "RunSpec.prox: the lazy sparse driver composes "
                        "skipped steps in closed form only for the "
                        "separable soft-threshold; sampling='sparse' "
                        f"supports prox='l1:...', got {self.prox!r}")
        if self.decay != 0.0 and self.algo not in _DECAY_ALGOS:
            raise ValueError(
                f"RunSpec.decay: step-size decay only applies to "
                f"{_DECAY_ALGOS}, not {self.algo!r}")
        if self.fused is None:
            _set("fused", False)
        if self.fused not in (True, False, "auto"):
            raise ValueError(
                f"RunSpec.fused: expected True, False or 'auto', got "
                f"{self.fused!r}")
        if self.fused and not caps.accepts_fused:
            raise ValueError(
                f"RunSpec.fused: algorithm {self.algo!r} has no VR inner "
                "loop to fuse; only the VR family (centralvr, "
                "centralvr_sync, centralvr_async, dsvrg, dsaga, svrg, "
                "saga) exposes fused=")

        # multi-host topology + elasticity (DESIGN.md §Multi-host &
        # elasticity) — validated before any JAX work, like everything
        # else here, so a bad launch fails in the parent, not the fleet
        if self.topology not in ("local", "process"):
            raise ValueError(
                f"RunSpec.topology: unknown topology {self.topology!r}: "
                "expected 'local' or 'process'")
        _set("elastic", bool(self.elastic))
        if self.topology == "process":
            if self.algo not in ("centralvr_sync", "centralvr_async"):
                raise ValueError(
                    f"RunSpec.topology: algorithm {self.algo!r} has no "
                    "process-mesh program; topology='process' supports "
                    "centralvr_sync and centralvr_async")
            if self.backend != "vmap":
                raise ValueError(
                    "RunSpec.backend: topology='process' runs each "
                    "process's workers as local jitted programs; set "
                    "backend='vmap' (the per-process spmd tier is the "
                    "accelerator path, DESIGN.md §Multi-host & elasticity)")
            if self.fused:
                raise ValueError(
                    "RunSpec.fused: the process-mesh engines pin "
                    "bit-exactness against the unfused event-serial "
                    "reference; fused= is not supported under "
                    "topology='process'")
            if self.prox is not None:
                raise ValueError(
                    "RunSpec.prox: the process-mesh engines run the "
                    "smooth objective only; prox= is not supported under "
                    "topology='process'")
        if self.elastic and self.algo != "centralvr_async":
            raise ValueError(
                f"RunSpec.elastic: only centralvr_async has wave "
                f"boundaries to repartition at; got algo={self.algo!r}")
        if self.elastic and self.prox is not None:
            raise ValueError(
                "RunSpec.prox: the elastic event-serial reference runs "
                "the smooth objective only; prox= is not supported with "
                "elastic=True")

    @property
    def epochs(self) -> int:
        """Alias: the single-worker algorithms call rounds 'epochs'."""
        return self.rounds

    @property
    def caps(self) -> AlgoCaps:
        return REGISTRY[self.algo].caps


# ---------------------------------------------------------------------------
# RunResult — the uniform return
# ---------------------------------------------------------------------------

@dataclass
class RunResult:
    """What every algorithm returns through :func:`solve`.

    ``spec`` is the *resolved* spec (eta filled in, fetch defaulted) — the
    exact configuration that produced the run, suitable for artifact
    provenance.  ``wall_s`` is the blocking wall clock of the driver call
    (the first call of a fresh process includes jit compilation);
    ``traces`` is the delta of ``runtime.TRACES`` over the call — 0 on a
    jit cache hit, the exact retrace/compile probe of DESIGN.md §3.
    ``comms`` is the analytical bytes-per-collective model of the run
    (``obs/comms.py``, roofline result-shape convention) and
    ``staleness`` the fetch-staleness/wave-utilization record of the
    deterministic event schedule (``obs/staleness.py``; None for the
    bulk-synchronous algorithms) — both derived host-side from the spec
    and shapes, so every provenance row carries them whatever backend ran.
    """

    spec: RunSpec
    rels: np.ndarray           # recorded rel-grad-norm trajectory
    x: np.ndarray              # final iterate (d,)
    state: Any                 # the driver's full final state pytree
    wall_s: float
    traces: dict
    grad_evals: Optional[np.ndarray] = None
    comms: Optional[dict] = None
    staleness: Optional[dict] = None
    transitions: Optional[list] = None   # elastic membership changes

    @property
    def final_rel(self) -> float:
        return float(self.rels[-1])

    def provenance(self, tail: int = 8) -> dict:
        """JSON-able record of exactly what configuration produced this
        result — embedded alongside each benchmark-artifact row.  The row
        shape is golden (``obs/schema.py: PROVENANCE_KEYS``); extend both
        together."""
        from repro.obs.recorder import SCHEMA_VERSION

        rels = np.asarray(self.rels, dtype=float)
        return {
            "spec": dataclasses.asdict(self.spec),
            "final_rel": float(rels[-1]) if rels.size else None,
            "rels_tail": [float(v) for v in rels[-tail:]],
            "rounds_recorded": int(rels.size),
            "wall_s": float(self.wall_s),
            "traces": dict(self.traces),
            "comms": dict(self.comms) if self.comms else None,
            "staleness": dict(self.staleness) if self.staleness else None,
            "schema_v": SCHEMA_VERSION,
        }


# ---------------------------------------------------------------------------
# solve
# ---------------------------------------------------------------------------

def _coerce_problem(spec: RunSpec, problem):
    """Match the data topology to the algorithm: shard a flat Problem for
    the distributed algorithms, merge a ShardedProblem for the
    single-worker ones, or build either from a ConvexConfig (dataset keyed
    by ``cfg.seed``, so the same config always yields the same data)."""
    import jax

    from repro.config import ConvexConfig
    from repro.core import convex, distributed

    caps = REGISTRY[spec.algo].caps
    if isinstance(problem, ConvexConfig):
        if caps.distributed:
            # cfg.workers left at its default (1) means "let the spec
            # decide"; an explicit conflicting value is an error, same as
            # the ShardedProblem mismatch below
            if problem.workers not in (1, spec.p):
                raise ValueError(
                    f"RunSpec.p: spec says p={spec.p} but the ConvexConfig "
                    f"sets workers={problem.workers}; make them agree (or "
                    "leave cfg.workers at its default)")
            cfg = dataclasses.replace(problem, workers=spec.p)
            return distributed.make_distributed(
                jax.random.PRNGKey(cfg.seed), cfg)
        if problem.workers > 1:
            # single-worker algorithm on a multi-worker config: run on the
            # merged total dataset — the same data the distributed
            # algorithms see, so baseline comparisons stay exact
            return distributed.make_distributed(
                jax.random.PRNGKey(problem.seed), problem).merged()
        return convex.make_problem(jax.random.PRNGKey(problem.seed), problem)
    if isinstance(problem, distributed.ShardedProblem):
        if not caps.distributed:
            return problem.merged()
        if problem.p != spec.p:
            raise ValueError(
                f"RunSpec.p: spec says p={spec.p} but the ShardedProblem "
                f"has p={problem.p}")
        return problem
    if isinstance(problem, convex.Problem):
        if caps.distributed:
            return distributed.shard_problem(problem, spec.p)
        return problem
    raise TypeError(
        f"solve() takes a ConvexConfig, Problem, or ShardedProblem; got "
        f"{type(problem).__name__}")


def solve(spec: RunSpec, problem, *, key=None, mesh=None,
          membership=None) -> RunResult:
    """Run ``spec`` against ``problem`` (a ``ConvexConfig``, ``Problem``,
    or ``ShardedProblem``) and return the uniform :class:`RunResult`.

    Uniform handling across every registry algorithm:

      * ``backend="spmd"``: the driver gets one worker per device of
        ``mesh`` (default: the first p devices).  On a chip those are p
        chips, and fewer fail naming the count found; on the CPU, p
        simulated host devices are forced *before* the first jax
        operation (``spmd.force_host_devices``; a fresh process acquires
        them, an already-initialized one validates the count);
      * data sharding/merging per the algorithm's topology
        (:func:`_coerce_problem`);
      * ``eta=None`` resolves to ``convex.auto_eta`` on the merged
        problem;
      * the RNG key derives from ``spec.seed`` unless ``key`` overrides
        it; all drivers precompute their draws on the host (DESIGN.md §2);
      * the driver's return tuple is normalized to
        (state, final iterate, rels, grad_evals).

    ``topology="process"`` routes to the process-mesh engines
    (``core/procmesh.py``; requires a ``repro.launch.distributed`` world).
    ``elastic=True`` under topology="local" replays the deterministic
    ``membership=`` plan (a ``core.elastic.PlannedMembership``) through
    ``run_async_elastic`` — the event-serial elastic reference.
    """
    entry = REGISTRY[spec.algo]
    if membership is not None and not (spec.elastic
                                       and spec.topology == "local"):
        raise ValueError(
            "solve(membership=...) is the deterministic dropout plan of a "
            "LOCAL elastic run; it needs spec.elastic=True and "
            "spec.topology='local' (process topology discovers membership "
            "through heartbeats)")
    if spec.backend == "spmd":
        from repro.core import spmd
        spmd.force_host_devices(max(spec.p, 1))

    import jax

    from repro.obs import recorder as obs_recorder

    with obs_recorder.solve_scope():
        with obs_recorder.span("solve/prepare", algo=spec.algo,
                               backend=spec.backend):
            problem, eta, key = _prepare(spec, problem, key)
        with runtime.traces_delta() as traces:
            t0 = time.perf_counter()
            with obs_recorder.span("solve/dispatch"):
                state, x, rels, grad_evals, transitions = _dispatch(
                    entry, spec, problem, eta, key, mesh, membership)
            with obs_recorder.span("solve/wait"):
                rels = jax.block_until_ready(rels)
            wall = time.perf_counter() - t0
        with obs_recorder.span("solve/finish"):
            return _finish(entry, spec, eta, state, x, rels, grad_evals,
                           transitions, wall, traces)


def _prepare(spec: RunSpec, problem, key):
    """``solve()``'s host work before the driver call: the problem in the
    algorithm's layout, the step size and the key."""
    import jax

    from repro.core import convex, distributed
    from repro.obs import recorder as obs_recorder

    with obs_recorder.span("solve/coerce"):
        problem = _coerce_problem(spec, problem)
    eta = spec.eta
    if eta is None:
        with obs_recorder.span("solve/auto_eta"):
            merged = (problem.merged()
                      if isinstance(problem, distributed.ShardedProblem)
                      else problem)
            eta = convex.auto_eta(merged)
    if key is None:
        key = jax.random.PRNGKey(spec.seed)
    return problem, eta, key


def _dispatch(entry, spec: RunSpec, problem, eta, key, mesh, membership):
    """The driver call: (state, final iterate, rels, grad_evals,
    transitions), still on the device."""
    if spec.topology == "process":
        from repro.core import procmesh
        state, x, rels, transitions = procmesh.solve_process(
            spec, problem, eta, key)
        return state, x, rels, None, transitions
    if spec.elastic:
        from repro.core import elastic as elasticmod
        eres = elasticmod.run_async_elastic(
            problem, eta=eta, rounds=spec.rounds, key=key,
            membership=membership, speeds=spec.speeds)
        return eres.state, eres.state.x_c, eres.rels, None, eres.transitions
    state, x, rels, grad_evals = entry.call(spec, problem, eta, key, mesh)
    return state, x, rels, grad_evals, None


def _finish(entry, spec: RunSpec, eta, state, x, rels, grad_evals,
            transitions, wall, traces) -> RunResult:
    """The result on the host, with its accounting and provenance."""
    from repro.obs import recorder as obs_recorder

    rels = np.asarray(rels)
    if grad_evals is not None:
        grad_evals = np.asarray(grad_evals)
    if spec.metric_every > 1 and rels.size:
        idx = np.arange(spec.metric_every - 1, rels.size, spec.metric_every)
        idx = np.unique(np.append(idx, rels.size - 1))
        rels = rels[idx]
        if grad_evals is not None:
            # keep the two trajectories aligned (rels[i] <-> grad_evals[i])
            grad_evals = grad_evals[idx]
    resolved = dataclasses.replace(spec, eta=float(eta))
    x = np.asarray(x)

    # comms/staleness accounting: host-side, derived from spec + shapes,
    # so it is cheap enough to compute for EVERY run (bench provenance
    # rows carry it with telemetry off)
    from repro.obs import comms as obs_comms
    from repro.obs import staleness as obs_staleness

    comms = obs_comms.comms_model(spec.algo, p=spec.p, d=int(x.shape[-1]),
                                  rounds=spec.rounds)
    staleness = None
    if entry.caps.is_async:
        staleness = obs_staleness.staleness_stats(
            runtime.event_schedule(spec.p, spec.rounds, spec.speeds), spec.p)

    res = RunResult(spec=resolved, rels=rels, x=x, state=state,
                    wall_s=wall, traces=traces, grad_evals=grad_evals,
                    comms=comms, staleness=staleness,
                    transitions=transitions)
    rec = obs_recorder.active()
    if rec is not None:
        rec.event("traces", **traces)
        rec.event("provenance", **res.provenance())
    return res


# ---------------------------------------------------------------------------
# Registry entries — the paper's algorithm family as data
# ---------------------------------------------------------------------------
# Each ``call`` adapter maps the uniform spec onto one driver's native
# keyword surface and normalizes its return tuple.  Driver modules are
# imported lazily: they import this module (inside their run_* bodies) for
# spec validation, and the registry must be importable first.

def _call_centralvr(spec, prob, eta, key, mesh):
    from repro.core import centralvr
    st, rels, evals = centralvr.run(prob, eta=eta, epochs=spec.rounds,
                                    key=key, sampling=spec.sampling,
                                    backend=spec.backend, mesh=mesh,
                                    fused=spec.fused, prox=spec.prox)
    return st, st.x, rels, evals


def _call_sync(spec, sp, eta, key, mesh):
    from repro.core import distributed
    st, rels = distributed.run_sync(sp, eta=eta, rounds=spec.rounds,
                                    key=key, backend=spec.backend, mesh=mesh,
                                    fused=spec.fused, prox=spec.prox)
    return st, st.x, rels, None


def _call_async(spec, sp, eta, key, mesh):
    from repro.core import distributed
    st, rels = distributed.run_async(sp, eta=eta, rounds=spec.rounds,
                                     key=key, speeds=spec.speeds,
                                     backend=spec.backend, mesh=mesh,
                                     fused=spec.fused, prox=spec.prox)
    return st, st.x_c, rels, None


def _call_dsvrg(spec, sp, eta, key, mesh):
    from repro.core import distributed
    x, rels = distributed.run_dsvrg(sp, eta=eta, rounds=spec.rounds,
                                    key=key, tau=spec.tau or 0,
                                    backend=spec.backend, mesh=mesh,
                                    fused=spec.fused, prox=spec.prox,
                                    snapshot=spec.snapshot or "last")
    return x, x, rels, None


def _call_dsaga(spec, sp, eta, key, mesh):
    from repro.core import distributed
    st, rels = distributed.run_dsaga(sp, eta=eta, rounds=spec.rounds,
                                     key=key, tau=spec.tau or 100,
                                     fetch=spec.fetch, speeds=spec.speeds,
                                     backend=spec.backend, mesh=mesh,
                                     fused=spec.fused, prox=spec.prox)
    return st, st.x_c, rels, None


def _call_sgd(spec, prob, eta, key, mesh):
    from repro.core import baselines
    x, rels = baselines.run_sgd(prob, eta=eta, epochs=spec.rounds, key=key,
                                decay=spec.decay)
    return x, x, rels, None


def _call_svrg(spec, prob, eta, key, mesh):
    from repro.core import baselines
    x, rels = baselines.run_svrg(prob, eta=eta, epochs=spec.rounds, key=key,
                                 inner=spec.tau or 0, fused=spec.fused,
                                 prox=spec.prox,
                                 snapshot=spec.snapshot or "last")
    return x, x, rels, None


def _call_saga(spec, prob, eta, key, mesh):
    from repro.core import baselines
    x, rels = baselines.run_saga(prob, eta=eta, epochs=spec.rounds, key=key,
                                 fused=spec.fused, prox=spec.prox)
    return x, x, rels, None


def _call_dist_sgd(spec, sp, eta, key, mesh):
    from repro.core import baselines
    x, rels = baselines.run_dist_sgd(sp, eta=eta, rounds=spec.rounds,
                                     key=key, tau=spec.tau or 0,
                                     decay=spec.decay,
                                     backend=spec.backend, mesh=mesh)
    return x, x, rels, None


def _call_easgd(spec, sp, eta, key, mesh):
    from repro.core import baselines
    xc, rels = baselines.run_easgd(sp, eta=eta, rounds=spec.rounds, key=key,
                                   tau=spec.tau or 16, decay=spec.decay,
                                   backend=spec.backend, mesh=mesh)
    return xc, xc, rels, None


def _call_ps_svrg(spec, sp, eta, key, mesh):
    from repro.core import baselines
    x, rels = baselines.run_ps_svrg(sp, eta=eta, rounds=spec.rounds,
                                    key=key, backend=spec.backend, mesh=mesh)
    return x, x, rels, None


register("centralvr", "repro.core.centralvr", "run",
         AlgoCaps(distributed=False, spmd_ok=True, is_async=False,
                  accepts_fused=True, accepts_prox=True,
                  snapshots=("last",)),
         _call_centralvr,
         "CentralVR, single worker (Algorithm 1); spmd = run on the mesh")
register("centralvr_sync", "repro.core.distributed", "run_sync",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=False,
                  accepts_fused=True, accepts_prox=True,
                  snapshots=("last",)),
         _call_sync, "CentralVR-Sync (Algorithm 2)")
register("centralvr_async", "repro.core.distributed", "run_async",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=True,
                  accepts_speeds=True, accepts_fused=True,
                  accepts_prox=True, snapshots=("last",)),
         _call_async,
         "CentralVR-Async (Algorithm 3), deterministic event schedule")
register("dsvrg", "repro.core.distributed", "run_dsvrg",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=False,
                  accepts_tau=True, accepts_fused=True, accepts_prox=True,
                  snapshots=("last", "avg", "rand")),
         _call_dsvrg, "Distributed SVRG (Algorithm 4)")
register("dsaga", "repro.core.distributed", "run_dsaga",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=True,
                  accepts_fetch=True, accepts_speeds=True,
                  accepts_tau=True, accepts_fused=True, accepts_prox=True,
                  snapshots=("last",)),
         _call_dsaga,
         "Distributed SAGA (Algorithm 5); spmd requires fetch='stale'")
register("sgd", "repro.core.baselines", "run_sgd",
         AlgoCaps(distributed=False, spmd_ok=False, is_async=False),
         _call_sgd, "plain SGD, permutation sampling (Fig. 1 baseline)")
register("svrg", "repro.core.baselines", "run_svrg",
         AlgoCaps(distributed=False, spmd_ok=False, is_async=False,
                  accepts_tau=True, accepts_fused=True, accepts_prox=True,
                  snapshots=("last", "avg", "rand")),
         _call_svrg, "SVRG [17]; tau = inner-loop length (default n)")
register("saga", "repro.core.baselines", "run_saga",
         AlgoCaps(distributed=False, spmd_ok=False, is_async=False,
                  accepts_fused=True, accepts_prox=True,
                  snapshots=("last",)),
         _call_saga, "SAGA [12] (Fig. 1 baseline)")
register("dist_sgd", "repro.core.baselines", "run_dist_sgd",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=False,
                  accepts_tau=True),
         _call_dist_sgd, "distributed SGD with periodic averaging")
register("easgd", "repro.core.baselines", "run_easgd",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=False,
                  accepts_tau=True),
         _call_easgd, "elastic averaging SGD [36]")
register("ps_svrg", "repro.core.baselines", "run_ps_svrg",
         AlgoCaps(distributed=True, spmd_ok=True, is_async=False),
         _call_ps_svrg, "parameter-server SVRG [29]")
