"""Multi-process execution of the sync and wave-partitioned async
runtimes over a ``jax.distributed`` process mesh (DESIGN.md §Multi-host &
elasticity).

Topology: N processes (process 0 co-hosts the coordination service), each
owning a CONTIGUOUS block of the p workers (:func:`worker_blocks`).
Every process derives the full deterministic plan — dataset, init state,
event schedule, wave partition, per-event RNG draws — from the shared
``(spec, key)``, so the processes agree on every round's structure
without exchanging a byte of control data.  Only the wave algebra's
payloads move: per-event deltas ``(dx, dgbar)`` are published to the
coordination-service KV store and applied at the wave boundary in the
schedule's event order — the SAME sequential delta additions the
event-serial reference performs, which is why the two-process async
trajectory pins bit-exact in f64 against ``run_async`` /
``run_async_elastic`` (``tests/test_multihost.py``).

Why a KV-store data plane instead of cross-process ``shard_map``: XLA
cannot compile multi-process computations on the CPU backend (it raises
``Multiprocess computations aren't implemented on the CPU backend``), so
on the CPU each process runs its owned workers' epochs as LOCAL
jitted programs and the paper's central server lives in the wave-boundary
delta exchange.  On a real accelerator backend the same worker partition
maps onto a global 1-D device mesh (``spmd.process_worker_mesh``) and the
existing ``core/spmd.py`` runners execute each process's block under
``shard_map``; the KV exchange then only carries the elastic control
plane.

Elasticity (``elastic=True``): at every round boundary — every round
boundary is a wave boundary — processes heartbeat through the KV store;
process 0 (the arbiter, co-located with the coordination service) waits
``hb_timeout`` seconds for each live peer, declares missing ones dead,
admits rejoin candidates, and publishes the membership decision plus the
resync state (central pair + merged VR table, assembled from the
boundary table snapshots every process publishes BEFORE anything can
die).  Survivors re-shard per ``core/elastic.py``'s determinism
contract, so the post-dropout trajectory equals the event-serial elastic
reference replaying the observed membership plan.  Boundary deaths only:
a process that vanishes MID-round trips the data-plane deadlock guard (a
hard timeout on the delta fetch) rather than a silent hang.  Process 0's
metric trajectory and transition log are canonical — the launcher reads
results from process 0, which is never the injected-fault process.
"""
from __future__ import annotations

import dataclasses
import functools
import io
import json
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import convex, elastic, runtime
from repro.core.convex import Problem
from repro.core.distributed import (ShardedProblem, _local_centralvr_epoch,
                                    async_init, sync_init)
from repro.obs import recorder as obs_recorder

# data-plane deadlock guard: a delta/gather fetch outliving this means a
# peer vanished mid-round (outside the boundary-death contract) or the
# coordinator wedged — fail loudly instead of eating the CI job budget
DATA_TIMEOUT_S = 120.0
# how long a rejoin candidate's heartbeat peek may block the arbiter
PEEK_TIMEOUT_S = 0.05


class KVTimeout(TimeoutError):
    """A blocking KV get ran out of time."""


# ---------------------------------------------------------------------------
# Array codec + KV transports
# ---------------------------------------------------------------------------

def encode_arrays(arrays: Dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **{k: np.asarray(v) for k, v in arrays.items()})
    return buf.getvalue()


def decode_arrays(blob: bytes) -> Dict[str, np.ndarray]:
    with np.load(io.BytesIO(blob)) as data:
        return {k: data[k] for k in data.files}


class LocalKV:
    """In-process KV store: the single-process stand-in for the
    coordination service, so the engines (and their tests) run without
    spawning a world."""

    def __init__(self):
        self._d: Dict[str, bytes] = {}

    def set(self, key: str, value: bytes) -> None:
        if key in self._d:
            raise ValueError(f"KV key {key!r} already set (the protocol "
                             "never overwrites)")
        self._d[key] = bytes(value)

    def get(self, key: str, timeout_s: float) -> bytes:
        try:
            return self._d[key]
        except KeyError:
            raise KVTimeout(f"key {key!r} not present (single-process KV "
                            "never blocks)") from None


class DistributedKV:
    """The ``jax.distributed`` coordination-service KV store.  It lives in
    process 0's coordinator and survives peer death; blocking gets poll
    until the key appears or the timeout elapses."""

    def __init__(self, client):
        self._c = client

    def set(self, key: str, value: bytes) -> None:
        self._c.key_value_set_bytes(key, bytes(value))

    def get(self, key: str, timeout_s: float) -> bytes:
        try:
            return self._c.blocking_key_value_get_bytes(
                key, int(max(timeout_s, PEEK_TIMEOUT_S) * 1000))
        except Exception as e:  # jaxlib surfaces its own error types
            raise KVTimeout(f"key {key!r}: {e}") from None


@dataclasses.dataclass
class ProcComm:
    """One process's handle on the world: rank, size, KV transport, and a
    per-run key prefix so repeated runs never collide."""

    kv: object
    pid: int
    nprocs: int
    prefix: str = "run"

    def _k(self, key: str) -> str:
        return f"{self.prefix}/{key}"

    def put(self, key: str, **arrays) -> None:
        self.kv.set(self._k(key), encode_arrays(arrays))

    def get(self, key: str, timeout_s: float = DATA_TIMEOUT_S) -> dict:
        return decode_arrays(self.kv.get(self._k(key), timeout_s))

    def put_flag(self, key: str, payload: dict) -> None:
        self.kv.set(self._k(key), json.dumps(payload).encode())

    def get_flag(self, key: str, timeout_s: float) -> dict:
        return json.loads(self.kv.get(self._k(key), timeout_s).decode())

    def peek_flag(self, key: str) -> Optional[dict]:
        try:
            return self.get_flag(key, PEEK_TIMEOUT_S)
        except KVTimeout:
            return None


@dataclasses.dataclass
class Fault:
    """Deterministic fault injection for the elastic CI lane: process
    ``process`` drops at the boundary of round ``round_`` — ``exit`` mode
    terminates it (the engine raises :class:`WorkerDropped`), ``stall``
    mode takes it off the air for ``rejoin_after`` rounds and then
    rejoins through the membership protocol."""

    process: int
    round_: int
    mode: str = "exit"           # "exit" | "stall"
    rejoin_after: int = 2

    def __post_init__(self):
        if self.mode not in ("exit", "stall"):
            raise ValueError(f"Fault.mode: {self.mode!r}")
        if self.process == 0:
            raise ValueError(
                "Fault.process: process 0 co-hosts the coordination "
                "service (and the membership arbiter); killing it kills "
                "the control plane, not a worker")
        if self.round_ < 1:
            raise ValueError("Fault.round_: membership changes take effect "
                             "at wave boundaries AFTER round 0")
        if self.mode == "stall" and self.rejoin_after < 1:
            raise ValueError("Fault.rejoin_after must be >= 1: a stalled "
                             "process must miss at least one boundary "
                             "heartbeat to be declared lost")


class WorkerDropped(Exception):
    """Raised inside the engine when THIS process executes an exit-mode
    fault: the caller finalizes (flush telemetry, write partial results)
    and terminates — the dropout is the test, not a failure."""

    def __init__(self, round_: int, rels):
        super().__init__(f"process dropped at round {round_}")
        self.round_ = round_
        self.rels = rels


# ---------------------------------------------------------------------------
# Ownership + jitted local programs
# ---------------------------------------------------------------------------

def worker_blocks(p: int, nprocs: int) -> List[range]:
    """Contiguous compact-slot blocks, one per live process rank (uneven
    splits front-loaded, the usual balanced convention)."""
    if nprocs < 1 or p < nprocs:
        raise ValueError(f"cannot split p={p} workers over {nprocs} "
                         "processes (need p >= nprocs >= 1)")
    return [range(i * p // nprocs, (i + 1) * p // nprocs)
            for i in range(nprocs)]


@functools.partial(jax.jit, static_argnames=("kind",))
def _epoch_vr(A, b, lam, kind, x, table, gbar, eta, perm):
    return _local_centralvr_epoch(A, b, lam, kind, x, table, gbar, eta, perm)


@functools.partial(jax.jit, static_argnames=("kind",))
def _rel_metric(A, b, lam, kind, x, g0):
    return convex.rel_grad_norm(Problem(A, b, lam, kind), x, g0)


@jax.jit
def _mean0(xs):
    return xs.mean(0)


def _perm_rows(keys, ns: int):
    """Host-precomputed permutation draws — the same vmap the reference
    runners perform (``sync_round``, ``core/spmd.py``), so every process
    consumes identical randomness by construction."""
    keys = jnp.asarray(keys)
    keys = keys.reshape((-1,) + keys.shape[-1:])
    return np.asarray(
        jax.vmap(lambda k: jax.random.permutation(k, ns))(keys))


def _wave_layout(row: np.ndarray, p: int):
    """Greedy wave grouping of one round's event row via
    ``runtime.wave_partition``; yields ``(workers_in_event_order,
    row_offset)`` per wave."""
    active, rank, _ = runtime.wave_partition(np.asarray(row), p)
    out = []
    offset = 0
    for w in range(active.shape[1]):
        workers = np.nonzero(active[0, w])[0]
        if workers.size == 0:
            break
        ordered = workers[np.argsort(rank[0, w, workers])]
        out.append((ordered.tolist(), offset))
        offset += workers.size
    return out


def _fresh_views(x_c, gbar_c, table, p):
    """The async handover construction (``async_init`` / ``resync_state``
    on host arrays): every worker's previous contribution and fetch
    snapshot start at the central values."""
    return (np.tile(x_c, (p, 1)), np.tile(gbar_c, (p, 1)),
            np.tile(x_c, (p, 1)), np.tile(gbar_c, (p, 1)),
            np.asarray(table).reshape(p, -1))


# ---------------------------------------------------------------------------
# The engines
# ---------------------------------------------------------------------------

def run_sync_process(sp: ShardedProblem, *, eta: float, rounds: int, key,
                     comm: ProcComm):
    """CentralVR-Sync (Algorithm 2) over the process mesh.

    Init is computed LOCALLY on every process (it is a pure function of
    the shared ``(sp, eta, key)``, so replication is bit-exact and free);
    each round, owned epochs run as local jitted programs and the central
    average is assembled from the KV-exchanged worker blocks — same
    draws, same per-worker arithmetic as the single-process backend."""
    blocks = worker_blocks(sp.p, comm.nprocs)
    block = blocks[comm.pid]
    merged = sp.merged()
    g0 = convex.grad_norm0(merged)
    k_init, k_run = jax.random.split(key)
    st0 = sync_init(sp, eta, k_init)
    x_c = np.array(st0.x)
    gbar_c = np.array(st0.gbar)
    tables = np.array(st0.tables)
    round_keys = jax.random.split(k_run, rounds)
    rels = []
    for r in range(rounds):
        perms = _perm_rows(jax.random.split(round_keys[r], sp.p), sp.ns)
        own_x, own_acc = [], []
        for s in block:
            x, table, acc = _epoch_vr(
                sp.A[s], sp.b[s], sp.lam, sp.kind, jnp.asarray(x_c),
                jnp.asarray(tables[s]), jnp.asarray(gbar_c), eta,
                jnp.asarray(perms[s]))
            tables[s] = np.asarray(table)
            own_x.append(np.asarray(x))
            own_acc.append(np.asarray(acc))
        comm.put(f"s/{r}/{comm.pid}", xs=np.stack(own_x),
                 accs=np.stack(own_acc))
        xs = np.zeros((sp.p,) + x_c.shape, dtype=x_c.dtype)
        accs = np.zeros_like(xs)
        for q, qblock in enumerate(blocks):
            part = (dict(xs=np.stack(own_x), accs=np.stack(own_acc))
                    if q == comm.pid else comm.get(f"s/{r}/{q}"))
            xs[qblock.start:qblock.stop] = part["xs"]
            accs[qblock.start:qblock.stop] = part["accs"]
        x_c = np.asarray(_mean0(xs))
        gbar_c = np.asarray(_mean0(accs))
        rels.append(float(_rel_metric(merged.A, merged.b, sp.lam, sp.kind,
                                      jnp.asarray(x_c), g0)))
    state = {"x": x_c, "tables": tables, "gbar": gbar_c}
    return state, np.asarray(rels)


def run_async_process(sp: ShardedProblem, *, eta: float, rounds: int, key,
                      comm: ProcComm, speeds=None, elastic_mode: bool = False,
                      hb_timeout: float = 10.0,
                      fault: Optional[Fault] = None):
    """CentralVR-Async (Algorithm 3) over the process mesh, wave by wave.

    Per round: every process derives the round's wave layout from the
    shared segment plan, computes its owned active workers' epochs as
    local jitted programs, publishes the ``(dx, dgbar)`` deltas, and
    applies the wave's deltas IN EVENT ORDER — each worker's fresh fetch
    is the central state immediately after its own event, exactly the
    event-serial reference algebra, so the trajectory pins bit-exact in
    f64.  With ``elastic_mode`` the round boundary additionally runs the
    heartbeat/membership protocol described in the module docstring.

    Returns ``(state, rels, transitions)``; ``rels`` carries NaN for
    rounds this process sat out (stall-mode rejoin) — process 0's output
    is canonical and process 0 never sits out.
    """
    p0 = sp.p
    if fault is not None and not elastic_mode:
        raise ValueError("fault injection requires elastic_mode=True")
    if fault is not None and fault.process >= comm.nprocs:
        raise ValueError(f"Fault.process {fault.process} outside the "
                         f"{comm.nprocs}-process world")
    merged = sp.merged()
    g0 = convex.grad_norm0(merged)
    k_init, k_run = jax.random.split(key)

    live_procs: Tuple[int, ...] = tuple(range(comm.nprocs))
    live_workers: Tuple[int, ...] = tuple(range(p0))
    lost_by_proc: Dict[int, Tuple[int, ...]] = {}
    sp_cur = sp
    blocks = worker_blocks(p0, comm.nprocs)
    block = blocks[comm.pid]

    # init replicated locally — a pure function of the shared inputs
    # (np.array, not asarray: device arrays view as read-only)
    st0 = async_init(sp, eta, k_init)
    x_c = np.array(st0.x_c)
    gbar_c = np.array(st0.gbar_c)
    x_old = np.array(st0.x_old)
    gbar_old = np.array(st0.gbar_old)
    x_fetch = np.array(st0.x_fetch)
    gbar_fetch = np.array(st0.gbar_fetch)
    tables = np.array(st0.tables)

    rec = obs_recorder.active()
    rels = np.full(rounds, np.nan)
    transitions: List[dict] = []
    seg_start = 0
    sched_rows, key_rows = elastic.segment_plan(
        k_run, 0, rounds, p0, elastic.survivor_speeds(speeds, live_workers))
    perms = _perm_rows(key_rows, sp.ns)

    def replan(r, p_cur):
        rows, krows = elastic.segment_plan(
            k_run, r, rounds, p_cur,
            elastic.survivor_speeds(speeds, live_workers))
        return rows, _perm_rows(krows, sp_cur.ns)

    r = 0
    skip_boundary = False   # set after a rejoin: round r's protocol ran
    while r < rounds:
        if elastic_mode and not skip_boundary:
            # ---- wave-boundary membership protocol --------------------
            # publish the boundary table snapshot BEFORE anything can
            # die: a boundary death always leaves its tables recoverable
            comm.put(f"tab/{r}/{comm.pid}",
                     tables=tables[block.start:block.stop])
            if (fault is not None and comm.pid == fault.process
                    and r == fault.round_):
                if fault.mode == "exit":
                    raise WorkerDropped(r, rels)
                # stall: vanish (no heartbeat this boundary) and rejoin
                # through the candidate path
                rejoined = _rejoin_loop(comm, r + fault.rejoin_after,
                                        rounds, hb_timeout)
                if rejoined is None:
                    return ({"x_c": x_c, "gbar_c": gbar_c}, rels,
                            transitions)
                r, mem = rejoined
                resync = comm.get(f"resync/{r}")
                live_procs = tuple(mem["procs"])
                live_workers = tuple(mem["workers"])
                p_cur = len(live_workers)
                sp_cur = elastic.reshard_problem(sp, p_cur)
                x_c, gbar_c = resync["x_c"], resync["gbar_c"]
                x_old, gbar_old, x_fetch, gbar_fetch, tables = _fresh_views(
                    x_c, gbar_c, resync["table"], p_cur)
                blocks = worker_blocks(p_cur, len(live_procs))
                block = blocks[live_procs.index(comm.pid)]
                seg_start = r
                sched_rows, perms = replan(r, p_cur)
                fault = None
                skip_boundary = True
                continue
            comm.put_flag(f"hb/{r}/{comm.pid}", {"pid": comm.pid})
            decision = _membership_round(
                comm, r, live_procs, live_workers, blocks, tables,
                x_c, gbar_c, lost_by_proc, hb_timeout)
            if tuple(decision["procs"]) != live_procs:
                new_procs = tuple(decision["procs"])
                new_workers = tuple(decision["workers"])
                transitions.append(elastic._emit_transition(
                    rec, r, live_workers, new_workers,
                    decision["detect_s"]))
                resync = comm.get(f"resync/{r}")
                for q in live_procs:
                    if q not in new_procs:
                        lost_by_proc[q] = tuple(
                            live_workers[i]
                            for i in blocks[live_procs.index(q)])
                live_procs, live_workers = new_procs, new_workers
                p_cur = len(live_workers)
                sp_cur = elastic.reshard_problem(sp, p_cur)
                x_c, gbar_c = resync["x_c"], resync["gbar_c"]
                x_old, gbar_old, x_fetch, gbar_fetch, tables = _fresh_views(
                    x_c, gbar_c, resync["table"], p_cur)
                blocks = worker_blocks(p_cur, len(live_procs))
                block = blocks[live_procs.index(comm.pid)]
                seg_start = r
                sched_rows, perms = replan(r, p_cur)
        skip_boundary = False

        # ---- one round of waves --------------------------------------
        p_cur = len(live_workers)
        alpha = 1.0 / p_cur
        row = np.asarray(sched_rows[r - seg_start])
        base = (r - seg_start) * p_cur
        for ordered, offset in _wave_layout(row, p_cur):
            own_results: Dict[int, tuple] = {}
            for j, s in enumerate(ordered):
                if s not in block:
                    continue
                x_new, table, gtilde = _epoch_vr(
                    sp_cur.A[s], sp_cur.b[s], sp_cur.lam, sp_cur.kind,
                    jnp.asarray(x_fetch[s]), jnp.asarray(tables[s]),
                    jnp.asarray(gbar_fetch[s]), eta,
                    jnp.asarray(perms[base + offset + j]))
                own_results[s] = (np.asarray(x_new), np.asarray(table),
                                  np.asarray(gtilde))
                comm.put(f"d/{seg_start}/{r}/{offset}/{s}",
                         dx=own_results[s][0] - x_old[s],
                         dg=own_results[s][2] - gbar_old[s])
            # apply the wave's deltas in event order — the sequential
            # additions of the event-serial reference, bit for bit
            for s in ordered:
                if s in own_results:
                    x_new, table, gtilde = own_results[s]
                    dx = x_new - x_old[s]
                    dg = gtilde - gbar_old[s]
                else:
                    part = comm.get(f"d/{seg_start}/{r}/{offset}/{s}")
                    dx, dg = part["dx"], part["dg"]
                x_c = x_c + alpha * dx
                gbar_c = gbar_c + alpha * dg
                if s in own_results:
                    tables[s] = own_results[s][1]
                    x_old[s] = own_results[s][0]
                    gbar_old[s] = own_results[s][2]
                    x_fetch[s] = x_c
                    gbar_fetch[s] = gbar_c
        rels[r] = float(_rel_metric(merged.A, merged.b, sp.lam, sp.kind,
                                    jnp.asarray(x_c), g0))
        r += 1

    state = {"x_c": x_c, "gbar_c": gbar_c, "tables": tables,
             "live": np.asarray(live_workers)}
    return state, rels, transitions


def _membership_round(comm: ProcComm, r: int, live_procs, live_workers,
                      blocks, tables, x_c, gbar_c, lost_by_proc,
                      hb_timeout: float) -> dict:
    """One boundary's membership decision.  The arbiter (lowest live
    rank — process 0 by construction, co-located with the coordination
    service) waits for live peers' heartbeats, peeks for rejoin
    candidates, publishes the resync state when membership changes, then
    the decision row; everyone else blocks on the decision row."""
    if comm.pid != min(live_procs):
        return comm.get_flag(f"mem/{r}", timeout_s=3 * hb_timeout + 30)

    t0 = time.perf_counter()
    dead: List[int] = []
    with obs_recorder.span("elastic/heartbeat", round=int(r)):
        for q in live_procs:
            if q == comm.pid:
                continue
            try:
                comm.get_flag(f"hb/{r}/{q}", timeout_s=hb_timeout)
            except KVTimeout:
                dead.append(q)
    detect_s = time.perf_counter() - t0
    joiners = [q for q in range(comm.nprocs)
               if q not in live_procs and comm.peek_flag(f"hb/{r}/{q}")]
    new_procs = tuple(sorted((set(live_procs) - set(dead)) | set(joiners)))
    new_workers = tuple(live_workers)
    if new_procs != tuple(live_procs):
        gone = [w for q in dead
                for w in (live_workers[i]
                          for i in blocks[list(live_procs).index(q)])]
        back = [w for q in joiners for w in lost_by_proc.get(q, ())]
        new_workers = tuple(
            sorted((set(live_workers) - set(gone)) | set(back)))
        # assemble the merged (n,) table from the boundary snapshots (the
        # table is per-SAMPLE: the current fleet always covers all n)
        parts = []
        for rank, q in enumerate(live_procs):
            if q == comm.pid:
                part = tables[blocks[rank].start:blocks[rank].stop]
            else:
                part = comm.get(f"tab/{r}/{q}")["tables"]
            parts.append(np.asarray(part).reshape(-1))
        comm.put(f"resync/{r}", x_c=x_c, gbar_c=gbar_c,
                 table=np.concatenate(parts))
    decision = {"procs": list(new_procs), "workers": list(new_workers),
                "detect_s": detect_s if dead else 0.0}
    comm.put_flag(f"mem/{r}", decision)
    return decision


def _rejoin_loop(comm: ProcComm, target: int, rounds: int,
                 hb_timeout: float):
    """Stall-mode rejoin: from ``target`` on, heartbeat each boundary and
    wait for a membership decision that includes us.  Returns ``(round,
    decision)`` for the boundary we rejoined at, or None if the run ended
    first."""
    for r2 in range(target, rounds):
        comm.put_flag(f"hb/{r2}/{comm.pid}", {"pid": comm.pid})
        try:
            mem = comm.get_flag(f"mem/{r2}", timeout_s=3 * hb_timeout + 60)
        except KVTimeout:
            return None
        if comm.pid in mem["procs"]:
            return r2, mem
    return None


# ---------------------------------------------------------------------------
# solve() entry point (RunSpec topology="process")
# ---------------------------------------------------------------------------

def solve_process(spec, sp: ShardedProblem, eta: float, key):
    """Dispatch a ``topology='process'`` RunSpec onto this process's mesh
    context (``repro.launch.distributed`` must have initialized the
    world).  Returns ``(state, x, rels, transitions)``."""
    from repro.launch import distributed as launchd

    ctx = launchd.context()
    if ctx is None:
        raise RuntimeError(
            "RunSpec.topology='process' needs an initialized process "
            "mesh: launch through `python -m repro.launch.distributed` "
            "or call repro.launch.distributed.init_process() first "
            "(DESIGN.md §Multi-host & elasticity)")
    comm = ctx.comm
    if sp.p < comm.nprocs:
        raise ValueError(
            f"RunSpec.p: p={sp.p} workers cannot be split over the "
            f"{comm.nprocs}-process world")
    if spec.algo == "centralvr_sync":
        state, rels = run_sync_process(sp, eta=eta, rounds=spec.rounds,
                                       key=key, comm=comm)
        return state, state["x"], rels, []
    state, rels, transitions = run_async_process(
        sp, eta=eta, rounds=spec.rounds, key=key, comm=comm,
        speeds=spec.speeds, elastic_mode=spec.elastic,
        hb_timeout=ctx.hb_timeout, fault=ctx.fault)
    return state, state["x_c"], rels, transitions
