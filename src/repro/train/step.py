"""Train / serve step factories.

The CentralVR worker model under SPMD (DESIGN.md §2): worker copies are a
LEADING AXIS on every state leaf, sharded over the worker mesh axes, and
the per-worker local step is vmapped — each device group computes its own
worker's step, no cross-worker traffic. The paper's epoch-boundary
server exchange is a mean over the worker axis (lowers to one all-reduce
over the worker mesh axes), executed only when step % (M*K) == M*K-1 —
this is THE communication-frequency lever the paper contributes, and it is
directly visible in the dry-run HLO as a conditional collective.

Modes (TrainConfig.vr / vr_workers):
  vr="none", W=1       — classic sync data-parallel SGD/Adam: loss is the
                         global-batch mean, GSPMD all-reduces gradients
                         EVERY step (the baseline the paper beats).
  vr=..., workers=data — paper-faithful CentralVR-Sync: full model copy
                         per data-axis group (dp_replicated).
  vr=..., workers=pod  — hierarchical (beyond-paper): FSDP inside a pod,
                         CentralVR across pods; cross-pod traffic only at
                         epoch boundaries.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, TrainConfig
from repro.data import synthetic
from repro.launch import mesh as meshlib
from repro.models import kernel_ctx, model
from repro.optim import optimizers, vr_wrapper
from repro.sharding import specs

tmap = jax.tree_util.tree_map


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    vr_state: Any       # VRState or () when vr="none"
    step: jax.Array


# LM worker mesh axis for the spmd epoch runtime — same axis name as the
# convex backend (core/spmd.py WORKER_AXIS / launch.mesh.make_worker_mesh)
LM_WORKER_AXIS = "workers"


def batch_geometry(tcfg: TrainConfig, W: int):
    """(accum, microbatch) for W workers. The seed code silently truncated
    a non-dividing accumulation factor to 1 (dropping most of the global
    batch); an uneven split is a config error and raises instead."""
    if tcfg.microbatch:
        denom = W * tcfg.microbatch
        if tcfg.global_batch % denom:
            raise ValueError(
                f"global_batch={tcfg.global_batch} is not divisible by "
                f"workers*microbatch = {W}*{tcfg.microbatch} = {denom}; "
                "every worker must process the same number of whole "
                "microbatches per step")
        return tcfg.global_batch // denom, tcfg.microbatch
    if tcfg.global_batch % W:
        raise ValueError(
            f"global_batch={tcfg.global_batch} is not divisible by "
            f"workers={W}")
    return 1, max(tcfg.global_batch // W, 1)


def worker_average(tree):
    """Algorithm 2 lines 16-18: the central server average over the
    leading worker axis, broadcast back to every worker copy (lowers to
    one all-reduce over the worker mesh axes under GSPMD)."""
    return tmap(
        lambda p: jnp.broadcast_to(p.mean(0, keepdims=True),
                                   p.shape).astype(p.dtype), tree)


def eval_params(params, W: int):
    """Params for held-out eval: between exchanges the W worker copies
    have DIVERGED, so worker 0 is not the algorithm's iterate — the
    central average is (fetched to host so eval runs on the default
    device regardless of backend placement)."""
    if W <= 1:
        return params
    return jax.device_get(tmap(lambda p: p.mean(0).astype(p.dtype), params))


def _loss(params, cfg, tcfg, tokens, fe, act_sharding=None):
    batch = {"tokens": tokens}
    if fe is not None:
        batch["frontend_embeds"] = fe
    return model.loss_fn(params, cfg, batch, remat=tcfg.remat,
                         act_sharding=act_sharding)


def _local_grads(params, cfg, tcfg, tokens, fe, act_sharding=None):
    """tokens: (A, mb, S); gradient accumulated over A microbatches.

    Gradients are taken against a COMPUTE-DTYPE (bf16) copy of the params,
    cast ONCE outside the accumulation loop: every per-microbatch FSDP
    weight all-gather then moves bf16 instead of the f32 masters, and the
    backward cotangents (incl. the deferred partial-sum all-reduces GSPMD
    emits for 2D-sharded weights) stay bf16 — measured ~2x collective cut
    on qwen1.5-110b/train_4k (EXPERIMENTS.md §Perf It.6). The f32 masters
    are touched only by the optimizer/VR update, once per step.
    """
    A = tokens.shape[0]
    compute = jnp.dtype(cfg.dtype)
    params_c = tmap(
        lambda p: p.astype(compute)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
    lg = jax.value_and_grad(_loss)

    def acc(carry, xs):
        loss_acc, g_acc = carry
        t, f = xs
        loss, g = lg(params_c, cfg, tcfg, t, f, act_sharding)
        g_acc = tmap(lambda a, b: a + b.astype(jnp.float32) / A, g_acc, g)
        return (loss_acc + loss / A, g_acc), None

    g0 = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if fe is None:
        def acc_nofe(carry, t):
            return acc(carry, (t, None))
        (loss, grads), _ = jax.lax.scan(acc_nofe, (jnp.zeros(()), g0), tokens)
    else:
        (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros(()), g0),
                                        (tokens, fe))
    return loss, grads


def _make_per_worker(cfg: ModelConfig, tcfg: TrainConfig, act_sharding=None,
                     fused: bool = False, interpret: bool = False):
    """One worker's local step (grads -> VR correction -> optimizer),
    shared by the per-step train_step, the vmap epoch scan, and the spmd
    epoch runner — the execution models differ, the math must not.

    ``fused`` (a RESOLVED bool — callers go through
    ``kernels.resolve_fused``) routes the hot paths through the Pallas
    kernels: the forward/backward traces under ``kernel_ctx`` (RMSNorm +
    flash attention), and for SGD the VR correction + update collapses
    into one ``vr_update`` launch (``vr_wrapper.apply``)."""
    M = tcfg.vr_table_size
    mode = tcfg.vr
    opt = optimizers.make(tcfg.optimizer, tcfg.learning_rate,
                          tcfg.weight_decay)
    fuse_vr = fused and mode != "none" and tcfg.optimizer == "sgd"

    def per_worker(params, vr_state, opt_state, tokens, fe, idx=None):
        # idx: scalar step % M, kept OUT of the vmapped axes so the VR
        # table switch stays unbatched (see vr_wrapper.correct)
        ctx = (kernel_ctx.scope(True, interpret) if fused
               else contextlib.nullcontext())
        with ctx:
            loss, g = _local_grads(params, cfg, tcfg, tokens, fe,
                                   act_sharding)
            g_snap = None
            if mode == "svrg":
                _, g_snap = _local_grads(vr_state.snapshot, cfg, tcfg,
                                         tokens, fe, act_sharding)
        if fuse_vr:
            params, vr_state = vr_wrapper.apply(
                mode, vr_state, g, M, lr=tcfg.learning_rate,
                g_snap=g_snap, params=params, idx=idx, interpret=interpret)
            return params, vr_state, opt_state, loss
        if mode == "svrg":
            v, vr_state = vr_wrapper.correct(mode, vr_state, g, M,
                                             g_snap=g_snap, params=params,
                                             idx=idx)
        elif mode != "none":
            v, vr_state = vr_wrapper.correct(mode, vr_state, g, M,
                                             params=params, idx=idx)
        else:
            v = g
        updates, opt_state = opt.update(v, opt_state, params)
        params = optimizers.apply_updates(params, updates)
        return params, vr_state, opt_state, loss

    return per_worker


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh,
                    vr_workers: str = "none", *,
                    workers: Optional[int] = None):
    """Returns (train_step(state, tokens, fe), meta dict).

    ``workers`` overrides the mesh-derived worker count: W stacked worker
    copies simulated under vmap on whatever devices the mesh has (the
    single-device reference configuration of the epoch-scan runtime)."""
    mesh = meshlib.gspmd(mesh)
    W = workers or (meshlib.worker_count(mesh, vr_workers)
                    if tcfg.vr != "none" else 1)
    M = tcfg.vr_table_size
    K = tcfg.local_epoch
    comm_every = M * K
    mode = tcfg.vr

    # In FSDP mode, pin the residual stream to batch-over-'data' so the
    # partitioner gathers per-layer WEIGHTS (ZeRO-3 semantics), not the
    # activations, and enable the explicit per-layer weight-gather context
    # (manual ZeRO; §Perf It.6). Only when the 'data' axis actually shards
    # the batch (W==1, or pod-level workers with data free).
    act_sharding = None
    # (never with an explicit ``workers`` simulation: stacked worker
    # copies are replicated by construction, and gather_ctx.enable is
    # process-global — engaging it here would leak into other runtimes)
    if (workers is None and not tcfg.dp_replicated
            and "data" in mesh.axis_names and mesh.devices.size > 1):
        w_axes = (meshlib.worker_axes(mesh, vr_workers)
                  if tcfg.vr != "none" else ())
        if "data" not in w_axes:
            act_sharding = NamedSharding(mesh, P("data", None, None))
            from repro.sharding import gather_ctx
            gather_ctx.enable(mesh, cfg, meshlib.mesh_axis_sizes(mesh))

    per_worker = _make_per_worker(cfg, tcfg, act_sharding)

    def train_step(state: TrainState, tokens, fe=None):
        """tokens: (W, A, mb, S) when W>1 else (A, mb, S)."""
        idx = state.step % M
        if W > 1:
            params, vr_state, opt_state, loss = jax.vmap(
                per_worker,
                in_axes=(0, 0, 0, 0, 0 if fe is not None else None, None)
            )(state.params, state.vr_state, state.opt_state, tokens, fe, idx)
            loss = loss.mean()

            def communicate(args):
                params, vr_state = args
                # average x and gbar across the worker axis;
                # tables/accumulators stay local
                params = worker_average(params)
                if mode != "none":
                    vr_state = vr_state._replace(
                        gbar=worker_average(vr_state.gbar))
                return params, vr_state

            boundary = (state.step + 1) % comm_every == 0
            params, vr_state = jax.lax.cond(
                boundary, communicate, lambda a: a, (params, vr_state))
        else:
            params, vr_state, opt_state, loss = per_worker(
                state.params, state.vr_state, state.opt_state, tokens, fe,
                idx)
        return TrainState(params, opt_state, vr_state, state.step + 1), {
            "loss": loss}

    meta = {"workers": W, "comm_every": comm_every,
            "grads_per_step": vr_wrapper.grads_per_step(mode),
            "vr_storage_mult": vr_wrapper.storage_multiplier(mode, M)}
    return train_step, meta


# ---------------------------------------------------------------------------
# Epoch-scan runtime (DESIGN.md §3, "LM epoch scan")
# ---------------------------------------------------------------------------

def make_epoch_runner(cfg: ModelConfig, tcfg: TrainConfig, W: int, *,
                      backend: str = "vmap", mesh=None, fused=False):
    """One whole communication epoch (M*K steps) as a single jitted
    ``lax.scan`` with donated TrainState: ``run_epoch(state) -> (state,
    (M*K,) losses)``, with the Algorithm-2 worker average applied at the
    scan's epoch boundary. ``state.step`` must be a multiple of M*K
    (``train/loop.py`` drives whole epochs, so it always is).

      * ``backend="vmap"`` — W stacked worker copies on one device;
        batches are generated ON DEVICE inside the scan body (the
        fold_in-keyed pipeline traces with the scan's step counter), so
        nothing crosses the host boundary during an epoch.
      * ``backend="spmd"`` — ``shard_map`` over a 1-D worker mesh
        (``launch.mesh.make_worker_mesh``), one worker per device; the
        epoch boundary is a ``lax.pmean`` collective. The epoch's token
        block is host-precomputed ONCE (it is step-independent: the
        finite sum replays indices 0..M-1 every epoch) and shipped
        sharded along the worker axis, like the convex spmd runners'
        host-precomputed draws (DESIGN.md §2).

    Returns (run_epoch, meta); meta carries the worker mesh for spmd so
    callers can place the state (``place_train_state``).

    ``fused``: False | True | "auto" — same axis as the convex drivers
    (``solver.RunSpec.fused``). True forces the Pallas kernels (interpret
    mode off-TPU); "auto" fuses only on a compiled Pallas backend. The
    fused VR step requires the SGD optimizer (the kernel bakes the plain
    ``x - lr*v`` update); forcing it with a stateful optimizer is an
    error, while "auto" quietly fuses just the model forward.
    """
    if backend not in ("vmap", "spmd"):
        raise ValueError(f"unknown backend {backend!r}: "
                         "expected 'vmap' or 'spmd'")
    from repro import kernels
    fuse_on, interpret = kernels.resolve_fused(fused)
    if (fused is True and tcfg.vr != "none"
            and tcfg.optimizer != "sgd"):
        raise ValueError(
            f"fused=True: the fused VR step bakes a plain SGD update, but "
            f"optimizer={tcfg.optimizer!r}; use optimizer='sgd' or "
            "fused='auto' (which fuses only the model forward)")
    E = tcfg.vr_table_size * tcfg.local_epoch
    accum, mb = batch_geometry(tcfg, W)
    meta = {"workers": W, "comm_every": E, "accum": accum,
            "microbatch": mb, "backend": backend,
            "grads_per_step": vr_wrapper.grads_per_step(tcfg.vr),
            "vr_storage_mult": vr_wrapper.storage_multiplier(
                tcfg.vr, tcfg.vr_table_size),
            "fused": fuse_on, "interpret": interpret}

    if backend == "vmap":
        return _epoch_runner_vmap(cfg, tcfg, W, fuse_on, interpret), meta

    if mesh is None:
        from repro.core import spmd
        mesh = spmd.worker_mesh(W)
    if mesh.devices.size != W:
        raise ValueError(
            f"worker mesh has {mesh.devices.size} devices but W={W}; the "
            "spmd epoch runtime places exactly one worker per device")
    meta["mesh"] = mesh
    if W == 1:
        # one worker has no axis to shard — like the convex backend
        # (core/spmd.py run_centralvr), "spmd" then means "execute on the
        # mesh device" so launchers address one API regardless of backend
        return _epoch_runner_vmap(cfg, tcfg, W, fuse_on, interpret), meta
    tokens = synthetic.epoch_tokens(
        cfg, tcfg.seed, workers=W, steps=E, accum=accum, microbatch=mb,
        seq=tcfg.seq_len, table_size=tcfg.vr_table_size)
    tokens = jax.device_put(
        tokens, NamedSharding(mesh, P(LM_WORKER_AXIS)))
    runner = _epoch_runner_spmd(cfg, tcfg, mesh, fuse_on, interpret)

    def run_epoch(state: TrainState):
        params, vr, opt, step, losses = runner(
            state.params, state.vr_state, state.opt_state, state.step,
            tokens)
        return TrainState(params, opt, vr, step), losses

    return run_epoch, meta


@functools.lru_cache(maxsize=None)
def _epoch_runner_vmap(cfg: ModelConfig, tcfg: TrainConfig, W: int,
                       fused: bool = False, interpret: bool = False):
    """One jitted runner per (cfg, tcfg, W, fused, interpret) — repeated
    run_training calls on the same config reuse the compiled epoch
    executable. The fused pair is part of the key because kernel dispatch
    is decided at trace time (models/kernel_ctx)."""
    per_worker = _make_per_worker(cfg, tcfg, fused=fused,
                                  interpret=interpret)
    E = tcfg.vr_table_size * tcfg.local_epoch
    accum, mb = batch_geometry(tcfg, W)

    def run_epoch(state: TrainState):
        def body(carry, s):
            params, vr, opt = carry
            idx = s % tcfg.vr_table_size
            toks = synthetic.epoch_batch(
                cfg, tcfg.seed, s, workers=W, accum=accum, microbatch=mb,
                seq=tcfg.seq_len, table_size=tcfg.vr_table_size)
            if W > 1:
                params, vr, opt, loss = jax.vmap(
                    per_worker, in_axes=(0, 0, 0, 0, None, None))(
                    params, vr, opt, toks, None, idx)
                loss = loss.mean()
            else:
                params, vr, opt, loss = per_worker(params, vr, opt,
                                                   toks[0], None, idx)
            return (params, vr, opt), loss

        steps = state.step + jnp.arange(E, dtype=jnp.int32)
        (params, vr, opt), losses = jax.lax.scan(
            body, (state.params, state.vr_state, state.opt_state), steps)
        if W > 1:
            params = worker_average(params)
            if tcfg.vr != "none":
                vr = vr._replace(gbar=worker_average(vr.gbar))
        return TrainState(params, opt, vr, state.step + E), losses

    return jax.jit(run_epoch, donate_argnums=0)


@functools.lru_cache(maxsize=None)
def _epoch_runner_spmd(cfg: ModelConfig, tcfg: TrainConfig, mesh,
                       fused: bool = False, interpret: bool = False):
    """One compiled executable per (cfg, tcfg, mesh, fused, interpret):
    the whole epoch scan inside a single jitted shard_map, worker state
    donated. ``check_vma=False`` for the same reason as the convex
    runners (core/spmd.py): the epoch carry enters unreplicated and
    leaves pmean-replicated."""

    per_worker = _make_per_worker(cfg, tcfg, fused=fused,
                                  interpret=interpret)
    E = tcfg.vr_table_size * tcfg.local_epoch
    mode = tcfg.vr
    ax = LM_WORKER_AXIS

    def body(params, vr, opt, step, tokens):
        # worker-stacked leaves arrive as this worker's (1, ...) shard
        take0 = functools.partial(tmap, lambda x: x[0])
        p, v, o = take0(params), take0(vr), take0(opt)

        def one(carry, xs):
            s, toks = xs
            p, v, o = carry
            p, v, o, loss = per_worker(p, v, o, toks, None,
                                       s % tcfg.vr_table_size)
            return (p, v, o), loss

        steps = step + jnp.arange(E, dtype=jnp.int32)
        (p, v, o), losses = jax.lax.scan(one, (p, v, o),
                                         (steps, tokens[0]))
        # epoch boundary: the central average as a collective
        pm = functools.partial(tmap, lambda x: jax.lax.pmean(x, ax))
        p = pm(p)
        if mode != "none":
            v = v._replace(gbar=pm(v.gbar))
        losses = jax.lax.pmean(losses, ax)
        lead = functools.partial(tmap, lambda x: x[None])
        return lead(p), lead(v), lead(o), step + E, losses

    ws, rep = P(ax), P()
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(ws, ws, ws, rep, ws),
        out_specs=(ws, ws, ws, rep, rep), check_vma=False),
        donate_argnums=(0, 1, 2))


def place_train_state(state: TrainState, mesh) -> TrainState:
    """Shard every worker-stacked leaf along the worker mesh axis (one
    worker per device) and replicate the step counter. A 1-device mesh
    (W=1: no worker axis in the state) commits everything to that
    device instead."""
    if mesh.devices.size == 1:
        return jax.device_put(state, mesh.devices.ravel()[0])
    ws = NamedSharding(mesh, P(LM_WORKER_AXIS))
    rep = NamedSharding(mesh, P())
    put = lambda t: tmap(lambda x: jax.device_put(x, ws), t)
    return TrainState(put(state.params), put(state.opt_state),
                      put(state.vr_state), jax.device_put(state.step, rep))


def init_train_state(cfg: ModelConfig, tcfg: TrainConfig, key, W: int
                     ) -> TrainState:
    """Concrete init (small models / examples). Workers start identical."""
    params = model.init_params(cfg, key)
    opt = optimizers.make(tcfg.optimizer, tcfg.learning_rate,
                          tcfg.weight_decay)
    opt_state = opt.init(params)
    vr_state = (vr_wrapper.init_vr(tcfg.vr, params, tcfg.vr_table_size)
                if tcfg.vr != "none" else ())
    state = TrainState(params, opt_state, vr_state, jnp.zeros((), jnp.int32))
    if W > 1:
        def rep(x):
            return jnp.broadcast_to(x[None], (W,) + x.shape)
        state = TrainState(tmap(rep, params), tmap(rep, opt_state),
                           tmap(rep, vr_state) if vr_state != () else (),
                           state.step)
    return state


def eval_shape_train_state(cfg: ModelConfig, tcfg: TrainConfig, W: int):
    """Abstract TrainState (ShapeDtypeStructs, no allocation) — dry-run."""
    return jax.eval_shape(
        functools.partial(init_train_state, cfg, tcfg, W=W),
        jax.random.PRNGKey(0))


def state_shardings(state_shapes, cfg: ModelConfig, tcfg: TrainConfig, mesh,
                    vr_workers: str):
    mesh = meshlib.gspmd(mesh)
    w_axes = (meshlib.worker_axes(mesh, vr_workers)
              if tcfg.vr != "none" else ())
    spec_tree = specs.tree_specs(state_shapes, cfg,
                                 fsdp=not tcfg.dp_replicated,
                                 worker_axes=w_axes,
                                 axis_sizes=meshlib.mesh_axis_sizes(mesh))
    return tmap(lambda s: NamedSharding(mesh, s), spec_tree)


def batch_sharding(mesh, tcfg: TrainConfig, vr_workers: str, *, with_fe=False):
    mesh = meshlib.gspmd(mesh)
    w_axes = (meshlib.worker_axes(mesh, vr_workers)
              if tcfg.vr != "none" else ())
    data_axes = tuple(a for a in ("pod", "data")
                      if a in mesh.axis_names and a not in w_axes)
    tok = specs.batch_specs(w_axes, data_axes)
    out = {"tokens": NamedSharding(mesh, tok)}
    if with_fe:
        out["fe"] = NamedSharding(mesh, P(*(tuple(tok) + (None,))))
    return out


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, act_sharding=None):
    def serve_step(params, token, cache, pos):
        return model.decode_step(params, cfg, token, cache, pos)

    def serve_prefill(params, tokens, fe=None):
        """Returns LAST-position logits (B, vocab) — the generation
        use-case. Materializing all (B, S, vocab) f32 logits costs 40
        GiB/device at 32k x 152k vocab (§Perf It.4); scoring workloads
        should stream positions instead."""
        batch = {"tokens": tokens}
        if fe is not None:
            batch["frontend_embeds"] = fe
        logits, _ = model.forward(params, cfg, batch, remat="none",
                                  act_sharding=act_sharding)
        return logits[:, -1]

    return serve_step, serve_prefill
