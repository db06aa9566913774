"""Epoch-driven training loop on the device-resident runtime.

Drives whole communication epochs (M*K steps each) through
``step.make_epoch_runner``: one jitted ``lax.scan`` per epoch with donated
state, per-step losses accumulated on device, and the Algorithm-2 worker
average at the epoch boundary. The host touches the run only BETWEEN
epochs — checkpoint, eval, and logging all happen at epoch boundaries, so
per-step host overhead is zero and independent of the worker count (the
paper's linear-scaling requirement, DESIGN.md §3 "LM epoch scan").

``backend="vmap"`` simulates the W workers stacked on one device;
``backend="spmd"`` places one worker per device of a worker mesh. The
seed per-step loop is retained verbatim as ``train/host_loop.py`` (the
pinned reference path).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import jax

from repro.checkpoint import checkpoint as ckpt
from repro.config import ModelConfig, TrainConfig
from repro.data import synthetic
from repro.obs import recorder as obs_recorder
from repro.obs import stage as obs_stage
from repro.train import step as tstep


@dataclass
class LoopResult:
    losses: List[float] = field(default_factory=list)
    steps: int = 0
    epochs: int = 0
    wall_time: float = 0.0
    final_eval_loss: Optional[float] = None
    state: Any = None


def run_training(cfg: ModelConfig, tcfg: TrainConfig, *,
                 epochs: Optional[int] = None, steps: Optional[int] = None,
                 workers: int = 1, backend: str = "vmap", mesh=None,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0, resume: bool = False,
                 log_every: int = 1,
                 log_fn: Callable[[str], None] = print) -> LoopResult:
    """Train for whole communication epochs (cadences count EPOCHS).

    ``steps`` may be given instead of ``epochs`` but must be a multiple of
    M*K — the scan runtime has no mid-epoch host boundary to stop at (use
    ``train.host_loop`` for arbitrary step counts). ``resume=True``
    restarts from ``checkpoint_path``'s latest epoch-boundary save.
    ``result.losses`` holds the per-step losses of the epochs THIS call
    ran (after the resume point, if any).
    """
    E = tcfg.vr_table_size * tcfg.local_epoch
    if epochs is None:
        if steps is None:
            raise ValueError("pass epochs= or steps=")
        if steps % E:
            raise ValueError(
                f"steps={steps} is not a multiple of the communication "
                f"epoch M*K={E}; the epoch-scan runtime drives whole "
                "epochs (train.host_loop runs arbitrary step counts)")
        epochs = steps // E
    run_epoch, meta = tstep.make_epoch_runner(cfg, tcfg, workers,
                                              backend=backend, mesh=mesh)
    W = meta["workers"]

    state = tstep.init_train_state(cfg, tcfg, jax.random.PRNGKey(tcfg.seed),
                                   W)
    start_epoch = 0
    if resume and checkpoint_path:
        saved = ckpt.latest_step(checkpoint_path)
        if saved is not None:
            if saved % E:
                raise ValueError(
                    f"checkpoint at step {saved} is not an epoch boundary "
                    f"(M*K={E}); it was not written by the epoch-scan loop")
            state = ckpt.restore(checkpoint_path, like=state)
            start_epoch = saved // E
            if start_epoch >= epochs:
                raise ValueError(
                    f"checkpoint is already at epoch {start_epoch} "
                    f"(step {saved}); nothing left of the requested "
                    f"{epochs} epoch(s) to train — raise epochs/steps "
                    "(continuing would relabel the checkpoint with an "
                    "earlier step)")
    if backend == "spmd":
        state = tstep.place_train_state(state, meta["mesh"])

    result = LoopResult()
    rec = obs_recorder.active()
    t0 = time.time()
    device_losses = []
    for e in range(start_epoch, epochs):
        if rec is None:
            state, losses = run_epoch(state)
        elif e == start_epoch:
            # first epoch staged (an execute span, with a compile row for
            # each compile inside it; the spmd wrapper has no .lower, so
            # its compiled module is not read)
            state, losses = obs_stage.staged_call(run_epoch, state,
                                                  _label="train/epoch")
        else:
            # blocked on inside the span so the duration is epoch work,
            # not async dispatch — telemetry-off keeps the pipelined loop
            with rec.span("train/epoch", epoch=e):
                state, losses = jax.block_until_ready(run_epoch(state))
        device_losses.append(losses)
        if log_every and ((e - start_epoch) % log_every == 0
                          or e == epochs - 1):
            loss = float(losses[-1])
            if rec is not None:
                rec.event("train_epoch", epoch=e, step=(e + 1) * E,
                          loss=loss, workers=W)
            log_fn(f"epoch {e:4d}  step {(e + 1) * E:6d}  "
                   f"loss {loss:.4f}")
        if checkpoint_path and checkpoint_every and \
                (e + 1) % checkpoint_every == 0:
            with obs_recorder.span("train/checkpoint", epoch=e):
                ckpt.save(checkpoint_path, state, step=(e + 1) * E)
    result.losses = [float(l) for arr in jax.device_get(device_losses)
                     for l in arr]
    result.steps = epochs * E
    result.epochs = epochs
    result.wall_time = time.time() - t0
    result.state = state

    # held-out eval on the worker-averaged params (at an epoch boundary
    # the copies coincide, so the average IS every worker's iterate —
    # eval_params keeps that invariant explicit)
    from repro.models import model as modellib
    with obs_recorder.span("train/eval"):
        ev = synthetic.eval_batch(cfg, tcfg.seed, batch=meta["microbatch"],
                                  seq=tcfg.seq_len)
        params = tstep.eval_params(state.params, W)
        result.final_eval_loss = float(modellib.loss_fn(
            params, cfg, {"tokens": ev}, remat="none"))
    if rec is not None:
        rec.event("train_done", epochs=epochs, steps=epochs * E,
                  eval_loss=result.final_eval_loss,
                  wall_s=result.wall_time)
    if checkpoint_path:
        ckpt.save(checkpoint_path, state, step=epochs * E)
    return result
