"""LM train-runtime throughput: the epoch-scan runtime vs the per-step
host-loop reference, per backend, on a reduced arch (DESIGN.md §3 "LM
epoch scan").

For each worker count W we measure warm wall clock of ONE communication
epoch (M*K steps) four ways:

  * ``host``        — the retained per-step reference exactly as the seed
    ``run_training`` executed it (``train/host_loop.py``): every
    invocation builds a fresh step closure and jits it (re-traced PER
    INVOCATION — the same semantics ``benchmarks/driver_throughput.py``
    measures for the convex host loop), then dispatches one step per
    iteration with batches built pairwise on the host;
  * ``host-steady`` — the same per-step loop with the jitted step hoisted
    out and reused: isolates the steady-state dispatch + host-feed
    overhead from the per-invocation retrace;
  * ``scan-vmap``   — ``step.make_epoch_runner`` with the W workers
    stacked on one device, batches generated on device inside the scan
    (warm calls hit the jit cache: one executable per config, ever);
  * ``scan-spmd``   — the same epoch scan under shard_map with one
    worker per (CPU-simulated) device;
  * ``scan-vmap-fused`` — the vmap epoch scan with ``fused=True``: model
    forward through the Pallas rmsnorm/flash-attention kernels and the
    VR correction + SGD update through the single-launch ``vr_update``
    kernel. Fused rows carry ``fused``/``interpret`` flags and
    ``speedup_vs_unfused`` (warm scan-vmap / warm fused) for the
    ``check_regression`` fused gate; on CPU the kernels run in interpret
    mode, so those rows are gate-exempt (and excluded from the legacy
    scan-vs-host gate, which pins the unfused runtime).

Writes ``BENCH_train.json`` at the repo root (the acceptance artifact:
warm epoch-scan steps/sec >= 3x the host-loop path at W=4) plus the
standard results CSV.  Must start in a fresh process: it forces 4
simulated host devices before the first jax operation so the spmd rows
run under a real multi-device platform (same rule as
``benchmarks/spmd_scaling.py``).

    PYTHONPATH=src python -m benchmarks.train_throughput [--quick]
"""
from __future__ import annotations

import json
import os

import numpy as np

try:
    import repro_bootstrap  # noqa: F401  (repo-root module/script form)
except ModuleNotFoundError:
    pass  # installed form: repro resolves without the fallback

ROOT = os.path.join(os.path.dirname(__file__), "..")

WORKER_COUNTS = (1, 2, 4)


def _chained(run_epoch, state0):
    """Per-call closure that threads the state through (the scan runtime
    DONATES its input state, so a fixed state cannot be replayed)."""
    box = {"state": state0}

    def call():
        state, losses = run_epoch(box["state"])
        box["state"] = state
        return losses

    return call


def _host_epoch(cfg, tcfg, W, E, jit_step, box):
    from repro.train import host_loop

    accum, mb = _geometry(cfg, tcfg, W)
    state = box["state"]
    for _ in range(E):
        toks = host_loop._epoch_batch_host(
            cfg, tcfg.seed, box["step"], workers=W, accum=accum,
            microbatch=mb, seq=tcfg.seq_len,
            table_size=tcfg.vr_table_size)
        if W == 1:
            toks = toks[0]
        state, m = jit_step(state, toks)
        box["step"] += 1
    box["state"] = state
    return m["loss"]


def _geometry(cfg, tcfg, W):
    from repro.train import step as tstep

    return tstep.batch_geometry(tcfg, W)


def _make_step(cfg, tcfg, W):
    # single-device mesh: the host path is the seed reference execution
    # model (stacked workers on one device), not an FSDP configuration
    from repro.launch import mesh as meshlib
    from repro.train import step as tstep

    train_step, _ = tstep.make_train_step(cfg, tcfg,
                                          meshlib.make_test_mesh(devices=1),
                                          "none", workers=W)
    return train_step


def _host_caller(cfg, tcfg, W, E):
    """Seed semantics: each invocation builds and jits a FRESH step
    closure, exactly like the seed ``run_training`` did — the re-trace
    is part of the execution model being replaced (the convex
    ``driver_throughput`` measures its host loop the same way)."""
    import jax

    from repro.train import step as tstep

    box = {"state": tstep.init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                           W),
           "step": 0}

    def call():
        jit_step = jax.jit(_make_step(cfg, tcfg, W))
        return _host_epoch(cfg, tcfg, W, E, jit_step, box)

    return call


def _host_steady_caller(cfg, tcfg, W, E):
    """The same per-step loop with the jitted step hoisted and reused:
    what remains is per-step dispatch + host-built batches."""
    import jax

    from repro.train import step as tstep

    jit_step = jax.jit(_make_step(cfg, tcfg, W))
    box = {"state": tstep.init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                           W),
           "step": 0}

    def call():
        return _host_epoch(cfg, tcfg, W, E, jit_step, box)

    return call


def run(quick: bool = False):
    from repro.core import spmd

    spmd.force_host_devices(max(WORKER_COUNTS))
    import jax

    from benchmarks.common import emit, timed_cold_warm
    from repro.config import TrainConfig, get_arch
    from repro.train import step as tstep

    cfg = get_arch("qwen2-7b").reduced()
    M = 2 if quick else 4
    tcfg = TrainConfig(seq_len=32, global_batch=8, microbatch=2,
                       optimizer="sgd", learning_rate=0.1, vr="centralvr",
                       vr_table_size=M, local_epoch=1)
    E = M * tcfg.local_epoch
    repeat = 2 if quick else 3
    rows = []
    warm_by = {}

    for W in WORKER_COUNTS:
        paths = {"host": _host_caller(cfg, tcfg, W, E),
                 "host-steady": _host_steady_caller(cfg, tcfg, W, E)}
        for backend in ("vmap", "spmd"):
            run_epoch, meta = tstep.make_epoch_runner(cfg, tcfg, W,
                                                      backend=backend)
            state = tstep.init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                           W)
            if backend == "spmd":
                state = tstep.place_train_state(state, meta["mesh"])
            paths[f"scan-{backend}"] = _chained(run_epoch, state)
        frun, fmeta = tstep.make_epoch_runner(cfg, tcfg, W, backend="vmap",
                                              fused=True)
        fstate = tstep.init_train_state(cfg, tcfg, jax.random.PRNGKey(0), W)
        paths["scan-vmap-fused"] = _chained(frun, fstate)
        for name, fn in paths.items():
            cold, warm, losses = timed_cold_warm(fn, repeat=repeat)
            warm_by[(name, W)] = warm
            # provenance row (same role as RunResult.provenance() in the
            # solver-driven artifacts): the resolved configuration that
            # produced this measurement + the last timed epoch's loss tail
            loss_tail = np.atleast_1d(np.asarray(losses, dtype=float))
            fused = name == "scan-vmap-fused"
            rows.append({
                "name": f"train_throughput/{name}-w{W}",
                "path": name,
                "workers": W,
                **({"fused": True, "interpret": fmeta["interpret"]}
                   if fused else {}),
                "us_per_call": warm * 1e6,
                "cold_s": cold,
                "warm_s": warm,
                "compile_s": max(cold - warm, 0.0),
                "steps_per_s": E / warm,
                "provenance": {
                    "spec": {"arch": cfg.name, "seq_len": tcfg.seq_len,
                             "global_batch": tcfg.global_batch,
                             "vr": tcfg.vr, "table_size": M,
                             "steps_per_epoch": E, "path": name,
                             "workers": W, "quick": quick},
                    "loss_tail": [float(v) for v in loss_tail[-8:]],
                },
                "derived": f"cold={cold:.3f}s,warm={warm:.3f}s,"
                           f"steps/s={E / warm:.1f}",
            })

    for r in rows:
        host = warm_by[("host", r["workers"])]
        r["speedup_vs_host"] = host / r["warm_s"]
        r["derived"] += f",vs_host={r['speedup_vs_host']:.1f}x"
        if r.get("fused"):
            unfused = warm_by[("scan-vmap", r["workers"])]
            r["unfused_warm_s"] = unfused
            r["speedup_vs_unfused"] = unfused / r["warm_s"]
            r["derived"] += (f",vs_unfused={r['speedup_vs_unfused']:.2f}x,"
                             f"interpret={r['interpret']}")
    scan_3x = warm_by[("host", 4)] / warm_by[("scan-vmap", 4)] >= 3.0

    payload = {
        "config": {"arch": cfg.name, "seq_len": tcfg.seq_len,
                   "global_batch": tcfg.global_batch,
                   "vr": tcfg.vr, "table_size": M,
                   "steps_per_epoch": E, "workers": list(WORKER_COUNTS),
                   "paths": ["host", "host-steady", "scan-vmap",
                             "scan-spmd", "scan-vmap-fused"],
                   "quick": quick, "device_count": jax.device_count(),
                   "backend_platform": jax.default_backend()},
        "rows": rows,
        "scan_3x_host_at_w4": scan_3x,
    }
    with open(os.path.join(ROOT, "BENCH_train.json"), "w") as f:
        json.dump(payload, f, indent=1)
    emit(rows, "train_throughput")
    print(f"scan_3x_host_at_w4={'yes' if scan_3x else 'no'}")
    return payload


if __name__ == "__main__":
    import sys
    run(quick="--quick" in sys.argv)
