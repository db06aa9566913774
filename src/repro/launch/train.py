"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --steps 96 --vr centralvr --num-workers 4 --backend spmd

Default runtime is the epoch-scan loop (``train/loop.py``, DESIGN.md §3
"LM epoch scan"): whole communication epochs as one jitted scan, with
``--backend vmap`` (W stacked workers on one device) or ``--backend spmd``
(one worker per device of a worker mesh; on CPU the devices are simulated,
forced before jax initializes). ``--runtime host`` selects the retained
per-step reference loop (``train/host_loop.py``), which also serves the
production meshes via --mesh.
"""
from __future__ import annotations

import argparse


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the CPU-smoke reduced variant")
    ap.add_argument("--steps", type=int, default=48,
                    help="scan runtime: must be a multiple of M*K")
    ap.add_argument("--epochs", type=int, default=0,
                    help="communication epochs (overrides --steps)")
    ap.add_argument("--runtime", default="scan", choices=["scan", "host"],
                    help="epoch-scan runtime vs per-step reference loop")
    ap.add_argument("--backend", default="vmap", choices=["vmap", "spmd"],
                    help="scan runtime: simulated worker stack vs one "
                         "worker per mesh device")
    ap.add_argument("--num-workers", type=int, default=1,
                    help="CentralVR worker count for the scan runtime")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--vr", default="centralvr",
                    choices=["none", "centralvr", "svrg", "saga"])
    ap.add_argument("--vr-table-size", type=int, default=8)
    ap.add_argument("--local-epoch", type=int, default=1)
    ap.add_argument("--workers", default="none",
                    choices=["none", "data", "pod"],
                    help="host runtime: which mesh axes carry worker copies")
    ap.add_argument("--dp-replicated", action="store_true")
    ap.add_argument("--mesh", default="test", choices=["test", "production",
                                                       "production-multipod"])
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="scan runtime: epochs; host runtime: steps")
    ap.add_argument("--resume", action="store_true",
                    help="scan runtime: continue from --checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs", default="", metavar="PATH",
                    help="record structured run telemetry (epoch spans + "
                         "structured epoch rows) to this JSONL file")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="capture a jax.profiler trace of the run into "
                         "this directory")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.runtime == "scan" and args.backend == "spmd":
        # must run before the first jax operation (core/spmd.py)
        from repro.core import spmd
        spmd.force_host_devices(args.num_workers)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro import obs
    from repro.config import TrainConfig, get_arch
    from repro.launch import mesh as meshlib

    if args.obs:
        obs.enable(args.obs)
    if args.profile:
        import jax
        jax.profiler.start_trace(args.profile)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        microbatch=args.microbatch, learning_rate=args.lr,
        optimizer=args.optimizer, vr=args.vr,
        vr_table_size=args.vr_table_size, local_epoch=args.local_epoch,
        dp_replicated=args.dp_replicated, seed=args.seed)

    if args.runtime == "host":
        if args.backend != "vmap":
            raise SystemExit("--runtime host is vmap-only; the spmd "
                             "backend lives in the epoch-scan runtime")
        if args.resume:
            raise SystemExit("--resume is an epoch-scan-runtime feature "
                             "(the host reference loop restarts from step "
                             "0 and would overwrite the checkpoint)")
        from repro.train import host_loop
        if args.mesh == "production":
            mesh = meshlib.make_production_mesh()
        elif args.mesh == "production-multipod":
            mesh = meshlib.make_production_mesh(multi_pod=True)
        else:
            mesh = meshlib.make_test_mesh()
        res = host_loop.run_training(
            cfg, tcfg, steps=args.steps, mesh=mesh,
            vr_workers=args.workers,
            workers=args.num_workers if args.num_workers > 1 else None,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpoint_every)
    else:
        if args.mesh != "test" or args.workers != "none":
            raise SystemExit(
                "--mesh production*/--workers data|pod drive the mesh-"
                "derived worker layout of the per-step reference loop; "
                "pass --runtime host for them (the scan runtime takes "
                "--num-workers and --backend instead)")
        from repro.train import loop
        mesh = (meshlib.make_worker_mesh(args.num_workers)
                if args.backend == "spmd" else None)
        res = loop.run_training(
            cfg, tcfg, epochs=args.epochs or None,
            steps=None if args.epochs else args.steps,
            workers=args.num_workers, backend=args.backend, mesh=mesh,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpoint_every, resume=args.resume)
    if args.profile:
        import jax
        jax.profiler.stop_trace()
        print(f"wrote profiler trace to {args.profile}")
    if args.obs:
        obs.disable()
        print(f"wrote telemetry to {args.obs}")
    print(f"done: {res.steps} steps in {res.wall_time:.1f}s; "
          f"final train loss {res.losses[-1]:.4f}; "
          f"eval loss {res.final_eval_loss:.4f}")


if __name__ == "__main__":
    main()
