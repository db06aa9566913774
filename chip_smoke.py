#!/usr/bin/env python3
"""On-chip smoke of the main path, through the entry points a user calls.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: spmd vs vmap, nothing else

One chip:
  (a) convex: the paper's solvers on IJCNN1 (35,000 x 22 logistic) through
      ``repro.solve``, each with ``fused="auto"`` (native Pallas kernel)
      and ``fused=False`` (unfused oracle);
  (b) kernels at the real widths of qwen2-7b against their ``ref.py``
      oracles: rmsnorm, flash attention, and a 2**24-element vr_update;
  (c) LM trainer: ``run_training`` on the reduced qwen2-7b with CentralVR;
  (d) serving: ``serve_trace`` on the same config, paged vs dense cache.

Four chips: CentralVR-Sync/Async under ``backend="spmd"`` (one worker per
chip) against ``backend="vmap"``, and one LM epoch at W=4 likewise.

It runs in one process and stops at the first failed check. Lines before
the last are a smoke, not benchmark numbers: compile_s sums XLA compile
(or persistent-cache load) time inside a phase, run_s is the rest of the
phase's wall time. The last line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It refuses to run, with no such line and a nonzero exit, off a TPU.

Bounds and tolerances, all stated before the first chip run:
  * final rel-grad-norm bounds are twice the final value of the same spec
    run unfused on the CPU in float32 (IJCNN1, 5 rounds, seed 0):
    centralvr 2.908e-4, centralvr_sync 4.377e-4, centralvr_async 5.581e-3,
    dsaga 2.013e-1;
  * fused vs unfused: every round's rel-grad-norm within a relative 5e-3;
    the same comparison in float32 on the CPU at this size differs by at
    most 4.2e-4 (centralvr_sync). The kernel computes s_new*a - s_old*a
    where the oracle computes (s_new - s_old)*a, so they agree to float
    tolerance, not bitwise. Phase (a) runs under
    ``jax.default_matmul_precision("highest")``: the chip's default f32
    matmul is a single bf16 pass, which would put ~1e-3 noise in the
    35,000-row gradient behind the metric;
  * kernels: the tolerances of ``tests/test_kernels.py`` (bf16: 2e-2
    absolute and relative; vr_update f32: 1e-6);
  * spmd vs vmap: 3e-5 on the rel-grad-norm trajectories, the float32
    tolerance of ``tests/test_spmd_backend.py``; the LM losses within
    5e-2, the bf16 tolerance of ``tests/test_mesh_semantics.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ALGOS = (
    ("centralvr", dict(p=1), 2 * 2.908e-4),
    ("centralvr_sync", dict(p=4), 2 * 4.377e-4),
    ("centralvr_async", dict(p=4, speeds=(1, 1, 2, 4)), 2 * 5.581e-3),
    ("dsaga", dict(p=4, fetch="stale"), 2 * 2.013e-1),
)
ROUNDS = 5
FUSED_RTOL = 5e-3
SPMD_TOL = 3e-5
LM_TOL = 5e-2
LM_KERNELS = "fused=False (the run_training default: pure JAX)"


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums XLA compile (or persistent-cache load) seconds and counts
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@contextlib.contextmanager
def phase(clock: CompileClock, name: str, kernels: str):
    import jax
    c0, h0, t0 = clock.seconds, clock.hits, time.perf_counter()
    print(f"[{name}] start device={jax.devices()[0].device_kind} {kernels}",
          flush=True)
    yield
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    print(f"[{name}] done compile_s={comp:.3f} run_s={wall - comp:.3f} "
          f"cache_hits={clock.hits - h0}", flush=True)


def _rels(spec, cfg):
    import numpy as np

    import repro
    res = repro.solve(spec, cfg)
    rels = np.asarray(res.rels, dtype=np.float64)
    check(rels.shape == (ROUNDS,) and bool(np.isfinite(rels).all()),
          f"{spec.algo}/{spec.backend}/fused={spec.fused}: rels {rels}")
    return res, rels


def phase_convex(clock):
    import jax
    import numpy as np

    import repro
    from repro import kernels
    from repro.configs.paper_convex import IJCNN1_LIKE

    resolved = kernels.resolve_fused("auto")
    print(f"[a] resolve_fused('auto') -> (fused, interpret) = {resolved}")
    check(resolved == (True, False), "fused='auto' must resolve to native "
          f"Mosaic (True, False), got {resolved}")
    with jax.default_matmul_precision("highest"):
        for algo, kw, bound in ALGOS:
            with phase(clock, f"a:{algo}",
                       f"fused='auto' -> {resolved} and fused=False"):
                rels = {}
                for fused in ("auto", False):
                    spec = repro.RunSpec(algo=algo, rounds=ROUNDS,
                                         backend="vmap", fused=fused, **kw)
                    _, rels[fused] = _rels(spec, IJCNN1_LIKE)
                    print(f"[a] {algo} p={spec.p} fused={fused!r} rels="
                          f"{[float(r) for r in rels[fused]]}")
                    check(rels[fused][-1] < bound,
                          f"{algo} fused={fused!r}: final rel "
                          f"{rels[fused][-1]:.4e} >= bound {bound:.4e}")
                drift = float(np.max(np.abs(rels["auto"] - rels[False])
                                     / rels[False]))
                print(f"[a] {algo} final_rel={rels['auto'][-1]:.6e} "
                      f"bound={bound:.4e} fused_vs_unfused_max_rel_diff="
                      f"{drift:.3e} tol={FUSED_RTOL}")
                check(drift <= FUSED_RTOL,
                      f"{algo}: fused vs unfused differ by {drift:.3e}")


def _allclose(name, got, want, tol):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want) - tol * np.abs(want)))
    print(f"[b] {name} shape={got.shape} max_abs_err="
          f"{float(np.max(np.abs(got - want))):.3e} tol={tol} "
          f"(atol and rtol)")
    check(err <= tol and bool(np.isfinite(got).all()),
          f"{name}: kernel disagrees with its oracle")


def phase_kernels(clock):
    import functools

    import jax
    import jax.numpy as jnp

    from repro.config import get_arch
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.rmsnorm import ops as rn_ops
    from repro.kernels.rmsnorm import ref as rn_ref
    from repro.kernels.vr_update import kernel as vr_kernel
    from repro.kernels.vr_update import ref as vr_ref

    cfg = get_arch("qwen2-7b")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    native = "interpret=False (native Mosaic)"
    with phase(clock, "b:rmsnorm", native):
        x = jax.random.normal(ks[0], (4096, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        s = jax.random.normal(ks[1], (cfg.d_model,), jnp.float32)
        y = rn_ops.rmsnorm(x, s, interpret=False)
        _allclose("rmsnorm bf16", y, rn_ref.rmsnorm_ref(x, s), 2e-2)

    with phase(clock, "b:flash_attention", native):
        S, hd = 4096, cfg.head_dim
        q = jax.random.normal(ks[2], (1, S, cfg.num_heads, hd),
                              jnp.float32).astype(jnp.bfloat16)
        k = jax.random.normal(ks[3], (1, S, cfg.num_kv_heads, hd),
                              jnp.float32).astype(jnp.bfloat16)
        v = jax.random.normal(ks[4], (1, S, cfg.num_kv_heads, hd),
                              jnp.float32).astype(jnp.bfloat16)
        out = fa_ops.flash_attention(q, k, v, interpret=False)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(fa_ref.flash_attention_ref)(
                *(t.astype(jnp.float32) for t in (q, k, v)))
        _allclose(f"flash_attention bf16 B=1 S={S} H={cfg.num_heads} "
                  f"KV={cfg.num_kv_heads} hd={hd}", out, ref, 2e-2)

    with phase(clock, "b:vr_update", native):
        n = 1 << 24
        bufs = [jax.random.normal(kk, (n,), jnp.float32) for kk in ks[3:8]]
        kw = dict(eta=0.05, m=8, saga=True, decay=2e-4)
        got = jax.jit(functools.partial(vr_kernel.vr_update_flat,
                                        interpret=False, **kw))(*bufs)
        want = jax.jit(functools.partial(vr_ref.vr_update_ref, **kw))(*bufs)
        for name, g, w in zip(("x", "table", "gtilde", "gbar"), got, want):
            _allclose(f"vr_update f32 n=2**24 {name}", g, w, 1e-6)


def _lm_config():
    from repro.config import TrainConfig, get_arch
    cfg = get_arch("qwen2-7b").reduced()
    tcfg = TrainConfig(seq_len=128, global_batch=8, optimizer="sgd",
                       learning_rate=0.1, vr="centralvr", vr_table_size=2)
    return cfg, tcfg


def phase_train(clock):
    import numpy as np

    from repro.train.loop import run_training

    cfg, tcfg = _lm_config()
    with phase(clock, "c:train", LM_KERNELS):
        res = run_training(cfg, tcfg, epochs=2, log_fn=lambda s: None)
        losses = np.asarray(res.losses)
        E = tcfg.vr_table_size * tcfg.local_epoch
        first, last = losses[:E].mean(), losses[-E:].mean()
        print(f"[c] {cfg.name} vr={tcfg.vr} optimizer={tcfg.optimizer} "
              f"M={tcfg.vr_table_size} losses={losses.tolist()} "
              f"epoch_means={float(first):.6f}->{float(last):.6f} "
              f"eval={res.final_eval_loss:.6f}")
        check(bool(np.isfinite(losses).all()), "LM losses are not finite")
        check(last < first, "LM loss did not fall from epoch 1 to epoch 2")


def phase_serve(clock):
    from repro.serve import serve_trace, synthetic_trace

    cfg, _ = _lm_config()
    trace = synthetic_trace(4, prompt_len=32, max_new=16, seed=0)
    ids = {}
    with phase(clock, "d:serve", "fused=False (the engine has no Pallas "
               "path)"):
        for kv in ("paged", "dense"):
            rep = serve_trace(cfg, trace, width=2, kv_cache=kv, seed=0)
            ids[kv] = {r.rid: list(r.tokens) for r in rep.results}
            print(f"[d] kv_cache={kv} requests={len(rep.results)} "
                  f"decode_tokens={rep.decode_tokens} ids={ids[kv]}")
        check(len(ids["paged"]) == 4 and all(ids["paged"].values()),
              "serve returned no tokens")
        check(ids["paged"] == ids["dense"],
              "paged greedy ids differ from the dense oracle")
        print("[d] paged ids == dense ids")


def _devices_of(arr):
    return {s.device for s in arr.addressable_shards}


def phase_four_chips(clock):
    import jax
    import numpy as np

    import repro
    from repro.configs.paper_convex import IJCNN1_LIKE
    from repro.train.loop import run_training

    with jax.default_matmul_precision("highest"):
        for algo, kw, bound in ALGOS[1:3]:
            with phase(clock, f"spmd:{algo}", "fused='auto'"):
                got = {}
                for backend in ("vmap", "spmd"):
                    spec = repro.RunSpec(algo=algo, rounds=ROUNDS,
                                         backend=backend, fused="auto", **kw)
                    res, got[backend] = _rels(spec, IJCNN1_LIKE)
                    print(f"[spmd] {algo} backend={backend} rels="
                          f"{[float(r) for r in got[backend]]}")
                devs = _devices_of(res.state.tables)
                diff = float(np.abs(got["spmd"] - got["vmap"]).max())
                print(f"[spmd] {algo} spmd_vs_vmap_max_abs_diff={diff:.3e} "
                      f"tol={SPMD_TOL} table_shard_devices="
                      f"{sorted(str(d) for d in devs)}")
                check(diff <= SPMD_TOL, f"{algo}: spmd differs from vmap")
                check(got["spmd"][-1] < bound, f"{algo}: spmd final rel "
                      f"{got['spmd'][-1]:.4e} >= bound {bound:.4e}")
                check(len(devs) == 4
                      and all(d.platform == "tpu" for d in devs),
                      f"{algo}: table shards on {devs}, want 4 TPU devices")

    cfg, tcfg = _lm_config()
    with phase(clock, "spmd:train", LM_KERNELS):
        runs = {b: run_training(cfg, tcfg, epochs=1, workers=4, backend=b,
                                log_fn=lambda s: None)
                for b in ("vmap", "spmd")}
        diff = float(np.abs(np.asarray(runs["spmd"].losses)
                            - np.asarray(runs["vmap"].losses)).max())
        leaf = jax.tree_util.tree_leaves(runs["spmd"].state.params)[0]
        devs = _devices_of(leaf)
        print(f"[spmd] lm W=4 losses vmap={runs['vmap'].losses} "
              f"spmd={runs['spmd'].losses} max_abs_diff={diff:.3e} "
              f"tol={LM_TOL} param_shard_devices="
              f"{sorted(str(d) for d in devs)}")
        check(diff <= LM_TOL, "LM spmd losses differ from vmap")
        check(len(devs) == 4, f"LM worker params on {devs}, want 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(d); 4: the spmd-vs-vmap phase only")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"smoke (not benchmark numbers): jax {jax.__version__} "
          f"device={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(clock)
    else:
        phase_convex(clock)
        phase_kernels(clock)
        phase_train(clock)
        phase_serve(clock)
    print(f"total compile_s={clock.seconds:.3f} cache_hits={clock.hits} "
          f"wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
