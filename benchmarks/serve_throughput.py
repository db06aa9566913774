"""Serving throughput: the continuous-batching engine (paged KV cache +
chunked prefill, ``repro/serve``) vs the legacy static-batch per-token
host loop it replaces, on a reduced arch.

Row families (each engine gate row is TWINNED with a host-loop row at the
exact same workload, so the regression gate compares measured-vs-measured
rather than measured-vs-remembered):

  * ``host-loop-w4`` / ``engine-paged-w4`` — the decode gate pair: a
    staggered-arrival trace with varied max_new (the workload static
    batching pads to the group max on while the engine retires/admits
    between steps).  The engine row carries ``decode_speedup_vs_host``
    (gate floor: 1.0 — the new runtime must not decode slower than the
    loop it replaces, even on CPU).
  * ``engine-dense-w4`` — the pure-JAX dense-cache oracle at the same
    workload, informational (its greedy ids are bit-identical to paged;
    tests/test_serve.py enforces that, this row just shows the cost).
  * ``host-loop-prefill128`` / ``engine-prefill128`` — the prefill gate
    pair at prompt-len 128: one 128-token chunked launch vs 128 per-token
    launches.  Engine row carries ``prefill_speedup_vs_host`` (gate
    floor: 5.0).
  * ``engine-*-w{2,8}`` — width / arrival-pattern sweep, informational
    (p50/p95 latency under burst vs poisson arrivals).
  * ``engine-tp2`` — tensor-parallel decode over 2 simulated host
    devices, ``estimated: true`` (CPU-simulated TP measures the plumbing,
    not real-accelerator scaling — informational, same convention as the
    interpret-mode fused rows).

Every row records ``cold_s`` (warmup compile) vs ``warm_s`` (steady run
wall) — the persistent cache (``launch/compile_cache.py``) is on, so
cold_s shrinks to deserialization time on the second process launch.

Writes ``BENCH_serve.json`` at the repo root plus the standard results
CSV.  Must start in a fresh process: it forces 2 simulated host devices
for the TP row before jax initializes (same rule as
``benchmarks/train_throughput.py``).

    PYTHONPATH=src python -m benchmarks.serve_throughput [--quick]
"""
from __future__ import annotations

import json
import os

try:
    import repro_bootstrap  # noqa: F401  (repo-root module/script form)
except ModuleNotFoundError:
    pass  # installed form: repro resolves without the fallback

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _row(name, path, rep, workload, cold_s, **extra):
    s = rep.summary()
    return {
        "name": f"serve_throughput/{name}",
        "path": path,
        "decode_tok_s": s["decode_tok_s"],
        "prefill_tok_s": s["prefill_tok_s"],
        "latency_p50_s": s["latency_p50_s"],
        "latency_p95_s": s["latency_p95_s"],
        "cold_s": cold_s,
        "warm_s": s["wall_s"],
        "us_per_call": s["wall_s"] * 1e6,
        **extra,
        "provenance": {
            "spec": workload,
            "steps": s["steps"],
            "decode_tokens": s["decode_tokens"],
            "prefill_tokens": s["prefill_tokens"],
            "blocks_reused": s["blocks_reused"],
        },
        "derived": f"decode={s['decode_tok_s']:.0f}tok/s,"
                   f"prefill={s['prefill_tok_s']:.0f}tok/s,"
                   f"p95={s['latency_p95_s'] * 1e3:.1f}ms,"
                   f"cold={cold_s:.2f}s",
    }


def _best(fn, repeat):
    """Best-of-N by decode tok/s (serving wall clocks are noisy on shared
    CI hosts; both twins get the same treatment)."""
    reps = [fn() for _ in range(repeat)]
    return max(reps, key=lambda r: r.decode_tok_s)


def run(quick: bool = False):
    from repro.core import spmd

    spmd.force_host_devices(2)            # for the TP row
    import jax

    from benchmarks.common import emit
    from repro.config import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_test_mesh
    from repro.models import model
    from repro.serve import ServeEngine, run_host_loop, synthetic_trace

    cache_dir = enable_compile_cache()
    cfg = get_arch("qwen2-7b").reduced()
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    repeat = 2
    rows = []

    # ---- decode gate pair: staggered arrivals, varied max_new ------------
    n, max_new, prompt, width = (8, 16, 32, 4) if quick else (16, 32, 32, 4)
    trace = synthetic_trace(n, pattern="uniform", prompt_len=prompt,
                            max_new=max_new, gap=2, vary_new=True, seed=0)
    wl = {"arch": cfg.name, "requests": n, "prompt_len": prompt,
          "max_new": max_new, "vary_new": True, "pattern": "uniform",
          "width": width, "quick": quick}
    host_rep = _best(lambda: run_host_loop(cfg, trace, params=params,
                                           width=width), repeat)
    rows.append(_row("host-loop-w4", "host-loop", host_rep, wl,
                     sum(host_rep.compile_s.values())))

    def engine_rep(kv_cache, w=width, tr=trace, mesh=None, buckets=(32,),
                   max_len=prompt + max_new):
        eng = ServeEngine(cfg, params, width=w, block_size=16,
                          max_seq_len=max_len, kv_cache=kv_cache,
                          chunk_buckets=buckets, mesh=mesh)
        eng.warmup()
        rep = _best(lambda: eng.run(tr), repeat)
        return rep, sum(eng.compile_s.values())

    rep, cold = engine_rep("paged")
    rows.append(_row(
        "engine-paged-w4", "engine-paged", rep, wl, cold,
        decode_speedup_vs_host=rep.decode_tok_s / host_rep.decode_tok_s))
    # the dense oracle and the sweep rows below are informational: they
    # carry no *_speedup_vs_host key, so the gate never sees them
    rep, cold = engine_rep("dense")
    rows.append(_row("engine-dense-w4", "engine-dense", rep, wl, cold))

    # ---- prefill gate pair: prompt-len 128, one chunk vs 128 steps -------
    np_, pw = (2, 2) if quick else (4, 4)
    trace128 = synthetic_trace(np_, pattern="burst", prompt_len=128,
                               max_new=2, seed=1)
    wl128 = {"arch": cfg.name, "requests": np_, "prompt_len": 128,
             "max_new": 2, "pattern": "burst", "width": pw, "quick": quick}
    host128 = _best(lambda: run_host_loop(cfg, trace128, params=params,
                                          width=pw), repeat)
    rows.append(_row("host-loop-prefill128", "host-loop", host128, wl128,
                     sum(host128.compile_s.values())))
    rep, cold = engine_rep("paged", w=pw, tr=trace128, buckets=(128,),
                           max_len=130)
    rows.append(_row(
        "engine-prefill128", "engine-paged", rep, wl128, cold,
        prefill_speedup_vs_host=rep.prefill_tok_s / host128.prefill_tok_s))

    # ---- width / arrival-pattern sweep (informational) -------------------
    for w, pattern in ((2, "poisson"), (8, "burst")):
        tr = synthetic_trace(n, pattern=pattern, prompt_len=prompt,
                             max_new=max_new, gap=2, vary_new=True, seed=2)
        rep, cold = engine_rep("paged", w=w, tr=tr)
        rows.append(_row(f"engine-{pattern}-w{w}", "engine-paged", rep,
                         {**wl, "pattern": pattern, "width": w}, cold))

    # ---- tensor-parallel decode over 2 simulated devices -----------------
    mesh = make_test_mesh(model_axis=2)
    rep, cold = engine_rep("paged", mesh=mesh)
    rows.append(_row(
        "engine-tp2", "engine-tp", rep, {**wl, "tp": 2}, cold,
        estimated=True,
        decode_speedup_vs_host=rep.decode_tok_s / host_rep.decode_tok_s))

    payload = {
        "config": {"arch": cfg.name, "quick": quick,
                   "device_count": jax.device_count(),
                   "backend_platform": jax.default_backend(),
                   "compile_cache": cache_dir},
        "rows": rows,
    }
    with open(os.path.join(ROOT, "BENCH_serve.json"), "w") as f:
        json.dump(payload, f, indent=1)
    emit(rows, "serve_throughput")
    gate = next(r for r in rows if r["name"].endswith("engine-paged-w4"))
    pf = next(r for r in rows if r["name"].endswith("engine-prefill128"))
    print(f"decode_speedup_vs_host={gate['decode_speedup_vs_host']:.2f}x "
          f"prefill_speedup_vs_host={pf['prefill_speedup_vs_host']:.2f}x")
    return payload


if __name__ == "__main__":
    import sys
    run(quick="--quick" in sys.argv)
