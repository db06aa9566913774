"""Scaling of the CentralVR drivers per execution backend: the vmap
single-device simulation vs the shard_map SPMD backend with one worker per
(CPU-simulated) device (DESIGN.md §2).

For each worker count p we measure cold (compile-inclusive) and warm wall
clock of a fixed-round CentralVR-Sync run (algo "sync") AND of
CentralVR-Async (algo "async" — the spmd side executes the event schedule
as concurrency waves), each cell one declarative
``repro.solve(RunSpec(...))`` call whose ``RunResult.provenance()`` is
embedded in its artifact row.  Writes ``BENCH_spmd.json`` at the repo
root (the acceptance artifact: per-algo per-backend epochs/sec for
p in {1, 2, 4}) plus the standard results CSV.

Must start in a fresh process: it forces 4 simulated host devices through
``spmd.force_host_devices`` before the first jax operation, so BOTH
backends run under the same 4-device CPU platform (the honest comparison —
on one real CPU the spmd backend pays real cross-device collective and
dispatch overhead, which is the point of measuring it).

    PYTHONPATH=src python -m benchmarks.spmd_scaling [--quick]
"""
from __future__ import annotations

import json
import os

try:
    import repro_bootstrap  # noqa: F401  (repo-root module/script form)
except ModuleNotFoundError:
    pass  # installed form: repro resolves without the fallback

ROOT = os.path.join(os.path.dirname(__file__), "..")

WORKER_COUNTS = (1, 2, 4)
BACKENDS = ("vmap", "spmd")
ALGOS = ("centralvr_sync", "centralvr_async")


def run(quick: bool = False):
    from repro.core import spmd

    spmd.force_host_devices(max(WORKER_COUNTS))
    import jax

    from benchmarks.common import emit, timed_cold_warm
    from repro import RunSpec, solve
    from repro.config import ConvexConfig
    from repro.core import convex, distributed

    n, d = (128, 16) if quick else (256, 64)
    rounds = 4 if quick else 8
    repeat = 2 if quick else 3
    rows = []

    for p in WORKER_COUNTS:
        cfg = ConvexConfig(problem="logistic", n=n, d=d, workers=p)
        sp = distributed.make_distributed(jax.random.PRNGKey(2), cfg)
        eta = convex.auto_eta(sp.merged(), 0.3)
        for algo in ALGOS:
            short = algo.replace("centralvr_", "")
            for backend in BACKENDS:
                # one declarative spec per measured cell; the async spmd
                # side is the wave-parallel staleness construction
                spec = RunSpec(algo=algo, p=p, eta=eta, rounds=rounds,
                               backend=backend)
                cold, warm, res = timed_cold_warm(
                    lambda spec=spec: solve(spec, sp), repeat=repeat)
                rows.append({
                    "name": f"spmd_scaling/{short}-{backend}-p{p}",
                    "algo": short,
                    "backend": backend,
                    "p": p,
                    "us_per_call": warm * 1e6,
                    "cold_s": cold,
                    "warm_s": warm,
                    "compile_s": max(cold - warm, 0.0),
                    "epochs_per_s": rounds / warm,
                    "provenance": res.provenance(),
                    "derived": f"cold={cold:.3f}s,warm={warm:.3f}s,"
                               f"epochs/s={rounds / warm:.1f}",
                })

    payload = {
        "config": {"n_per_worker": n, "d": d, "rounds": rounds,
                   "workers": list(WORKER_COUNTS),
                   "algos": [a.replace("centralvr_", "") for a in ALGOS],
                   "backends": list(BACKENDS),
                   "quick": quick,
                   "device_count": jax.device_count(),
                   "backend_platform": jax.default_backend()},
        "rows": rows,
    }
    with open(os.path.join(ROOT, "BENCH_spmd.json"), "w") as f:
        json.dump(payload, f, indent=1)
    emit(rows, "spmd_scaling")
    return payload


if __name__ == "__main__":
    import sys
    run(quick="--quick" in sys.argv)
