"""Driver-runtime throughput: scan-based device-resident drivers vs the
seed host-loop drivers (core/host_loop), establishing the repo's perf
trajectory for the driver layer (DESIGN.md §3).

The scan side goes through the unified solver API — each measured run is
one ``repro.solve(RunSpec(...))`` call, and every artifact row embeds the
``RunResult.provenance()`` record (resolved spec + rels tail), so the
artifact states exactly what configuration produced it.

Selected rows also get a FUSED TWIN (``-fused`` suffix): the same spec
with ``fused=True``, routing the VR inner loop through the Pallas
``vr_update`` kernel. Twin rows carry ``fused``/``interpret`` flags and
``speedup_vs_unfused`` (warm unfused / warm fused); ``check_regression``
gates that ratio at the 1.0x floor on compiled Pallas backends
(interpret-mode rows — CPU — are exempt: emulating a kernel is not the
configuration the gate protects).

For each worker count p we measure, on CPU:

  * cold wall clock (first invocation — includes jit compilation; the
    host-loop model re-traces its closures EVERY invocation, and for the
    event-driven algorithms compiles p per-worker closures, so its cold
    time grows with p);
  * warm wall clock (subsequent invocations — the scan drivers hit the
    module-level jit cache; the host loop compiles again);
  * epochs/sec derived from warm wall clock.

Selected rows also get a PROX TWIN (``-l1``/``-elasticnet`` suffix): the
same spec with a composite objective, measuring the prox epilogue's
overhead vs the smooth twin (informational — prox rows are excluded from
the legacy scan-vs-host gates, which pin pre-prox configurations). One
SPARSE row (``centralvr-sparse``) runs the lazy CSR driver against the
dense prox'd oracle on the same low-density problem;
``speedup_sparse_vs_dense`` is gated at the 1.0x floor whenever
``nnz_frac <= 0.05`` (lazy catch-up must not lose to the dense
O(d)-per-step path it skips).

Writes ``BENCH_drivers.json`` at the repo root (the acceptance artifact:
scan beats host loop on wall clock at p=8) plus the standard results CSV.

    python -m benchmarks.driver_throughput [--quick]
"""
from __future__ import annotations

import dataclasses
import json
import os

try:
    import repro_bootstrap  # noqa: F401  (repo-root module/script form)
except ModuleNotFoundError:
    pass  # installed form: repro resolves without the fallback

import jax

from benchmarks.common import emit, timed_cold_warm
from repro import RunSpec, solve
from repro.config import ConvexConfig
from repro.core import convex, distributed, host_loop

ROOT = os.path.join(os.path.dirname(__file__), "..")

WORKER_COUNTS = (1, 2, 4, 8)


def _bench_pair(name, spec, problem, loop_fn, epochs, repeat):
    scan_cold, scan_warm, res = timed_cold_warm(
        lambda: solve(spec, problem), repeat=repeat)
    loop_cold, loop_warm, _ = timed_cold_warm(loop_fn, repeat=repeat)
    return {
        "name": name,
        "us_per_call": scan_warm * 1e6,
        "cold_s": scan_cold,
        "scan_cold_s": scan_cold,
        "scan_warm_s": scan_warm,
        "scan_compile_s": max(scan_cold - scan_warm, 0.0),
        "loop_cold_s": loop_cold,
        "loop_warm_s": loop_warm,
        "scan_epochs_per_s": epochs / scan_warm,
        "loop_epochs_per_s": epochs / loop_warm,
        "speedup_warm": loop_warm / scan_warm,
        "provenance": res.provenance(),
        "derived": (f"scan:cold={scan_cold:.3f}s,warm={scan_warm:.3f}s;"
                    f"loop:cold={loop_cold:.3f}s,warm={loop_warm:.3f}s;"
                    f"speedup={loop_warm / scan_warm:.1f}x"),
    }


def _fused_twin(base_row, spec, problem, epochs, repeat):
    """The same run with fused=True, measured against its unfused twin."""
    from repro import kernels

    _, interpret = kernels.resolve_fused(True)
    fspec = dataclasses.replace(spec, fused=True)
    cold, warm, res = timed_cold_warm(
        lambda: solve(fspec, problem), repeat=repeat)
    speedup = base_row["scan_warm_s"] / warm
    return {
        "name": base_row["name"] + "-fused",
        "us_per_call": warm * 1e6,
        "fused": True,
        "interpret": interpret,
        "cold_s": cold,
        "scan_cold_s": cold,
        "scan_warm_s": warm,
        "scan_compile_s": max(cold - warm, 0.0),
        "unfused_warm_s": base_row["scan_warm_s"],
        "scan_epochs_per_s": epochs / warm,
        "speedup_vs_unfused": speedup,
        "provenance": res.provenance(),
        "derived": (f"fused:cold={cold:.3f}s,warm={warm:.3f}s;"
                    f"vs_unfused={speedup:.2f}x;"
                    f"interpret={interpret}"),
    }


def _prox_twin(base_row, spec, problem, epochs, repeat, prox):
    """The same run with a composite objective (``-l1``/``-elasticnet``
    suffix): measures the prox epilogue's cost against the smooth twin.
    Prox rows have no seed host-loop counterpart (the host loop predates
    composite objectives), so ``check_regression`` prints their overhead
    but excludes them from the legacy scan-vs-host gate."""
    pspec = dataclasses.replace(spec, prox=prox)
    cold, warm, res = timed_cold_warm(
        lambda: solve(pspec, problem), repeat=repeat)
    name = prox.split(":")[0]
    return {
        "name": base_row["name"] + "-" + name,
        "prox": res.spec.prox,
        "us_per_call": warm * 1e6,
        "cold_s": cold,
        "scan_cold_s": cold,
        "scan_warm_s": warm,
        "scan_compile_s": max(cold - warm, 0.0),
        "smooth_warm_s": base_row["scan_warm_s"],
        "scan_epochs_per_s": epochs / warm,
        "overhead_vs_smooth": warm / base_row["scan_warm_s"],
        "provenance": res.provenance(),
        "derived": (f"prox:cold={cold:.3f}s,warm={warm:.3f}s;"
                    f"vs_smooth={warm / base_row['scan_warm_s']:.2f}x"),
    }


def _sparse_row(quick: bool, repeat: int):
    """Sparse lazy driver vs the dense prox'd oracle on the same problem
    (``sampling="sparse"`` vs ``"permutation"``, identical trajectories):
    the lazy catch-up must not lose to the dense O(d)-per-step path at
    low density.  ``check_regression`` gates ``speedup_sparse_vs_dense``
    at the 1.0x floor whenever ``nnz_frac <= 0.05``."""
    from repro.prox import lazy

    n, d, nnz = (96, 8192, 16) if quick else (128, 16384, 32)
    rounds = 3 if quick else 4
    prob = lazy.make_sparse_data(jax.random.PRNGKey(2), n, d, nnz)
    eta = 0.05
    dense_spec = RunSpec(algo="centralvr", eta=eta, rounds=rounds,
                         prox="l1:0.001")
    sparse_spec = dataclasses.replace(dense_spec, sampling="sparse")
    d_cold, d_warm, _ = timed_cold_warm(
        lambda: solve(dense_spec, prob), repeat=repeat)
    s_cold, s_warm, res = timed_cold_warm(
        lambda: solve(sparse_spec, prob), repeat=repeat)
    speedup = d_warm / s_warm
    return {
        "name": "drivers/centralvr-sparse",
        "sparse": True,
        "prox": res.spec.prox,
        "nnz_frac": nnz / d,
        "n": n, "d": d, "nnz": nnz,
        "us_per_call": s_warm * 1e6,
        "cold_s": s_cold,
        "scan_cold_s": s_cold,
        "scan_warm_s": s_warm,
        "scan_compile_s": max(s_cold - s_warm, 0.0),
        "dense_warm_s": d_warm,
        "scan_epochs_per_s": rounds / s_warm,
        "speedup_sparse_vs_dense": speedup,
        "provenance": res.provenance(),
        "derived": (f"sparse:warm={s_warm:.3f}s;dense:warm={d_warm:.3f}s;"
                    f"speedup={speedup:.2f}x@nnz/d={nnz / d:.2%}"),
    }


def _obs_twin(base_row, spec, problem):
    """The same run with telemetry ON (``-obs`` suffix): a recorded
    ``solve()`` that compiles the streaming runner, then a second,
    warm one, whose cost is read off the staged execute span (the
    blocked warm execution).  The twin quantifies telemetry overhead
    against the telemetry-off base row — ``check_regression`` prints
    these rows but gates only the base (telemetry-off) rows, which must
    stay at the pre-telemetry floor."""
    import tempfile

    from repro import obs
    from repro.obs import report as obs_report
    from repro.obs import schema as obs_schema

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "run.jsonl")
        with obs.recording(path):
            solve(spec, problem)
        cold = obs_report.summarize(obs_schema.load_rows(path))
        with obs.recording(path):
            res = solve(spec, problem)
        s = obs_report.summarize(obs_schema.load_rows(path))
    warm = s["warm_s"]
    return {
        "name": base_row["name"] + "-obs",
        "telemetry": True,
        "us_per_call": warm * 1e6,
        "scan_warm_s": warm,
        "obs_compile_s": cold["compile_s"],
        "overhead_vs_off": warm / base_row["scan_warm_s"],
        "off_speedup_vs_host": base_row["speedup_warm"],
        "n_telemetry_rows": s["n_rows"],
        "provenance": res.provenance(),
        "derived": (f"obs:warm={warm:.3f}s,compile={cold['compile_s']:.3f}s;"
                    f"overhead_vs_off="
                    f"{warm / base_row['scan_warm_s']:.2f}x"),
    }


def run(quick: bool = False):
    n, d = (128, 16) if quick else (256, 64)
    rounds = 4 if quick else 8
    repeat = 2 if quick else 3
    key = jax.random.PRNGKey(0)
    rows = []

    for p in WORKER_COUNTS:
        if p == 1:
            prob = convex.make_logistic_data(jax.random.PRNGKey(2), n, d)
            eta = convex.auto_eta(prob, 0.3)
            spec = RunSpec(algo="centralvr", eta=eta, rounds=rounds)
            rows.append(_bench_pair(
                "drivers/centralvr-p1", spec, prob,
                lambda: host_loop.run(prob, eta=eta, epochs=rounds, key=key),
                rounds, repeat))
            base = rows[-1]
            rows.append(_fused_twin(base, spec, prob, rounds, repeat))
            rows.append(_prox_twin(base, spec, prob, rounds, repeat,
                                   "l1:0.001"))
            continue
        cfg = ConvexConfig(problem="logistic", n=n, d=d, workers=p)
        sp = distributed.make_distributed(jax.random.PRNGKey(2), cfg)
        eta = convex.auto_eta(sp.merged(), 0.3)
        spec = RunSpec(algo="centralvr_sync", p=p, eta=eta, rounds=rounds)
        rows.append(_bench_pair(
            f"drivers/sync-p{p}", spec, sp,
            lambda: host_loop.run_sync(sp, eta=eta, rounds=rounds, key=key),
            rounds, repeat))
        if p == max(WORKER_COUNTS):
            base = rows[-1]
            rows.append(_fused_twin(base, spec, sp, rounds, repeat))
            rows.append(_prox_twin(base, spec, sp, rounds, repeat,
                                   "elasticnet:0.001:0.0001"))
        spec = RunSpec(algo="centralvr_async", p=p, eta=eta, rounds=rounds)
        rows.append(_bench_pair(
            f"drivers/async-p{p}", spec, sp,
            lambda: host_loop.run_async(sp, eta=eta, rounds=rounds, key=key),
            rounds, repeat))
        if p == max(WORKER_COUNTS):
            base = rows[-1]
            rows.append(_fused_twin(base, spec, sp, rounds, repeat))
            rows.append(_obs_twin(base, spec, sp))

    rows.append(_sparse_row(quick, repeat))

    p8 = [r for r in rows
          if r["name"].endswith("-p8") and not r.get("telemetry")
          and not r.get("prox")]
    beats = all(r["speedup_warm"] > 1.0 for r in p8)
    payload = {
        "config": {"n_per_worker": n, "d": d, "rounds": rounds,
                   "workers": list(WORKER_COUNTS), "quick": quick,
                   "backend": jax.default_backend()},
        "rows": rows,
        "scan_beats_loop_at_p8": beats,
    }
    with open(os.path.join(ROOT, "BENCH_drivers.json"), "w") as f:
        json.dump(payload, f, indent=1)
    emit(rows, "driver_throughput")
    print(f"scan_beats_loop_at_p8={'yes' if beats else 'no'}")
    return payload


if __name__ == "__main__":
    import sys
    run(quick="--quick" in sys.argv)
