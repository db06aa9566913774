#!/usr/bin/env python3
"""Chip benchmark of the CentralVR solvers: seconds to a stated accuracy.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell (an entry of ``workloads`` in ``BENCHMARK.json``):

1. set-up: find the chips the cell asks for (and refuse to run without
   them), turn on the program's persistent compilation cache, make the
   configuration's dataset on the device from ``--seed``, and warm up
   with one solve of the cell's own spec;
2. the window: ``repro.solve`` back to back on that dataset, each call
   with the next seed, until ``--seconds`` have passed; every solve is
   timed from the call to its result on the host;
3. with ``--trace 1``, a bounded profiler slice (the end of one solve and
   the start of the next) and the per-layer metrics, each read by
   ``bench/metrics/<name>.py``;
4. the comparison that decides ``correct`` (``bench/compare.py``) on a
   sample of the window's solves drawn from the seed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``; ``checks`` comes last with each compared number beside
its limit, which also make the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells  # noqa: E402

METRICS_DIR = os.path.join(cells.BENCH, "metrics")
EXIT_NO_CHIP = 3
# solves of the window redone by the reference once it has closed
VERIFY_SAMPLES = 2
# the traced slice: the last TRACE_TAIL_S of one solve and the first
# TRACE_HEAD_S of the next
TRACE_TAIL_S = 0.3
TRACE_HEAD_S = 0.3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Solve:
    seed: int
    wall_s: float          # host call to result on the host
    driver_s: float        # RunResult.wall_s: the driver call alone
    x: object              # final iterate
    table: object          # final table of scalar residuals, on device


@dataclass
class RunView:
    """What a per-layer metric reader may read."""

    cell: dict
    config: dict
    chips: int
    device_kind: str
    solves: list
    window_s: float
    window_compiles: int
    trace: object = None

    @property
    def solve_s(self) -> float | None:
        return self.window_s / len(self.solves) if self.solves else None


class CompileCounter:
    """Counts XLA compilations (and their seconds) and persistent-cache
    hits and misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.count, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        compile_event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == compile_event:
                self.count += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def chips_of(chips: int, require_chip: bool) -> list:
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "the benchmark runs only on the chip")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def load_reader(name: str):
    path = os.path.join(METRICS_DIR, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def traced_slice(solve_once, solve_wall_s: float):
    """Trace the last ``TRACE_TAIL_S`` of one solve and the first
    ``TRACE_HEAD_S`` of the next; return the reduced trace. The solves run in threads so that
    the profiler starts and stops inside them."""
    import jax

    from bench import devtrace

    errors = []

    def go(k):
        try:
            with jax.profiler.TraceAnnotation(f"bench/solve{k}"):
                solve_once(k)
        except Exception as e:   # re-raised in the caller below
            errors.append(e)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        first = threading.Thread(target=go, args=(0,))
        first.start()
        time.sleep(max(0.0, solve_wall_s - TRACE_TAIL_S))
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                first.join()
                second = threading.Thread(target=go, args=(1,))
                second.start()
                time.sleep(TRACE_HEAD_S)
        finally:
            jax.profiler.stop_trace()
        second.join()
        if errors:
            raise errors[0]
        events = devtrace.events_from_xplane(devtrace.find_xplane(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return devtrace.Trace(events)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, cell: dict | None = None,
             require_chip: bool = True) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``cell`` may be given in place of the cell's files (tests)."""
    bench = bench or cells.benchmark()
    cell = cell or cells.load_cell(name, bench)
    chips = int(cell["entry"]["chips"])
    config, spec_fields = cell["config_data"], cell["traffic_data"]["spec"]

    devices = chips_of(chips, require_chip)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileCounter()

    import repro
    from repro.core import convex

    from bench import compare, data, reference

    precision = config["matmul_precision"]
    rounds, target = int(cell["rounds"]), float(cell["target"])

    def spec_of(i):
        return repro.RunSpec(seed=data.solve_seed(seed, i), rounds=rounds,
                             **spec_fields)

    # --- set-up: data on the device, one warm solve of the cell's spec
    with jax.default_matmul_precision(precision):
        ds = data.make(config, seed)
        jax.block_until_ready((ds.A, ds.b))
        problem = convex.Problem(ds.A, ds.b, jnp.float32(ds.lam), ds.loss)
        repro.solve(spec_of(0), problem)
    t_win = time.perf_counter()
    setup_s = t_win - T_START
    log(f"setup_s={setup_s!r} compiles={clock.count} "
        f"compile_s={clock.seconds!r} cache_hits={clock.hits} "
        f"cache_misses={clock.misses} cache_dir={cache_dir}")

    # --- the window
    solves, attempted, failed, i = [], 0, 0, 1
    compiles0 = clock.count
    with jax.default_matmul_precision(precision):
        while True:
            attempted += 1
            t0 = time.perf_counter()
            try:
                res = repro.solve(spec_of(i), problem)
                rels, x = np.asarray(res.rels), np.asarray(res.x)
            except Exception:   # a solve that raises counts as failed
                failed += 1
                log(f"solve {i} raised:\n{traceback.format_exc()}")
            else:
                t1 = time.perf_counter()
                solves.append(Solve(data.solve_seed(seed, i), t1 - t0,
                                    float(res.wall_s), x,
                                    compare.table_of(res.state)))
                if not (rels.size and math.isfinite(float(rels[-1]))
                        and float(rels[-1]) <= target):
                    failed += 1
                del res
            i += 1
            if time.perf_counter() - t_win >= seconds:
                break
    window_s = time.perf_counter() - t_win
    window_compiles = clock.count - compiles0
    used = devices[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    view = RunView(cell, config, chips, used[0].device_kind, solves,
                   window_s, window_compiles)
    log(f"window_s={window_s!r} solves={len(solves)} attempted={attempted} "
        f"failed={failed} window_compiles={window_compiles}")

    metrics = {}
    result_device = {"platform": used[0].platform, "kind":
                     used[0].device_kind, "count": len(devices),
                     "memory_peak_bytes": peak}
    breakdown = None
    entries = cells.metrics_for(name, bench, traced=trace)
    if trace and solves:
        wall = float(np.median([s.wall_s for s in solves]))

        def solve_once(k):
            with jax.default_matmul_precision(precision):
                r = repro.solve(spec_of(i + k), problem)
                np.asarray(r.x)

        view.trace = traced_slice(solve_once, wall)
        busy = view.trace.mean_busy_s()
        result_device["busy_s"] = busy if busy is not None else 0.0
        result_device["window_s"] = view.trace.window_s
        breakdown = view.trace.breakdown()
    if not trace:
        e2e = {"solve_s": view.solve_s, "setup_s": setup_s}
        for m in entries:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    elif solves:
        for m in entries:
            value = load_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    # --- correctness, once the window has closed and memory is read
    per_solve = []
    if solves:
        rng = np.random.default_rng(seed)
        k = min(VERIFY_SAMPLES, len(solves))
        picks = sorted(rng.choice(len(solves), size=k, replace=False))
        for j, s in enumerate(solves):
            if j not in picks:
                s.table = None      # free the program's state
        A64 = np.asarray(ds.A, np.float64)
        b64 = np.asarray(ds.b, np.float64)
        eta = reference.step_size(A64, ds.lam, ds.loss)
        for j in picks:
            s = solves[j]
            x_hist, _, table_ref = reference.solve(
                ds.A, ds.b, ds.lam, ds.loss, algo=spec_fields["algo"],
                p=int(spec_fields["p"]), rounds=rounds, seed=s.seed, eta=eta)
            final64 = reference.rel_grad_norm64(A64, b64, ds.lam, ds.loss,
                                                s.x)
            per_solve.append(compare.numbers_of(
                s.x, np.asarray(s.table), (x_hist, table_ref), final64))
    correct, checks = compare.judge(compare.worst(per_solve),
                                    compare.limits_of(cell))
    correct = correct and bool(per_solve)

    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    log(f"verified {len(per_solve)} of {len(solves)} solves")
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} limit {c['limit']!r}")
    return out


def _finite(v: float) -> float:
    """JSON has no infinity: a non-finite reading prints as the largest
    float."""
    return v if math.isfinite(v) else sys.float_info.max


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return EXIT_NO_CHIP
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
