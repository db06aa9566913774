"""Structured run telemetry pins (``repro.obs``, DESIGN.md
§Observability).

Five layers:

  * recorder/schema — JSONL rows round-trip through the pinned v1 schema;
    the stream cadence gate and the jax-free import contract hold;
  * the central guarantee — a telemetry-ON solve is BIT-identical to the
    telemetry-off run (same trajectory, same iterate), while its record
    carries the runner's compile/execute split, streamed per-round
    metrics, and the provenance event; a warm recorded solve compiles
    nothing;
  * spans on the profiler's clock — solve()'s and the drivers' spans land
    in a ``jax.profiler`` trace with their fields, one ``solve`` id per
    call, and the VR loops carry their ``jax.named_scope`` names, also
    through the persistent compilation cache;
  * the analytical models — fetch-staleness/wave stats of the
    deterministic event schedule and bytes-per-collective comms, pinned
    against hand-computed values, with ``comms._MODELS`` covering the
    registry exactly;
  * the golden provenance row shape (``schema.PROVENANCE_KEYS``) that
    every BENCH artifact embeds — set-equal in BOTH directions, so adding
    or dropping a field is a deliberate two-sided edit.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import jax
import numpy as np
import pytest

import repro
from repro import RunSpec, obs, solve
from repro.config import ConvexConfig
from repro.core import distributed, runtime
from repro.obs import comms, report, schema, staleness

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _sharded(p=2, n=24, d=6):
    cfg = ConvexConfig(problem="logistic", n=n, d=d, workers=p)
    return distributed.make_distributed(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# Recorder + schema
# ---------------------------------------------------------------------------

def test_recorder_rows_validate_and_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.recording(path, run_id="fixed-id") as rec:
        rec.event("custom", payload={"k": 1})
        rec.metric("loss", step=3, value=0.25)
        with rec.span("phase/a", tag="x"):
            pass
    n = schema.validate_file(path)
    rows = schema.load_rows(path)
    assert n == len(rows) == 4          # run_start + event + metric + span
    assert all(r["run"] == "fixed-id" for r in rows)
    kinds = [r["kind"] for r in rows]
    assert kinds == ["event", "event", "metric", "span"]
    span_row = rows[-1]
    assert span_row["name"] == "phase/a" and span_row["dur_s"] >= 0.0
    # timestamps are monotone relative to the recorder's start
    assert [r["t"] for r in rows] == sorted(r["t"] for r in rows)


def test_stream_every_gates_metric_cadence(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.recording(path, stream_every=3) as rec:
        for step in range(10):
            rec.metric("rel", step=step, value=float(step))
    steps = [r["step"] for r in schema.load_rows(path)
             if r["kind"] == "metric"]
    assert steps == [0, 3, 6, 9]


def test_schema_rejects_malformed_rows():
    ok = {"v": schema.SCHEMA_VERSION, "run": "r", "t": 0.0,
          "kind": "metric", "name": "m", "step": 0, "value": 1.0}
    assert schema.validate_row(dict(ok)) == ok
    with pytest.raises(schema.SchemaError, match="missing base fields"):
        schema.validate_row({"kind": "event", "name": "e"})
    with pytest.raises(schema.SchemaError, match="schema version"):
        schema.validate_row({**ok, "v": 999})
    with pytest.raises(schema.SchemaError, match="unknown row kind"):
        schema.validate_row({**ok, "kind": "frobnicate"})
    bad = dict(ok)
    del bad["value"]
    with pytest.raises(schema.SchemaError, match="missing required fields"):
        schema.validate_row(bad)
    with pytest.raises(schema.SchemaError, match="has no rows"):
        schema.validate_rows([])


def test_telemetry_off_is_the_default_and_recording_scopes():
    assert obs.active() is None
    assert not obs.stream_active()
    with obs.recording(os.devnull) as rec:
        assert obs.active() is rec
        assert obs.stream_active()
    assert obs.active() is None


def test_import_repro_obs_never_imports_jax():
    """The recorder/schema/report layer is stdlib-only: the CLI tooling
    (``repro.launch.obs``) must work on machines without the toolchain,
    and enabling telemetry must not reorder jax initialization."""
    code = ("import sys; import repro.obs; import repro.launch.obs; "
            "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)")
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0, (
        "import repro.obs pulled in jax\n" + r.stdout + r.stderr)


# ---------------------------------------------------------------------------
# The central guarantee: telemetry observes, never perturbs
# ---------------------------------------------------------------------------

def test_recorded_async_solve_is_bit_identical_with_full_record(tmp_path):
    """One heterogeneous-speeds async solve, off then on: trajectories and
    final iterates EXACTLY equal, while the record carries the span split,
    the streamed per-round metric, and the provenance event with the
    staleness histogram + comms model."""
    sp = _sharded(p=2)
    spec = RunSpec(algo="centralvr_async", p=2, eta=0.05, rounds=4,
                   speeds=(2.0, 1.0))
    off = solve(spec, sp)

    path = str(tmp_path / "run.jsonl")
    with obs.recording(path):
        on = solve(spec, sp)

    np.testing.assert_array_equal(np.asarray(off.rels), np.asarray(on.rels))
    np.testing.assert_array_equal(off.x, on.x)

    rows = schema.load_rows(path)
    schema.validate_rows(rows)
    s = report.summarize(rows)
    # the recorded call compiles its streaming runner (``stream`` is a
    # static argument), so the split shows a real compile inside execute
    assert s["compile_s"] > 0 and s["warm_s"] > 0
    names = {r["name"] for r in rows if r["kind"] == "span"}
    assert {"solve/centralvr_async/compile",
            "solve/centralvr_async/execute"} <= names
    # one streamed metric row per recorded round
    assert s["metrics"]["rel"]["count"] == int(np.asarray(on.rels).size)
    assert s["metrics"]["rel"]["last_value"] == pytest.approx(on.final_rel)

    prov = [r for r in rows if r["kind"] == "event"
            and r["name"] == "provenance"]
    assert len(prov) == 1
    assert prov[0]["staleness"]["histogram"]
    assert prov[0]["comms"]["bytes_per_round"] > 0
    # the rendered report round-trips without jax
    text = report.render(rows)
    assert "phase split" in text and "streamed metrics" in text


def test_disable_degrades_cached_streaming_executable(tmp_path):
    """An executable compiled WITH the streaming callback stays in jax's
    jit cache after ``obs.disable()``; its callback must degrade to a
    silent no-op (the host side re-checks the active recorder), not an
    error and not a write to a closed file."""
    from repro.obs import stream

    @jax.jit
    def f(x):
        stream.scan_metric("rel", 0, x)
        return x * 2

    path = str(tmp_path / "run.jsonl")
    with obs.recording(path):
        assert float(jax.block_until_ready(f(1.0))) == 2.0
    n_rows = len(schema.load_rows(path))
    assert any(r["kind"] == "metric" for r in schema.load_rows(path))
    # same cached executable, recorder gone: callback fires, emits nothing
    assert float(jax.block_until_ready(f(3.0))) == 6.0
    assert len(schema.load_rows(path)) == n_rows


def test_staged_call_falls_back_on_plain_callables(tmp_path):
    """A producer handing ``staged_call`` something without ``.lower``
    still runs (with an execute span + a stage_fallback event) — telemetry
    must never fail a run it only observes."""
    from repro.obs import stage

    path = str(tmp_path / "run.jsonl")
    with obs.recording(path):
        out = stage.staged_call(lambda v: v * 2, jax.numpy.arange(3.0),
                                _label="t/plain")
    np.testing.assert_array_equal(np.asarray(out), [0.0, 2.0, 4.0])
    rows = schema.load_rows(path)
    assert any(r["name"] == "stage_fallback" for r in rows)
    assert any(r["kind"] == "span" and r["name"] == "t/plain/execute"
               for r in rows)


def test_train_loop_emits_structured_epoch_rows(tmp_path):
    """The epoch loop's recorder path: structured ``train_epoch`` rows and
    epoch spans alongside the legacy ``log_fn`` shim, plus the final
    ``train_done`` summary — and the recorded run trains to the same
    result as the bare one."""
    from test_train_scan import tiny_cfg, tiny_tcfg

    from repro.train import loop

    cfg, tcfg = tiny_cfg(), tiny_tcfg(1)
    bare = loop.run_training(cfg, tcfg, epochs=2, workers=1, log_every=0)

    path = str(tmp_path / "train.jsonl")
    lines = []
    with obs.recording(path):
        res = loop.run_training(cfg, tcfg, epochs=2, workers=1,
                                log_fn=lines.append)
    np.testing.assert_allclose(res.losses, bare.losses, rtol=1e-6)

    rows = schema.load_rows(path)
    schema.validate_rows(rows)
    epoch_rows = [r for r in rows if r["name"] == "train_epoch"]
    assert [r["epoch"] for r in epoch_rows] == [0, 1]
    E = tcfg.vr_table_size * tcfg.local_epoch
    assert [r["step"] for r in epoch_rows] == [E, 2 * E]
    assert all(r["workers"] == 1 for r in epoch_rows)
    # the log_fn shim is unchanged: one line per logged epoch
    assert len(lines) == 2 and all("loss" in ln for ln in lines)
    # first epoch staged (span split or recorded fallback), rest spanned
    names = [r["name"] for r in rows if r["kind"] == "span"]
    assert any(n.startswith("train/epoch") for n in names)
    assert "train/eval" in names
    done = [r for r in rows if r["name"] == "train_done"]
    assert len(done) == 1 and done[0]["epochs"] == 2
    assert done[0]["eval_loss"] == pytest.approx(res.final_eval_loss)


# ---------------------------------------------------------------------------
# Spans inside solve(), on the profiler's clock; named device loops
# ---------------------------------------------------------------------------

# solve()'s spans (parent -> children) for a vmap centralvr_sync solve with
# eta=None; every one carries the call's ``solve`` id
SOLVE_SPANS = {
    "solve/prepare": {"solve/coerce", "solve/auto_eta"},
    "solve/dispatch": {"driver/keys", "driver/init", "driver/grad_norm0",
                       "driver/run"},
    "solve/wait": set(),
    "solve/finish": set(),
}
PROGRAM_PREFIXES = ("solve/", "driver/", "spmd/")


def _profiled_spans(logdir):
    """(name, start_ns, end_ns, stats) of the program's host spans in the
    one ``.xplane.pb`` under ``logdir``."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return out


def test_solve_spans_land_in_the_profiler_trace(tmp_path):
    """Under ``jax.profiler`` one solve() shows prepare (coerce, auto_eta),
    dispatch (the driver's keys/init/grad_norm0/run), wait and finish, in
    that order, nested as named, all with one ``solve`` id: and with no
    recorder active."""
    sp = _sharded(p=2)
    spec = RunSpec(algo="centralvr_sync", p=2, rounds=3)
    solve(spec, sp)                     # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        solve(spec, sp)
    spans = _profiled_spans(str(tmp_path))
    by_name = {n: (s, e, st) for n, s, e, st in spans}
    want = set(SOLVE_SPANS) | set().union(*SOLVE_SPANS.values())
    assert want == set(by_name) and len(spans) == len(want)
    assert len({st["solve"] for _, _, _, st in spans}) == 1
    for parent, children in SOLVE_SPANS.items():
        ps, pe, _ = by_name[parent]
        for child in children:
            cs, ce, _ = by_name[child]
            assert ps <= cs and ce <= pe, (parent, child)
    order = ["solve/prepare", "solve/dispatch", "solve/wait",
             "solve/finish"]
    starts = [by_name[n][0] for n in order]
    assert starts == sorted(starts)
    assert by_name["solve/prepare"][2]["algo"] == "centralvr_sync"


def test_solve_spans_are_recorder_rows_too(tmp_path):
    """The same names as JSONL span rows under a recorder, one ``solve``
    id per call and a fresh one for the next call; the staged runner's
    rows carry the id too."""
    sp = _sharded(p=2)
    spec = RunSpec(algo="centralvr_sync", p=2, rounds=3)
    path = str(tmp_path / "run.jsonl")
    with obs.recording(path):
        solve(spec, sp)
        solve(spec, sp)
    rows = [r for r in schema.load_rows(path) if r["kind"] == "span"
            and r["name"].startswith(PROGRAM_PREFIXES)]
    ids = sorted({r["solve"] for r in rows})
    assert len(ids) == 2
    want = set(SOLVE_SPANS) | set().union(*SOLVE_SPANS.values())
    staged = {"solve/centralvr_sync/execute", "solve/centralvr_sync/compile"}
    for sid in ids:
        names = {r["name"] for r in rows if r["solve"] == sid}
        assert want < names and names - want <= staged


def test_recorded_warm_solve_compiles_nothing(tmp_path):
    """With a recorder on, a warm solve() goes through the jit cache: JAX
    reports no compilation, and the record has an execute span, no compile
    row and no comms_hlo event (those belong to the call that compiled)."""
    from jax._src import dispatch

    sp = _sharded(p=2, n=18, d=5)       # shapes no other test compiles
    spec = RunSpec(algo="centralvr_sync", p=2, eta=0.05, rounds=3)
    cold = str(tmp_path / "cold.jsonl")
    with obs.recording(cold):
        solve(spec, sp)
    warm = str(tmp_path / "warm.jsonl")
    compiles = []

    def on_duration(name, secs, **_):
        if name == dispatch.BACKEND_COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with obs.recording(warm):
            solve(spec, sp)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    names = [r["name"] for r in schema.load_rows(warm)]
    assert names.count("solve/centralvr_sync/execute") == 1
    assert not any(n.endswith("/compile") for n in names)
    assert "comms_hlo" not in names
    assert "comms_hlo" in [r["name"] for r in schema.load_rows(cold)]


def _scopes(lowered) -> set:
    """The VR scope names in the op names of a lowered program: a path
    part, wrapped as ``vmap(vr/...)`` under vmap, or a location's start."""
    import re

    text = lowered.as_text(debug_info=True)
    return {s for s in ("vr/init_epoch", "vr/local_epoch", "vr/average",
                        "vr/rel_grad_norm")
            if re.search(rf"[/(\"]{s}[/)]", text)}


def test_device_loops_are_named():
    """The VR loops carry ``jax.named_scope`` names in the lowered programs
    of both drivers the cells run: metadata only, the computation is the
    same (the bit-identity test above and test_solver_api pin that)."""
    from repro.core import centralvr, convex

    sp = _sharded(p=2)
    key = jax.random.PRNGKey(0)
    st = distributed.sync_init(sp, 0.05, key)
    g0 = convex.grad_norm0(sp.merged())
    keys = jax.random.split(key, 2)
    assert _scopes(distributed.sync_init.lower(sp, 0.05, key)) == {
        "vr/init_epoch", "vr/average"}
    assert _scopes(distributed._sync_scan.lower(sp, st, 0.05, g0, keys)) == {
        "vr/local_epoch", "vr/average", "vr/rel_grad_norm"}
    prob = sp.local(0)
    vst = centralvr.init_state(prob, 0.05, key)
    assert _scopes(centralvr.init_state.lower(prob, 0.05, key)) == {
        "vr/init_epoch"}
    assert _scopes(centralvr._run_scan.lower(
        prob, vst, 0.05, g0, keys, sampling="permutation")) == {
        "vr/local_epoch", "vr/rel_grad_norm"}


CACHED_SCOPE = textwrap.dedent("""
    import sys
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    scope = sys.argv[1]

    @jax.jit
    def f(x):
        with jax.named_scope(scope):
            return (jnp.sin(x) * 3.0).sum()

    text = f.lower(jnp.ones(16)).compile().as_text()
    print(sorted(s for s in ("vr/first", "vr/second") if s in text))
""")


def test_persistent_cache_keeps_the_programs_own_op_names(tmp_path):
    """Two processes compile the same computation under different scope
    names through the persistent cache: the second executable carries its
    own names, not those of the entry the first one cached (the cache key
    includes the metadata)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               PYTHONPATH=os.path.join(ROOT, "src"))
    seen = []
    for scope in ("vr/first", "vr/second"):
        r = subprocess.run([sys.executable, "-c", CACHED_SCOPE, scope],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr
        seen.append(r.stdout.strip().splitlines()[-1])
    assert seen == ["['vr/first']", "['vr/second']"]
    assert os.listdir(tmp_path / "cc")


SPMD_SPANS = textwrap.dedent("""
    import sys, json, os
    sys.path.insert(0, "src")
    from repro.core import spmd
    spmd.force_host_devices(4)
    import jax
    from repro import RunSpec, obs, solve
    from repro.config import ConvexConfig
    from repro.core import distributed
    from test_obs import _profiled_spans, _scopes

    logdir, path = sys.argv[1], sys.argv[2]
    cfg = ConvexConfig(problem="ridge", n=40, d=6, workers=4)
    sp = distributed.make_distributed(jax.random.PRNGKey(0), cfg)
    spec = RunSpec(algo="centralvr_sync", p=4, eta=0.01, rounds=3,
                   backend="spmd")
    solve(spec, sp)
    with obs.recording(path), jax.profiler.trace(logdir):
        res = solve(spec, sp)
    p, ns, d = sp.A.shape
    f32 = 4
    mesh = spmd.worker_mesh(p)
    sds = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=jax.sharding.NamedSharding(mesh, spec))
    P = jax.sharding.PartitionSpec
    runner = spmd._sync_runner(mesh, sp.kind).lower(
        sds(sp.A.shape, "float32", P("workers")),
        sds(sp.b.shape, "float32", P("workers")),
        *[sds((), "float32", P())] * 3,
        sds((p, ns), "int32", P("workers")),
        sds((spec.rounds, p, ns), "int32", P(None, "workers")))
    print(json.dumps({
        "rows": [r["bytes"] for r in obs.schema.load_rows(path)
                 if r["name"] == "spmd/place"],
        "stats": [st["bytes"] for n, _, _, st in _profiled_spans(logdir)
                  if n == "spmd/place"],
        # A, b, perm0 sharded; lam, eta, g0 replicated; then the perms
        "want": [sp.A.nbytes + sp.b.nbytes + p * ns * 4 + 3 * f32,
                 spec.rounds * p * ns * 4],
        "devices": len(res.state.tables.sharding.device_set),
        "scopes": sorted(_scopes(runner))}))
""")


def test_spmd_place_counts_the_bytes_it_moves(tmp_path):
    """On 4 host devices an spmd solve's two placements carry ``bytes``:
    the sizes of the leaves moved onto the mesh (all of them here, since
    the problem starts on one device), in the profile and the record; and
    the spmd runner's loops and pmeans are named."""
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           os.path.dirname(__file__)]))
    r = subprocess.run([sys.executable, "-c", SPMD_SPANS,
                        str(tmp_path / "prof"), str(tmp_path / "run.jsonl")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["rows"] == out["stats"] == out["want"]
    # the shard_map runner names its loops and its pmeans too
    assert out["scopes"] == ["vr/average", "vr/init_epoch", "vr/local_epoch",
                             "vr/rel_grad_norm"]


# ---------------------------------------------------------------------------
# Analytical models: staleness / waves / comms
# ---------------------------------------------------------------------------

def test_staleness_round_robin_pins():
    """Round-robin p=4: each worker's first event measures against the
    shared t=0 fetch (staleness = t, one each of 0..3); every post-warmup
    event sees exactly p-1 = 3 other updates; one full wave per round."""
    p, rounds = 4, 3
    st = staleness.staleness_stats(runtime.event_schedule(p, rounds), p)
    assert st["events"] == p * rounds and st["rounds"] == rounds
    assert st["histogram"] == {"0": 1, "1": 1, "2": 1,
                               "3": p * rounds - 3}
    assert st["min"] == 0 and st["max"] == p - 1
    assert st["mean"] == pytest.approx((0 + 1 + 2 + 3 * 9) / 12)
    assert st["waves_per_round_mean"] == 1.0
    assert st["waves_per_round_max"] == 1
    assert st["wave_occupancy_mean"] == 1.0


def test_staleness_heterogeneous_speeds_spread_the_histogram():
    """A 4x-faster worker refetches often (low staleness) and forces the
    slow worker to see many interleaved updates (staleness above p-1);
    rounds split into multiple partially-occupied waves."""
    p, rounds = 2, 8
    sched = runtime.event_schedule(p, rounds, speeds=(4.0, 1.0))
    st = staleness.staleness_stats(sched, p)
    assert st["events"] == p * rounds
    assert st["max"] > p - 1                  # the slow worker's fetches
    assert "0" in st["histogram"]             # back-to-back fast events
    assert st["waves_per_round_mean"] > 1.0
    assert st["wave_occupancy_mean"] < 1.0
    assert sum(st["histogram"].values()) == st["events"]


def test_staleness_rejects_ragged_schedule():
    with pytest.raises(ValueError, match="not a multiple"):
        staleness.staleness_stats(np.zeros(5, dtype=np.int64), p=2)


def test_comms_model_pins():
    # Algorithm-2 sync boundary: 2 all-reduces of the (d,) iterate/gbar
    sync = comms.comms_model("centralvr_sync", p=4, d=8, rounds=5)
    assert sync["allreduce_bytes_per_round"] == 2 * 8 * 4
    assert sync["p2p_bytes_per_round"] == 0
    assert sync["total_bytes"] == 5 * 2 * 8 * 4
    # async event: (dx, dgbar) up + (x_c, gbar_c) down, p events per round
    asy = comms.comms_model("centralvr_async", p=4, d=8, rounds=5)
    assert asy["allreduce_bytes_per_round"] == 0
    assert asy["events_per_round"] == 4
    assert asy["p2p_bytes_per_round"] == 4 * (8 * 4) * 4
    # the event count is overridable (uneven schedules)
    asy2 = comms.comms_model("centralvr_async", p=4, d=8, rounds=5,
                             events_per_round=6)
    assert asy2["p2p_bytes_per_round"] == 4 * (8 * 4) * 6
    # single-worker algorithms move nothing
    assert comms.comms_model("sgd", p=1, d=8, rounds=5)["total_bytes"] == 0
    with pytest.raises(ValueError, match="no comms model"):
        comms.comms_model("nope", p=1, d=1, rounds=1)


def test_comms_models_cover_the_registry_exactly():
    """Adding a registry algorithm without a comms model (or retiring one
    without cleaning up) fails here, not in a benchmark run — naming the
    offending algorithm, not just dumping two sets."""
    missing, extra = comms.coverage_gaps(repro.algorithms())
    assert not missing, (
        f"registry algorithms with no comms model: {list(missing)} — add "
        f"a _MODELS row in obs/comms.py")
    assert not extra, (
        f"comms models for retired algorithms: {list(extra)} — drop the "
        f"_MODELS row in obs/comms.py")


# ---------------------------------------------------------------------------
# Trace-probe accounting (runtime.TRACES)
# ---------------------------------------------------------------------------

def test_traces_delta_scopes_increments():
    runtime.TRACES.inc("obs_test_outside")
    with runtime.traces_delta() as delta:
        runtime.TRACES.inc("obs_test_inside", 2)
    assert delta == {"obs_test_inside": 2}
    with runtime.traces_delta() as delta:
        pass
    assert delta == {}


def test_trace_counter_is_race_safe():
    """Concurrent inc() from many threads (the spmd factories and the
    streamed-callback path both drive the probe off the main thread) must
    not lose increments to the read-modify-write race."""
    counter = runtime._TraceCounter()
    threads = [threading.Thread(
        target=lambda: [counter.inc("k") for _ in range(1000)])
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counter.snapshot() == {"k": 8000}
    counter.clear()
    assert counter.snapshot() == {}


# ---------------------------------------------------------------------------
# Golden provenance row shape
# ---------------------------------------------------------------------------

def test_provenance_row_matches_golden_schema():
    """Set-equality BOTH directions against ``schema.PROVENANCE_KEYS`` /
    ``PROVENANCE_SPEC_KEYS`` on a real async run: a new field must be
    added to the golden tuples deliberately, a dropped/renamed one fails
    immediately (BENCH artifacts embed these rows)."""
    sp = _sharded(p=2)
    res = solve(RunSpec(algo="centralvr_async", p=2, eta=0.05, rounds=3,
                        speeds=(2.0, 1.0)), sp)
    row = res.provenance()
    assert set(row) == set(schema.PROVENANCE_KEYS)
    assert set(row["spec"]) == set(schema.PROVENANCE_SPEC_KEYS)
    assert row["schema_v"] == schema.SCHEMA_VERSION
    assert row["comms"]["algo"] == "centralvr_async"
    assert sum(row["staleness"]["histogram"].values()) == 2 * 3
    json.dumps(row)     # JSON-able end to end

    # bulk-synchronous runs carry comms but no staleness record
    sync = solve(RunSpec(algo="centralvr_sync", p=2, eta=0.05, rounds=3),
                 sp).provenance()
    assert set(sync) == set(schema.PROVENANCE_KEYS)
    assert sync["staleness"] is None
    assert sync["comms"]["n_allreduce_per_round"] == 2


# ---------------------------------------------------------------------------
# CLI (repro.launch.obs)
# ---------------------------------------------------------------------------

def test_obs_cli_report_and_validate(tmp_path):
    from repro.launch import obs as obs_cli

    path = str(tmp_path / "run.jsonl")
    with obs.recording(path) as rec:
        rec.metric("rel", step=0, value=1.0)
        with rec.span("solve/x/compile"):
            pass
    summary = str(tmp_path / "summary.json")
    assert obs_cli.main(["report", path, "--json", summary]) == 0
    with open(summary) as f:
        assert json.load(f)["n_rows"] == 3

    assert obs_cli.main(["validate", path]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "kind": "event"}\n')
    assert obs_cli.main(["validate", path, str(bad)]) == 1
