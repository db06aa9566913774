"""Multi-process execution: KV data plane, process engines, launcher lanes
(DESIGN.md §Multi-host & elasticity).

Fast tests exercise the KV codec/semantics, the worker-block split, the
nprocs=1 degenerate process engines (which must be bit-identical to the
event-serial references in x64 — the engines are the same algebra re-run
over a KV exchange), and the ``topology="process"`` solver surface.

Slow tests launch the real two-local-process ``jax.distributed`` fleet
through ``python -m repro.launch.distributed --verify`` — the same lanes
the multihost-smoke CI job runs — and assert the in-process verdict
(worker trajectories vs the single-process reference) via the exit code.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.config import ConvexConfig
from repro.core import convex, distributed, procmesh, solver
from repro.launch import distributed as launchd

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


# ---------------------------------------------------------------- fast --

def test_worker_blocks_split():
    assert [list(b) for b in procmesh.worker_blocks(4, 2)] == [[0, 1], [2, 3]]
    assert [list(b) for b in procmesh.worker_blocks(5, 2)] == [[0, 1], [2, 3, 4]]
    assert [list(b) for b in procmesh.worker_blocks(3, 3)] == [[0], [1], [2]]
    with pytest.raises(ValueError):
        procmesh.worker_blocks(2, 3)


def test_array_codec_roundtrip():
    arrays = {"a": np.arange(6.0).reshape(2, 3),
              "b": np.array([1], dtype=np.int64)}
    out = procmesh.decode_arrays(procmesh.encode_arrays(arrays))
    assert set(out) == {"a", "b"}
    for k in arrays:
        assert out[k].dtype == arrays[k].dtype
        np.testing.assert_array_equal(out[k], arrays[k])


def test_local_kv_semantics():
    kv = procmesh.LocalKV()
    kv.set("k", b"v")
    assert kv.get("k", 1.0) == b"v"
    # the membership protocol never overwrites a key; the KV enforces it
    with pytest.raises(ValueError, match="already set"):
        kv.set("k", b"w")
    with pytest.raises(procmesh.KVTimeout):
        kv.get("missing", 1.0)


def test_fault_validation():
    procmesh.Fault(process=1, round_=2)
    with pytest.raises(ValueError, match="mode"):
        procmesh.Fault(process=1, round_=2, mode="explode")
    with pytest.raises(ValueError):
        procmesh.Fault(process=0, round_=2)
    with pytest.raises(ValueError, match="round"):
        procmesh.Fault(process=1, round_=0)
    with pytest.raises(ValueError, match="rejoin"):
        procmesh.Fault(process=1, round_=2, mode="stall", rejoin_after=0)


@pytest.fixture(scope="module")
def prob4():
    cfg = ConvexConfig(problem="logistic", n=48, d=8, seed=0, workers=4)
    sp = distributed.make_distributed(jax.random.PRNGKey(0), cfg)
    return sp, convex.auto_eta(sp.merged())


def _comm():
    return procmesh.ProcComm(procmesh.LocalKV(), 0, 1, prefix="t")


def test_single_process_async_engine_is_bit_exact(prob4):
    """nprocs=1 degenerate fleet: the KV engine runs the identical wave
    algebra, so the trajectory must match ``run_async`` bit for bit."""
    sp, eta = prob4
    key = jax.random.PRNGKey(0)
    for speeds in (None, (1.0, 1.0, 2.0, 4.0)):
        _, rels_ref = distributed.run_async(sp, eta=eta, rounds=5, key=key,
                                            speeds=speeds)
        state, rels, transitions = procmesh.run_async_process(
            sp, eta=eta, rounds=5, key=key, comm=_comm(), speeds=speeds)
        np.testing.assert_array_equal(np.asarray(rels_ref), rels)
        assert transitions == []


def test_single_process_sync_engine_matches(prob4):
    sp, eta = prob4
    key = jax.random.PRNGKey(0)
    _, rels_ref = distributed.run_sync(sp, eta=eta, rounds=5, key=key)
    state, rels = procmesh.run_sync_process(sp, eta=eta, rounds=5, key=key,
                                            comm=_comm())
    # separately-jitted per-worker epochs vs one vmapped program: same
    # math, one-ULP reassociation headroom
    np.testing.assert_allclose(np.asarray(rels_ref), rels,
                               rtol=1e-10, atol=1e-12)


def test_solve_process_topology_matches_local(prob4):
    cfg = ConvexConfig(problem="logistic", n=12, d=8, seed=0)
    kw = dict(algo="centralvr_async", p=4, rounds=6, seed=0,
              speeds=(1.0, 1.0, 2.0, 4.0))
    ref = solver.solve(solver.RunSpec(**kw), cfg)
    launchd.set_local_context(1, 0, prefix="solve-t")
    try:
        res = solver.solve(solver.RunSpec(topology="process", **kw), cfg)
    finally:
        launchd.clear_context()
    np.testing.assert_array_equal(np.asarray(ref.rels), np.asarray(res.rels))
    prov = res.provenance()["spec"]
    assert prov["topology"] == "process" and prov["elastic"] is False


def test_solve_process_requires_context(prob4):
    sp, eta = prob4
    launchd.clear_context()
    spec = solver.RunSpec(algo="centralvr_async", p=4, rounds=2,
                          topology="process")
    with pytest.raises(RuntimeError, match="process mesh"):
        procmesh.solve_process(spec, sp, eta, jax.random.PRNGKey(0))


def test_runspec_topology_validation():
    ok = solver.RunSpec(algo="centralvr_async", p=4, topology="process")
    assert ok.elastic is False
    with pytest.raises(ValueError, match="topology"):
        solver.RunSpec(algo="centralvr_async", p=4, topology="bogus")
    with pytest.raises(ValueError):
        solver.RunSpec(algo="dsaga", p=4, topology="process")
    with pytest.raises(ValueError):
        solver.RunSpec(algo="centralvr_async", p=4, topology="process",
                       backend="spmd")
    with pytest.raises(ValueError):
        solver.RunSpec(algo="centralvr_async", p=4, topology="process",
                       fused=True)
    with pytest.raises(ValueError, match="elastic"):
        solver.RunSpec(algo="centralvr_sync", p=4, elastic=True)


def test_solve_membership_requires_elastic_local():
    cfg = ConvexConfig(problem="logistic", n=12, d=8, seed=0)
    from repro.core import elastic
    plan = elastic.PlannedMembership(4, {2: (0, 1)})
    spec = solver.RunSpec(algo="centralvr_async", p=4, rounds=4)
    with pytest.raises(ValueError, match="elastic"):
        solver.solve(spec, cfg, membership=plan)


def test_worker_mesh_simulation_guard(monkeypatch):
    """Satellite bugfix: ``simulate_host_devices=True`` after jax already
    initialized must fail fast when THIS process holds fewer devices than
    p, even though the global count satisfies the force_host_devices
    check (the jax.distributed world shape)."""
    from repro.launch import mesh

    jax.devices()   # ensure the backend is initialized
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    monkeypatch.setattr(jax, "local_device_count", lambda: 2)
    with pytest.raises(RuntimeError, match="DESIGN"):
        mesh.make_worker_mesh(4, simulate_host_devices=True)


def test_launcher_refuses_on_accelerator(monkeypatch, capsys):
    """The one-host process tier is a CPU rehearsal: where JAX would find
    an accelerator, the launcher refuses before starting any process."""
    monkeypatch.setattr(launchd, "_accelerator_platform", lambda: "tpu")

    def no_spawn(*a, **k):
        raise AssertionError("the launcher started a process")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    args = launchd.parse_args(["--nprocs", "2", "--workers", "4"])
    assert launchd.run_launcher(args) == 2
    assert "JAX_PLATFORMS=cpu" in capsys.readouterr().err


@pytest.mark.parametrize("pinned,probed,want", [
    ("cpu", None, None),        # pinned to the CPU: no probe at all
    ("", "tpu", "tpu"),
    ("", "cpu", None),
])
def test_accelerator_probe(monkeypatch, pinned, probed, want):
    monkeypatch.setenv("JAX_PLATFORMS", pinned)

    def probe(argv, **kw):
        assert probed is not None, "probed while pinned to the CPU"
        return subprocess.CompletedProcess(argv, 0, stdout=f"{probed}\n")

    monkeypatch.setattr(subprocess, "run", probe)
    assert launchd._accelerator_platform() == want


def test_process_worker_mesh_validates_world():
    from repro.core import spmd
    m = spmd.process_worker_mesh(1)
    assert m.devices.shape == (1,)
    with pytest.raises(RuntimeError, match="devices across the world"):
        spmd.process_worker_mesh(1024)


# ---------------------------------------------------- slow (subprocess) --

def _launch(tmp_path, *extra):
    """Run the two-process launcher; --verify makes the parent re-solve
    the spec locally and exit nonzero on trajectory mismatch."""
    argv = [sys.executable, "-m", "repro.launch.distributed",
            "--nprocs", "2", "--workers", "4", "--rounds", "5",
            "--n", "12", "--d", "8", "--timeout", "200",
            "--logdir", str(tmp_path / "logs"),
            "--json", str(tmp_path / "results.json"),
            "--verify", *extra]
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(argv, env=env, capture_output=True, text=True,
                          timeout=280)


@pytest.mark.slow
def test_two_process_async_lane(tmp_path):
    r = _launch(tmp_path, "--algo", "centralvr_async",
                "--speeds", "1,1,2,4", "--x64")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fleet ok" in r.stdout
    results = json.loads((tmp_path / "results.json").read_text())
    assert results["dropped"] is False and results["transitions"] == []


@pytest.mark.slow
def test_two_process_sync_lane(tmp_path):
    r = _launch(tmp_path, "--algo", "centralvr_sync", "--x64")
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.slow
def test_two_process_elastic_dropout_lane(tmp_path):
    """Process 1 exits at the round-2 boundary; the survivor repartitions
    deterministically, emits schema-valid worker_lost/repartition events,
    and the post-drop trajectory matches the planned-membership reference
    (exact in x64)."""
    from repro.launch import obs as launch_obs

    obs_base = str(tmp_path / "obs")
    r = _launch(tmp_path, "--algo", "centralvr_async",
                "--speeds", "1,1,2,4", "--x64", "--elastic",
                "--drop-process", "1", "--drop-round", "2",
                "--drop-mode", "exit", "--hb-timeout", "5",
                "--obs", obs_base)
    assert r.returncode == 0, r.stdout + r.stderr
    results = json.loads((tmp_path / "results.json").read_text())
    assert [t["round"] for t in results["transitions"]] == [2]
    assert results["transitions"][0]["live"] == [0, 1]

    from repro.obs import schema
    rows = schema.load_rows(obs_base + "-p0.jsonl")
    assert schema.validate_rows(rows) == len(rows)
    names = [row["name"] for row in rows if row["kind"] == "event"]
    assert names.count("worker_lost") == 2       # workers 2 and 3
    assert names.count("repartition") == 1
    lost = [row for row in rows if row["name"] == "worker_lost"]
    assert all(row["detect_s"] > 0 for row in lost)
    assert launch_obs  # imported above: launch.obs stays importable


@pytest.mark.slow
def test_two_process_elastic_rejoin_lane(tmp_path):
    r = _launch(tmp_path, "--algo", "centralvr_async", "--rounds", "7",
                "--speeds", "1,1,2,4", "--x64", "--elastic",
                "--drop-process", "1", "--drop-round", "2",
                "--drop-mode", "stall", "--rejoin-after", "2",
                "--hb-timeout", "5")
    assert r.returncode == 0, r.stdout + r.stderr
    results = json.loads((tmp_path / "results.json").read_text())
    rounds = [t["round"] for t in results["transitions"]]
    assert rounds == [2, 4]
    assert results["transitions"][1]["joined"] == [2, 3]
