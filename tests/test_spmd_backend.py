"""SPMD execution-backend pins (DESIGN.md §2, ``core/spmd.py``).

The heavy comparisons run in a SUBPROCESS with 8 forced host devices (the
main pytest process must keep the real single-device view — see conftest):
spmd trajectories must match the event-equivalent vmap driver within
float32 tolerance for p ∈ {2, 4} on both toy problems — the synchronous
drivers AND the async drivers (CentralVR-Async against the event-serial
staleness scan, D-SAGA against its ``fetch="stale"`` event-serial
reference), round-robin and heterogeneous-speed schedules alike — and
each worker's table shard must be resident on its own device.  Cheap
contract checks (backend validation, instant-fetch D-SAGA refusing spmd,
the shared host-device helper) run in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

# float32 tolerance: identical arithmetic and identical (host-precomputed)
# RNG draws on both backends; only collective reduction order differs
TOL = 3e-5

SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, "src")
    from repro.core import spmd
    spmd.force_host_devices(8)      # before the first jax operation
    import json
    import jax
    import numpy as np
    from repro.config import ConvexConfig
    from repro.core import baselines, centralvr, convex, distributed

    def diff(a, b):
        return float(np.abs(np.asarray(a) - np.asarray(b)).max())

    out = {"device_count": jax.device_count(), "drivers": []}
    key = jax.random.PRNGKey(4)
    for p in (2, 4):
        for kind in ("logistic", "ridge"):
            cfg = ConvexConfig(problem=kind, n=48, d=8, workers=p)
            sp = distributed.make_distributed(jax.random.PRNGKey(0), cfg)
            eta = convex.auto_eta(sp.merged(), 0.3)
            st_v, rels_v = distributed.run_sync(sp, eta=eta, rounds=4,
                                                key=key)
            st_s, rels_s = distributed.run_sync(sp, eta=eta, rounds=4,
                                                key=key, backend="spmd")
            devs = sorted({str(s.device)
                           for s in st_s.tables.addressable_shards})
            xv, rv = distributed.run_dsvrg(sp, eta=eta, rounds=4, key=key,
                                           tau=32)
            xs, rs = distributed.run_dsvrg(sp, eta=eta, rounds=4, key=key,
                                           tau=32, backend="spmd")
            out["drivers"].append({
                "p": p, "kind": kind,
                "sync_drel": diff(rels_v, rels_s),
                "sync_dx": diff(st_v.x, st_s.x),
                "sync_shard_devices": devs,
                "dsvrg_drel": diff(rv, rs), "dsvrg_dx": diff(xv, xs),
            })

    # minibatch baselines, p=4 logistic
    cfg = ConvexConfig(problem="logistic", n=48, d=8, workers=4)
    sp = distributed.make_distributed(jax.random.PRNGKey(0), cfg)
    eta = convex.auto_eta(sp.merged(), 0.3)
    out["baselines"] = {}
    for name, kw in (("dist_sgd", dict(tau=24)), ("easgd", dict(tau=8)),
                     ("ps_svrg", dict(epoch_mult=1))):
        fn = getattr(baselines, "run_" + name)
        xv, rv = fn(sp, eta=eta / 2, rounds=3, key=key, **kw)
        xs, rs = fn(sp, eta=eta / 2, rounds=3, key=key, backend="spmd",
                    **kw)
        out["baselines"][name] = {"drel": diff(rv, rs), "dx": diff(xv, xs)}

    # async drivers as concurrency waves: spmd vs the event-serial vmap
    # reference (same schedule, same RNG, same delta algebra), round-robin
    # for p in {2, 4} x {logistic, ridge} plus heterogeneous-speed
    # schedules (speeds=[1,2,3] at p=3, speeds=[1,1,2,4] at p=4)
    out["async"] = []
    for p, speeds, kinds in ((2, None, ("logistic", "ridge")),
                             (4, None, ("logistic", "ridge")),
                             (3, (1.0, 2.0, 3.0), ("logistic",)),
                             (4, (1.0, 1.0, 2.0, 4.0), ("ridge",))):
        for kind in kinds:
            cfg = ConvexConfig(problem=kind, n=48, d=8, workers=p)
            sp = distributed.make_distributed(jax.random.PRNGKey(0), cfg)
            eta = convex.auto_eta(sp.merged(), 0.3)
            st_v, rv = distributed.run_async(sp, eta=eta, rounds=4, key=key,
                                             speeds=speeds)
            st_s, rs = distributed.run_async(sp, eta=eta, rounds=4, key=key,
                                             speeds=speeds, backend="spmd")
            dv, rdv = distributed.run_dsaga(sp, eta=eta / 2, rounds=4,
                                            key=key, tau=24, fetch="stale",
                                            speeds=speeds)
            ds, rds = distributed.run_dsaga(sp, eta=eta / 2, rounds=4,
                                            key=key, tau=24, speeds=speeds,
                                            backend="spmd")
            out["async"].append({
                "p": p, "kind": kind, "heterogeneous": speeds is not None,
                "async_drel": diff(rv, rs),
                "async_dx": diff(st_v.x_c, st_s.x_c),
                "async_shard_devices": sorted(
                    {str(s.device) for s in st_s.tables.addressable_shards}),
                "dsaga_drel": diff(rdv, rds),
                "dsaga_dx": diff(dv.x_c, ds.x_c),
                "dsaga_shard_devices": sorted(
                    {str(s.device) for s in ds.tables.addressable_shards}),
            })

    # Algorithm 1: spmd == execute on the mesh's first device
    prob = convex.make_logistic_data(jax.random.PRNGKey(1), 64, 8)
    eta1 = convex.auto_eta(prob, 0.3)
    _, r1, _ = centralvr.run(prob, eta=eta1, epochs=3, key=key)
    _, r2, _ = centralvr.run(prob, eta=eta1, epochs=3, key=key,
                             backend="spmd")
    out["centralvr_drel"] = diff(r1, r2)
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


@pytest.mark.slow
def test_simulated_devices_present(results):
    assert results["device_count"] >= 4


@pytest.mark.slow
@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("kind", ["logistic", "ridge"])
def test_spmd_matches_vmap(results, p, kind):
    row = [r for r in results["drivers"]
           if r["p"] == p and r["kind"] == kind][0]
    assert row["sync_drel"] < TOL, row
    assert row["sync_dx"] < TOL, row
    assert row["dsvrg_drel"] < TOL, row
    assert row["dsvrg_dx"] < TOL, row


@pytest.mark.slow
@pytest.mark.parametrize("p", [2, 4])
def test_worker_shards_on_distinct_devices(results, p):
    rows = [r for r in results["drivers"] if r["p"] == p]
    for row in rows:
        assert len(row["sync_shard_devices"]) == p, row


@pytest.mark.slow
def test_baselines_match_vmap(results):
    for name, row in results["baselines"].items():
        assert row["drel"] < TOL, (name, row)
        assert row["dx"] < TOL, (name, row)


@pytest.mark.slow
def test_centralvr_spmd_is_exact(results):
    # single worker: same executable on one device — bit-identical
    assert results["centralvr_drel"] == 0.0, results["centralvr_drel"]


@pytest.mark.slow
@pytest.mark.parametrize("p,kind", [(2, "logistic"), (2, "ridge"),
                                    (4, "logistic"), (4, "ridge")])
def test_async_spmd_matches_event_serial(results, p, kind):
    """CentralVR-Async and stale-fetch D-SAGA under the wave-parallel spmd
    backend vs their event-serial vmap references, round-robin."""
    row = [r for r in results["async"]
           if r["p"] == p and r["kind"] == kind
           and not r["heterogeneous"]][0]
    assert row["async_drel"] < TOL, row
    assert row["async_dx"] < TOL, row
    assert row["dsaga_drel"] < TOL, row
    assert row["dsaga_dx"] < TOL, row


@pytest.mark.slow
@pytest.mark.parametrize("p", [3, 4])
def test_async_spmd_matches_heterogeneous_schedule(results, p):
    """Heterogeneous speeds split rounds into several waves (a worker
    firing twice in a round forces a wave boundary); trajectories must
    still match the event-serial schedule."""
    row = [r for r in results["async"]
           if r["p"] == p and r["heterogeneous"]][0]
    assert row["async_drel"] < TOL, row
    assert row["async_dx"] < TOL, row
    assert row["dsaga_drel"] < TOL, row
    assert row["dsaga_dx"] < TOL, row


@pytest.mark.slow
@pytest.mark.parametrize("p", [2, 3, 4])
def test_async_worker_state_on_distinct_devices(results, p):
    for row in [r for r in results["async"] if r["p"] == p]:
        assert len(row["async_shard_devices"]) == p, row
        assert len(row["dsaga_shard_devices"]) == p, row


# ---------------------------------------------------------------------------
# In-process contract checks (no forced devices needed)
# ---------------------------------------------------------------------------

def _sharded(p=2):
    import jax

    from repro.config import ConvexConfig
    from repro.core import distributed

    cfg = ConvexConfig(problem="logistic", n=16, d=4, workers=p)
    return distributed.make_distributed(jax.random.PRNGKey(0), cfg)


def test_instant_fetch_dsaga_refuses_spmd():
    """Instant-fetch D-SAGA is a serial dependency chain between events —
    no worker-parallel program exists, so asking for one must error rather
    than silently running the stale-fetch construction."""
    import jax

    from repro.core import distributed

    sp = _sharded()
    key = jax.random.PRNGKey(0)
    with pytest.raises(NotImplementedError, match="event-serial"):
        distributed.run_dsaga(sp, eta=0.1, rounds=1, key=key,
                              backend="spmd", fetch="instant")
    with pytest.raises(ValueError, match="unknown fetch"):
        distributed.run_dsaga(sp, eta=0.1, rounds=1, key=key,
                              fetch="bogus")


def test_async_spmd_needs_devices():
    """run_async accepts backend="spmd" now; on a single-device process it
    must fail with the actionable device-count error, not the old
    event-serial NotImplementedError."""
    import jax

    from repro.core import distributed

    jax.device_count()              # initialize the single-device backend
    sp = _sharded(p=2)
    key = jax.random.PRNGKey(0)
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count"):
        distributed.run_async(sp, eta=0.1, rounds=1, key=key,
                              backend="spmd")


def test_unknown_backend_rejected():
    import jax

    from repro.core import baselines, distributed

    sp = _sharded()
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="unknown backend"):
        distributed.run_sync(sp, eta=0.1, rounds=1, key=key,
                             backend="bogus")
    with pytest.raises(ValueError, match="unknown backend"):
        baselines.run_dist_sgd(sp, eta=0.1, rounds=1, key=key,
                               backend="pmap")


def test_worker_mesh_error_names_the_flag():
    import jax  # noqa: F401  (initializes the backend)

    from repro.core import spmd

    jax.device_count()
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count"):
        spmd.worker_mesh(4096)


def test_worker_mesh_error_on_chip_names_the_count(monkeypatch):
    """On a chip the workers are chips: too few fails naming the count
    found, with no CPU simulation flag in the advice."""
    import types

    import jax

    from repro.core import spmd

    chip = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    with pytest.raises(RuntimeError, match="needs 4 devices, found 1 tpu") \
            as err:
        spmd.worker_mesh(4)
    assert "xla_force_host_platform_device_count" not in str(err.value)


def test_force_host_devices_after_init(monkeypatch):
    import jax

    from repro.core import spmd

    n = jax.device_count()          # initializes the backend
    spmd.force_host_devices(n)      # satisfied already: no-op
    with pytest.raises(RuntimeError, match="already initialized"):
        spmd.force_host_devices(n + 4096)
    # an accelerator's devices cannot be forced: no-op, and worker_mesh
    # names the count it found
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spmd.force_host_devices(n + 4096)


def test_bench_artifact_structure():
    """BENCH_spmd.json (written by benchmarks/spmd_scaling.py) reports warm
    epochs/sec per algorithm per backend per worker count — including the
    async rows the acceptance criteria name (CentralVR-Async on both
    backends, the spmd side running the wave construction)."""
    path = os.path.join(ROOT, "BENCH_spmd.json")
    assert os.path.exists(path), "run: python -m benchmarks.spmd_scaling"
    with open(path) as f:
        payload = json.load(f)
    rows = payload["rows"]
    for algo in ("sync", "async"):
        for backend in ("vmap", "spmd"):
            for p in (1, 2, 4):
                match = [r for r in rows
                         if r.get("algo") == algo
                         and r["backend"] == backend and r["p"] == p]
                assert match, (algo, backend, p)
                assert match[0]["epochs_per_s"] > 0, match[0]
