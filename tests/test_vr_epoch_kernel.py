"""The ``vr_epoch`` Pallas kernel (interpret mode on the CPU) against the
unfused scans it replaces on a TPU, the path chooser, and the ``epoch``
field of the drivers' spans.

* one local epoch of p stacked workers, VR epoch and init epoch, against
  ``distributed._local_centralvr_epoch`` / ``_local_sgd_epoch`` under
  ``vmap`` and, at p = 1, ``centralvr.epoch``: logistic, ridge and huber,
  no prox and ``l1``, ns = 300 (not a multiple of the kernel's block);
* whole solves with the drivers steered onto the kernel (the chooser
  told it is on a TPU, the kernel interpreted) against the same solves
  on the scan: vmap ``centralvr_sync``, Algorithm 1, and the spmd runner
  (in a subprocess with four host devices);
* the chooser: the kernel only on a TPU with permutation sampling, no
  iterate trajectory, no per-step ``fused`` params and no prox or an
  elementwise one.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import kernels, obs
from repro.core import centralvr, convex, distributed, fused
from repro.kernels.vr_epoch import ops as vr_epoch
from repro.kernels.vr_epoch import ref as vr_ref
from repro.obs import schema
from repro.prox import operators as proxops

NS, D = 300, 22
ETA, LAM = 0.02, 1e-4


def _data(kind, p, seed=0):
    k = jax.random.PRNGKey(seed)
    A = jax.random.normal(k, (p, NS, D), jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 1), (p, NS), jnp.float32)
    b = jnp.sign(y) if kind == "logistic" else y
    table = 0.1 * jax.random.normal(jax.random.fold_in(k, 2), (p, NS),
                                    jnp.float32)
    x = 0.1 * jax.random.normal(jax.random.fold_in(k, 3), (D,), jnp.float32)
    gbar = 0.1 * jax.random.normal(jax.random.fold_in(k, 4), (D,),
                                   jnp.float32)
    perms = jax.vmap(lambda kk: jax.random.permutation(kk, NS))(
        jax.random.split(jax.random.fold_in(k, 5), p))
    return A, b, table, x, gbar, perms


def _close(got, want):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float64),
                                   np.asarray(w, np.float64),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("prox", [None, "l1"])
@pytest.mark.parametrize("init", [False, True], ids=["vr", "init"])
@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("kind", ["logistic", "ridge", "huber"])
def test_kernel_matches_the_scan(kind, p, init, prox):
    A, b, table, x, gbar, perms = _data(kind, p)
    px = None if prox is None else proxops.parse(prox)
    lam = jnp.float32(LAM)
    if init:
        got = vr_epoch.local_epochs(A, b, perms, x, ETA, LAM, kind=kind,
                                    prox=px)
        want = jax.vmap(lambda A, b, perm: distributed._local_sgd_epoch(
            A, b, lam, kind, x, ETA, perm, prox=px))(A, b, perms)
    else:
        got = vr_epoch.local_epochs(A, b, perms, x, ETA, LAM, kind=kind,
                                    table=table, gbar=gbar, prox=px)
        want = vr_ref.vr_epoch_ref(A, b, table, perms, x, gbar, ETA, lam,
                                   kind=kind, prox=px)
    assert [g.shape for g in got] == [(p, D), (p, NS), (p, D)]
    _close(got, want)
    if p == 1 and not init:
        st, _ = centralvr.epoch(
            convex.Problem(A[0], b[0], lam, kind),
            centralvr.VRState(x, table[0], gbar), ETA, perms[0], prox=px)
        _close((got[0][0], got[1][0], got[2][0]), st)


def _tpu_reduce_order(prod, d):
    """float32 row sums in the order of XLA's TPU reduce over a stacked
    (p, d) operand: lane j, j+8, ... into 8 partial sums, then halved."""
    out = []
    for row in prod:
        part = list(row[:8])
        for k in range(1, len(row) // 8):
            part = [np.float32(part[j] + row[8 * k + j]) for j in range(8)]
        for h in (4, 2, 1):
            part = [np.float32(part[j] + part[j + h]) for j in range(h)]
        out.append(part[0])
    return np.array(out, np.float32)


@pytest.mark.parametrize("d", [22, 90])
def test_row_sums_round_as_xla_on_a_tpu(d):
    """At p > 1 the kernel's ``a . x`` adds in XLA's TPU order, bit for
    bit, so it rounds as the scan it replaces does there."""
    from jax.experimental import pallas as pl

    from repro.kernels.vr_epoch import kernel

    rng = np.random.default_rng(d)
    prod = np.zeros((4, 128), np.float32)
    prod[:, :d] = (rng.standard_normal((4, d))
                   * 10.0 ** rng.integers(-3, 3, (4, d))).astype(np.float32)

    def sums(p_ref, o_ref):
        o_ref[...] = jnp.broadcast_to(kernel._row_sums(p_ref[...], d),
                                      o_ref.shape)

    got = pl.pallas_call(
        sums, out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
        interpret=True)(jnp.asarray(prod))
    np.testing.assert_array_equal(np.asarray(got)[:, 0],
                                  _tpu_reduce_order(prod, d))


def test_kernel_takes_eta_and_lam_at_run_time():
    """A new step size or lam reuses the compiled epoch: both are traced
    scalars, not static floats."""
    A, b, table, x, gbar, perms = _data("logistic", 4)
    traces = []

    @jax.jit
    def run(eta, lam):
        traces.append(1)
        return vr_epoch.local_epochs(A, b, perms, x, eta, lam,
                                     kind="logistic", table=table, gbar=gbar)

    first = run(jnp.float32(0.02), jnp.float32(1e-4))
    second = run(jnp.float32(0.01), jnp.float32(1e-3))
    assert len(traces) == 1
    _close(second, vr_ref.vr_epoch_ref(A, b, table, perms, x, gbar, 0.01,
                                       jnp.float32(1e-3), kind="logistic"))
    assert not np.allclose(np.asarray(first[0]), np.asarray(second[0]))


@pytest.mark.parametrize("tpu,what,want", [
    (True, {}, True),
    (True, {"prox": proxops.parse("l1")}, True),
    (True, {"prox": proxops.parse("box")}, True),
    (True, {"sampling": "uniform"}, False),
    (True, {"track_iterates": True}, False),
    (True, {"prox": proxops.parse("group_l2")}, False),
    (True, {"fused": (0.05, 1e-4, False, None)}, False),
    (False, {}, False),
], ids=["tpu", "tpu-l1", "tpu-box", "uniform", "track_iterates",
        "group_l2", "fused", "cpu"])
def test_path_chooser(monkeypatch, tpu, what, want):
    monkeypatch.setattr(kernels, "has_pallas_support", lambda: tpu)
    assert vr_epoch.engaged(**what) is want
    assert vr_epoch.path(**what) == ("kernel" if want else "scan")


def test_fused_true_keeps_the_per_step_kernel(monkeypatch):
    """``fused=True`` resolves to the per-step ``vr_update`` params, so
    its rounds keep their path on a TPU too."""
    fp = fused.make_params(True, ETA, LAM)
    assert fp is not None
    monkeypatch.setattr(kernels, "has_pallas_support", lambda: True)
    assert not vr_epoch.engaged(fused=fp)


@pytest.mark.parametrize("spec", [
    {"algo": "centralvr_sync", "p": 4},
    {"algo": "centralvr", "p": 1},
], ids=["centralvr_sync", "centralvr"])
def test_cpu_solve_spans_read_scan(tmp_path, spec):
    A, b, *_ = _data("logistic", 1)
    prob = convex.Problem(A[0], b[0], jnp.float32(LAM), "logistic")
    path = str(tmp_path / "run.jsonl")
    with obs.recording(path):
        repro.solve(repro.RunSpec(rounds=2, **spec), prob)
    rows = {r["name"]: r for r in schema.load_rows(path)
            if r["kind"] == "span"}
    assert rows["driver/init"]["epoch"] == "scan"
    assert rows["driver/run"]["epoch"] == "scan"


@pytest.fixture
def kernel_path(monkeypatch):
    """The drivers steered onto the kernel, interpreted on the CPU; the
    jit caches are cleared on both sides, since the path is chosen at
    trace time."""
    jax.clear_caches()
    monkeypatch.setattr(kernels, "has_pallas_support", lambda: True)
    monkeypatch.setattr(kernels, "default_interpret", lambda: True)
    yield
    jax.clear_caches()


def _solves(spec, prob):
    out = repro.solve(repro.RunSpec(seed=5, rounds=3, **spec), prob)
    return np.asarray(out.x), np.asarray(out.rels)


@pytest.mark.parametrize("spec", [
    {"algo": "centralvr_sync", "p": 4},
    {"algo": "centralvr_sync", "p": 4, "prox": "l1"},
    {"algo": "centralvr", "p": 1},
], ids=["centralvr_sync", "centralvr_sync-l1", "centralvr"])
def test_drivers_on_the_kernel_match_the_scan(spec, request):
    k = jax.random.PRNGKey(3)
    A = jax.random.normal(k, (4 * NS, D), jnp.float32)
    b = jnp.sign(jax.random.normal(jax.random.fold_in(k, 1), (4 * NS,)))
    prob = convex.Problem(A, b, jnp.float32(LAM), "logistic")
    want = _solves(spec, prob)
    request.getfixturevalue("kernel_path")
    got = _solves(spec, prob)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=1e-6)


def test_spmd_runner_on_the_kernel_matches_the_scan():
    """The per-chip body with p = 1 kernels, on four host devices (in a
    subprocess: the main process keeps its single device)."""
    code = textwrap.dedent("""
        import json
        from repro.core import spmd
        spmd.force_host_devices(4)
        import jax, jax.numpy as jnp, numpy as np
        import repro
        from repro import kernels
        from repro.core import convex
        k = jax.random.PRNGKey(3)
        A = jax.random.normal(k, (1200, 22))
        b = jnp.sign(jax.random.normal(jax.random.fold_in(k, 1), (1200,)))
        prob = convex.Problem(A, b, jnp.float32(1e-4), "logistic")
        spec = repro.RunSpec(algo="centralvr_sync", p=4, backend="spmd",
                             seed=5, rounds=3)
        want = repro.solve(spec, prob)
        kernels.has_pallas_support = lambda: True
        kernels.default_interpret = lambda: True
        jax.clear_caches()
        spmd._sync_runner.cache_clear()
        got = repro.solve(spec, prob)
        print(json.dumps({
            "x": float(np.abs(np.asarray(got.x) - np.asarray(want.x)).max()),
            "rels": float(np.abs(np.asarray(got.rels)
                                 - np.asarray(want.rels)).max())}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    gaps = json.loads(out.stdout.strip().splitlines()[-1])
    assert gaps["x"] < 1e-6 and gaps["rels"] < 1e-6, gaps
