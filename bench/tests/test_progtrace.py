"""The program-span and named-loop reductions (``bench/progtrace.py``), on
hand-made events laid out as a TPU trace is, with the stats the program's
spans and the device ops carry.

    python -m pytest -q bench/tests
"""
import pytest

from bench import devtrace, progtrace

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
OPS, HOST = devtrace.OPS_LINE, devtrace.HOST_PLANE
LOCAL = "jit(_sync_scan)/while/body/closed_call/vmap(vr/local_epoch)/while/body/mul"
INIT = "jit(sync_init)/vmap(vr/init_epoch)/while/body/dynamic_slice"


def op(name, start, dur, path=None, plane=DEV0):
    stats = {} if path is None else {progtrace.OP_NAME: path}
    return (plane, OPS, name, start, dur, stats)


def span(name, start, dur, **stats):
    return (HOST, "python", name, start, dur, stats)


@pytest.mark.parametrize("path,scope", [
    (LOCAL, "vr/local_epoch"),
    (INIT, "vr/init_epoch"),
    ("jit(_run_scan)/while/body/vr/rel_grad_norm/dot_general",
     "vr/rel_grad_norm"),
    ("vr/average/div", "vr/average"),
    ("jit(f)/vr/local_epoch/while/body/vr/average/add", "vr/average"),
    ("jit(f)/while/body/add", None),
    ("jit(f)/survr/local/add", None),
    (None, None),
])
def test_scope_of_reads_the_innermost_vr_scope(path, scope):
    assert progtrace.scope_of(path) == scope


def _mixed_events():
    """Many cheap init-epoch steps and fewer VR steps at another period:
    the most frequent op overall is the init one."""
    events = [span("bench/traced", 0, 10_000_000)]
    for k in range(60):
        events.append(op("fusion.init", 1_000 + k * 2_000, 500, INIT))
    for k in range(20):
        t = 500_000 + k * 5_000
        events += [op("dynamic-slice.3", t, 300, LOCAL),
                   op("fusion.9", t + 400, 900, LOCAL)]
    return events


def test_five_tuples_read_as_devtrace_reads_them():
    """Stripped of stats and program spans, the events give every
    devtrace reduction its devtrace value."""
    events = _mixed_events() + [op("all-reduce.1", 900_000, 2_000),
                                op("late", 20_000_000, 10)]
    five = [e[:5] for e in events]
    old, new = devtrace.Trace(five), progtrace.Trace(events)
    assert new.window_s == old.window_s
    assert new.idle_share() == old.idle_share()
    assert new.inner_steps(0) == old.inner_steps(0)
    assert new.exchanges(0) == old.exchanges(0)
    assert new.breakdown() == old.breakdown()
    assert progtrace.Trace(five).breakdown() == old.breakdown()


def test_scoped_steps_find_the_vr_loop_by_name():
    tr = progtrace.Trace(_mixed_events())
    assert tr.inner_steps(0)[1] == pytest.approx(2_000e-9)  # the init op
    name, steps, period = tr.scoped_steps(0, "vr/local_epoch")
    assert (name, steps) == ("fusion.9", 20)
    assert period == pytest.approx(5_000e-9)
    assert tr.scoped_steps(0, "vr/init_epoch")[2] == pytest.approx(2_000e-9)
    assert tr.scoped_steps(0, "vr/average") is None
    assert tr.scopes(0) == ["vr/init_epoch", "vr/local_epoch"]


def test_host_idle_counts_idle_inside_host_phases_only():
    """Idle time under prepare, dispatch and finish, each phase averaged
    over its whole spans; never under wait, and a span cut by the window
    is not read."""
    events = [span("bench/traced", 1_000, 100_000),
              # solve 1 begins before the window: its prepare is cut
              span("solve/prepare", 500, 2_000, solve=1),
              span("solve/wait", 3_000, 20_000, solve=1),
              span("solve/finish", 23_000, 4_000, solve=1),
              span("solve/prepare", 30_000, 6_000, solve=2),
              span("solve/coerce", 30_000, 1_000, solve=2),
              span("solve/dispatch", 36_000, 10_000, solve=2),
              span("solve/wait", 46_000, 40_000, solve=2),
              op("a", 3_000, 21_000),            # busy to 24_000
              op("b", 32_000, 1_000),            # 1 us busy in prepare
              op("c", 40_000, 50_000)]           # busy from 40_000 on
    tr = progtrace.Trace(events)
    # finish 23-27k: 3k idle; prepare 30-36k: 5k; dispatch 36-46k: 4k
    assert tr.host_idle_s(0) == pytest.approx((3_000 + 5_000 + 4_000) * 1e-9)
    # each idle gap is named by the innermost span covering its middle
    assert [(n, round(s * 1e9)) for n, s in tr.idle_gaps(0)] == [
        ("bench/traced", 11_000), ("bench/traced", 8_000),
        ("solve/dispatch", 7_000), ("solve/prepare", 2_000)]


def test_host_idle_reads_nothing_without_host_spans():
    tr = progtrace.Trace([span("bench/traced", 0, 100), op("a", 0, 50)])
    assert tr.host_idle_s(0) is None


def test_placed_bytes_sum_place_spans_per_solve():
    events = [span("bench/traced", 0, 1_000),
              span("spmd/place", -50, 10, solve=1, bytes=100),   # read too
              span("spmd/place", 10, 10, solve=2, bytes=170),
              span("spmd/place", 30, 5, solve=2, bytes=9),
              span("spmd/place", 600, 10, solve=3, bytes=170),
              span("spmd/place", 620, 5, solve=3, bytes=9)]
    tr = progtrace.Trace(events)
    assert tr.placed_bytes_per_solve() == pytest.approx((100 + 179 + 179) / 3)
    assert progtrace.Trace(events[:1]).placed_bytes_per_solve() is None


def test_name_ops_join_each_op_to_the_executable_running_it():
    """An op is named by the instruction of its name in the executable that
    the modules line shows running at its start; none outside a module or
    for an instruction the HLO does not name."""
    modules = [(200, 300, "jit_b(2)"), (0, 100, "jit_a(1)")]
    op_names = {"jit_a(1)": {"fusion.1": "jit(a)/vmap(vr/init_epoch)/x"},
                "jit_b(2)": {"fusion.1": "jit(b)/vr/local_epoch/y"}}
    fusion = "%fusion.1 = f32[4]{0:T(128)} fusion(f32[4]{0} %p), kind=kLoop"
    ops = [(fusion, 10, 5), (fusion, 210, 5), (fusion, 150, 5),
           ("%copy.2 = f32[4] copy(f32[4] %p)", 220, 1)]
    out = list(progtrace.name_ops(ops, modules, op_names))
    assert [o[:3] for o in out] == ops
    assert [o[3].get(progtrace.OP_NAME) for o in out] == [
        "jit(a)/vmap(vr/init_epoch)/x", "jit(b)/vr/local_epoch/y", None, None]


def test_hlo_op_names_read_the_executables_a_trace_holds(tmp_path):
    """The trace's metadata plane holds the HLO of the executables, whose
    instructions carry the scopes (a CPU trace: its device ops are not
    traced, the HLO is)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x):
        with jax.named_scope("vr/local_epoch"):
            y = jnp.sin(x) * 2.0
        return y.sum()

    x = jnp.ones(8)
    scoped(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        scoped(x).block_until_ready()
    names = progtrace.hlo_op_names(devtrace.find_xplane(str(tmp_path)))
    ours = [t for m, t in names.items() if m.startswith("jit_scoped(")]
    assert len(ours) == 1
    assert "vr/local_epoch" in {progtrace.scope_of(o) for o in ours[0].values()}
