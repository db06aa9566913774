"""The trace-to-metrics reduction, on hand-made events laid out as a TPU
trace is: ``/device:TPU:<i>`` planes with an ``XLA Ops`` line, and the
benchmark's ``bench/`` host spans.

    python -m pytest -q bench/tests
"""
import pytest

from bench import devtrace

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
OPS, HOST = devtrace.OPS_LINE, devtrace.HOST_PLANE


def op(name, start, dur, plane=DEV0):
    return (plane, OPS, name, start, dur)


def span(name, start, dur):
    return (HOST, "python", name, start, dur)


def brute_union(intervals, lo, hi):
    covered = set()
    for s, e in intervals:
        covered.update(range(max(s, lo), min(e, hi)))
    return len(covered)


@pytest.mark.parametrize("intervals,lo,hi", [
    ([(0, 10), (5, 15), (20, 30)], 0, 40),
    ([(0, 10), (5, 15), (20, 30)], 7, 25),
    ([(3, 4), (4, 9), (1, 2), (8, 12)], 0, 12),
    ([(0, 100), (10, 20)], 50, 60),
    ([], 0, 10),
])
def test_union_matches_brute_force(intervals, lo, hi):
    assert devtrace.union_ns(intervals, lo, hi) == brute_union(intervals,
                                                               lo, hi)


def test_window_busy_idle_and_op_time():
    events = [span("bench/traced", 100, 100),
              op("fusion.1", 90, 20),       # clipped to [100, 110]
              op("fusion.2", 105, 10),      # inside fusion.1 partly
              op("fusion.1", 150, 10),
              op("late", 250, 10)]          # outside the window
    tr = devtrace.Trace(events)
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s(0) == pytest.approx(25e-9)     # [100,115] + [150,160]
    assert tr.idle_share() == pytest.approx(0.75)
    ops = tr.op_seconds(0)
    assert ops["fusion.1"] == pytest.approx(20e-9)
    assert ops["fusion.2"] == pytest.approx(10e-9)
    assert "late" not in ops


def test_busy_is_averaged_over_chips():
    events = [span("bench/traced", 0, 100), op("a", 0, 100),
              op("a", 0, 50, plane=DEV1)]
    tr = devtrace.Trace(events)
    assert tr.devices == [0, 1]
    assert tr.mean_busy_s() == pytest.approx(75e-9)
    assert tr.idle_share() == pytest.approx(0.25)


def test_inner_steps_take_the_most_frequent_op_and_median_period():
    step_ns = 7_000
    events = [span("bench/traced", 0, 10_000_000)]
    for k in range(50):
        t = 1_000 + k * step_ns + (500_000 if k >= 30 else 0)  # a round gap
        events += [op("dynamic-slice.3", t, 300), op("fusion.9", t + 400, 900)]
    events += [op("all-reduce.1", 900_000, 2_000)]
    tr = devtrace.Trace(events)
    steps, per_step = tr.inner_steps(0)
    assert steps == 50
    assert per_step == pytest.approx(step_ns * 1e-9)


def test_exchanges_group_collectives_by_gap():
    gap = devtrace.EXCHANGE_GAP_NS
    events = [span("bench/traced", 0, 10 * gap),
              op("all-reduce.1", 100, 1_000), op("fusion", 2_000, 500),
              op("all-reduce.2", 3_000, 3_000),
              op("all-reduce.1", 3 * gap, 2_000),
              op("fusion.7", 3 * gap + 10_000, 100)]
    tr = devtrace.Trace(events)
    groups = tr.exchanges(0)
    assert [g[1] for g in groups] == [2, 1]
    assert groups[0][0] == pytest.approx(4_000e-9)
    assert groups[1][0] == pytest.approx(2_000e-9)


def test_idle_gaps_are_named_by_the_innermost_covering_span():
    events = [span("bench/traced", 0, 1_000),
              span("bench/solve0", 0, 400), span("bench/solve1", 600, 400),
              op("a", 0, 100), op("b", 300, 50), op("c", 900, 100)]
    tr = devtrace.Trace(events)
    gaps = tr.idle_gaps(0)
    # [350, 900] is the longest; its middle 625 lies in bench/solve1
    assert gaps[0] == ["bench/solve1", pytest.approx(550e-9)]
    assert gaps[1] == ["bench/solve0", pytest.approx(200e-9)]
    assert len(tr.breakdown()["device_ops"]) == 3


def test_a_trace_without_device_ops_reads_nothing():
    tr = devtrace.Trace([span("bench/traced", 0, 100)])
    assert tr.devices == []
    assert tr.mean_busy_s() is None
    assert tr.idle_share() is None
