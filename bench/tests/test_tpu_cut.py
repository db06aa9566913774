"""The trace reductions on a cut of a real TPU trace.

``tpu_cut_ijcnn1.sync-p4.json`` is 17 ms of one traced slice of
``ijcnn1.sync-p4`` on a TPU v5 lite: the last VR steps of one solve
(``vr/local_epoch``), its final average and metric, the boundary (its
``solve/finish``, the next solve's ``solve/prepare`` and ``solve/dispatch``
up to ``driver/init``) and the first steps of the next init epoch
(``vr/init_epoch``). The events are those ``progtrace.events_from_xplane``
read from the slice's ``.xplane.pb`` (device ops with their ``op_name``
resolved from the trace's HLO, host spans with their stats), kept where
they overlap the cut; the window span ``bench/traced`` is set to the
cut's bounds. Strings are stored once, in tables.

``PINNED`` holds what ``devtrace``, unchanged, reads on the events it
keeps (device ops and ``bench/`` spans); ``progtrace``, which extends it,
must read the same on the whole cut.

    python -m pytest -q bench/tests
"""
import json
import os
import types

import pytest

from bench import devtrace, progtrace
from bench.run import load_reader

CUT = os.path.join(os.path.dirname(__file__), "tpu_cut_ijcnn1.sync-p4.json")
READERS = ("inner_step_us", "exchange_us_per_round", "device_idle_share")
PINNED = {
    "inner_step_us": 1.748,
    "exchange_us_per_round": None,
    "device_idle_share": 89.92593210803138,
    "busy_s": 0.0017261190000000001,
    "window_s": 0.01713428,
    "inner_steps": (650, 1.748e-06),
    "idle_gaps_s": [0.004952356000000001, 0.004779981,
                    0.0011347430000000001, 0.0008181110000000001,
                    0.0006724260000000001, 0.000650651, 0.000549957,
                    0.000520199, 0.000448397, 0.00038202500000000003],
}


@pytest.fixture(scope="module")
def events():
    with open(CUT) as f:
        doc = json.load(f)
    return [(doc["planes"][p], doc["lines"][line], doc["names"][n], s, d,
             doc["stats"][k]) for p, line, n, s, d, k in doc["events"]]


def _devtrace_view(events):
    """What ``devtrace.events_from_xplane`` keeps of the same trace."""
    return [e[:5] for e in events
            if devtrace.DEVICE_PLANE.match(e[0])
            or e[2].startswith(devtrace.SPAN_PREFIX)]


def _readings(tr):
    run = types.SimpleNamespace(trace=tr)
    out = {name: load_reader(name)(run) for name in READERS}
    out.update(busy_s=tr.mean_busy_s(), window_s=tr.window_s,
               inner_steps=tr.inner_steps(0),
               idle_gaps_s=[s for _, s in tr.idle_gaps(0)])
    return out


@pytest.mark.parametrize("reduce", ["devtrace", "progtrace"])
def test_every_existing_reading_is_the_pinned_one(events, reduce):
    tr = (devtrace.Trace(_devtrace_view(events)) if reduce == "devtrace"
          else progtrace.Trace(events))
    assert _readings(tr) == PINNED
    assert tr.exchanges(0) == []


def test_breakdown_ops_stay_and_gaps_take_program_names(events):
    old = devtrace.Trace(_devtrace_view(events)).breakdown()
    new = progtrace.Trace(events).breakdown()
    assert new["device_ops"] == old["device_ops"]
    assert {n for n, _ in old["idle_gaps"]} == {devtrace.WINDOW_SPAN}
    assert [n for n, _ in new["idle_gaps"][:3]] == [
        "solve/auto_eta", "solve/finish", "driver/keys"]
    assert all(n.startswith(progtrace.PROGRAM_PREFIXES)
               for n, _ in new["idle_gaps"])


def test_vr_and_init_steps_are_found_by_scope(events):
    """By name, the VR step is not the most frequent op of the cut (that
    is the init epoch's, as ``inner_step_us`` reads it)."""
    tr = progtrace.Trace(events)
    assert tr.scopes(0) == ["vr/average", "vr/init_epoch", "vr/local_epoch",
                            "vr/rel_grad_norm"]
    _, steps, period = tr.scoped_steps(0, "vr/local_epoch")
    assert (steps, period) == (166, 2.102e-06)
    _, steps, period = tr.scoped_steps(0, "vr/init_epoch")
    assert (steps, period) == (650, 1.748e-06)
    assert tr.scoped_steps(0, "vr/average") is None


def test_host_idle_reads_the_program_spans(events):
    """The cut holds one solve's finish and the next one's prepare whole;
    its dispatch runs past the cut, so it is not read."""
    tr = progtrace.Trace(events)
    assert {p[3]["solve"] for p in tr.program} == {4, 5}
    assert [p[0] for p in tr.spans_named("solve/dispatch")] == []
    finish, = tr.spans_named("solve/finish")
    prepare, = tr.spans_named("solve/prepare")
    want = (tr.idle_in_s(0, finish[1], finish[2])
            + tr.idle_in_s(0, prepare[1], prepare[2]))
    assert tr.host_idle_s(0) == pytest.approx(want)
    assert tr.host_idle_s(0) == pytest.approx(0.011174417)
