"""repro.obs — structured run telemetry.

One JSONL record per run (spans, streamed in-scan metrics, counters,
comms/staleness accounting), OFF by default and zero-overhead when off.
The package is import-light by design: ``import repro.obs`` never imports
jax (``repro``'s force_host_devices contract), and every module here is
safe to import from ``repro.core`` without cycles.

Quickstart::

    from repro import obs, solve, RunSpec

    with obs.recording("run.jsonl"):
        res = solve(RunSpec(algo="centralvr_async", p=4, eta=0.05,
                            rounds=40, speeds=(4.0, 2.0, 1.0, 1.0)))
    # then: python -m repro.launch.obs report run.jsonl

Pieces:

  * :mod:`repro.obs.recorder` — the JSONL sink (``Recorder``), module
    recorder slot (``enable``/``disable``/``active``/``recording``) and
    ``span``, which also lands in a ``jax.profiler`` trace (with
    ``solve_scope`` tagging one call's spans with its id).
  * :mod:`repro.obs.stage`    — ``staged_call``: ``execute`` spans around
    the jitted runners, through the jit cache, and a ``compile`` row for
    each compile JAX reports during the call.
  * :mod:`repro.obs.stream`   — cadence-gated ``jax.debug.callback``
    metric streaming from inside the jitted scans.
  * :mod:`repro.obs.comms`    — analytical bytes-per-collective models.
  * :mod:`repro.obs.staleness`— fetch-staleness histogram + wave stats
    from the deterministic async event schedule.
  * :mod:`repro.obs.schema`   — row schema, validators, and the golden
    provenance key sets the tests pin.
  * :mod:`repro.obs.report`   — timeline/summary rendering for the
    ``repro.launch.obs`` CLI.
"""
from __future__ import annotations

from repro.obs.comms import comms_model
from repro.obs.recorder import (Recorder, active, disable, enable,
                                recording, span)
from repro.obs.schema import SCHEMA_VERSION, SchemaError, validate_file
from repro.obs.stage import staged_call
from repro.obs.staleness import staleness_stats
from repro.obs.stream import scan_metric, stream_active

__all__ = [
    "Recorder", "active", "enable", "disable", "recording", "span",
    "staged_call", "scan_metric", "stream_active",
    "comms_model", "staleness_stats",
    "SCHEMA_VERSION", "SchemaError", "validate_file",
]
