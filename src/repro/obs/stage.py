"""Recorded calls of the jitted scan runners.

``solve()`` (and the driver wrappers it dispatches to) call their
module-level jitted runners through :func:`staged_call`.  With no recorder
active it is a plain call.  With one active, the call still goes through
the jit cache, so a warm call compiles nothing, and the record gets:

    <label>/execute   the call, blocked on (so the duration is real work,
                      not async dispatch), any compile included
    <label>/compile   one row per XLA compilation JAX reports
                      (``BACKEND_COMPILE_EVENT``) during the call, in this
                      thread; none on a warm call

plus, on a call that compiled, a ``comms_hlo`` event with the
per-collective-kind result bytes of the compiled module
(``roofline/analysis.collective_bytes`` — the same result-shape
convention as the roofline reports), which is the measured cross-check of
the analytical comms model ``solve()`` embeds in provenance.  Reading the
module lowers and compiles the runner once more for the same argument
shapes, outside the execute span, on compiling calls only.

Convention: dynamic arguments positional, static arguments as keywords.
Donation declared on the runner is honored exactly as by a direct call.
"""
from __future__ import annotations

import contextlib
import threading

from repro.obs import recorder as _recorder


def staged_call(fn, *args, _label: str, **statics):
    """Call jitted ``fn(*args, **statics)``; recorded as above when a
    recorder is active, a plain (cached) call otherwise."""
    rec = _recorder.active()
    if rec is None:
        return fn(*args, **statics)

    import jax

    lowerable = hasattr(fn, "lower")
    if not lowerable:
        # no compiled module to read comms from (plain callable, exotic
        # wrapper): say why, and run the call all the same
        rec.event("stage_fallback", label=_label,
                  reason=f"{type(fn).__name__} has no .lower")
    # shapes before the call: donated arguments are gone after it
    shapes = jax.tree_util.tree_map(_shape_of, args) if lowerable else None
    with _compiles(rec.clock) as compiles:
        with _recorder.span(f"{_label}/execute"):
            out = jax.block_until_ready(fn(*args, **statics))
    for t0, secs in compiles:
        rec.span_row(f"{_label}/compile", t0=t0, dur_s=secs)
    if compiles and lowerable:
        _record_hlo_comms(rec, _label, fn, shapes, statics)
    return out


@contextlib.contextmanager
def _compiles(clock):
    """The XLA compilations JAX reports in this thread during the block,
    as ``(start on clock(), seconds)`` each."""
    import jax
    from jax._src import dispatch

    seen, me = [], threading.get_ident()

    def on_duration(name, secs, **_):
        if (name == dispatch.BACKEND_COMPILE_EVENT
                and threading.get_ident() == me):
            seen.append((clock() - secs, secs))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _shape_of(leaf):
    import jax

    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=leaf.sharding,
                                    weak_type=leaf.aval.weak_type)
    return leaf


def _record_hlo_comms(rec, label: str, fn, shapes, statics) -> None:
    """Per-collective result bytes of the compiled module, best effort."""
    try:
        from repro.roofline import analysis

        compiled = fn.lower(*shapes, **statics).compile()
        rec.event("comms_hlo", label=label,
                  **analysis.collective_bytes(compiled.as_text()))
    except Exception as e:     # telemetry must never fail the run
        rec.event("comms_hlo_error", label=label, reason=repr(e))
