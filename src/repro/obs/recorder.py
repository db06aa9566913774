"""The run recorder: structured telemetry rows to a JSONL sink.

One :class:`Recorder` per run: every row it writes carries the run id, a
monotonic timestamp relative to the recorder's start, and a ``kind``
(``span`` / ``metric`` / ``event``) whose required fields are pinned by
``obs/schema.py``.  Rows are appended to one JSONL file under a lock, so
host callbacks firing from XLA's runtime threads (the streamed in-scan
metric path, ``obs/stream.py``) interleave safely with the main thread's
spans.

Telemetry is OFF by default: the module-level active recorder is ``None``
until :func:`enable` (or the :func:`recording` context manager) installs
one, and every producer in the runtime checks :func:`active` first — the
telemetry-off hot path is the exact pre-telemetry program (DESIGN.md
§Observability).  Zero dependencies beyond the stdlib; importing this
module never imports jax.

:func:`span` is also the program's one way into the profiler's trace: it
always opens a ``jax.profiler.TraceAnnotation`` carrying its fields as
event stats (a native no-op check when no profile is being taken), so the
same span names land in a ``jax.profiler`` trace, on the device timeline's
clock, and in the JSONL record when a recorder is active.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
import uuid
from typing import IO, Optional

SCHEMA_VERSION = 1

_LOCK = threading.Lock()          # guards the module-level active recorder
_ACTIVE: Optional["Recorder"] = None

# the per-call id every span opened inside one ``solve()`` carries
_SOLVE: contextvars.ContextVar = contextvars.ContextVar("repro_obs_solve",
                                                        default=None)
_SOLVE_IDS = itertools.count(1)


class Recorder:
    """JSONL telemetry sink for one run.

    ``path`` is the target file (created/truncated on construction; parent
    directories are created).  ``stream_every`` gates the streamed in-scan
    metric cadence: a ``metric`` row is dropped unless
    ``step % stream_every == 0`` (the final step of a stream is the
    producer's responsibility — drivers emit every round and the recorder
    keeps the cadence subset, so enabling telemetry never changes what the
    scan computes).
    """

    def __init__(self, path: str, *, run_id: Optional[str] = None,
                 stream_every: int = 1):
        if stream_every < 1:
            raise ValueError(f"stream_every: need >= 1, got {stream_every}")
        self.path = str(path)
        self.run_id = run_id or (
            time.strftime("%Y%m%d-%H%M%S") + "-" + uuid.uuid4().hex[:6])
        self.stream_every = int(stream_every)
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        parent = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(parent, exist_ok=True)
        self._f: Optional[IO[str]] = open(self.path, "w")
        self.event("run_start", pid=os.getpid(),
                   wall=time.time())

    # -- row plumbing -------------------------------------------------------

    def _write(self, row: dict) -> None:
        with self._lock:
            if self._f is None:      # closed: late callbacks drop silently
                return
            self._f.write(json.dumps(row, default=str) + "\n")
            self._f.flush()

    def _row(self, kind: str, name: str, **fields) -> dict:
        return {"v": SCHEMA_VERSION, "run": self.run_id,
                "t": time.perf_counter() - self._t0,
                "kind": kind, "name": name, **fields}

    # -- producers ----------------------------------------------------------

    def event(self, name: str, **fields) -> None:
        """A point-in-time structured row (counters, provenance, rows)."""
        self._write(self._row("event", name, **fields))

    def metric(self, name: str, step: int, value: float, **fields) -> None:
        """A streamed scalar; cadence-gated by ``stream_every``."""
        step = int(step)
        if step % self.stream_every:
            return
        self._write(self._row("metric", name, step=step, value=float(value),
                              **fields))

    def clock(self) -> float:
        """Seconds since the recorder started: the clock of ``t``/``t0``."""
        return time.perf_counter() - self._t0

    def span_row(self, name: str, t0: float, dur_s: float, **fields) -> None:
        """One ``span`` row for a phase timed elsewhere (``t0`` on
        :meth:`clock`), with the enclosing :func:`solve_scope`'s id."""
        self._write(self._row("span", name, t0=t0, dur_s=dur_s,
                              **_with_solve(fields)))

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        """Timed phase: emits one ``span`` row with ``t0``/``dur_s`` on
        exit (exceptions still close the span, flagged ``failed``)."""
        t0 = self.clock()
        try:
            yield self
        except BaseException:
            self.span_row(name, t0, self.clock() - t0, failed=True, **fields)
            raise
        self.span_row(name, t0, self.clock() - t0, **fields)

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# ---------------------------------------------------------------------------
# Module-level active recorder (the switch every producer checks)
# ---------------------------------------------------------------------------

def active() -> Optional[Recorder]:
    """The installed recorder, or None (telemetry off — the default)."""
    return _ACTIVE


def enable(path: str, *, run_id: Optional[str] = None,
           stream_every: int = 1) -> Recorder:
    """Install a recorder writing to ``path``; replaces (and closes) any
    previously active one."""
    global _ACTIVE
    rec = Recorder(path, run_id=run_id, stream_every=stream_every)
    with _LOCK:
        old, _ACTIVE = _ACTIVE, rec
    if old is not None:
        old.close()
    return rec


def disable() -> None:
    """Uninstall (and close) the active recorder, if any."""
    global _ACTIVE
    with _LOCK:
        old, _ACTIVE = _ACTIVE, None
    if old is not None:
        old.close()


@contextlib.contextmanager
def recording(path: str, *, run_id: Optional[str] = None,
              stream_every: int = 1):
    """Scoped telemetry: enable for the block, always disable after."""
    rec = enable(path, run_id=run_id, stream_every=stream_every)
    try:
        yield rec
    finally:
        disable()


def _with_solve(fields: dict) -> dict:
    solve = _SOLVE.get()
    return fields if solve is None else {"solve": solve, **fields}


@contextlib.contextmanager
def solve_scope():
    """Give every span opened inside the block (in this thread or task)
    the field ``solve``: an id fresh for each block, so that one call's
    spans group together."""
    token = _SOLVE.set(next(_SOLVE_IDS))
    try:
        yield
    finally:
        _SOLVE.reset(token)


@contextlib.contextmanager
def span(name: str, **fields):
    """A named phase of the program, with ``fields`` (counts, labels).

    It opens a ``jax.profiler.TraceAnnotation(name, **fields)`` whenever
    jax is already imported (never importing it here), so a profile
    taken around the call shows the span on the trace's clock with its
    fields as event stats; with no profile running that is a native no-op
    check. When a recorder is active it also writes one JSONL ``span``
    row. It adds no device sync. Inside :func:`solve_scope` the span
    carries that call's ``solve`` id."""
    fields = _with_solve(fields)
    jax = sys.modules.get("jax")
    rec = active()
    with (jax.profiler.TraceAnnotation(name, **fields) if jax is not None
          else contextlib.nullcontext()):
        if rec is None:
            yield None
        else:
            with rec.span(name, **fields):
                yield rec
