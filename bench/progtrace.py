"""The program's own spans and named device loops, read from a trace.

``bench/devtrace.py`` reduces a trace to device operations and the
benchmark's ``bench/`` host spans. This module reads the same trace and
keeps more, leaving every ``devtrace`` reduction as it is:

* the program's host spans (``repro.obs.span``: names starting
  ``solve/``, ``driver/``, ``spmd/``), with their fields as stats: the
  call's ``solve`` id, and counts such as ``spmd/place``'s ``bytes``;
* each device operation's name path, its HLO ``op_name``, from which the
  innermost ``jax.named_scope`` of the VR loops is read: ``vr/init_epoch``,
  ``vr/local_epoch``, ``vr/average``, ``vr/rel_grad_norm``.

On a TPU the ``XLA Ops`` events carry no such stat: an event is named by
its HLO instruction (``%fusion.79 = f32[4,8750] fusion(...)``), and its
timing stats alone. The path is found as a profile viewer finds it: the
``XLA Modules`` line of the same device says which executable ran at the
time (``jit__sync_scan(<id>)``), and the trace's ``/host:metadata`` plane
holds each executable's optimized HLO, whose instruction of that name
carries the ``op_name``. ``events_from_xplane`` joins the two and gives
each device operation the stat ``OP_NAME``.

An event is ``devtrace``'s ``(plane, line, name, start_ns, dur_ns)`` with
an optional sixth item, its stats as a dict. :class:`Trace` gives, for the
same 5-tuples, what ``devtrace.Trace`` gives; with program spans present,
an idle gap is named by the innermost span of either kind that covers it.
Its own reductions:

* ``scoped_steps``: the most frequent operation inside one scope and the
  median distance between its starts: one step of that loop, by name;
* ``host_idle_s``: device idle time inside ``solve()``'s host phases
  (``HOST_SPANS``; never ``solve/wait``), each phase averaged over its
  spans seen whole, summed over the phases;
* ``placed_bytes_per_solve``: ``spmd/place``'s ``bytes`` summed per
  ``solve`` id, averaged over the ids seen.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from bench import devtrace

PROGRAM_PREFIXES = ("solve/", "driver/", "spmd/")
HOST_PREFIXES = (devtrace.SPAN_PREFIX,) + PROGRAM_PREFIXES
# the stat events_from_xplane gives a device operation: its name path,
# scopes included (the HLO instruction's ``op_name`` metadata)
OP_NAME = "op_name"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# an XLA Ops event's name starts with its HLO instruction's
INSTRUCTION = re.compile(r"%?([^\s=]+)")
# a named scope of the VR loops, as a part of a name path; under vmap the
# part reads ``vmap(vr/...)``
SCOPE = re.compile(r"(?:^|[/(])(vr/[a-z_0-9]+)(?=[/)]|$)")
HOST_SPANS = ("solve/prepare", "solve/dispatch", "solve/finish")
PLACE_SPAN = "spmd/place"


def scope_of(path) -> str | None:
    """The innermost VR scope named in an operation's name path."""
    found = SCOPE.findall(path) if isinstance(path, str) else []
    return found[-1] if found else None


def events_from_xplane(path: str) -> list:
    """``devtrace.events_from_xplane``'s events and the program's spans,
    each with its stats: every stat of a host span, ``OP_NAME`` of a
    device operation where its executable's HLO names it."""
    from jax.profiler import ProfileData

    op_names = hlo_op_names(path)
    out = []
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if plane.name == devtrace.HOST_PLANE:
            out += [(plane.name, line.name, e.name, int(e.start_ns),
                     int(e.duration_ns), {k: v for k, v in e.stats})
                    for line in plane.lines for e in line.events
                    if e.name.startswith(HOST_PREFIXES)]
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = ([(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in lines[MODULES_LINE].events]
                   if MODULES_LINE in lines else [])
        ops = [(e.name, int(e.start_ns), int(e.duration_ns))
               for e in lines[devtrace.OPS_LINE].events] \
            if devtrace.OPS_LINE in lines else []
        for name, start, dur, stats in name_ops(ops, modules, op_names):
            out.append((plane.name, devtrace.OPS_LINE, name, start, dur,
                        stats))
    return out


def name_ops(ops, modules, op_names):
    """``(name, start, dur, stats)`` of each device operation ``(name,
    start, dur)``: ``stats`` holds its ``OP_NAME`` when a module of
    ``modules`` (``(start, end, executable)``, one device's) runs at the
    operation's start and ``op_names[executable]`` names its
    instruction."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    seen = {}
    for name, start, dur in ops:
        k = bisect.bisect_right(starts, start) - 1
        module = modules[k][2] if k >= 0 and start < modules[k][1] else None
        if (module, name) not in seen:
            m = INSTRUCTION.match(name)
            path = op_names.get(module, {}).get(m.group(1)) if m else None
            seen[(module, name)] = {OP_NAME: path} if path else {}
        yield name, start, dur, seen[(module, name)]


def hlo_op_names(path: str) -> dict:
    """``{executable: {instruction: op_name}}`` from the optimized HLO of
    every executable that a ``.xplane.pb`` file's ``/host:metadata`` plane
    holds."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:                             # XSpace.planes
            continue
        fields = list(_fields(plane))
        if _text(fields, 2) != METADATA_PLANE:   # XPlane.name
            continue
        # map entries: key = 1, value = 2
        stat_ids = {dict(_fields(_value_of(e, 2))).get(1)
                    for k, e in fields if k == 5   # XPlane.stat_metadata
                    if _text(list(_fields(_value_of(e, 2))), 2) == HLO_STAT}
        out = {}
        for k, entry in fields:
            if k != 4:                           # XPlane.event_metadata
                continue
            meta = list(_fields(_value_of(entry, 2)))
            for stat in (v for n, v in meta if n == 5):    # .stats
                stat = dict(_fields(stat))
                if stat.get(1) in stat_ids and 6 in stat:   # bytes_value
                    out[_text(meta, 2)] = _instruction_op_names(stat[6])
        return out
    return {}


def _instruction_op_names(hlo_proto) -> dict:
    """``{instruction: op_name}`` of an ``xla.HloProto``."""
    out = {}
    module = _value_of(hlo_proto, 1)             # HloProto.hlo_module
    for num, comp in _fields(module):
        if num != 3:                             # .computations
            continue
        for n, inst in _fields(comp):
            if n != 2:                           # .instructions
                continue
            fields = list(_fields(inst))
            meta = [v for k, v in fields if k == 7]      # .metadata
            op_name = _text(list(_fields(meta[0])), 2) if meta else ""
            if op_name:                          # OpMetadata.op_name
                out[_text(fields, 1)] = op_name
    return out


# -- the protobuf wire format, as far as the two messages above need it


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a message: an int for a varint, the
    bytes (a memoryview) for a length-delimited or fixed-size field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _value_of(message, number: int):
    return next((v for n, v in _fields(message) if n == number), b"")


def _text(fields, number: int) -> str:
    return next((bytes(v).decode() for n, v in fields if n == number), "")


def median_period(starts) -> float | None:
    """Median seconds between sorted ``starts`` (ns); None under 3."""
    seen = sorted(starts)
    if len(seen) < 3:
        return None
    gaps = sorted(b - a for a, b in zip(seen, seen[1:]))
    return gaps[len(gaps) // 2] * 1e-9


class Trace(devtrace.Trace):
    """The reduced trace of one traced window, program spans and scopes
    kept."""

    def __init__(self, events: list):
        super().__init__([e[:5] for e in events if e[1] != MODULES_LINE])
        self.program = []                 # [(name, s, e, stats)]
        self.scoped = defaultdict(list)   # (device, scope) -> [(name, s, e)]
        for plane, line, name, start, dur, *rest in events:
            stats = rest[0] if rest else {}
            m = devtrace.DEVICE_PLANE.match(plane)
            if m and line != MODULES_LINE:
                scope = scope_of(stats.get(OP_NAME))
                if scope and start + dur > self.lo and start < self.hi:
                    self.scoped[(int(m.group(1)), scope)].append(
                        (name, start, start + dur))
            elif not m and name.startswith(PROGRAM_PREFIXES):
                self.program.append((name, start, start + dur, stats))
        self.program.sort(key=lambda p: p[1])
        # idle gaps are named by the innermost span of either kind
        self.spans = self.spans + [(n, s, e) for n, s, e, _ in self.program]

    def scopes(self, dev: int) -> list:
        return sorted(s for d, s in self.scoped if d == dev)

    def scoped_steps(self, dev: int, scope: str):
        """(operation, steps seen, median seconds per step) of the loop
        named ``scope`` on ``dev``, or None where no operation in it
        repeats."""
        starts = defaultdict(list)
        for n, s, _ in self.scoped.get((dev, scope), ()):
            starts[n].append(s)
        if not starts:
            return None
        name = max(starts, key=lambda k: (len(starts[k]), k))
        period = median_period(starts[name])
        return None if period is None else (name, len(starts[name]), period)

    def spans_named(self, name: str, whole: bool = True) -> list:
        """The program's spans called ``name``; with ``whole``, only those
        lying wholly inside the window."""
        return [p for p in self.program if p[0] == name and
                (not whole or (p[1] >= self.lo and p[2] <= self.hi))]

    def idle_in_s(self, dev: int, s: int, e: int) -> float:
        """Seconds of ``[s, e]`` in which ``dev`` ran no operation."""
        return ((e - s) - devtrace.union_ns(
            ((os, oe) for _, os, oe in self.ops[dev]), s, e)) * 1e-9

    def host_idle_s(self, dev: int) -> float | None:
        """Device idle seconds inside one solve's ``HOST_SPANS``: each
        phase's idle averaged over its spans seen whole, summed over the
        phases seen; None where none is."""
        total, seen = 0.0, False
        for name in HOST_SPANS:
            spans = self.spans_named(name)
            if spans:
                seen = True
                total += sum(self.idle_in_s(dev, s, e)
                             for _, s, e, _ in spans) / len(spans)
        return total if seen else None

    def placed_bytes_per_solve(self) -> float | None:
        """``bytes`` of the ``spmd/place`` spans (whole or not), summed
        per ``solve`` id and averaged over the ids seen."""
        per = Counter()
        for _, _, _, stats in self.spans_named(PLACE_SPAN, whole=False):
            if "bytes" in stats and "solve" in stats:
                per[stats["solve"]] += stats["bytes"]
        return sum(per.values()) / len(per) if per else None
