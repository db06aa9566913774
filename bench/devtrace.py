"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to events ``(plane, line, name, start_ns, dur_ns)``:
the device's operations (plane ``/device:TPU:<i>``, line ``XLA Ops``) and
the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
starting ``bench/``). The traced window is the host span
``bench/traced``. Everything below is clipped to it.

* busy time: the union of the device's operation intervals;
* idle share: 1 - busy / window;
* operation time by name;
* collective time: operations whose name is a collective, grouped into
  exchanges (a gap of more than ``EXCHANGE_GAP_NS`` starts a new one);
* inner steps: the operation that runs most often is in the innermost
  loop; its count is the number of steps seen, and the median distance
  between its starts is the time of one step.
"""
from __future__ import annotations

import glob
import os
import re
from collections import Counter, defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)\b")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/traced"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"collective-broadcast")
EXCHANGE_GAP_NS = 10_000_000


def events_from_xplane(path: str) -> list:
    """The events this module reads, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = []
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append((plane.name, line.name, e.name,
                            int(e.start_ns), int(e.duration_ns)))
    return out


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {logdir}, "
                                f"found {paths}")
    return paths[0]


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals inside
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Trace:
    """The reduced trace of one traced window."""

    def __init__(self, events: list):
        self.ops = defaultdict(list)      # device index -> [(name, s, e)]
        self.spans = []                   # [(name, s, e)] host spans
        for plane, _line, name, start, dur in events:
            m = DEVICE_PLANE.match(plane)
            if m:
                self.ops[int(m.group(1))].append((name, start, start + dur))
            elif name.startswith(SPAN_PREFIX):
                self.spans.append((name, start, start + dur))
        windows = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if windows:
            self.lo, self.hi = windows[0]
        else:
            every = [(s, e) for ops in self.ops.values() for _, s, e in ops]
            self.lo = min((s for s, _ in every), default=0)
            self.hi = max((e for _, e in every), default=0)
        for dev in self.ops:
            self.ops[dev] = sorted(
                ((n, s, e) for n, s, e in self.ops[dev]
                 if e > self.lo and s < self.hi), key=lambda o: o[1])

    @property
    def devices(self) -> list:
        return sorted(d for d, ops in self.ops.items() if ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, dev: int) -> float:
        return union_ns(((s, e) for _, s, e in self.ops[dev]),
                        self.lo, self.hi) * 1e-9

    def mean_busy_s(self) -> float | None:
        devs = self.devices
        if not devs:
            return None
        return sum(self.busy_s(d) for d in devs) / len(devs)

    def idle_share(self) -> float | None:
        busy = self.mean_busy_s()
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    def op_seconds(self, dev: int) -> Counter:
        out = Counter()
        for n, s, e in self.ops[dev]:
            out[n] += (min(e, self.hi) - max(s, self.lo)) * 1e-9
        return out

    def mean_op_seconds(self) -> Counter:
        devs = self.devices
        out = Counter()
        for d in devs:
            for n, sec in self.op_seconds(d).items():
                out[n] += sec / len(devs)
        return out

    def exchanges(self, dev: int) -> list:
        """Collective operations grouped into exchanges: a list of
        ``[seconds, count]``, one per exchange seen."""
        groups, last_end = [], None
        for n, s, e in self.ops[dev]:
            if not COLLECTIVE.search(n):
                continue
            if last_end is None or s - last_end > EXCHANGE_GAP_NS:
                groups.append([0.0, 0])
            groups[-1][0] += (e - s) * 1e-9
            groups[-1][1] += 1
            last_end = e
        return groups

    def inner_steps(self, dev: int) -> tuple[int, float] | None:
        """(steps seen, median seconds per step) of the innermost loop,
        or None where no operation repeats."""
        starts = defaultdict(list)
        for n, s, _ in self.ops[dev]:
            starts[n].append(s)
        if not starts:
            return None
        name = max(starts, key=lambda k: (len(starts[k]), k))
        seen = sorted(starts[name])
        if len(seen) < 3:
            return None
        gaps = sorted(b - a for a, b in zip(seen, seen[1:]))
        return len(seen), gaps[len(gaps) // 2] * 1e-9

    def idle_gaps(self, dev: int, top: int = 10) -> list:
        """The longest idle gaps on ``dev``, each named by the innermost
        benchmark span that covers its middle."""
        gaps, cur = [], self.lo
        for _, s, e in self.ops[dev]:
            if s > cur:
                gaps.append((cur, min(s, self.hi)))
            cur = max(cur, e)
        if cur < self.hi:
            gaps.append((cur, self.hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            mid = (s + e) // 2
            covering = [(se - ss, n) for n, ss, se in self.spans
                        if ss <= mid <= se and n != WINDOW_SPAN]
            label = min(covering)[1] if covering else WINDOW_SPAN
            out.append([label, (e - s) * 1e-9])
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = self.mean_op_seconds().most_common(top)
        dev = self.devices[0] if self.devices else None
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": self.idle_gaps(dev, top) if dev is not None
                else []}
