"""Reduces a JAX profiler trace to what the per-layer metrics read.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` file the profiler
writes.  In it every device is a plane named ``/device:TPU:<n>`` whose
``XLA Ops`` line holds one event per operation the device ran, and whose
``XLA Modules`` line holds one event per program; the host is the
``/host:CPU`` plane, where ``jax.profiler.TraceAnnotation`` spans appear
under their names when host tracing is on.  Device and host events share
one clock: nanoseconds from ``profile_start_time``, the wall-clock
instant (``time.time_ns``) the ``Task Environment`` plane records.

The benchmark traces with host tracing off, because the runtime's host
transposes of each chunk fill that trace (thousands of events per 66 MB
chunk), and hands the reduction its own spans instead, timed from a known
wall-clock instant, which the reduction shifts onto the trace's clock.

The reduction gives:

- ``busy_s``: the length of the union of the device's operation
  intervals, averaged over the devices; ``window_s``, the trace's length;
- ``op_seconds``: device time per operation, named
  ``<program>/<operation>`` as the trace prints them (``jit_`` prefix
  and hash dropped from the program, ``%`` and the shape from the op);
- ``spans``: the host spans, read from the trace by name or given, and
  for each idle gap of device 0 the span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def merge_intervals(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def op_label(module: str, op: str) -> str:
    """``jit_event_filter_batch(123)`` and ``%copy.4 = f32[...] copy(...)``
    become ``event_filter_batch/copy.4``."""
    m = re.sub(r"\(\d+\)$", "", module)
    m = m[4:] if m.startswith("jit_") else m
    o = op.split(" = ", 1)[0].strip().lstrip("%")
    return f"{m}/{o}" if m else o


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy: List[List[Interval]]          # per device, merged, seconds
    op_seconds: Dict[str, float]
    spans: List[Tuple[str, float, float]]

    @property
    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the devices traced."""
        if not self.busy:
            return 0.0
        return sum(e - s for dev in self.busy for s, e in dev) \
            / len(self.busy)

    def span_intervals(self, names: Iterable[str]) -> List[Interval]:
        names = set(names)
        return merge_intervals((s, e) for n, s, e in self.spans
                               if n in names)

    def busy_within(self, names: Iterable[str]) -> float:
        """Device-busy seconds inside the host spans of ``names``,
        averaged over the devices."""
        if not self.busy:
            return 0.0
        spans = self.span_intervals(names)
        return sum(overlap(dev, spans) for dev in self.busy) / len(self.busy)

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.op_seconds.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest gaps between device 0's operations within the
        trace, each named for the host span that covers more than half of
        it, or ``outside spans``."""
        if not self.busy:
            return []
        gaps, t = [], 0.0
        for s, e in self.busy[0] + [(self.window_s, self.window_s)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        named = []
        for s, e in gaps:
            best, cover = "outside spans", 0.5 * (e - s)
            for name, hs, he in self.spans:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            named.append([best, e - s])
        named.sort(key=lambda g: -g[1])
        return named[:n]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _stats(obj) -> dict:
    try:
        return {k: v for k, v in obj.stats}
    except (TypeError, ValueError):
        return {}


def reduce_trace(path: str, span_names: Sequence[str],
                 window_s: float = None,
                 spans: Sequence[Tuple[str, float, float]] = None,
                 origin_ns: int = None) -> Reduction:
    """Reduce one ``.xplane.pb`` file (or the newest under a directory).

    Host spans are the trace's own events named in ``span_names``, or,
    where ``spans`` is given, those ``(name, start, end)`` seconds from the
    wall-clock instant ``origin_ns``, placed on the trace's clock."""
    import jax
    if os.path.isdir(path):
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    busy: List[List[Interval]] = []
    ops: Dict[str, float] = {}
    given, spans = spans, []
    last = 0.0
    env = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            env = _stats(plane)
        if _DEVICE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in lines.get("XLA Modules", []))
            events = lines.get("XLA Ops") or lines.get("XLA Modules", [])
            if not events:
                continue
            ivals = []
            mi = 0
            for e in sorted(events, key=lambda e: e.start_ns):
                s, d = e.start_ns, e.duration_ns
                ivals.append((s * 1e-9, (s + d) * 1e-9))
                last = max(last, (s + d) * 1e-9)
                while mi + 1 < len(modules) and modules[mi + 1][0] <= s:
                    mi += 1
                mod = (modules[mi][2] if modules and modules[mi][0] <= s
                       <= modules[mi][1] else "")
                label = op_label(mod, e.name)
                ops[label] = ops.get(label, 0.0) + d * 1e-9
            busy.append(merge_intervals(ivals))
        elif plane.name.startswith("/host:") and given is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
                        last = max(last, s + e.duration_ns * 1e-9)
    start, stop = env.get("profile_start_time"), env.get("profile_stop_time")
    if given is not None:
        if not start:
            raise ValueError(f"{path} records no profile_start_time")
        shift = (int(origin_ns) - int(start)) * 1e-9
        spans = [(n, s + shift, e + shift) for n, s, e in given]
    if window_s is None:
        window_s = ((int(stop) - int(start)) * 1e-9 if start and stop
                    else last)
    spans.sort(key=lambda s: s[1])
    return Reduction(window_s, busy, ops, spans)
