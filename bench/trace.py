"""Reduce a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the per-layer metrics read.

A TPU trace holds one plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops``
line lists every operation the chip ran (a ``while`` op encloses the ops of
its body), and host planes whose lines carry the harness's
``TraceAnnotation`` spans (``bench.window``, ``bench.drain``, ...).  Both
are on the profiler's one clock.  The reduction yields:

* device busy time inside the ``bench.window`` span (the union of the op
  intervals, averaged over the chips) and the window's length;
* the idle gaps inside the window, each attributed to the innermost harness
  span open at the gap's midpoint (``bench.loop`` where none is);
* the device ops that took the most time, by self time (an enclosing op's
  time less that of the ops inside it), under their trace names;
* each ``bench.drain`` span inside the window with the busy time inside it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
DRAIN = "bench.drain"
OUTSIDE = "bench.loop"


@dataclasses.dataclass
class Reduced:
    window_ns: tuple            # (start, end) of the bench.window span
    busy_ns: float              # busy time in the window, mean over chips
    devices: int
    idle_by_span: dict          # span name -> idle ns attributed to it
    top_ops: list               # [(op name, self ns)], most first
    drains: list                # [(start, end, busy ns inside)]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9


def xplane_file(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    return files[0]


def union(intervals) -> list:
    """Merged, sorted [(start, end)] covering the given intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Cover:
    """Merged intervals that answer "how much of [lo, hi) do they cover"
    in logarithmic time."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def within(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)
        j = bisect.bisect_left(self.starts, hi)
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, lo - self.starts[i])
                - max(0.0, self.ends[j - 1] - hi))


def self_times(events) -> dict:
    """Self time per op name on one line: nested events (a ``while`` and
    the ops of its body) count once, in the innermost op."""
    totals: dict = {}
    stack: list = []            # [name, end, start, time of children]

    def pop():
        name, end, start, child = stack.pop()
        totals[name] = totals.get(name, 0.0) + (end - start) - child
        if stack:
            stack[-1][3] += end - start
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            pop()
        stack.append([name, e, s, 0.0])
    while stack:
        pop()
    return totals


def op_name(text: str) -> str:
    """An HLO op's trace name (``%fusion.30 = f32[...] fusion(...)``) cut to
    the op's own name (``fusion.30``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce(profile) -> Reduced:
    """Reduce a ``ProfileData`` (or anything with its planes/lines/events)."""
    device_ops, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            device_ops.append(ops)
        else:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    if not device_ops:
        raise ValueError("the trace holds no TPU device plane")
    merged = [union(clip([(s, e) for _, s, e in ops], lo, hi))
              for ops in device_ops]
    busy = sum(sum(e - s for s, e in m) for m in merged) / len(merged)
    # the harness's inner spans follow one another on one thread
    inner = sorted(((n, s, e) for n, s, e in spans if n != WINDOW),
                   key=lambda x: x[1])
    starts = [s for _, s, _ in inner]
    idle: dict = {}
    for m in merged:
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            name = inner[i][0] if i >= 0 and inner[i][2] > mid else OUTSIDE
            idle[name] = idle.get(name, 0.0) + (e - s) / len(merged)
    totals: dict = {}
    for ops in device_ops:
        for name, t in self_times(
                [(op_name(n), s, e) for n, s, e in ops
                 if e > lo and s < hi]).items():
            totals[name] = totals.get(name, 0.0) + t / len(device_ops)
    top = sorted(totals.items(), key=lambda kv: -kv[1])
    covers = [Cover(m) for m in merged]
    drains = [(s, e, sum(c.within(s, e) for c in covers) / len(covers))
              for n, s, e in inner if n == DRAIN and s >= lo and e <= hi]
    return Reduced((lo, hi), busy, len(device_ops), idle, top, drains)


def load(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(xplane_file(trace_dir)))


def breakdown(r: Reduced, n: int = 10) -> dict:
    """The result line's ``breakdown``: top device ops and idle time by
    what the host was doing, in seconds."""
    return {
        "device_ops": [[name, t * 1e-9] for name, t in r.top_ops[:n]],
        "idle_gaps": [[name, t * 1e-9] for name, t in
                      sorted(r.idle_by_span.items(),
                             key=lambda kv: -kv[1])[:n]],
    }
