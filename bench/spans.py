"""Reduce a profiler trace to the program's own spans and scopes.

The program names its serving path in the trace the harness already writes
under ``.bench_trace/`` (``--trace 1``):

* host spans: ``chase.drain`` (arguments ``drain``, ``size``, ``bucket``)
  around one scheduler drain, and inside it ``chase.stack``, ``chase.pad``,
  ``chase.dispatch``, ``chase.fetch`` and ``chase.slice``, each carrying
  the drain's ``drain`` id;
* device scopes: ``jax.named_scope`` names (``chase.flat.scan``,
  ``chase.ivf.probe_round``, ...) in each HLO op's ``op_name``.  On a TPU
  the trace keeps that path in the ``tf_op`` stat of the op's event
  metadata (``jit(run)/jit(fused_scan_topk_batch)/chase.flat.scan/...:``),
  which ``ProfileData`` does not expose, so :func:`op_scopes` reads it from
  the ``.xplane.pb`` file itself.

The reduction (:class:`Spans`) gives, inside the ``bench.window`` span: each
``chase.drain`` with the time of its children and the device busy time
inside its ``chase.fetch``; the count, time and self time of every
``chase.*`` span; the device time under each ``chase.*`` scope (the union
of its ops' intervals, averaged over the chips); and the device idle time
inside ``bench.drain`` spans by the innermost ``chase.*`` span open at the
gap's midpoint (``bench.drain`` where none is).  A trace of a program
without these names reduces to empty tables, never to an error.

    python3 bench/spans.py [<trace dir>]    # prints the reduction as JSON
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness                                              # noqa: E402

trace = harness.own("trace")

PREFIX = "chase."
DRAIN = "chase.drain"
FETCH = "chase.fetch"
OP_NAME_STAT = "tf_op"          # the event-metadata stat holding op_name


@dataclasses.dataclass
class Drain:
    start: float                # ns, the profiler's clock
    end: float
    size: int                   # requests drained
    bucket: int                 # the executable's batch bucket
    parts: dict                 # child span name -> ns inside this drain
    fetch_busy_ns: float        # device busy inside its chase.fetch spans


@dataclasses.dataclass
class Spans:
    window_ns: tuple
    drains: list                # [Drain] inside the window, by start
    spans: dict                 # span name -> (count, ns, self ns)
    scope_ns: dict              # device scope -> busy ns under it
    idle_in_drain: dict         # innermost span -> idle ns in bench.drain


# ---------------------------------------------------------------------------
# op_name of each device op, from the file's event metadata
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: ints for varint and
    fixed fields, bytes for length-delimited ones (sub-messages)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _map_values(entry: bytes) -> bytes:
    return next((v for f, v in _fields(entry) if f == 2), b"")


def scopes_of(op_name: str) -> tuple:
    """The ``chase.*`` components of an op_name path, outermost first."""
    return tuple(p for p in op_name.rstrip(":").split("/")
                 if p.startswith(PREFIX))


def op_scopes(data: bytes) -> dict:
    """Device op name -> its ``chase.*`` scopes, from the event metadata of
    the trace's TPU planes (XSpace: planes = 1; XPlane: name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata: name = 2,
    stats = 5; XStat: metadata_id = 1, str_value = 5)."""
    out: dict = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        parts = collections.defaultdict(list)
        for f, v in _fields(plane):
            if f in (2, 4, 5):
                parts[f].append(v)
        name = parts[2][0].decode() if parts[2] else ""
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        stat_ids = set()
        for entry in parts[5]:
            meta = dict(_fields(_map_values(entry)))
            if meta.get(2, b"").decode() == OP_NAME_STAT:
                stat_ids.add(meta.get(1))
        for entry in parts[4]:
            meta = collections.defaultdict(list)
            for f, v in _fields(_map_values(entry)):
                meta[f].append(v)
            for stat in meta[5]:
                st = dict(_fields(stat))
                if st.get(1) in stat_ids and 5 in st:
                    found = scopes_of(st[5].decode())
                    if found:
                        op = meta[2][0].decode() if meta[2] else ""
                        out[op] = found
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def _holding(spans: list, starts: list, t: float):
    """The span of ``spans`` (sorted, not overlapping) that holds ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i] if i >= 0 and spans[i][2] > t else None


def reduce(profile, scopes: dict) -> Spans:
    """Reduce a ``ProfileData`` and its op scopes (:func:`op_scopes`)."""
    device_ops, host = [], []
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            device_ops.append(ops)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((PREFIX, trace.SPAN_PREFIX)):
                    args = dict(e.stats) if e.name.startswith(PREFIX) else {}
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, args))
    windows = [(s, e) for n, s, e, _ in host if n == trace.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    chips = max(len(device_ops), 1)
    inside = sorted((x for x in host if x[1] >= lo and x[2] <= hi),
                    key=lambda x: (x[1], -x[2]))
    ours = [x for x in inside if x[0].startswith(PREFIX)]
    drain_spans = [x for x in ours if x[0] == DRAIN]
    kids = [x for x in ours if x[0] != DRAIN]
    merged = [trace.union(trace.clip([(s, e) for _, s, e in ops], lo, hi))
              for ops in device_ops]
    covers = [trace.Cover(m) for m in merged]

    # every chase.* span: count, time, self time; each drain's children
    # found by the drain id they carry
    counts = collections.Counter(n for n, *_ in ours)
    totals = collections.Counter()
    for n, s, e, _ in ours:
        totals[n] += e - s
    selfs = trace.self_times([(n, s, e) for n, s, e, _ in ours])
    spans = {n: (counts[n], totals[n], selfs.get(n, 0.0)) for n in counts}
    parts = collections.defaultdict(collections.Counter)
    fetch_busy = collections.Counter()
    for n, s, e, args in kids:
        parts[args.get("drain")][n] += e - s
        if n == FETCH:
            fetch_busy[args.get("drain")] += sum(
                c.within(s, e) for c in covers) / chips
    drains = [Drain(s, e, int(a.get("size", 0)), int(a.get("bucket", 0)),
                    dict(parts[a.get("drain")]), fetch_busy[a.get("drain")])
              for _, s, e, a in drain_spans]

    # device time under each scope
    scope_ns = collections.Counter()
    for ops in device_ops:
        by_scope = collections.defaultdict(list)
        for name, s, e in ops:
            if e > lo and s < hi:
                for scope in scopes.get(name, ()):
                    by_scope[scope].append((max(s, lo), min(e, hi)))
        for scope, ivs in by_scope.items():
            scope_ns[scope] += sum(e - s for s, e in trace.union(ivs)) / chips

    # idle inside bench.drain by the innermost chase.* span
    bench_drains = [x for x in inside if x[0] == trace.DRAIN]
    bd_starts, d_starts, k_starts = ([x[1] for x in group] for group in
                                     (bench_drains, drain_spans, kids))
    idle = collections.Counter()
    for m in merged:
        edges = [lo] + [x for iv in m for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            mid = (s + e) / 2
            if e <= s or not _holding(bench_drains, bd_starts, mid):
                continue
            span = (_holding(kids, k_starts, mid)
                    or _holding(drain_spans, d_starts, mid))
            idle[span[0] if span else trace.DRAIN] += (e - s) / chips
    return Spans((lo, hi), drains, spans, dict(scope_ns), dict(idle))


_CACHE: dict = {}


def load(trace_dir: str) -> Spans:
    """The reduction of the one ``.xplane.pb`` under ``trace_dir``, read
    once per file."""
    path = trace.xplane_file(trace_dir)
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        from jax.profiler import ProfileData
        with open(path, "rb") as f:
            data = f.read()
        _CACHE.clear()
        _CACHE[key] = reduce(ProfileData.from_serialized_xspace(data),
                             op_scopes(data))
    return _CACHE[key]


def of(record) -> Spans | None:
    """The spans of a run record's trace (None without ``--trace 1``)."""
    if record.trace is None:
        return None
    return load(harness.TRACE_DIR)


def summary(r: Spans) -> dict:
    """The reduction in ms: per drain means, scope time per drain, span
    self times, idle inside drains, and the three longest drains."""
    n = max(len(r.drains), 1)
    names = sorted({k for d in r.drains for k in d.parts})
    longest = sorted(r.drains, key=lambda d: d.start - d.end)[:3]
    return {
        "drains": len(r.drains),
        "per_drain_ms": {k: sum(d.parts.get(k, 0.0) for d in r.drains)
                         / n * 1e-6 for k in names},
        "fetch_busy_ms_per_drain": sum(d.fetch_busy_ns for d in r.drains)
        / n * 1e-6,
        "scope_ms_per_drain": {k: v / n * 1e-6
                               for k, v in sorted(r.scope_ns.items())},
        "spans_ms": {k: {"count": c, "total": t * 1e-6, "self": s * 1e-6}
                     for k, (c, t, s) in sorted(r.spans.items())},
        "idle_in_drain_ms": {k: v * 1e-6 for k, v in
                             sorted(r.idle_in_drain.items(),
                                    key=lambda kv: -kv[1])},
        "longest_drains": [
            {"ms": (d.end - d.start) * 1e-6, "size": d.size,
             "parts_ms": {k: v * 1e-6 for k, v in d.parts.items()},
             "fetch_busy_ms": d.fetch_busy_ns * 1e-6} for d in longest],
    }


if __name__ == "__main__":
    where = sys.argv[1] if len(sys.argv) > 1 else harness.TRACE_DIR
    print(json.dumps(summary(load(where)), indent=1))
