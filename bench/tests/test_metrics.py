"""Every metric reader on a synthetic run record whose answers are known."""
import json
import os

import numpy as np
import pytest

import harness

trace = harness.own("trace")
peaks = harness.own("peaks")


def _timeline():
    # five requests in a 2 s window; the last one failed
    due = np.array([0.0, 0.1, 0.2, 0.3, 0.4])
    start = np.array([0.05, 0.15, 0.25, 2.5, 0.45])
    done = np.array([0.06, 0.17, 0.30, 2.6, 0.5])
    ok = np.array([True, True, True, True, False])
    return harness.Timeline(
        t0=0.0, end=2.0, due=due, sent=due, start=start, done=done, ok=ok,
        answers={"probes": np.array([8, 10, 12, 30, 0])},
        drains=[(0.05, 0.06, 64), (0.15, 0.17, 64), (0.25, 0.30, 33)],
        counters=({"executed": 10, "batches": 2},
                  {"executed": 40, "batches": 8}),
        compiles=0)


def _reduced():
    ms = 1_000_000
    return trace.Reduced(window_ns=(0, 2000 * ms), busy_ns=500 * ms,
                         devices=1, idle_by_span={}, top_ops=[],
                         drains=[(0, 40 * ms, 30 * ms),
                                 (50 * ms, 60 * ms, 9 * ms),
                                 (70 * ms, 100 * ms, 25 * ms)])


def _record(trace_on=True):
    cell = harness.load_cell("laion1m_flat.q1_closed128")
    return harness.Record(cell, _timeline(), 42.5, {"recall": 0.97},
                          _reduced() if trace_on else None,
                          peaks.peaks_for("TPU v5 lite"))


def _read(name, record):
    return harness.load_module("metrics", name).read(record)


def test_every_metric_in_benchmark_json_has_a_reader():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_end_to_end_readers():
    r = _record(trace_on=False)
    assert _read("qps", r) == pytest.approx(3 / 2.0)     # 3 answered by 2 s
    lat = np.array([0.06, 0.07, 0.10, 2.3, np.inf]) * 1e3
    assert _read("p50_ms", r) == pytest.approx(np.percentile(lat, 50))
    assert _read("p95_ms", r) is None      # the failed request is the tail
    assert _read("setup_s", r) == 42.5
    assert _read("recall_at_k", r) == 0.97


def test_p95_of_all_answered():
    r = _record(trace_on=False)
    r.timeline.ok[:] = True
    lat = (r.timeline.done - r.timeline.due) * 1e3
    assert _read("p95_ms", r) == pytest.approx(np.percentile(lat, 95))


def test_program_counter_and_host_clock_readers():
    r = _record(trace_on=False)
    assert _read("probes_per_query.ivf", r) == pytest.approx(10.0)
    assert _read("batch_occupancy.ivf", r) == pytest.approx(30 / 6)
    # drains started inside the window: waits 50, 50, 50 ms (and 50 for
    # the failed one, left out)
    assert _read("queue_wait_ms.ivf", r) == pytest.approx(50.0)


def test_trace_readers():
    r = _record()
    for name in ("idle_share.flat", "idle_share.ivf"):
        assert _read(name, r) == pytest.approx(75.0)
    n, d = 1_000_000, 512
    mem = (n * d * 4 + n * 4) / 819e9
    floors = [max(2 * n * d * q / 197e12, mem) for q in (64, 64, 64)]
    assert _read("flat_scan_roofline", r) == pytest.approx(
        100 * sum(floors) / 0.5)


def test_trace_readers_find_nothing_without_a_trace():
    r = _record(trace_on=False)
    for name in ("idle_share.flat", "idle_share.ivf", "flat_scan_roofline"):
        assert _read(name, r) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
