"""bench/trace.py on a small trace written out as an XSpace text proto:
one TPU plane (a while op around two fusions, a kernel, an op outside the
window) and the harness's host spans."""
import pytest
from jax.profiler import ProfileData

import harness

trace = harness.own("trace")

US = 1_000_000          # picoseconds per microsecond


def _events(spec):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {s * US} "
        f"duration_ps: {(e - s) * US} }}" for m, s, e in spec)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())


def _device(pid, name, ops):
    names = {1: "%while.1 = (s32[]) while(s32[] %x)",
             2: "%fusion.7 = f32[8] fusion(f32[8] %a)",
             3: "%fusion.9 = f32[8] fusion(f32[8] %b)",
             4: "%scan_topk_batch_pallas.1 = f32[8] custom-call(f32[8] %c)"}
    return (f'planes {{ id: {pid} name: "{name}" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
            f'{_events(ops)} }} '
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 '
            f'events {{ metadata_id: 5 offset_ps: 0 duration_ps: '
            f'{200 * US} }} }} '
            f'{_meta({**names, 5: "jit_run(1)"})} }}')


HOST = {1: "bench.window", 2: "bench.drain", 3: "bench.result",
        4: "bench.sleep", 5: "PjitFunction(run)"}
SPANS = [(1, 8, 100), (2, 9, 45), (5, 10, 44), (3, 45, 48), (2, 48, 75),
         (4, 80, 95)]
OPS = [(1, 0, 5), (1, 10, 40), (2, 15, 25), (3, 27, 35), (4, 50, 70)]


def _profile(devices=((1, OPS),)):
    planes = [_device(10 + i, f"/device:TPU:{i}", ops)
              for i, (_, ops) in enumerate(devices)]
    planes.append(f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 7 '
                  f'name: "python" timestamp_ns: 0 {_events(SPANS)} }} '
                  f'{_meta(HOST)} }}')
    return ProfileData.from_text_proto("\n".join(planes))


def test_busy_union_and_idle_share():
    r = trace.reduce(_profile())
    assert r.window_ns == (8000, 100000)
    assert r.busy_ns == pytest.approx(50000)        # [10,40] + [50,70]
    assert r.window_s == pytest.approx(92e-6)
    assert r.devices == 1


def test_gaps_go_to_the_span_open_at_their_middle():
    r = trace.reduce(_profile())
    # [8,10] mid 9: drain; [40,50] mid 45: result; [70,100] mid 85: sleep
    assert r.idle_by_span == pytest.approx(
        {"bench.drain": 2000, "bench.result": 10000, "bench.sleep": 30000})


def test_gap_outside_every_span_is_the_loop():
    ops = [(4, 8, 60), (4, 70, 100)]        # gap [60,70], mid 65 in drain 2
    r = trace.reduce(_profile(((1, ops),)))
    assert r.idle_by_span == pytest.approx({"bench.drain": 10000})
    ops = [(4, 8, 76), (4, 78, 100)]        # gap [76,78]: no inner span
    r = trace.reduce(_profile(((1, ops),)))
    assert r.idle_by_span == pytest.approx({trace.OUTSIDE: 2000})


def test_top_ops_by_self_time():
    r = trace.reduce(_profile())
    assert r.top_ops == [("scan_topk_batch_pallas.1", 20000),
                         ("while.1", 12000), ("fusion.7", 10000),
                         ("fusion.9", 8000)]


def test_drains_with_busy_inside():
    r = trace.reduce(_profile())
    assert r.drains == [(9000, 45000, 30000), (48000, 75000, 20000)]


def test_two_chips_average():
    other = [(4, 20, 60)]                   # busy 40 on the second chip
    r = trace.reduce(_profile(((1, OPS), (1, other))))
    assert r.devices == 2
    assert r.busy_ns == pytest.approx((50000 + 40000) / 2)


def test_breakdown_lists_seconds():
    b = trace.breakdown(trace.reduce(_profile()))
    assert b["device_ops"][0] == ["scan_topk_batch_pallas.1",
                                  pytest.approx(20e-6)]
    assert [n for n, _ in b["idle_gaps"]] == ["bench.sleep", "bench.result",
                                              "bench.drain"]


def test_a_trace_without_the_window_span_is_refused():
    bare = ProfileData.from_text_proto(_device(1, "/device:TPU:0", OPS))
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(bare)


@pytest.mark.parametrize("intervals,lo,hi,want", [
    ([(0, 10), (20, 30)], 5, 25, 10),
    ([(0, 10), (20, 30)], 10, 20, 0),
    ([(0, 10), (20, 30)], -5, 50, 20),
    ([(0, 10)], 2, 3, 1),
])
def test_cover_within(intervals, lo, hi, want):
    assert trace.Cover(trace.union(intervals)).within(lo, hi) == want


def test_union_merges_overlaps():
    assert trace.union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
