"""bench/spans.py and the metrics that read it, on a small trace recorded
in the layout a TPU run writes: an XSpace text proto with one TPU plane
(ops whose event metadata carry their ``op_name`` in ``tf_op``, as on the
chip) and a host line with the harness's ``bench.*`` spans and the
program's ``chase.*`` spans, serialized to ``plugins/profile/*/*.xplane.pb``.
"""
import numpy as np
import pytest
from jax.profiler import ProfileData

import harness

trace = harness.own("trace")
spans = harness.own("spans")
peaks = harness.own("peaks")

US = 1_000_000          # picoseconds per microsecond

# device ops: metadata id -> (name, tf_op)
OPS_META = {
    1: ("%pad.4 = f32[1000448,512] pad(f32[1000000,512] %c, f32[] %z)",
        "jit(run)/jit(fused_scan_topk_batch)/chase.flat.pad_corpus/"
        "jit(_pad)/pad:"),
    2: ("%scan_topk_batch_pallas.1 = (f32[977,50,64]) custom-call()",
        "jit(run)/jit(fused_scan_topk_batch)/chase.flat.scan/"
        "jit(scan_topk_batch_pallas)/pallas_call:"),
    3: ("%fusion.1 = s32[64,50] fusion(f32[64,48850] %k)",
        "jit(run)/jit(fused_scan_topk_batch)/chase.flat.merge/sort:"),
    4: ("%broadcast_compare_fusion = pred[1000000,64] fusion()",
        "jit(run)/chase.flat.mask/vmap()/lt:"),
    5: ("%select_bitcast_fusion = s32[64] fusion()",
        "jit(run)/jit(_where)/select_n:"),
}
# (metadata id, start us, end us): drain 1's ops in [12, 40], drain 2's in
# [52, 70], one op outside the window
OPS = [(1, 0, 5), (4, 12, 14), (1, 14, 20), (2, 20, 36), (3, 36, 39),
       (5, 39, 40), (1, 52, 56), (2, 56, 68), (3, 68, 70)]

HOST = {1: "bench.window", 2: "bench.drain", 3: "chase.drain",
        4: "chase.stack", 5: "chase.pad", 6: "chase.dispatch",
        7: "chase.fetch", 8: "chase.slice", 9: "bench.result",
        10: "bench.sleep"}
STAT_DRAIN, STAT_SIZE, STAT_BUCKET = 21, 22, 23
# (metadata id, start us, end us, {stat: value})
SPANS = [(1, 8, 100, {}),
         (2, 10, 45, {}), (3, 10, 44, {STAT_DRAIN: 7, STAT_SIZE: 64,
                                       STAT_BUCKET: 64}),
         (4, 10, 11, {STAT_DRAIN: 7}), (5, 11, 12, {STAT_DRAIN: 7}),
         (6, 12, 13, {STAT_DRAIN: 7}), (7, 13, 42, {STAT_DRAIN: 7}),
         (8, 42, 44, {STAT_DRAIN: 7}),
         (9, 45, 48, {}),
         (2, 48, 80, {}), (3, 49, 79, {STAT_DRAIN: 8, STAT_SIZE: 3,
                                       STAT_BUCKET: 4}),
         (4, 49, 50, {STAT_DRAIN: 8}), (5, 50, 51, {STAT_DRAIN: 8}),
         (6, 51, 53, {STAT_DRAIN: 8}), (7, 53, 75, {STAT_DRAIN: 8}),
         (8, 75, 79, {STAT_DRAIN: 8}),
         (10, 80, 95, {})]


def _events(spec):
    out = []
    for m, s, e, *rest in spec:
        stats = "".join(f" stats {{ metadata_id: {k} int64_value: {v} }}"
                        for k, v in (rest[0] if rest else {}).items())
        out.append(f"events {{ metadata_id: {m} offset_ps: {s * US} "
                   f"duration_ps: {(e - s) * US}{stats} }}")
    return "\n".join(out)


def _device(with_scopes=True, ops_meta=OPS_META):
    meta = []
    for i, (name, op) in ops_meta.items():
        stat = (f' stats {{ metadata_id: 26 str_value: "{op}" }}'
                if with_scopes else "")
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name}"{stat} }} }}')
    return (f'planes {{ id: 10 name: "/device:TPU:0" '
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
            f'{_events(OPS)} }} {" ".join(meta)} '
            f'stat_metadata {{ key: 26 value {{ id: 26 name: "tf_op" }} }} '
            f'stat_metadata {{ key: 24 value {{ id: 24 '
            f'name: "hlo_category" }} }} }}')


def _host(with_chase=True):
    spec = [s for s in SPANS if with_chase or not HOST[s[0]].startswith(
        "chase.")]
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for i, n in HOST.items())
    stats = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in
                     ((STAT_DRAIN, "drain"), (STAT_SIZE, "size"),
                      (STAT_BUCKET, "bucket")))
    return (f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 7 '
            f'name: "python3" timestamp_ns: 0 {_events(spec)} }} '
            f'{meta} {stats} }}')


def _text(program=True, ops_meta=OPS_META):
    return _device(program, ops_meta) + "\n" + _host(program)


def _write(tmp_path, program=True, ops_meta=OPS_META):
    where = tmp_path / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            _text(program, ops_meta)))
    return str(tmp_path)


def test_op_scopes_read_tf_op_from_the_event_metadata(tmp_path):
    path = trace.xplane_file(_write(tmp_path))
    with open(path, "rb") as f:
        scopes = spans.op_scopes(f.read())
    assert scopes == {OPS_META[1][0]: ("chase.flat.pad_corpus",),
                      OPS_META[2][0]: ("chase.flat.scan",),
                      OPS_META[3][0]: ("chase.flat.merge",),
                      OPS_META[4][0]: ("chase.flat.mask",)}
    assert spans.scopes_of("jit(run)/jit(ivf_topk_batch)/while/body/"
                           "chase.ivf.probe_round/chase.ivf.gather/gather:"
                           ) == ("chase.ivf.probe_round", "chase.ivf.gather")


def test_drains_children_and_fetch_busy(tmp_path):
    r = spans.load(_write(tmp_path))
    assert r.window_ns == (8000, 100000)
    assert [(d.size, d.bucket) for d in r.drains] == [(64, 64), (3, 4)]
    first, second = r.drains
    assert first.parts == {"chase.stack": 1000, "chase.pad": 1000,
                           "chase.dispatch": 1000, "chase.fetch": 29000,
                           "chase.slice": 2000}
    # busy inside fetch [13, 42]: [13, 40]; inside [53, 75]: [53, 70]
    assert first.fetch_busy_ns == pytest.approx(27000)
    assert second.fetch_busy_ns == pytest.approx(17000)
    count, total, self_ns = r.spans["chase.drain"]
    assert (count, total) == (2, 64000)
    assert self_ns == pytest.approx(64000 - 34000 - 30000)


def test_device_time_by_scope(tmp_path):
    r = spans.load(_write(tmp_path))
    assert r.scope_ns == pytest.approx({
        "chase.flat.pad_corpus": 6000 + 4000,   # the op at [0, 5] is outside
        "chase.flat.scan": 16000 + 12000,
        "chase.flat.merge": 3000 + 2000,
        "chase.flat.mask": 2000})


def test_idle_inside_drains_by_innermost_span(tmp_path):
    r = spans.load(_write(tmp_path))
    # gaps in the window: [8,12] (mids 10: bench.drain opens at 10, the
    # drain's stack at 10..11 holds it), [40,52] mid 46: bench.result,
    # outside every drain; [70,100] mid 85: bench.sleep
    assert r.idle_in_drain == pytest.approx({"chase.stack": 4000})


def test_a_trace_without_program_spans_reduces_to_empty_tables(tmp_path):
    r = spans.load(_write(tmp_path, program=False))
    assert r.drains == [] and r.spans == {} and r.scope_ns == {}
    assert r.idle_in_drain == pytest.approx({"bench.drain": 4000})


def test_the_harness_reduction_ignores_program_spans(tmp_path):
    a = trace.reduce(ProfileData.from_text_proto(_text(program=True)))
    b = trace.reduce(ProfileData.from_text_proto(_text(program=False)))
    assert a == b


def test_the_reduction_is_read_once_per_file(tmp_path):
    where = _write(tmp_path)
    assert spans.load(where) is spans.load(where)


# ---------------------------------------------------------------------------
# the metric readers
# ---------------------------------------------------------------------------

def _timeline(counters):
    ok = np.array([True, True])
    return harness.Timeline(
        t0=0.0, end=1.0, due=np.zeros(2), sent=np.zeros(2),
        start=np.full(2, 0.01), done=np.full(2, 0.02), ok=ok,
        answers={"probes": np.zeros(2, np.int64)}, drains=[],
        counters=counters, compiles=0)


COUNTERS = ({"executed": 10, "batches": 4, "wait_s": 0.5,
             "probe_rounds": 100, "rows_gathered": 1000, "rows_scored": 50},
            {"executed": 30, "batches": 8, "wait_s": 0.56,
             "probe_rounds": 140, "rows_gathered": 5000, "rows_scored": 1650})
PARENT_COUNTERS = ({"executed": 10, "batches": 4},
                   {"executed": 30, "batches": 8})


def _record(tmp_path, monkeypatch, program=True, cell="laion1m_flat.q1_serial",
            counters=COUNTERS, ops_meta=OPS_META):
    monkeypatch.setattr(harness, "TRACE_DIR",
                        _write(tmp_path, program, ops_meta))
    reduced = trace.load(harness.TRACE_DIR)
    return harness.Record(harness.load_cell(cell), _timeline(counters), 1.0,
                          {}, reduced, peaks.peaks_for("TPU v5 lite"))


def _read(name, record):
    return harness.load_module("metrics", name).read(record)


def test_host_path_readers(tmp_path, monkeypatch):
    r = _record(tmp_path, monkeypatch)
    # stack + pad + dispatch: 3 us and 4 us
    assert _read("prep_ms.flat", r) == pytest.approx((3 + 4) / 2 * 1e-3)
    # fetch less busy inside it, plus slice: (29-27+2) and (22-17+4) us
    assert _read("post_ms.flat", r) == pytest.approx((4 + 9) / 2 * 1e-3)


def test_kernel_readers(tmp_path, monkeypatch):
    r = _record(tmp_path, monkeypatch)
    assert _read("corpus_pad_ms.flat", r) == pytest.approx(10e-3 / 2)
    n, d = 1_000_000, 512
    floors = [max(2 * n * d * q / 197e12, (n * d * 4 + n * 4) / 819e9)
              for q in (64, 4)]
    assert _read("scan_kernel_roofline.flat", r) == pytest.approx(
        100 * sum(floors) / 28e-6)


def test_corpus_pad_reads_zero_once_the_copy_is_gone(tmp_path, monkeypatch):
    unscoped = {**OPS_META, 1: (OPS_META[1][0], "jit(run)/jit(_pad)/pad:")}
    r = _record(tmp_path, monkeypatch, ops_meta=unscoped)
    assert _read("corpus_pad_ms.flat", r) == 0.0


def test_counter_readers(tmp_path, monkeypatch):
    r = _record(tmp_path, monkeypatch, cell="laion1m_ivf.q1_poisson")
    assert _read("sched_wait_ms", r) == pytest.approx(0.06 / 20 * 1e3)
    assert _read("probe_efficiency.ivf", r) == pytest.approx(
        100 * 1600 / 4000)
    # no chase.ivf.probe_round scope in this trace
    assert _read("probe_round_ms.ivf", r) is None


def test_probe_round_reader(tmp_path, monkeypatch):
    probe = {**OPS_META, 2: (OPS_META[2][0], "jit(run)/jit(ivf_topk_batch)/"
                             "while/body/chase.ivf.probe_round/"
                             "chase.ivf.gather/gather:")}
    r = _record(tmp_path, monkeypatch, cell="laion1m_ivf.q1_poisson",
                ops_meta=probe)
    # 28 us under the scope over 40 rounds
    assert _read("probe_round_ms.ivf", r) == pytest.approx(28e-3 / 40)


NEW = ("prep_ms.flat", "post_ms.flat", "corpus_pad_ms.flat",
       "scan_kernel_roofline.flat", "sched_wait_ms", "probe_efficiency.ivf",
       "probe_round_ms.ivf")


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_reads_nothing(name, tmp_path,
                                                   monkeypatch):
    """The parent program writes no chase.* spans, scopes or counters: each
    new reader returns None and does not raise."""
    r = _record(tmp_path, monkeypatch, program=False,
                counters=PARENT_COUNTERS)
    assert _read(name, r) is None


@pytest.mark.parametrize("name", NEW)
def test_untraced_runs_read_no_trace_metric(name, monkeypatch, tmp_path):
    r = _record(tmp_path, monkeypatch)
    r.trace = None
    if name in ("sched_wait_ms", "probe_efficiency.ivf"):
        assert _read(name, r) is not None       # counters need no trace
    else:
        assert _read(name, r) is None
