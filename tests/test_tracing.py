"""Tracing of the serving path: the scheduler's counters, the host spans of
a drain and the device scopes of the flat and IVF lanes.

Contracts under test:
* ``BatchScheduler.counters`` adds ``wait_s`` (take - submit, summed over
  executed requests), ``probe_rounds`` (the batch's lock-step IVF rounds,
  ceil(max probes / round width)), ``rows_gathered`` (rounds x bucket x
  width x list cap) and ``rows_scored`` (the summed ``distance_evals``);
  plans that probe no IVF index leave the last three at 0, and
  ``ResilientScheduler`` drains count through the same point;
* the lowerings' stats dicts are unchanged (the bit-parity contract);
* under ``jax.profiler.trace`` a drain writes ``chase.drain`` with its five
  children nested inside it, all carrying one ``drain`` id;
* the ``chase.flat.*`` / ``chase.ivf.*`` scopes reach the compiled HLO's
  ``op_name`` metadata.
"""
import glob
import math
import os
import re

import jax
import numpy as np
import pytest

from repro.api import connect
from repro.core import EngineOptions, Metric, compile_query
from repro.index import build_ivf
from repro.index.ivf import ProbeConfig, round_width
from repro.serving.resilience import DegradePolicy
from repro.serving.scheduler import (BatchScheduler, ResilientScheduler,
                                     SchedulerConfig)

SQL = ("SELECT sample_id FROM products WHERE price < ${p} "
       "ORDER BY DISTANCE(embedding, ${qv}) LIMIT 4")
PROBE = ProbeConfig(max_probes=32, probe_batch=2, termination="counter")
CHILDREN = ("chase.stack", "chase.pad", "chase.dispatch", "chase.fetch",
            "chase.slice")


@pytest.fixture(scope="module")
def env():
    from repro.data import make_laion_catalog

    cat = make_laion_catalog(n_rows=1500, n_queries=8, dim=16, n_modes=8,
                             seed=0)
    idx = build_ivf(jax.random.key(0), cat.table("laion")["vec"], nlist=32,
                    metric=Metric.INNER_PRODUCT, iters=3)
    cat.register_index("products", "embedding", idx)
    ivf = compile_query(SQL, cat, EngineOptions(engine="chase", probe=PROBE))
    flat = compile_query(SQL, cat, EngineOptions(engine="brute",
                                                 use_pallas=True))
    return cat, idx, ivf, flat


def _requests(cat, n, seed=1):
    rng = np.random.default_rng(seed)
    base = np.asarray(cat.table("queries")["embedding"])
    price = np.asarray(cat.table("laion")["price"])
    qs = np.tile(base, (-(-n // base.shape[0]), 1))[:n]
    qs = (qs + 0.01 * rng.standard_normal(qs.shape)).astype(np.float32)
    ps = np.quantile(price, rng.uniform(0.05, 1.0, n)).astype(np.float32)
    return [dict(qv=qs[i], p=np.float32(ps[i])) for i in range(n)]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _serve(compiled, reqs, max_wait_ms=2.0, max_batch=8, hold_s=0.004):
    """Submit every request at t=0, drain once at t=hold_s; returns the
    scheduler and the per-request stats."""
    clock = FakeClock()
    sched = BatchScheduler(compiled, SchedulerConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms), clock=clock)
    rids = [sched.submit(**r) for r in reqs]
    clock.t = hold_s
    assert sorted(sched.poll()) == sorted(rids)
    stats = [sched.result(rid)["stats"] for rid in rids]
    return sched, stats


@pytest.mark.parametrize("n", [1, 3, 5, 8])
def test_ivf_counters_count_rounds_and_rows(env, n):
    _cat, idx, ivf, _flat = env
    sched, stats = _serve(ivf, _requests(env[0], n))
    c = sched.counters
    width = round_width(idx, PROBE)
    probes = [int(s["probes"]) for s in stats]
    rounds = math.ceil(max(probes) / width)
    bucket = ivf.executor.bucket_for(n)
    assert width == 2 and rounds > 0
    assert c["probe_rounds"] == rounds
    assert c["rows_gathered"] == rounds * bucket * width * idx.cap
    assert c["rows_scored"] == sum(int(s["distance_evals"]) for s in stats)
    assert 0 < c["rows_scored"] <= c["rows_gathered"]
    assert c["wait_s"] == pytest.approx(n * 0.004)
    assert (c["executed"], c["batches"]) == (n, 1)


def test_wait_grows_with_max_wait(env):
    _cat, _idx, ivf, _flat = env
    req = _requests(env[0], 1)
    waits = []
    for max_wait_ms in (0.0, 2.0, 5.0):
        clock = FakeClock()
        sched = BatchScheduler(ivf, SchedulerConfig(
            max_batch=8, max_wait_ms=max_wait_ms), clock=clock)
        sched.submit(**req[0])
        while not sched.poll():         # the deadline rule, on the clock
            clock.t += 1e-3
        waits.append(sched.counters["wait_s"])
    assert waits[0] >= 0.0
    assert waits == sorted(waits) and waits[0] < waits[1] < waits[2]
    assert waits[2] == pytest.approx(5e-3)


def test_flat_plan_leaves_probe_counters_zero(env):
    _cat, _idx, _ivf, flat = env
    sched, _stats = _serve(flat, _requests(env[0], 3))
    c = sched.counters
    assert (c["probe_rounds"], c["rows_gathered"], c["rows_scored"]) == (
        0, 0, 0)
    assert c["wait_s"] == pytest.approx(3 * 0.004)
    assert c["executed"] == 3


def test_resilient_drains_count_at_the_same_point(env):
    cat, idx, _ivf, _flat = env
    stmt = connect(cat, EngineOptions(engine="chase", probe=PROBE)).prepare(
        SQL)
    clock = FakeClock()
    budget = 4
    sched = ResilientScheduler(
        stmt, SchedulerConfig(max_batch=8, max_wait_ms=2.0), clock=clock,
        policy=DegradePolicy(steps=((2, budget),), hysteresis=0))
    rids = [sched.submit_request(r) for r in _requests(cat, 5)]
    clock.t = 0.003
    sched.flush()
    stats = [sched.result(rid).counters for rid in rids]
    probes = [int(np.asarray(s["probes"])) for s in stats]
    assert max(probes) <= budget                    # degraded: capped
    c = sched.counters
    rounds = math.ceil(max(probes) / 2)
    assert c["probe_rounds"] == rounds
    assert c["rows_gathered"] == rounds * 8 * 2 * idx.cap
    assert c["rows_scored"] == sum(int(np.asarray(s["distance_evals"]))
                                   for s in stats)
    assert c["wait_s"] == pytest.approx(5 * 0.003)


@pytest.mark.parametrize("lane", ["ivf", "flat"])
def test_lowering_stats_dicts_unchanged(env, lane):
    cat, _idx, ivf, flat = env
    q = {"ivf": ivf, "flat": flat}[lane]
    reqs = _requests(cat, 3)
    bucketed = q.execute_bucketed(reqs)
    exact = q.execute_batch(reqs)
    assert set(bucketed["stats"]) == {"probes", "distance_evals"}
    for key in ("probes", "distance_evals"):
        np.testing.assert_array_equal(np.asarray(bucketed["stats"][key]),
                                      np.asarray(exact["stats"][key]))


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                         for e in line.events if e.name.startswith("chase."))
    return spans


def test_drain_spans_nest_with_one_drain_id(env, tmp_path):
    cat, _idx, ivf, _flat = env
    reqs = _requests(cat, 3)
    clock = FakeClock()
    sched = BatchScheduler(ivf, SchedulerConfig(max_batch=8,
                                                max_wait_ms=2.0),
                           clock=clock)
    sched.warm(reqs[0], [3])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        for r in reqs:
            sched.submit(**r)
        clock.t = 0.004
        sched.poll()
    spans = _host_spans(str(tmp_path))
    drains = [s for s in spans if s[0] == "chase.drain"]
    assert len(drains) == 1
    _, lo, hi, args = drains[0]
    assert (args["size"], args["bucket"]) == (3, 4)
    children = [s for s in spans if s[0] != "chase.drain"]
    assert sorted(name for name, *_ in children) == sorted(CHILDREN)
    for name, s, e, child_args in children:
        assert lo <= s <= e <= hi, name
        assert child_args == {"drain": args["drain"]}, name


@pytest.mark.parametrize("lane,scopes", [
    ("flat", ("chase.flat.mask", "chase.flat.pad_corpus", "chase.flat.scan",
              "chase.flat.merge")),
    ("ivf", ("chase.ivf.order", "chase.ivf.probe_round", "chase.ivf.gather",
             "chase.ivf.merge")),
])
def test_scopes_reach_the_compiled_op_names(env, lane, scopes):
    cat, _idx, ivf, flat = env
    q = {"ivf": ivf, "flat": flat}[lane]
    binds = q._stack_binds(_requests(cat, 2), {})
    ex = q.executor
    text = ex.executable(2).lower(ex.arrays, binds, np.ones(2, bool),
                                  None).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(scope in name.split("/") for name in op_names), scope
    if lane == "ivf":
        # the gather and the merge run inside the probe round
        inner = [n for n in op_names if "chase.ivf.gather" in n]
        assert all("chase.ivf.probe_round" in n for n in inner)
