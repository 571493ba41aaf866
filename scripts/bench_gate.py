#!/usr/bin/env python
"""Benchmark regression gate: diff a fresh benchmark run against the
committed baselines.

Compares the working-tree ``BENCH_batch.json`` / ``BENCH_join.json``
(freshly rewritten by ``benchmarks/run.py --quick``) against the versions
committed at HEAD (``git show``), and fails on a QPS regression greater
than the tolerance on the FLAT-path rows — the rows whose interpret-mode
performance is stable enough to gate on (the ``*_ivf`` rows are
straggler-dominated on CPU and tracked in the JSON, not gated).

Rows gated:
  * BENCH_batch.json: workloads.flat entries          (key: batch,  qps)
  * BENCH_join.json:  workloads.q3_flat / q4_flat     (key: left_rows,
                                                       qps_batch)
  * BENCH_sched.json: poisson sched-policy rows       (key: rate_multiplier,
                                                       qps) — the q8 arrival
    sweep runs the deadline scheduler on the flat (index-less, fused-kernel)
    plan, so its QPS is as timing-stable as the other flat rows; the
    straggler-dominated effort row stays tracked-not-gated.
  * BENCH_serve.json: q11 overload degraded-policy row (key: policy,
                                                        goodput_ratio) —
    goodput_ratio is deadline-met QPS over measured capacity, so the gate
    is machine-independent; the naive row's met count rides the exact spot
    the backlog crosses the deadline and stays tracked-not-gated.
  * BENCH_dist.json:  workloads.sharded shards=1 rows (key: batch, qps) —
    the sharded lowering at one shard IS the flat path plus a no-op merge,
    so its QPS is gate-stable; multi-shard rows measure fake-CPU-device
    collective overhead and stay tracked-not-gated.
  * BENCH_live.json:  zero_delta rows (key: batch, qps) — live-corpus
    scans with an empty delta segment are the flat path plus a shared
    validity mask and a runtime-skipped merge.  Two gates: fresh-vs-
    committed QPS like every other row, AND live-vs-frozen-twin overhead
    within one run (the q12 report carries a frozen ``frozen_qps`` twin
    measured back-to-back, so the <20% zero-delta regression bound never
    rides cross-run machine noise).  ``batch: 1`` gates too: live single
    queries reuse the batch lowering at Q=1 (``compiler._single_via_batch``)
    but the Q=1 + 1-D validity-lane fast path routes them through the
    single-query fused kernel, so b1 no longer pays the (Q, N) broadcast.
  * BENCH_adaptive.json: q14 adaptive-vs-static rows (key: workload,
    qps_adaptive) — fresh-vs-committed QPS per workload, AND the within-run
    contract that the advisor's per-left profile budgets at least match the
    static p75 pilot on the join row (ratio_adaptive_vs_static >= 1.0,
    measured back-to-back in one run); the single-table drift row's
    thinner margin is tracked, not gated.
  * BENCH_api.json:   q9 restart row — within-run contract only: the
    AOT-warm restart (prepare + first batch execute against a populated
    persistent plan cache, DESIGN.md §15) must be >= 10x faster than the
    cold restart compile, both emulated back-to-back by one q9 run.
  * BENCH_quant.json: flat quantized-scan rows (key: batch, qps) — the
    same interpret-mode fused-kernel stability argument as BENCH_batch,
    per mode (fp32 / bf16 / int8).  Two gates: fresh-vs-committed QPS per
    (mode, batch) row, AND the within-run speedup contract int8 b64 QPS
    >= 1.5x fp32 b64 QPS (both measured back-to-back in one q13 run, so
    the ratio never rides cross-run machine noise).

Exit codes: 0 pass/skip (no committed baseline, or git unavailable),
1 regression.  Tolerance: BENCH_GATE_TOL env var (default 0.20 = 20%).

Usage:  python scripts/bench_gate.py        (after benchmarks/run.py --quick)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = float(os.environ.get("BENCH_GATE_TOL", "0.20"))


def _committed(path: str) -> dict | None:
    try:
        blob = subprocess.run(
            ["git", "show", f"HEAD:{path}"], cwd=REPO, capture_output=True,
            text=True, check=True).stdout
        return json.loads(blob)
    except (subprocess.CalledProcessError, FileNotFoundError,
            json.JSONDecodeError):
        return None


def _fresh(path: str) -> dict | None:
    full = os.path.join(REPO, path)
    if not os.path.exists(full):
        return None
    with open(full) as f:
        return json.load(f)


def _same_config(name: str, base: dict, fresh: dict, fields: tuple) -> bool:
    """Only compare runs with matching benchmark configuration — a smoke
    run diffed against committed full-scale numbers (or vice versa) would
    spuriously fail (or vacuously pass) the tolerance check."""
    mismatched = {f: (base.get(f), fresh.get(f)) for f in fields
                  if base.get(f) != fresh.get(f)}
    if mismatched:
        print(f"bench_gate: skip {name} — config mismatch vs committed "
              f"baseline: {mismatched}")
        return False
    return True


def _gate_rows(name: str, base_rows: list, fresh_rows: list, key: str,
               qps_field: str, failures: list) -> int:
    fresh_by_key = {e[key]: e for e in fresh_rows}
    checked = 0
    for b in base_rows:
        f = fresh_by_key.get(b[key])
        if f is None or qps_field not in b or qps_field not in f:
            continue
        checked += 1
        floor = (1.0 - TOL) * b[qps_field]
        if f[qps_field] < floor:
            failures.append(
                f"{name}[{key}={b[key]}].{qps_field}: "
                f"{f[qps_field]:.1f} < {floor:.1f} "
                f"(committed {b[qps_field]:.1f}, tol {TOL:.0%})")
    return checked


def main() -> int:
    failures: list[str] = []
    checked = 0

    base = _committed("BENCH_batch.json")
    fresh = _fresh("BENCH_batch.json")
    if base and fresh and _same_config("BENCH_batch.json", base, fresh,
                                       ("n_rows", "flat_rows", "dim", "k")):
        checked += _gate_rows(
            "batch.flat", base.get("workloads", {}).get("flat", []),
            fresh.get("workloads", {}).get("flat", []),
            "batch", "qps", failures)

    base = _committed("BENCH_join.json")
    fresh = _fresh("BENCH_join.json")
    if base and fresh and _same_config("BENCH_join.json", base, fresh,
                                       ("right_rows", "dim", "k")):
        for wl in ("q3_flat", "q4_flat"):
            checked += _gate_rows(
                f"join.{wl}", base.get("workloads", {}).get(wl, []),
                fresh.get("workloads", {}).get(wl, []),
                "left_rows", "qps_batch", failures)

    base = _committed("BENCH_sched.json")
    fresh = _fresh("BENCH_sched.json")
    if base and fresh and _same_config("BENCH_sched.json", base, fresh,
                                       ("sched_rows", "dim", "k",
                                        "max_batch", "n_requests")):
        # flatten the nested per-policy dicts onto gateable rows
        def sched_rows(report: dict) -> list:
            return [{"rate_multiplier": e["rate_multiplier"],
                     "qps": e.get("sched", {}).get("qps")}
                    for e in report.get("poisson", [])
                    if e.get("sched", {}).get("qps") is not None]

        checked += _gate_rows("sched.poisson", sched_rows(base),
                              sched_rows(fresh), "rate_multiplier", "qps",
                              failures)

    base = _committed("BENCH_serve.json")
    fresh = _fresh("BENCH_serve.json")
    if base and fresh and _same_config("BENCH_serve.json", base, fresh,
                                       ("n_rows", "dim", "k", "max_batch",
                                        "n_requests", "overload_mult",
                                        "deadline_batches")):
        # only the degraded-policy row gates: its goodput ratio is pinned
        # by the arrival trace (the resilient policy keeps up with the
        # offered load), while the naive row's met-count rides the exact
        # spot the backlog crosses the deadline — tracked, not gated.
        # goodput_ratio is qps_met / measured capacity, so the gate is
        # machine-independent.
        def serve_rows(report: dict) -> list:
            return [r for r in report.get("rows", [])
                    if r.get("policy") == "degraded"]

        checked += _gate_rows("serve.overload", serve_rows(base),
                              serve_rows(fresh), "policy", "goodput_ratio",
                              failures)

    base = _committed("BENCH_dist.json")
    fresh = _fresh("BENCH_dist.json")
    if base and fresh and _same_config("BENCH_dist.json", base, fresh,
                                       ("n_rows", "dim", "k",
                                        "device_count")):
        # only the shards=1 parity rows gate (see module docstring)
        def dist_rows(report: dict) -> list:
            return [{"batch": e["batch"], "qps": e["qps"]}
                    for e in report.get("workloads", {}).get("sharded", [])
                    if e.get("shards") == 1]

        checked += _gate_rows("dist.shards1", dist_rows(base),
                              dist_rows(fresh), "batch", "qps", failures)

    base = _committed("BENCH_live.json")
    fresh = _fresh("BENCH_live.json")
    if base and fresh and _same_config("BENCH_live.json", base, fresh,
                                       ("flat_rows", "dim", "k",
                                        "delta_cap", "cap_main")):
        # every row gates, b1 included: the Q=1 validity-lane fast path
        # put live single queries on the single-query fused kernel
        checked += _gate_rows("live.zero_delta",
                              base.get("zero_delta", []),
                              fresh.get("zero_delta", []),
                              "batch", "qps", failures)
    # live-vs-frozen twin bound, within one run (fresh if present)
    for e in ((fresh or base) or {}).get("zero_delta", []):
        if "frozen_qps" not in e:
            continue
        checked += 1
        floor = (1.0 - TOL) * e["frozen_qps"]
        if e["qps"] < floor:
            failures.append(
                f"live.zero_delta[batch={e['batch']}].qps: live "
                f"{e['qps']:.1f} < {floor:.1f} "
                f"(same-run frozen twin {e['frozen_qps']:.1f}, "
                f"tol {TOL:.0%})")

    base = _committed("BENCH_quant.json")
    fresh = _fresh("BENCH_quant.json")
    if base and fresh and _same_config("BENCH_quant.json", base, fresh,
                                       ("n_rows", "dim", "k",
                                        "rescore_factor")):
        for mode in ("fp32", "bf16", "int8"):
            checked += _gate_rows(
                f"quant.{mode}", base.get("workloads", {}).get(mode, []),
                fresh.get("workloads", {}).get(mode, []),
                "batch", "qps", failures)
    # within-run speedup contract: the quantized scan must EARN its keep —
    # int8 b64 QPS >= 1.5x fp32 b64 QPS, both timed back-to-back in one
    # q13 run so the ratio never rides cross-run machine noise
    rep = (fresh or base) or {}

    def _b64_qps(mode: str):
        for e in rep.get("workloads", {}).get(mode, []):
            if e.get("batch") == 64:
                return e.get("qps")
        return None

    i8, f32 = _b64_qps("int8"), _b64_qps("fp32")
    if i8 is not None and f32 is not None:
        checked += 1
        if i8 < 1.5 * f32:
            failures.append(
                f"quant.speedup[batch=64]: int8 {i8:.1f} < 1.5x fp32 "
                f"{f32:.1f} (same-run ratio {i8 / f32:.2f}x)")

    # within-run restart contract (BENCH_api.json): preparing a persisted
    # statement after a restart (in-memory executables dropped) must be
    # >= 10x faster than the cold compile — cold and AOT-warm restarts run
    # back-to-back in one q9 invocation, so the ratio never rides
    # cross-run machine noise
    restart = ((_fresh("BENCH_api.json") or _committed("BENCH_api.json"))
               or {}).get("restart")
    if restart and restart.get("speedup") is not None:
        checked += 1
        if restart["speedup"] < 10.0:
            failures.append(
                f"api.restart: AOT-warm speedup {restart['speedup']:.1f}x "
                f"< 10x (cold {restart.get('cold_ms')}ms, warm "
                f"{restart.get('warm_ms')}ms, warm_traces="
                f"{restart.get('warm_traces')})")

    base = _committed("BENCH_adaptive.json")
    fresh = _fresh("BENCH_adaptive.json")
    if base and fresh and _same_config("BENCH_adaptive.json", base, fresh,
                                       ("single_rows", "join_rows", "dim",
                                        "n_batch", "n_left", "b_sets")):
        checked += _gate_rows("adaptive.rows", base.get("rows", []),
                              fresh.get("rows", []), "workload",
                              "qps_adaptive", failures)
    # within-run adaptive-vs-static contract: on the JOIN row the advisor's
    # per-left profile budgets must at least match the static p75 pilot
    # (both timed back-to-back in one q14 run, so the ratio never rides
    # cross-run machine noise); the single-table drift row's thinner margin
    # is tracked in the JSON, not gated
    for e in ((fresh or base) or {}).get("rows", []):
        if e.get("workload") != "join":
            continue
        ratio = e.get("ratio_adaptive_vs_static")
        if ratio is None:
            continue
        checked += 1
        if ratio < 1.0:
            failures.append(
                f"adaptive.join: ratio_adaptive_vs_static {ratio:.3f} < "
                f"1.0 — advisor per-left budgets lost to the static p75 "
                f"pilot (same-run, ms_adaptive={e.get('ms_adaptive')}, "
                f"ms_static={e.get('ms_static')})")

    if checked == 0:
        print("bench_gate: no committed baselines to compare against — skip")
        return 0
    if failures:
        print(f"bench_gate: FAIL — {len(failures)} flat-path QPS "
              f"regression(s) > {TOL:.0%}:")
        for f in failures:
            print("  " + f)
        return 1
    print(f"bench_gate: OK — {checked} flat-path rows within {TOL:.0%} "
          f"of committed QPS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
