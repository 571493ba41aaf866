"""Find the highest rate an open-loop cell sustains: one process builds the
cell once, then drives a window at each offered rate, for each seed.

    python3 bench/sweep.py --workload <cell> --seeds 11,12,13 \
        --seconds <s> --rates 100,200,300

For each rate and seed it prints one JSON line: the offered rate, the rate
answered inside the window, p50 / p95 latency, the backlog (requests due
but not yet answered) at each quarter of the window, and the generator's
lateness.  A rate is sustained when, on every seed, the answered rate keeps
up with the offered one, the backlog does not grow from the second quarter
to the close, and p95 stays within a few drain periods.  The cell's
traffic file keeps the rate chosen from such a sweep as a number; the
benchmark never searches.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def backlog(tl, at: float) -> int:
    """Requests due by ``at`` (seconds into the window) and not answered."""
    t = tl.t0 + at
    return int(np.count_nonzero((tl.due <= t) & ~(tl.done <= t)))


def sweep(name: str, seeds: list, seconds: float, rates: list,
          device_check=None, adjust=None) -> list:
    import harness
    traffic_gen = harness.own("traffic")
    cell = harness.load_cell(name)
    if adjust is not None:
        adjust(cell)
    (device_check or harness.check_device)(cell.chips)
    clock = harness.CompileClock()
    world = harness.build_world(cell, seeds[0], seconds, clock)
    rows = []
    for rate in rates:
        tr = dict(cell.traffic, rate_per_s=rate)
        for seed in seeds:
            world.requests = traffic_gen.generate(
                tr, world.dataset, cell.config["data_seed"], seed, seconds)
            tl = harness.drive(world, dataclasses.replace(cell, traffic=tr),
                               seconds, clock)
            lat = np.where(tl.ok, tl.done - tl.due, np.inf) * 1e3
            inside = tl.ok & (tl.done <= tl.end)
            row = {"rate_per_s": rate, "seed": seed,
                   "answered_per_s": float(inside.sum() / seconds),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "backlog_quarters": [backlog(tl, seconds * q / 4)
                                        for q in (1, 2, 3, 4)],
                   "mean_batch": float(tl.ok.size / max(len(tl.drains), 1)),
                   "late_p99_ms": float(np.quantile(tl.sent - tl.due, 0.99)
                                        * 1e3),
                   "window_compiles": tl.compiles}
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sweep(args.workload, [int(s) for s in args.seeds.split(",")],
          args.seconds, [float(r) for r in args.rates.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
