"""The readings a cell's limits are set from: for each seed, the compared
numbers of the program (a short window at the cell's own load and size)
and of the control (the reference one precision below, put in the
program's place, on the same sampled requests).  One process and one
set-up (the corpus is the configuration's own), one seed's window after
another.

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--control-seeds 11,12,13] \
        [--set probe.min_probes=2 ...]

``--set`` changes a key of the cell's configuration for this reading
(dotted for a nested key, the value in JSON): it shows how far a compared
number moves when a setting is cut, as a later PR might cut it.

Prints one JSON line per seed and side (``program``; ``control``, the
three bfloat16 passes written out; ``control_high``, the chip's own
``Precision.HIGH``, which the CPU ignores), then the largest program
reading and the smallest control reading of each number.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def apply_sets(config: dict, sets: list) -> None:
    """``key.sub=value`` assignments (values in JSON) into ``config``."""
    for item in sets:
        path, value = item.split("=", 1)
        *outer, last = path.split(".")
        node = config
        for k in outer:
            node = node[k]
        if last not in node:
            raise KeyError(f"the configuration has no key {path!r}")
        node[last] = json.loads(value)


def readings(name: str, seconds: float, seeds: list, control_seeds: list,
             device_check=None, adjust=None, sets=()) -> dict:
    import harness
    cell = harness.load_cell(name)
    if adjust is not None:
        adjust(cell)
    apply_sets(cell.config, sets)
    (device_check or harness.check_device)(cell.chips)
    clock = harness.CompileClock()
    spec = cell.traffic["check"]
    checker = harness.load_module("checks", spec["module"], cell.root)
    out = {"program": [], "control": [], "control_high": []}
    order = sorted(set(seeds) | set(control_seeds))
    world = harness.build_world(cell, order[0], seconds, clock)
    for seed in order:
        world.requests = harness.own("traffic").generate(
            cell.traffic, world.dataset, cell.config["data_seed"], seed,
            seconds)
        tl = harness.drive(world, cell, seconds, clock)
        sides = []
        if seed in seeds:
            numbers, _ = harness.check(world, cell, tl, seed)
            sides.append(("program", numbers))
        if seed in control_seeds:
            binds = harness.sampled_binds(
                world.requests, harness._sample(tl, spec["sample"], seed))
            for side, precision in (("control", "bf16_3x"),
                                    ("control_high", "high")):
                sides.append((side, checker.control_run(
                    world.dataset, binds, spec, cell.config["guarantee"],
                    precision, cell.config["limits"])))
        probes = tl.answers["probes"][tl.ok]
        for side, numbers in sides:
            print(json.dumps({"seed": seed, "side": side, **numbers,
                              "probes_mean": float(probes.mean())}),
                  flush=True)
            out[side].append(numbers)
    summary = {}
    for name_ in out["program"][0] if out["program"] else []:
        row = {}
        for side, vals in out.items():
            vals = [r[name_] for r in vals if name_ in r]
            if vals:
                row[f"{side}_min"], row[f"{side}_max"] = min(vals), max(vals)
        summary[name_] = row
    print(json.dumps({"summary": summary}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    readings(args.workload, args.seconds, ints(args.seeds),
             ints(args.control_seeds), sets=args.set)
    return 0


if __name__ == "__main__":
    sys.exit(main())
