"""Q10 — multi-device sharded batched scan QPS (DESIGN.md §10).

Sweeps the shard × tile composition: shards ∈ {1, 2, 4} (simulated with
fake CPU devices via ``xla_force_host_platform_device_count``) × request
batch Q ∈ {8, 64} on the fused flat VKNN workload, through the session
API's bucketed serving path (``EngineOptions.dist``).

Every run also asserts the acceptance invariants, not just times them:

* **shards=1 bit-parity** — the dist plan's bucketed output is
  bit-identical to the single-device bucketed path (ids, sims, valid,
  counters);
* **per-query counter exactness at every shard count** — each valid query
  reports exactly N distance evals (the shards' psum'd local counts) and
  the result id set matches the single-device reference.

Writes ``BENCH_dist.json`` (consumed by scripts/bench_gate.py: the
shards=1 rows gate fresh QPS within tolerance of the committed baseline;
multi-shard rows are tracked, not gated — on a CPU host the "devices" share
one socket, so shard scaling measures collective overhead, not speedup).

On CPU the sweep runs in a child process so the fake-device topology exists
no matter how the harness was launched; on an accelerator it runs in the
harness's own process, which holds the chips:

  PYTHONPATH=src python -m benchmarks.q10_sharded_qps [--full]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

SHARDS = (1, 2, 4)
BATCHES = (8, 64)
DEVICE_COUNT = max(SHARDS)
SQL = ("SELECT sample_id FROM products "
       "ORDER BY DISTANCE(embedding, ${qv}) LIMIT {K}")
OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_dist.json")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLAT_ROWS = 2048   # like q7's flat workload: interpret-mode flat scans are
                   # CPU-emulated, so the sweep stays tiny & fixed (and the
                   # row count exercises exact shard divisibility at 2 and 4)


def _queries(base, q: int):
    """Tile+jitter the catalog's query set out to ``q`` vectors."""
    import numpy as np
    rng = np.random.default_rng(7)
    reps = -(-q // base.shape[0])
    qs = np.tile(base, (reps, 1))[:q]
    return (qs + 0.01 * rng.standard_normal(qs.shape)).astype(np.float32)


def _child(n_rows: int, dim: int, k: int, seed: int,
           shards_sweep: tuple = SHARDS) -> dict:
    """The measured sweep (fake CPU devices, or the real chips in-process)."""
    import numpy as np
    from repro.api import connect
    from repro.core import EngineOptions
    from repro.data import make_laion_catalog
    from repro.dist import DistSpec

    from .common import timeit
    from .counters import per_query_amortized

    sql = SQL.replace("{K}", str(k))
    cat = make_laion_catalog(n_rows=n_rows, n_queries=8, dim=dim,
                             n_modes=16, seed=seed)
    qbase = np.asarray(cat.table("queries")["embedding"])
    flat = EngineOptions(engine="brute", use_pallas=True)
    ref_stmt = connect(cat, flat).prepare(sql)

    report = {"n_rows": n_rows, "dim": dim, "k": k,
              "device_count": max(shards_sweep), "batches": list(BATCHES),
              "workloads": {"sharded": []},
              "parity": {"shards1_bitparity": False,
                         "counter_exact_shards": []}}
    entries = report["workloads"]["sharded"]
    base_qps: dict[int, float] = {}
    for shards in shards_sweep:
        db = connect(cat, EngineOptions(
            engine="brute", use_pallas=True,
            dist=DistSpec(mesh_shape=(shards,))))
        stmt = db.prepare(sql)
        counters_exact = True
        for b in BATCHES:
            qs = _queries(qbase, b)
            out = stmt.execute({"qv": qs})
            ref = ref_stmt.execute({"qv": qs})
            # per-query counter exactness at EVERY shard count: each valid
            # query scans all N rows exactly once across the shards
            evals = np.asarray(out["stats"]["distance_evals"])
            counters_exact &= bool((evals == n_rows).all())
            for q in range(b):
                counters_exact &= (
                    set(np.asarray(out["ids"])[q].tolist())
                    == set(np.asarray(ref["ids"])[q].tolist()))
            if shards == 1:
                bits = all(
                    np.array_equal(np.asarray(out[key]),
                                   np.asarray(ref[key]))
                    for key in ("ids", "sim", "valid"))
                bits &= all(
                    np.array_equal(np.asarray(out["stats"][s]),
                                   np.asarray(ref["stats"][s]))
                    for s in out["stats"])
                report["parity"]["shards1_bitparity"] = bits
                if not bits:
                    raise AssertionError(
                        "shards=1 is NOT bit-identical to the "
                        "single-device bucketed path")
            ms = timeit(lambda: stmt.execute({"qv": qs}).data, repeats=3)
            qps = 1e3 * b / ms
            base_qps.setdefault(b, qps)
            derived = per_query_amortized(out.counters, b)
            derived.update(
                shards=shards, batch=b, qps=round(qps, 1),
                speedup_vs_shard1=round(qps / base_qps[b], 2),
                merge_bytes_per_query=k * shards * 8)
            entries.append({"shards": shards, "batch": b,
                            "ms": round(ms, 3), "qps": round(qps, 1),
                            **derived})
        if not counters_exact:
            raise AssertionError(
                f"per-query counters/results not exact at shards={shards}")
        report["parity"]["counter_exact_shards"].append(shards)
    return report


def run(env, rows: list) -> dict:
    """Harness entry: run the sweep, collect rows.

    On CPU the sweep runs in a child process, because the fake-device count
    must be set before jax initializes — the parent harness already booted
    jax on the real (1-device) topology.  On an accelerator it runs in THIS
    process over the devices it already holds (a child could not reach
    them), at the shard counts the host has."""
    import jax

    from .common import Row

    rows_n = min(env.cfg.n_rows, FLAT_ROWS)
    k = min(env.cfg.k_top, 10)
    if jax.default_backend() != "cpu":
        sweep = tuple(s for s in SHARDS if s <= jax.device_count())
        report = _child(rows_n, env.cfg.dim, k, env.cfg.seed, sweep)
        with open(OUT_JSON, "w") as f:
            json.dump(report, f, indent=2)
        return _collect(report, rows, Row)
    cmd = [sys.executable, "-m", "benchmarks.q10_sharded_qps", "--child",
           "--rows", str(rows_n), "--dim", str(env.cfg.dim), "--k", str(k),
           "--seed", str(env.cfg.seed)]
    child_env = dict(os.environ)
    child_env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={DEVICE_COUNT}")
    child_env["PYTHONPATH"] = (os.path.join(ROOT, "src")
                               + os.pathsep
                               + child_env.get("PYTHONPATH", ""))
    r = subprocess.run(cmd, cwd=ROOT, env=child_env, capture_output=True,
                       text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"q10 child failed:\n{r.stdout}\n{r.stderr}")
    with open(OUT_JSON) as f:
        report = json.load(f)
    return _collect(report, rows, Row)


def _collect(report: dict, rows: list, row_cls) -> dict:
    for e in report["workloads"]["sharded"]:
        rows.append(row_cls(f"q10_s{e['shards']}_b{e['batch']}", e["ms"],
                            **{kk: vv for kk, vv in e.items()
                               if kk not in ("ms",)}))
    return report


def main(argv=None) -> None:
    """Standalone/child entry (see module docstring)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="run the measured sweep in THIS process (expects "
                         "the fake-device XLA flag already set)")
    ap.add_argument("--full", action="store_true",
                    help="full-scale dim/K (default: smoke)")
    ap.add_argument("--rows", type=int, default=FLAT_ROWS)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.child:
        report = _child(args.rows, args.dim, args.k, args.seed)
        with open(OUT_JSON, "w") as f:
            json.dump(report, f, indent=2)
        return
    # standalone: behave like the harness (spawn the fake-device child)
    from .common import get_env
    env = get_env(smoke=not args.full)
    rows = []
    report = run(env, rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(r.csv())
    print(f"\nparity: {report['parity']}", file=sys.stderr)


if __name__ == "__main__":
    main()
