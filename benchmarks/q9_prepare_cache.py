"""Q9 — prepared-statement plan-cache economics (the session-API bench).

Plan reuse across requests is the dominant cost of "multiple hybrid
queries" serving workloads: a cold ``prepare`` pays parse + analyze +
rewrite + trace + XLA compile, while a warm one pays parse + fingerprint
only.  This bench measures that gap on the session API
(:mod:`repro.api`) and verifies the cache normalizes across textual
variants:

* ``prepare_cold``   — first-ever prepare of Q1 (full compile, includes the
  first execute's jit; JAX's persistent compilation cache is off for it),
* ``prepare_warm``   — re-prepare of the *same text* (cache hit),
* ``prepare_variant``— re-prepare of a whitespace + param-renamed +
  conjunct-reordered variant (MUST also hit: zero new executables,
  asserted via ``trace_counts``),
* ``execute_hit``    — a bucketed batch execute through a variant statement
  (rename translation on the hot path, reusing the original's bucket
  executable),
* ``restart_cold`` / ``restart_warm`` — restart prepare + first batch
  execute latency, without vs with a populated persistent AOT plan cache
  (DESIGN.md §15): three restarts run back-to-back (cold, untimed
  populate, warm), so the ``restart.speedup`` ratio never rides cross-run
  machine noise.  Each restart is emulated in this process (in-memory
  executables dropped, a fresh session), so one process holds the
  accelerator throughout.  The warm restart hard-asserts zero retraces.
  ``scripts/bench_gate.py`` gates ``speedup >= 10``.

Writes ``BENCH_api.json``.

Standalone:  PYTHONPATH=src python -m benchmarks.q9_prepare_cache [--full]
"""
from __future__ import annotations

import contextlib
import json
import os
import tempfile
import time

import numpy as np

from repro.api import connect
from repro.core import EngineOptions

from .common import BenchEnv, Row

K = 10
N_BATCH = 8
REPEATS = 50
OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_api.json")

SQL = ("SELECT sample_id FROM products "
       "WHERE price < ${max_price} AND nsfw <> ${mid} "
       "ORDER BY DISTANCE(embedding, ${qv}) LIMIT 10")
# whitespace + renamed params + swapped conjuncts: one plan-cache entry
SQL_VARIANT = """
SELECT sample_id
FROM products
WHERE nsfw <> ${m} AND price < ${cap}
ORDER BY DISTANCE(embedding, ${vec})
LIMIT 10
"""


def _restart_binds(env: BenchEnv) -> list:
    return [{"qv": env.qvecs[i % len(env.qvecs)],
             "max_price": env.price_thresholds[0.5], "mid": 0}
            for i in range(N_BATCH)]


@contextlib.contextmanager
def _no_compile_cache():
    """Keep JAX's persistent compilation cache out of the cold-prepare and
    restart rows: a "cold" prepare that found its XLA executables on disk
    would not be cold (the restart rows measure the persistent AOT plan
    cache, not JAX's)."""
    import jax
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _restart(role: str, aot_dir: str, env: BenchEnv) -> dict:
    """Time ONE restart's prepare + first batch execute — the restart cost
    a serving process actually pays.  The restart is emulated in this
    process: every in-memory executable is dropped (``jax.clear_caches``)
    and the session connects afresh, so one process keeps the accelerator
    (cross-process AOT restarts are covered by ``tests/test_aot_cache.py``).
    ``cold`` runs without a cache; ``populate`` / ``warm`` attach
    ``aot_dir`` (DESIGN.md §15).  The warm role hard-asserts zero retraces:
    if the persistent cache misses, the bench fails loud."""
    import jax

    jax.clear_caches()
    db = connect(env.catalog,
                 EngineOptions(engine="chase", probe=env.cfg.probe),
                 aot_cache_path=(None if role == "cold" else aot_dir))
    binds = _restart_binds(env)
    with _no_compile_cache():
        t0 = time.perf_counter()
        stmt = db.prepare(SQL)
        out = stmt.execute(binds)
        jax.block_until_ready(out["ids"])
        ms = 1e3 * (time.perf_counter() - t0)
    traces = sum(stmt.executor.trace_counts.values())
    if role == "warm" and traces:
        raise SystemExit(f"warm restart retraced ({traces} traces) — the "
                         f"persistent AOT cache missed")
    return {"role": role, "ms": round(ms, 3), "traces": traces}


def restart_bench(env: BenchEnv, rows: list) -> dict:
    """Cold vs AOT-warm restart latency: three restarts back-to-back
    (cold, untimed populate, warm) over one temporary cache dir."""
    with tempfile.TemporaryDirectory(prefix="q9aot-") as aot_dir:
        cold = _restart("cold", aot_dir, env)
        _restart("populate", aot_dir, env)     # untimed: persists entries
        warm = _restart("warm", aot_dir, env)
    speedup = cold["ms"] / max(warm["ms"], 1e-6)
    rows.append(Row("q9_restart_cold", cold["ms"]))
    rows.append(Row("q9_restart_warm", warm["ms"],
                    speedup=round(speedup, 1)))
    return {"cold_ms": cold["ms"], "warm_ms": warm["ms"],
            "cold_traces": cold["traces"], "warm_traces": warm["traces"],
            "speedup": round(speedup, 2)}


def _timed_ms(fn, repeats: int = REPEATS) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(times))


def run(env: BenchEnv, rows: list) -> dict:
    import jax

    db = connect(env.catalog, EngineOptions(engine="chase",
                                            probe=env.cfg.probe))
    binds = {"qv": env.qvecs[0], "max_price": env.price_thresholds[0.5],
             "mid": 0}
    vbinds_list = [{"vec": env.qvecs[i % len(env.qvecs)] + 1e-3 * i,
                    "cap": env.price_thresholds[0.5], "m": 0}
                   for i in range(N_BATCH)]

    with _no_compile_cache():
        t0 = time.perf_counter()
        stmt = db.prepare(SQL)
        out = stmt.execute(binds)
        jax.block_until_ready(out["ids"])
        cold_ms = 1e3 * (time.perf_counter() - t0)

    warm_ms = _timed_ms(lambda: db.prepare(SQL))
    variant_ms = _timed_ms(lambda: db.prepare(SQL_VARIANT))
    vstmt = db.prepare(SQL_VARIANT)
    assert vstmt.cache_hit and vstmt.compiled is stmt.compiled, \
        "variant prepare missed the normalized plan cache"

    # warm the bucket, then time the variant's bucketed execute (rename
    # translation + pad/slice on the hot path)
    jax.block_until_ready(vstmt.execute(vbinds_list)["ids"])
    traces_before = dict(stmt.executor.trace_counts)
    exec_ms = _timed_ms(lambda: vstmt.execute(vbinds_list), repeats=10)
    assert stmt.executor.trace_counts == traces_before, \
        "variant execute retraced an executable"

    info = db.cache_info()
    report = {
        "n_rows": env.cfg.n_rows, "dim": env.cfg.dim, "k": K,
        "n_batch": N_BATCH,
        "prepare_cold_ms": round(cold_ms, 3),
        "prepare_warm_ms": round(warm_ms, 4),
        "prepare_variant_ms": round(variant_ms, 4),
        "execute_hit_ms": round(exec_ms, 3),
        "cold_over_warm": round(cold_ms / max(warm_ms, 1e-6), 1),
        "cache": {"hits": info.hits, "misses": info.misses,
                  "entries": info.entries},
    }
    report["restart"] = restart_bench(env, rows)
    with open(OUT_JSON, "w") as f:
        json.dump(report, f, indent=2)
    rows.append(Row("q9_prepare_cold", cold_ms))
    rows.append(Row("q9_prepare_warm", warm_ms,
                    cold_over_warm=report["cold_over_warm"]))
    rows.append(Row("q9_prepare_variant", variant_ms,
                    cache_hit=1))
    rows.append(Row("q9_execute_hit_b8", exec_ms))
    return report


if __name__ == "__main__":
    import argparse

    from .common import get_env

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-scale catalog (default: smoke)")
    args = ap.parse_args()
    env = get_env(smoke=not args.full)
    rows: list[Row] = []
    report = run(env, rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(r.csv())
    print(f"\ncold prepare {report['prepare_cold_ms']:.1f} ms vs warm "
          f"{report['prepare_warm_ms']:.3f} ms "
          f"({report['cold_over_warm']}x); variant hit "
          f"{report['prepare_variant_ms']:.3f} ms; restart cold "
          f"{report['restart']['cold_ms']:.1f} ms vs AOT-warm "
          f"{report['restart']['warm_ms']:.1f} ms "
          f"({report['restart']['speedup']}x)")
