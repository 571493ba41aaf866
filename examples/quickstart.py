"""Quickstart: hybrid queries on structured + unstructured data with CHASE.

Builds a LAION-shaped catalog and an IVF index, opens a session with the
front-door API (``connect -> prepare -> execute``), runs the paper's Q1
(VKNN-SF) through four engine modes, shows the normalized plan cache
collapsing textual variants, and EXPLAINs the live executor state.

  PYTHONPATH=src python examples/quickstart.py            # 20k rows
  PYTHONPATH=src python examples/quickstart.py --smoke    # CI-scale shapes
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax

from repro.api import ExecutionHints, connect
from repro.compile_cache import enable_compile_cache
from repro.core import EngineOptions, Metric, compile_query
from repro.data import make_laion_catalog, selectivity_threshold
from repro.index import build_ivf
from repro.index.ivf import ProbeConfig

SQL = """
SELECT sample_id FROM products
WHERE price < ${max_price}
ORDER BY DISTANCE(embedding, ${image_embedding})
LIMIT 10
"""

# same query, different whitespace AND renamed parameters — the normalized
# plan cache must collapse this onto SQL's compiled plan
SQL_VARIANT = ("SELECT sample_id FROM products WHERE price < ${cap} "
               "ORDER BY DISTANCE(embedding, ${qv}) LIMIT 10")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale shapes (small catalog, fast)")
    args = ap.parse_args()
    n_rows, nlist = (2_000, 16) if args.smoke else (20_000, 64)

    print(f"== building catalog ({n_rows} rows, 128-d) ==")
    cat = make_laion_catalog(n_rows=n_rows, n_queries=4, dim=128,
                             n_modes=64, seed=0)
    corpus = cat.table("laion")["vec"]
    idx = build_ivf(jax.random.key(0), corpus, nlist=nlist,
                    metric=Metric.INNER_PRODUCT)
    cat.register_index("products", "embedding", idx)

    qv = np.asarray(cat.table("queries")["embedding"][0])
    price = selectivity_threshold(
        np.asarray(cat.table("laion")["price"]), 0.5)
    probe = ProbeConfig(max_probes=32)

    print("\n== session API: connect -> prepare -> execute ==")
    db = connect(cat, EngineOptions(engine="chase", probe=probe))
    stmt = db.prepare(SQL)
    res = stmt.execute({"image_embedding": qv, "max_price": price})
    ids = np.asarray(res.ids)[np.asarray(res.valid)]
    print(f"single query -> Result, top3={ids[:3].tolist()}")

    # batched: a list of bind dicts rides the size-bucketed serving path
    batch = stmt.execute([
        {"image_embedding": qv + 0.01 * i, "max_price": price}
        for i in range(3)])
    print(f"batch of {len(batch)} -> ResultBatch, ids shape "
          f"{np.asarray(batch.ids).shape}")

    print("\n== normalized plan cache ==")
    variant = db.prepare(SQL_VARIANT)       # renamed params, same plan
    vres = variant.execute({"qv": qv, "cap": price})
    assert np.array_equal(np.asarray(vres.ids), np.asarray(res.ids))
    info = db.cache_info()
    print(f"variant prepare was a cache {'hit' if variant.cache_hit else 'miss'}"
          f" (hits={info.hits}, misses={info.misses}, entries={info.entries})"
          f" — zero new executables compiled")

    print("\n== explain (live executor state) ==")
    print(batch.explain())

    print("\n== engine modes ==")
    for engine in ("chase", "vbase", "pase", "brute"):
        edb = connect(cat, EngineOptions(engine=engine, probe=probe))
        q = edb.prepare(SQL)
        binds = {"image_embedding": qv, "max_price": price}
        out = q.execute(binds)            # compile
        t0 = time.perf_counter()
        for _ in range(10):
            out = q.execute(binds)
        jax.block_until_ready(out["ids"])
        dt = (time.perf_counter() - t0) / 10 * 1e3
        ids = np.asarray(out.ids)[np.asarray(out.valid)]
        print(f"{engine:6s}: {dt:7.2f} ms  "
              f"evals={int(out.counters['distance_evals']):6d}  "
              f"top3={ids[:3].tolist()}")

    print("\n== legacy shim (old -> new mapping) ==")
    # old: q = compile_query(sql, cat, options); out = q(**binds)
    # new: stmt = connect(cat, options).prepare(sql); res = stmt.execute(binds)
    # (compile_query compiles fresh per call — no plan cache — but results
    #  are bit-identical to Statement.execute)
    legacy = compile_query(SQL, cat, EngineOptions(engine="chase",
                                                   probe=probe))
    lout = legacy(image_embedding=qv, max_price=price)
    assert np.array_equal(np.asarray(lout["ids"]), np.asarray(res["ids"]))
    print("compile_query(...)(**binds) == Statement.execute(binds)  [ok]")


if __name__ == "__main__":
    enable_compile_cache()
    main()
