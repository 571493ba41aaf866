"""Distributed hybrid query: corpus sharded over an 8-device mesh,
per-shard fused scan-topk, hierarchical collective merge.

Part 1 drives the raw single-query collective (DESIGN.md §5); part 2 runs
the shard × tile composition through the session API (`EngineOptions.dist`,
DESIGN.md §10): every device scans its row shard for ALL queries in the
batch at once, and `explain()` reports the shard count and merge depth.

Run with fake devices (any machine):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_query.py
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.schema import Metric
from repro.dist.collectives import (distributed_range, distributed_topk,
                                    shard_corpus)
from repro.index import FlatIndex
from repro.launch.mesh import make_mesh


def main():
    print(f"devices: {len(jax.devices())}")
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    n, d = 65536, 256
    corpus = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    mask = jnp.asarray(rng.random(n) < 0.5)       # structured filter

    flat = FlatIndex(Metric.INNER_PRODUCT, corpus)
    gt_ids, gt_sims, _ = flat.topk(q, 10, mask)

    with mesh:
        sh_corpus, sh_ids = shard_corpus(mesh, corpus)
        sh_mask = jax.device_put(mask, sh_ids.sharding)
        topk = jax.jit(distributed_topk(mesh, Metric.INNER_PRODUCT, 10))
        ids, sims, valid = topk(sh_corpus, sh_ids, q, sh_mask)   # compile
        t0 = time.perf_counter()
        for _ in range(10):
            ids, sims, valid = topk(sh_corpus, sh_ids, q, sh_mask)
        jax.block_until_ready(ids)
        dt = (time.perf_counter() - t0) / 10 * 1e3

    match = set(np.asarray(ids).tolist()) == set(np.asarray(gt_ids).tolist())
    print(f"distributed filtered top-10 over {n} sharded rows: {dt:.2f} ms, "
          f"exact={match}")
    print("ids:", np.asarray(ids).tolist())
    wire = 10 * 8 * 8   # K * (id+sim bytes) * shards
    print(f"wire bytes for the merge ≈ {wire} B vs {n*d*4/1e6:.0f} MB corpus"
          f" — the reason hybrid search shards across pods (DESIGN.md §5)")


def main_batched():
    """Part 2: the shard x tile composition through the session API."""
    from repro.api import DistSpec, connect
    from repro.core import EngineOptions
    from repro.data import make_laion_catalog

    cat = make_laion_catalog(n_rows=16384, n_queries=8, dim=64, n_modes=32,
                             seed=0)
    db = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                    dist=DistSpec(mesh_shape=(4,))))
    stmt = db.prepare("SELECT sample_id FROM products "
                      "ORDER BY DISTANCE(embedding, ${qv}) LIMIT 10")
    qs = np.asarray(cat.table("queries")["embedding"])      # (8, 64)
    out = stmt.execute({"qv": qs})                           # bucketed batch
    jax.block_until_ready(out["ids"])                        # compile
    t0 = time.perf_counter()
    for _ in range(10):
        out = stmt.execute({"qv": qs})
        jax.block_until_ready(out["ids"])
    dt = (time.perf_counter() - t0) / 10 * 1e3
    rep = out.explain()
    print(f"\nsession-API sharded batch (Q=8, shards={rep.shards}, "
          f"merge_depth={rep.merge_depth}): {dt:.2f} ms "
          f"({np.asarray(out['stats']['distance_evals'])[0]} evals/query)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
    main_batched()
