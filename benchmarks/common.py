"""Shared benchmark environment: LAION-shaped corpus + IVF index + ground
truth, selectivity calibration per §7.1, timing protocol."""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.chase_laion import (ChaseBenchConfig, bench_config,
                                       smoke_bench_config)    # noqa: E402
from repro.core import Metric                                 # noqa: E402
from repro.data import make_laion_catalog                     # noqa: E402
from repro.index import FlatIndex, build_ivf                  # noqa: E402

SELECTIVITIES = (1.0, 0.9, 0.7, 0.5, 0.3, 0.03)

# every benchmark imports this module before it compiles anything
enable_compile_cache()


@dataclasses.dataclass
class BenchEnv:
    cfg: ChaseBenchConfig
    catalog: object
    flat: FlatIndex
    qvecs: np.ndarray            # (Q, dim)
    sims: np.ndarray             # (Q, N) ground-truth similarities
    price: np.ndarray
    price_thresholds: dict       # selectivity -> threshold
    radius_topk: float           # tuned so avg matches ≈ range_match_target


_ENV = {}


def get_env(smoke: bool = False) -> BenchEnv:
    if smoke in _ENV:
        return _ENV[smoke]
    cfg = smoke_bench_config() if smoke else bench_config()
    t0 = time.time()
    catalog = make_laion_catalog(
        n_rows=cfg.n_rows, n_queries=cfg.n_queries, dim=cfg.dim,
        n_modes=cfg.n_modes, num_categories=cfg.num_categories,
        seed=cfg.seed, metric=cfg.metric)
    corpus = catalog.table("laion")["vec"]
    idx = build_ivf(jax.random.key(cfg.seed), corpus, nlist=cfg.nlist,
                    metric=cfg.metric, iters=cfg.kmeans_iters)
    for name in ("laion", "products", "images", "recipes", "movies"):
        catalog.register_index(name, "vec", idx)
        catalog.register_index(name, "embedding", idx)
    flat = FlatIndex(cfg.metric, corpus)
    qvecs = np.asarray(catalog.table("queries")["embedding"])
    sims = np.asarray(
        jnp.einsum("qd,nd->qn", jnp.asarray(qvecs), corpus))
    price = np.asarray(catalog.table("laion")["price"])
    thresholds = {s: float(np.quantile(price, s)) if s < 1.0 else None
                  for s in SELECTIVITIES}
    # radius: avg #matches == range_match_target (paper: ~120 per query)
    target = cfg.range_match_target
    per_query_kth = np.partition(sims, -target, axis=1)[:, -target]
    radius = float(np.median(per_query_kth))
    env = BenchEnv(cfg, catalog, flat, qvecs, sims, price, thresholds,
                   radius)
    print(f"[bench] env ready: N={cfg.n_rows} dim={cfg.dim} "
          f"nlist={cfg.nlist} radius={radius:.4f} "
          f"({time.time()-t0:.1f}s)", file=sys.stderr, flush=True)
    _ENV[smoke] = env
    return env


def timeit(fn, repeats: int = 5) -> float:
    """Median wall-clock ms over ``repeats`` (after a warmup/compile call)."""
    out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0] if isinstance(out, dict)
                          else out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree.leaves(out)[0]
                              if isinstance(out, dict) else out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e3)


def recall_sets(got_ids: np.ndarray, got_valid: np.ndarray,
                gt_ids: np.ndarray, gt_valid: np.ndarray | None = None
                ) -> float:
    got = set(np.asarray(got_ids)[np.asarray(got_valid)].tolist())
    if gt_valid is None:
        gt = set(np.asarray(gt_ids).tolist())
    else:
        gt = set(np.asarray(gt_ids)[np.asarray(gt_valid)].tolist())
    gt.discard(-1)
    got.discard(-1)
    if not gt:
        return 1.0
    return len(got & gt) / len(gt)


class Row:
    """One CSV record: name,us_per_call,derived."""

    def __init__(self, name: str, ms: float, **derived):
        self.name = name
        self.ms = ms
        self.derived = derived

    def csv(self) -> str:
        extra = ";".join(f"{k}={v}" for k, v in self.derived.items())
        return f"{self.name},{self.ms*1e3:.1f},{extra}"
