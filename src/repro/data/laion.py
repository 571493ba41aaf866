"""Synthetic LAION-shaped corpus (paper §7.1, Table 2).

The evaluation dataset (laion1m + queries, 512-d CLIP embeddings, mutually
exclusive) is reproduced synthetically in this offline container with the same
*schema* and the geometric property IVF/HNSW both depend on: embeddings drawn
from a Gaussian mixture (clustered, anisotropic), L2-normalized like CLIP
vectors.  Selectivity levels are calibrated by quantiles exactly as §7.1.

Tables:
  laion(sample_id, url:int surrogate, text:int surrogate, height, width,
        nsfw:category{0,1,2}, similarity, calorie_level:category, vec)
  queries(id, cuisine:category, preferred_*, capture_date, vec)
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..core.schema import (Catalog, Metric, Schema, Table, category_col,
                           float_col, int_col, vector_col)


def _make_modes(rng: np.random.Generator, n_modes: int,
                dim: int) -> np.ndarray:
    modes = rng.standard_normal((n_modes, dim)).astype(np.float32)
    modes /= np.linalg.norm(modes, axis=1, keepdims=True)
    return modes


def _mixture_vectors(rng: np.random.Generator, n: int, dim: int,
                     n_modes: int, spread: float = 0.35,
                     modes: np.ndarray | None = None) -> np.ndarray:
    """Gaussian mixture on the unit sphere.  ``spread`` is the noise NORM
    relative to the unit mode vector (per-coordinate sigma = spread/sqrt(d)),
    so cluster tightness is dimension-independent — at d=512 an unscaled
    sigma would swamp the mode signal entirely.  Pass shared ``modes`` so
    corpus and queries live in the SAME clusters (mutually-exclusive rows,
    shared distribution — the LAION/queries relationship)."""
    if modes is None:
        modes = _make_modes(rng, n_modes, dim)
    which = rng.integers(0, modes.shape[0], size=n)
    sigma = spread / np.sqrt(dim)
    x = modes[which] + sigma * rng.standard_normal((n, dim)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x.astype(np.float32)


def selectivity_threshold(column: np.ndarray, selectivity: float) -> float:
    """Quantile calibration (§7.1): value v s.t. P(col < v) ≈ selectivity."""
    return float(np.quantile(column, selectivity))


def make_laion_catalog(n_rows: int = 100_000, n_queries: int = 100,
                       dim: int = 128, n_modes: int = 64,
                       num_categories: int = 8, seed: int = 0,
                       metric: Metric = Metric.INNER_PRODUCT,
                       query_spread: float = 0.15) -> Catalog:
    """Synthetic LAION-shaped catalog (§7.1): a mixture-of-modes vector
    corpus with correlated scalar columns, registered under the table
    aliases the Q1–Q6 benchmark SQL expects (laion/products/images/
    recipes/movies share one table; queries/users another)."""
    rng = np.random.default_rng(seed)
    modes = _make_modes(rng, n_modes, dim)
    vec = _mixture_vectors(rng, n_rows, dim, n_modes, modes=modes)
    # queries sit near mode centers (image-retrieval realism: a query image
    # resembles its cluster) — mirrors LAION queries being CLIP embeddings
    # of the same visual distribution; SAME modes as the corpus
    qvec = _mixture_vectors(rng, n_queries, dim, n_modes,
                            spread=query_spread, modes=modes)

    height = rng.integers(64, 2048, size=n_rows).astype(np.int32)
    width = rng.integers(64, 2048, size=n_rows).astype(np.int32)
    nsfw = rng.choice(3, size=n_rows, p=[0.9, 0.07, 0.03]).astype(np.int32)
    similarity = rng.beta(2.0, 4.0, size=n_rows).astype(np.float32)
    price = (rng.lognormal(3.5, 1.0, size=n_rows)).astype(np.float32)
    capture_date = rng.integers(0, 3650, size=n_rows).astype(np.int32)
    calorie = rng.integers(0, num_categories, size=n_rows).astype(np.int32)
    cuisine = rng.integers(0, num_categories, size=n_rows).astype(np.int32)
    rating = rng.integers(0, 5, size=n_rows).astype(np.int32)
    release_year = rng.integers(1980, 2026, size=n_rows).astype(np.int32)

    laion_schema = Schema({
        "sample_id": int_col(jnp.int64),
        "height": int_col(), "width": int_col(),
        "nsfw": category_col(3),
        "similarity": float_col(),
        "price": float_col(),
        "capture_date": int_col(),
        "calorie_level": category_col(num_categories),
        "cuisine": category_col(num_categories),
        "rating": category_col(5),
        "release_year": int_col(),
        "vec": vector_col(dim, metric),
        "embedding": vector_col(dim, metric),
    }, primary_key="sample_id")
    # one device buffer behind both vector column names: the aliases are
    # the same embedding, and a second copy would double the corpus's HBM
    dvec = jnp.asarray(vec)
    laion = Table(laion_schema, {
        "sample_id": jnp.arange(n_rows, dtype=jnp.int64),
        "height": jnp.asarray(height), "width": jnp.asarray(width),
        "nsfw": jnp.asarray(nsfw), "similarity": jnp.asarray(similarity),
        "price": jnp.asarray(price),
        "capture_date": jnp.asarray(capture_date),
        "calorie_level": jnp.asarray(calorie),
        "cuisine": jnp.asarray(cuisine),
        "rating": jnp.asarray(rating),
        "release_year": jnp.asarray(release_year),
        "vec": dvec,
        "embedding": dvec,
    })

    q_pref_rating = rng.integers(0, 5, size=n_queries).astype(np.int32)
    q_pref_year = rng.integers(1990, 2020, size=n_queries).astype(np.int32)
    q_cuisine = rng.integers(0, num_categories, size=n_queries).astype(np.int32)
    q_capture = rng.integers(0, 3650, size=n_queries).astype(np.int32)
    queries_schema = Schema({
        "id": int_col(jnp.int64),
        "preferred_rating": category_col(5),
        "preferred_release_year": int_col(),
        "cuisine": category_col(num_categories),
        "capture_date": int_col(),
        "embedding": vector_col(dim, metric),
        "vec": vector_col(dim, metric),
    }, primary_key="id")
    dqvec = jnp.asarray(qvec)
    queries = Table(queries_schema, {
        "id": jnp.arange(n_queries, dtype=jnp.int64),
        "preferred_rating": jnp.asarray(q_pref_rating),
        "preferred_release_year": jnp.asarray(q_pref_year),
        "cuisine": jnp.asarray(q_cuisine),
        "capture_date": jnp.asarray(q_capture),
        "embedding": dqvec,
        "vec": dqvec,
    })

    cat = Catalog()
    cat.register("laion", laion)
    cat.register("products", laion)     # Q1 template alias
    cat.register("images", laion)       # Q2/Q3 template alias
    cat.register("recipes", laion)      # Q5/Q6 template alias
    cat.register("movies", laion)       # Q4 template alias
    cat.register("queries", queries)
    cat.register("users", queries)      # Q4 template alias
    return cat
