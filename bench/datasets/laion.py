"""LAION-shaped catalog made on the device from the seed.

The same mixture model and schema as the program's ``data/laion.py``
(CHASE §7.1: 512-d CLIP-shaped rows under inner product, price and
category columns, the Q1-Q6 table aliases), rewritten on ``jax.random`` so
that the paper's 1,000,000 x 512 corpus is made in one jitted call on the
chip instead of in host NumPy.  The benchmark keeps the device arrays it
made (corpus, scalar columns) for its own reference; the program gets them
only through its public ``Catalog``.

Configuration keys read here: ``rows``, ``dim``, ``modes``, ``groups``,
``group_spread``, ``spread``, ``query_spread``, ``price_lognormal``
([mu, sigma]), ``categories``.

The modes are not independent directions, as in ``data/laion.py``: they
come in ``groups`` of near neighbours, each mode centre the unit vector of
its group's centre plus ``group_spread`` times a unit vector of its own.
In 512 dimensions independent modes are all but orthogonal, so every top-K
would sit in the query's own mode and one IVF list; grouped modes overlap
as concepts in CLIP space do, so a top-K spans several lists and recall
depends on how many an IVF lane probes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Catalog, Metric, Schema, Table, category_col,
                        float_col, int_col, vector_col)


class HostColumns(dict):
    """Scalar column name -> host NumPy array, each copied from the device
    the first time it is read."""

    def __init__(self, device: dict):
        super().__init__()
        self._device = device

    def __missing__(self, name: str) -> np.ndarray:
        self[name] = value = np.asarray(self._device[name])
        return value


@dataclasses.dataclass
class Dataset:
    catalog: Catalog
    corpus: jax.Array          # (N, D) float32, on the device
    columns: HostColumns       # scalar column name -> host NumPy array
    modes: jax.Array           # (M, D) mixture centres (the queries' too)
    query_spread: float

    def queries(self, key: jax.Array, n: int) -> np.ndarray:
        """``n`` fresh query vectors from the corpus's mixture, on the host."""
        return np.asarray(_queries(key, self.modes, n, self.query_spread))


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _queries(key, modes, n, spread):
    m, d = modes.shape
    k1, k2 = jax.random.split(key)
    which = jax.random.randint(k1, (n,), 0, m)
    noise = jax.random.normal(k2, (n, d), jnp.float32)
    return _unit(modes[which] + (spread / np.sqrt(d)) * noise)


@functools.partial(jax.jit, static_argnames=(
    "rows", "dim", "modes", "groups", "group_spread", "spread", "mu",
    "sigma", "categories"))
def _make(key, *, rows, dim, modes, groups, group_spread, spread, mu, sigma,
          categories):
    ks = jax.random.split(key, 13)
    tops = _unit(jax.random.normal(ks[12], (groups, dim), jnp.float32))
    own = _unit(jax.random.normal(ks[0], (modes, dim), jnp.float32))
    centres = _unit(tops[jnp.arange(modes) % groups] + group_spread * own)
    which = jax.random.randint(ks[1], (rows,), 0, modes)
    noise = jax.random.normal(ks[2], (rows, dim), jnp.float32)
    corpus = _unit(centres[which] + (spread / np.sqrt(dim)) * noise)
    ints = lambda k, lo, hi: jax.random.randint(k, (rows,), lo, hi, jnp.int32)
    cols = {
        "height": ints(ks[3], 64, 2048),
        "width": ints(ks[4], 64, 2048),
        "nsfw": jax.random.choice(ks[5], 3, (rows,),
                                  p=jnp.array([0.9, 0.07, 0.03])
                                  ).astype(jnp.int32),
        "similarity": jax.random.beta(ks[6], 2.0, 4.0, (rows,)),
        "price": jnp.exp(mu + sigma * jax.random.normal(ks[7], (rows,))),
        "capture_date": ints(ks[8], 0, 3650),
        "calorie_level": ints(ks[9], 0, categories),
        "cuisine": ints(ks[10], 0, categories),
        "rating": ints(ks[11], 0, 5),
        "release_year": ints(jax.random.fold_in(ks[11], 1), 1980, 2026),
    }
    return centres, corpus, cols


def build(cfg: dict, key: jax.Array) -> Dataset:
    """The catalog of one configuration, made from ``key``."""
    dim, cats = cfg["dim"], cfg["categories"]
    mu, sigma = cfg["price_lognormal"]
    centres, corpus, cols = _make(
        key, rows=cfg["rows"], dim=dim, modes=cfg["modes"],
        groups=cfg["groups"], group_spread=float(cfg["group_spread"]),
        spread=float(cfg["spread"]), mu=float(mu), sigma=float(sigma),
        categories=cats)
    metric = Metric.INNER_PRODUCT
    n = cfg["rows"]
    schema = Schema({
        "sample_id": int_col(jnp.int64),
        "height": int_col(), "width": int_col(),
        "nsfw": category_col(3),
        "similarity": float_col(),
        "price": float_col(),
        "capture_date": int_col(),
        "calorie_level": category_col(cats),
        "cuisine": category_col(cats),
        "rating": category_col(5),
        "release_year": int_col(),
        "vec": vector_col(dim, metric),
        "embedding": vector_col(dim, metric),
    }, primary_key="sample_id")
    # one device buffer behind both vector column names, as in the program
    laion = Table(schema, {"sample_id": jnp.arange(n, dtype=jnp.int64),
                           **cols, "vec": corpus, "embedding": corpus})
    cat = Catalog()
    for alias in ("laion", "products", "images", "recipes", "movies"):
        cat.register(alias, laion)
    return Dataset(cat, corpus, HostColumns(cols), centres,
                   float(cfg["query_spread"]))
