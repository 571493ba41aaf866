"""Plain reference for the category-partition top-k (CHASE's Q5), and the
comparison that decides ``correct``.

The statement is ``SELECT ... RANK() OVER (PARTITION BY <partition> ORDER
BY DISTANCE(<vector>, ${qv})) ... WHERE DISTANCE(<vector>, ${qv}) <= ${r}
AND <exclude column> <> ${ex} ... WHERE rank <= K`` under inner product.
There the engine's distance key is ``-q . x`` (``kernels/distance.py``),
and it reads the radius as a similarity turned into a key the same way:
``DISTANCE <= r`` holds where ``-q . x <= -r``, that is where ``q . x >= r``
(``core/expr.py``, ``in_range``).  So a row qualifies when ``q . x >= r``
and its exclude column differs from ``ex``; for each category c in
0..C-1, slot c of the answer holds the K qualifying rows of that category
with the largest ``q . x``.

The reference is independent of the program: a plain ``jnp.dot`` over the
whole corpus at ``Precision.HIGHEST`` in blocks of queries, ``lax.top_k``
of K + EXTRA candidates per category under the filter, then every
candidate and every served row scored again on the host in float64.
Ranks, gaps and the radius are judged on those float64 scores.

At the radius: a row whose float64 score lies within the ``sim_err`` limit
of ``r`` is neither required nor forbidden.  A sound kernel's float32
score may be off by that much, so it may put such a row on either side of
the radius; holding it to the float64 side would fail sound runs on ties
with the radius alone.

Numbers compared (each with its limit from the configuration file):

* ``bad_rows``: served rows that are out of range, repeated within a slot,
  below the radius, of the excluded value, or in another category's slot
  (their partition column, or the answer's own ``category``, differs from
  the slot's);
* ``short_answers``: slots whose number of rows differs from min(K, rows
  of that category that qualify), edge rows counted either way;
* ``sim_err``: the widest gap between a served score and the float64 score
  of the same row;
* ``rank_gap``: the widest amount by which a served row's float64 score
  lies below the float64 K-th best of its category.

The mix's ``check`` names ``k``, the query vector's bind
(``vector_bind``), the radius's (``radius_bind``), the excluded value's
(``exclude_bind``), the column it is tested on (``exclude_column``) and
the partition column (``partition_column``).  Only exact answers are
judged: the configuration's guarantee is ``exact``.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"{__name__}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# one precision ladder and one judgement for every reference
_knn = _sibling("filtered_knn")
judge = _knn.judge

EXTRA = _knn.EXTRA  # candidates beyond K, so float64 re-ranking sees the K-th
BLOCK = _knn.BLOCK  # queries per reference block


@functools.partial(jax.jit,
                   static_argnames=("k", "categories", "precision"))
def _block_topk(corpus, part, excl, qv, floor, ex, *, k, categories,
                precision):
    """Per category, the top ``k`` rows whose score is at least ``floor``
    and whose exclude column differs from ``ex``: (scores, ids, rows over
    the floor), each (B, C, ...); empty slots score -inf."""
    s = _knn._dot(qv, corpus, precision)
    keep = (s >= floor[:, None]) & (excl[None, :] != ex[:, None])
    vals, ids, over = [], [], []
    for c in range(categories):
        here = keep & (part[None, :] == c)
        v, i = jax.lax.top_k(jnp.where(here, s, -jnp.inf), k)
        vals.append(v)
        ids.append(i)
        over.append(here.sum(1))
    return jnp.stack(vals, 1), jnp.stack(ids, 1), jnp.stack(over, 1)


def candidates(corpus, part, excl, qv: np.ndarray, floor: np.ndarray,
               ex: np.ndarray, k: int, categories: int,
               precision: str = "highest"):
    """(scores, ids, over): each query's top ``k`` rows per category, on
    the device, and how many rows of each category passed."""
    n = qv.shape[0]
    pad = -n % BLOCK
    qv, floor, ex = (np.concatenate([a, np.repeat(a[-1:], pad, 0)])
                     for a in (qv, floor, ex))
    outs = [jax.device_get(_block_topk(
        corpus, part, excl, jnp.asarray(qv[s:s + BLOCK]),
        jnp.asarray(floor[s:s + BLOCK]), jnp.asarray(ex[s:s + BLOCK]),
        k=k, categories=categories, precision=precision))
        for s in range(0, n + pad, BLOCK)]
    return tuple(np.concatenate(parts)[:n] for parts in zip(*outs))


def score64(corpus, qv: np.ndarray, ids: np.ndarray,
            use: np.ndarray) -> np.ndarray:
    """float64 inner products of each query with its listed rows where
    ``use`` holds (ids in range there), nan elsewhere; ``ids`` is (S, ...)
    with the query on the first axis."""
    out = np.full(ids.shape, np.nan)
    where = np.nonzero(use)
    rows = np.asarray(corpus[jnp.asarray(ids[where])]).astype(np.float64)
    out[where] = np.einsum("nd,nd->n", rows,
                           qv[where[0]].astype(np.float64))
    return out


def _inputs(dataset, binds: dict, spec: dict):
    part = dataset.columns[spec["partition_column"]]
    excl = dataset.columns[spec["exclude_column"]]
    qv = binds[spec["vector_bind"]]
    radius = np.asarray(binds[spec["radius_bind"]], np.float32)
    ex = np.asarray(binds[spec["exclude_bind"]], np.int32)
    return part, excl, qv, radius, ex


def compare_run(dataset, binds: dict, served: dict, spec: dict,
                guarantee: dict, limits: dict) -> dict:
    """The compared numbers for the sampled answers: ``binds`` and
    ``served`` hold each bind and answer key over the sample, the answers'
    ``ids``, ``sim`` and ``valid`` (and ``category``, where served) of
    shape (S, C, K)."""
    if guarantee["kind"] != "exact":
        raise ValueError("partition_topk judges exact answers only")
    corpus, n_rows = dataset.corpus, dataset.corpus.shape[0]
    part, excl, qv, radius, ex = _inputs(dataset, binds, spec)
    k, tol = spec["k"], float(limits["sim_err"]["max"])
    C = max(int(part.max()) + 1, served["ids"].shape[1])
    # every row within tol of the radius in float64 lies over this floor
    # in the reference's float32, whose own error is well under tol
    floor = radius - np.float32(2 * tol)
    cvals, cids, over = candidates(corpus, jnp.asarray(part),
                                   jnp.asarray(excl), qv, floor, ex,
                                   k + EXTRA, C)
    c64 = score64(corpus, qv, cids, np.isfinite(cvals))
    ids, sim, valid = served["ids"], served["sim"], served["valid"]
    in_range = valid & (ids >= 0) & (ids < n_rows)
    s64 = score64(corpus, qv, ids, in_range)
    r64 = radius.astype(np.float64)
    out = {"bad_rows": 0, "short_answers": 0, "sim_err": 0.0,
           "rank_gap": 0.0}
    for s in range(ids.shape[0]):
        for c in range(C):
            scores = c64[s, c][np.isfinite(cvals[s, c])]
            sure = int((scores >= r64[s] + tol).sum())
            edge = int((np.abs(scores - r64[s]) < tol).sum())
            if over[s, c] <= k + EXTRA:         # every candidate is here
                lo, hi = min(k, sure), min(k, sure + edge)
            else:
                lo, hi = min(k, sure), k
            if c >= ids.shape[1]:
                out["short_answers"] += int(lo > 0)
                continue
            got = ids[s, c][valid[s, c]]
            ok = in_range[s, c][valid[s, c]]
            row = got[ok]
            g64 = s64[s, c][in_range[s, c]]
            wrong = ((g64 < r64[s] - tol) | (excl[row] == ex[s])
                     | (part[row] != c))
            if "category" in served:
                wrong |= served["category"][s, c][in_range[s, c]] != c
            out["bad_rows"] += (int((~ok).sum())
                                + (got.size - np.unique(got).size)
                                + int(wrong.sum()))
            out["short_answers"] += int(not lo <= got.size <= hi)
            if row.size:
                err = np.abs(sim[s, c][in_range[s, c]].astype(np.float64)
                             - g64)
                out["sim_err"] = max(out["sim_err"], float(err.max()))
            ranked = np.sort(scores[scores >= r64[s] - tol])[::-1]
            if row.size and ranked.size:
                kth = ranked[min(k, ranked.size) - 1]
                out["rank_gap"] = max(out["rank_gap"],
                                      float(kth - g64.min()))
    return out


def control_run(dataset, binds: dict, spec: dict, guarantee: dict,
                precision: str, limits: dict) -> dict:
    """The control: the reference put in the program's place, computed one
    precision below the configuration's (``high``: three bfloat16 passes;
    ``bf16_3x`` writes them out, as ``filtered_knn`` says), radius and all
    on its own scores."""
    part, excl, qv, radius, ex = _inputs(dataset, binds, spec)
    C = int(part.max()) + 1
    vals, ids, _ = candidates(dataset.corpus, jnp.asarray(part),
                              jnp.asarray(excl), qv, radius, ex, spec["k"],
                              C, precision=precision)
    valid = np.isfinite(vals)
    served = {"ids": np.where(valid, ids, -1), "sim": vals, "valid": valid,
              "category": np.broadcast_to(
                  np.arange(C, dtype=np.int32)[None, :, None], ids.shape)}
    return compare_run(dataset, binds, served, spec, guarantee, limits)
