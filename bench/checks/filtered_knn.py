"""Plain reference for filtered k-NN, and the comparison that decides
``correct``.

The statement is ``SELECT id ... WHERE <column> < ${bound} ORDER BY
DISTANCE(<vector>, ${qv}) LIMIT K`` under inner product: the K rows of
largest ``q . x`` among those whose column lies below the bound.  The
reference is independent of the program: a plain ``jnp.dot`` over the whole
corpus at ``Precision.HIGHEST`` in blocks of queries, ``lax.top_k`` of
K + EXTRA candidates, then every candidate and every served row scored
again on the host in float64.  Ranks and gaps are judged on those float64
scores.

Numbers compared (each with its limit from the configuration file):

* ``bad_rows``: served rows that are out of range, repeated within one
  answer, or fail the filter;
* ``short_answers``: answers whose number of rows differs from
  min(K, rows passing the filter) (exact guarantee only);
* ``sim_err``: the widest gap between a served score and the float64 score
  of the same row;
* ``rank_gap``: the widest amount by which a served row's float64 score
  lies below the float64 K-th best (exact guarantee only);
* ``recall``: the mean recall@K against the float64 top K (recall
  guarantee only; its limit is the configuration's stated floor).

The mix's ``check`` names ``k``, the query vector's bind
(``vector_bind``), the bound's (``bound_bind``) and the filtered column
(``column``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXTRA = 16          # candidates beyond K, so float64 re-ranking sees the K-th
BLOCK = 128         # queries per reference block


def _truncate(x):
    """``x`` cut to bfloat16's 8 bits of mantissa, rounding toward zero,
    still in float32 (a mask on the bits, which no compiler folds away)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    hi = _truncate(x)
    return (hi.astype(jnp.bfloat16),
            _truncate(x - hi).astype(jnp.bfloat16))


def _dot(qv, corpus, precision: str):
    """Scores of every query against every row: ``highest`` is a float32
    dot at ``Precision.HIGHEST``; ``high`` is ``Precision.HIGH``, three
    bfloat16 passes on the chip; ``bf16_3x`` writes those passes out
    (hi*hi + hi*lo + lo*hi with float32 sums, each part cut toward zero as
    the chip's ``high`` reads), so that the control reads alike on the CPU,
    whose ``high`` keeps more bits, and on the chip."""
    if precision in ("highest", "high"):
        return jnp.dot(qv, corpus.T, precision=precision)
    if precision != "bf16_3x":
        raise ValueError(f"unknown precision {precision!r}")
    (qh, ql), (ch, cl) = _split(qv), _split(corpus)
    dot = lambda a, b: jnp.dot(a, b.T, preferred_element_type=jnp.float32)
    return dot(qh, ch) + (dot(qh, cl) + dot(ql, ch))


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(corpus, column, qv, bounds, *, k, precision):
    s = _dot(qv, corpus, precision)
    s = jnp.where(column[None, :] < bounds[:, None], s, -jnp.inf)
    return jax.lax.top_k(s, k)


def candidates(corpus, column, qv: np.ndarray, bounds: np.ndarray, k: int,
               precision: str = "highest"):
    """(scores, ids) of each query's top ``k`` rows under the filter,
    computed on the device; filtered-out slots score -inf."""
    n = qv.shape[0]
    pad = -n % BLOCK
    qv = np.concatenate([qv, np.repeat(qv[-1:], pad, 0)])
    bounds = np.concatenate([bounds, np.repeat(bounds[-1:], pad, 0)])
    vals, ids = [], []
    for s in range(0, n + pad, BLOCK):
        v, i = _block_topk(corpus, column, jnp.asarray(qv[s:s + BLOCK]),
                           jnp.asarray(bounds[s:s + BLOCK]), k=k,
                           precision=precision)
        vals.append(np.asarray(v))
        ids.append(np.asarray(i))
    return np.concatenate(vals)[:n], np.concatenate(ids)[:n]


def score64(corpus, qv: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 inner products of each query with its listed rows (ids are
    clipped into range; callers mask the slots they do not use)."""
    rows = corpus[jnp.asarray(np.clip(ids, 0, corpus.shape[0] - 1))]
    rows = np.asarray(rows).astype(np.float64)
    return np.einsum("skd,sd->sk", rows, qv.astype(np.float64))


def compare(corpus, column_host: np.ndarray, column_dev, qv: np.ndarray,
            bounds: np.ndarray, served: dict, k: int,
            guarantee: dict) -> dict:
    """The compared numbers for one sample of served answers.

    ``served`` holds (S, K) ``ids``, ``sim`` and ``valid`` of the sampled
    requests, whose query vectors and bounds are ``qv`` and ``bounds``."""
    n_rows = corpus.shape[0]
    ids, sim, valid = served["ids"], served["sim"], served["valid"]
    cvals, cids = candidates(corpus, column_dev, qv, bounds, k + EXTRA)
    c64 = np.where(np.isfinite(cvals), score64(corpus, qv, cids), -np.inf)
    s64 = score64(corpus, qv, ids)
    order = np.argsort(-c64, axis=1, kind="stable")
    top = np.take_along_axis(c64, order, 1)
    top_ids = np.take_along_axis(cids, order, 1)
    matches = np.searchsorted(np.sort(column_host), bounds, side="left")
    want = np.minimum(k, matches)
    out = {"bad_rows": 0, "sim_err": 0.0}
    gaps, recalls, short = [], [], 0
    for s in range(ids.shape[0]):
        got = ids[s][valid[s]]
        ok = (got >= 0) & (got < n_rows)
        bad = int((~ok).sum()) + (got.size - np.unique(got).size)
        bad += int((column_host[got[ok]] >= bounds[s]).sum())
        out["bad_rows"] += bad
        short += int(got.size != want[s])
        if got.size:
            err = np.abs(sim[s][valid[s]].astype(np.float64)
                         - s64[s][valid[s]])
            out["sim_err"] = max(out["sim_err"], float(err.max()))
        w = int(want[s])
        if w == 0:
            continue
        kth = top[s, w - 1]
        if got.size:
            gaps.append(kth - float(s64[s][valid[s]].min()))
        truth = set(top_ids[s, :w].tolist())
        recalls.append(len(truth & set(got.tolist())) / w)
    if guarantee["kind"] == "exact":
        out["short_answers"] = short
        out["rank_gap"] = max([0.0] + gaps)
    else:
        out["recall"] = float(np.mean(recalls)) if recalls else 1.0
    return out


def control(corpus, column_host, column_dev, qv, bounds, k: int,
            guarantee: dict, precision: str = "high") -> dict:
    """The control: the reference put in the program's place, computed one
    precision below the configuration's: three bfloat16 passes
    (``Precision.HIGH``) where the float32 configuration states HIGHEST.
    ``bf16_3x`` writes the passes out for a CPU, whose ``high`` keeps more
    bits than the chip's."""
    vals, ids = candidates(corpus, column_dev, qv, bounds, k,
                           precision=precision)
    served = {"ids": ids, "sim": vals, "valid": np.isfinite(vals)}
    return compare(corpus, column_host, column_dev, qv, bounds, served, k,
                   guarantee)


def _inputs(dataset, binds: dict, spec: dict):
    col = dataset.columns[spec["column"]]
    return (dataset.corpus, col, jnp.asarray(col), binds[spec["vector_bind"]],
            np.asarray(binds[spec["bound_bind"]], np.float32))


def compare_run(dataset, binds: dict, served: dict, spec: dict,
                guarantee: dict, limits: dict) -> dict:
    """:func:`compare` on the harness's sample: ``binds`` and ``served``
    hold each bind and answer key stacked over the sampled requests."""
    corpus, col, col_dev, qv, bounds = _inputs(dataset, binds, spec)
    return compare(corpus, col, col_dev, qv, bounds, served, spec["k"],
                   guarantee)


def control_run(dataset, binds: dict, spec: dict, guarantee: dict,
                precision: str, limits: dict) -> dict:
    """:func:`control` on the harness's sample."""
    corpus, col, col_dev, qv, bounds = _inputs(dataset, binds, spec)
    return control(corpus, col, col_dev, qv, bounds, spec["k"], guarantee,
                   precision)


def judge(numbers: dict, limits: dict) -> list:
    """[(name, value, limit text, ok)] for every limited number."""
    rows = []
    for name, lim in limits.items():
        v = numbers[name]
        if "max" in lim:
            rows.append((name, v, f"<= {lim['max']}", v <= lim["max"]))
        else:
            rows.append((name, v, f">= {lim['min']}", v >= lim["min"]))
    return rows
