"""The flat kernels read the corpus in place: N need not be a multiple of
``block_n``, and the grid's last block overhangs the corpus (NaN rows in
interpret mode).  Every fused wrapper must answer bit for bit as the padded
layout does — the corpus copied with zero rows to a ``block_n`` multiple and
fed to the same Pallas kernel — ties and id order included, and a short
answer must pad with -1, never with a row id at or past N."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.expr import order_key
from repro.core.schema import Metric
from repro.kernels import ref
from repro.kernels.ops import (LANE, _block_sizes, _mask_nq_i8, _pad_dim,
                               _qvalid_row_i8, best_first,
                               fused_range_scan_batch, fused_range_topk_batch,
                               fused_scan_topk_batch)
from repro.kernels.range_scan import range_scan_batch_pallas
from repro.kernels.scan_topk import scan_topk_batch_pallas

K = 10
Q = 5
QVALID = np.array([True, True, False, True, True])   # query 2: a bucket pad
# (N, block_n, D, metric): 24, 60, 24 and 572 rows of overhang
CASES = [(1000, 256, 128, Metric.INNER_PRODUCT),
         (2500, 256, 72, Metric.L2),
         (1000, 1024, 72, Metric.COSINE),
         (2500, 1024, 128, Metric.INNER_PRODUCT)]


def _data(n, d, mask_kind, seed=0):
    """Corpus with duplicated rows (exact key ties across blocks), queries,
    and a mask whose live rows crowd the corpus's last block; under the
    per-query mask query 0 has three live rows (fewer than K), the last of
    them row N-1."""
    rng = np.random.default_rng(seed)
    c = rng.standard_normal((n, d)).astype(np.float32)
    c[n - 7] = c[3]
    c[n // 2] = c[3]
    q = rng.standard_normal((Q, d)).astype(np.float32)
    if mask_kind == "none":
        m = None
    elif mask_kind == "shared":
        m = jnp.asarray((rng.random(n) < 0.3) | (np.arange(n) >= n - 40))
    else:
        m = (rng.random((Q, n)) < 0.5) | (np.arange(n) >= n - 40)
        m[0] = False
        m[0, [3, n - 7, n - 1]] = True
        m = jnp.asarray(m)
    return jnp.asarray(c), jnp.asarray(q), m


def _padded_operands(c, q, m, n, bn, bq):
    """The padded layout: corpus and queries copied to whole tiles."""
    cp = _pad_dim(_pad_dim(c, LANE, 1), bn, 0)
    qp = _pad_dim(_pad_dim(q, LANE, 1), bq, 0)
    mp = _mask_nq_i8(m, n, Q, bn, bq)
    qv = _qvalid_row_i8(jnp.asarray(QVALID), Q, bq)
    return cp, qp, mp, qv


def _padded_topk(c, q, m, metric, block_n):
    n = c.shape[0]
    bq, bn = _block_sizes(n, Q, 128, block_n)
    cp, qp, mp, qv = _padded_operands(c, q, m, n, bn, bq)
    assert cp.shape[0] % bn == 0
    keys, ids = scan_topk_batch_pallas(cp, qp, mp, qv, K, metric,
                                       block_q=bq, block_n=bn,
                                       interpret=True)
    num_n = cp.shape[0] // bn
    keys, ids = keys.T, ids.T
    base = (jnp.arange(num_n * K, dtype=jnp.int32) // K) * bn
    gids = jnp.where(ids >= 0, ids + base[None, :], -1)
    out_keys, out_ids = best_first(keys, gids, K)
    valid = jnp.isfinite(out_keys)
    out_ids = jnp.where(valid, out_ids, -1)
    sims = jnp.where(valid,
                     -out_keys if metric.is_similarity() else out_keys, 0.0)
    return out_ids[:Q], sims[:Q], valid[:Q]


def _radius(c, q, metric):
    """Raw radii that keep about a third of the rows for each query."""
    keys = np.quantile(np.asarray(ref.pairwise_keys_ref(q, c, metric)), 0.33,
                       axis=1)
    return jnp.asarray(-keys if metric.is_similarity() else keys, jnp.float32)


def _padded_range(c, q, radius, m, metric, block_n):
    n = c.shape[0]
    bq, bn = _block_sizes(n, Q, 128, block_n)
    cp, qp, mp, qv = _padded_operands(c, q, m, n, bn, bq)
    rk = order_key(metric, jnp.broadcast_to(radius, (Q,)))
    rk = _pad_dim(rk.reshape(1, Q), bq, 1, value=-jnp.inf)
    keys, hits, counts = range_scan_batch_pallas(
        cp, qp, rk, mp, qv, metric, block_q=bq, block_n=bn, interpret=True)
    keys = keys[:n, :Q].T
    hit = hits[:n, :Q].T != 0
    raw = jnp.where(hit, -keys if metric.is_similarity() else keys, 0.0)
    return hit, raw, jnp.sum(counts, axis=0)[:Q]


def _padded_range_topk(c, q, radius, m, metric, block_n):
    hit, raw, counts = _padded_range(c, q, radius, m, metric, block_n)
    keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
    rows = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    keys, sel = best_first(keys, rows, K)
    valid = jnp.isfinite(keys)
    ids = jnp.where(valid, sel, -1)
    sims = jnp.where(valid, jnp.take_along_axis(raw, sel, axis=1), 0.0)
    return ids, sims, valid, counts


def _run(op, c, q, m, metric, block_n):
    """(in place, padded) results of one fused wrapper."""
    qvalid = jnp.asarray(QVALID)
    if op == "topk":
        return (fused_scan_topk_batch(c, q, K, m, metric, block_n=block_n,
                                      qvalid=qvalid),
                _padded_topk(c, q, m, metric, block_n))
    radius = _radius(c, q, metric)
    if op == "range":
        return (fused_range_scan_batch(c, q, radius, m, metric,
                                       block_n=block_n, qvalid=qvalid),
                _padded_range(c, q, radius, m, metric, block_n))
    return (fused_range_topk_batch(c, q, radius, m, metric, K,
                                   block_n=block_n, qvalid=qvalid),
            _padded_range_topk(c, q, radius, m, metric, block_n))


@pytest.mark.parametrize("mask_kind", ["none", "shared", "per_query"])
@pytest.mark.parametrize("n,block_n,d,metric", CASES)
@pytest.mark.parametrize("op", ["topk", "range", "range_topk"])
def test_in_place_scan_matches_padded(op, n, block_n, d, metric, mask_kind):
    assert n % block_n != 0
    c, q, m = _data(n, d, mask_kind)
    got, want = _run(op, c, q, m, metric, block_n)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8))
    if op == "range":
        hit = np.asarray(got[0])
        assert not hit[~QVALID].any() and hit[QVALID].any()
        return
    ids, valid = np.asarray(got[0]), np.asarray(got[2])
    assert (ids < n).all() and (ids[~valid] == -1).all()
    assert (ids[valid] >= 0).all()
    assert not valid[2].any()                       # the bucket pad query
    if mask_kind == "per_query":                    # 3 live rows < K
        live = valid[0].sum()
        assert live == 3 if op == "topk" else live <= 3
        assert (ids[0, live:] == -1).all()
    if op == "topk":
        assert valid[[1, 3, 4]].all()
