"""jit'd wrappers around the Pallas kernels (padding, two-stage merges,
and the public contracts the physical operators consume)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.schema import Metric
from .distance import pairwise_keys_pallas
from .range_scan import range_scan_batch_pallas
from .scan_topk import scan_topk_batch_pallas

LANE = 128


def default_interpret() -> bool:
    """Pallas interpret mode iff no accelerator backend is attached.

    TPU/GPU runs compile real Mosaic/Triton kernels; the CPU container (CI,
    laptops) transparently falls back to the interpreter — callers pass
    ``interpret=None`` and never thread the flag."""
    return jax.default_backend() == "cpu"


def _resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def _pad_dim(x: jnp.ndarray, mult: int, axis: int, value=0.0) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _mask_nq_i8(row_mask: jnp.ndarray | None, n: int, qn: int,
                block_n: int, block_q: int) -> jnp.ndarray:
    """Normalize a mask (None | (N,) shared | (Q, N) per-query) to the padded
    (Npad, Qm) int8 layout the batched kernels consume (Qm ∈ {1, Qpad}).
    Its zero rows past N are what keep the overhang of a corpus read in
    place (a last block past N) out of every result."""
    if row_mask is None:
        m = jnp.ones((n, 1), jnp.int8)
    elif row_mask.ndim == 1:
        m = row_mask.astype(jnp.int8).reshape(n, 1)
    else:
        assert row_mask.shape == (qn, n), (row_mask.shape, qn, n)
        m = _pad_dim(row_mask.astype(jnp.int8).T, block_q, 1, value=0)
    return _pad_dim(m, block_n, 0, value=0)


def _block_sizes(n: int, qn: int, block_q: int, block_n: int):
    bn = min(block_n, max(LANE, 1 << (n - 1).bit_length()))
    bq = min(block_q, max(8, 1 << (qn - 1).bit_length()))
    return bq, bn


def best_first(keys: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Row-wise ``k`` smallest ``keys`` and their ``ids``, in (key, id)
    order.  ``lax.top_k`` picks the set; the sort puts exact key ties in
    ascending id order on every backend — XLA's TPU top_k does not keep a
    wide row's ties in index order (seen at N = 1M), and the flat lanes
    must agree bit for bit.  Returns (keys (…, k), ids (…, k))."""
    neg, idx = jax.lax.top_k(-keys, k)
    sel = jnp.take_along_axis(ids, idx, axis=-1)
    return jax.lax.sort((-neg, sel), dimension=keys.ndim - 1, num_keys=2)


def _qvalid_row_i8(qvalid: jnp.ndarray | None, qn: int,
                   block_q: int) -> jnp.ndarray:
    """Normalize a per-query valid vector (None | (Q,) bool) to the padded
    (1, Qpad) int8 row the batched kernels AND into their mask layout.
    Query columns beyond Q (tile padding) are invalid either way."""
    if qvalid is None:
        row = jnp.ones((1, qn), jnp.int8)
    else:
        assert qvalid.shape == (qn,), (qvalid.shape, qn)
        row = qvalid.astype(jnp.int8).reshape(1, qn)
    return _pad_dim(row, block_q, 1, value=0)


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_n",
                                             "interpret"))
def fused_scan_topk(corpus: jnp.ndarray, query: jnp.ndarray, k: int,
                    row_mask: jnp.ndarray | None, metric: Metric,
                    block_n: int = 1024, interpret: bool | None = None):
    """Drop-in fused replacement for FlatIndex.topk: the batched kernel at
    Q=1 (a shared (N,) mask stays a (N, 1) lane — no (Q, N) broadcast).

    Returns (ids (k,), sims raw-metric (k,), valid (k,))."""
    ids, sims, valid = fused_scan_topk_batch(
        corpus, query.reshape(1, -1), k, row_mask, metric, block_n=block_n,
        interpret=interpret)
    return ids[0], sims[0], valid[0]


@functools.partial(jax.jit, static_argnames=("metric", "block_n", "interpret"))
def fused_range_scan(corpus: jnp.ndarray, query: jnp.ndarray, radius,
                     row_mask: jnp.ndarray | None, metric: Metric,
                     block_n: int = 1024, interpret: bool | None = None):
    """Drop-in fused replacement for FlatIndex.range_mask: the batched
    range kernel at Q=1.

    Returns (hit (N,), raw sims (N,), count)."""
    hit, raw, counts = fused_range_scan_batch(
        corpus, query.reshape(1, -1), radius, row_mask, metric,
        block_n=block_n, interpret=interpret)
    return hit[0], raw[0], counts[0]


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_c",
                                             "interpret"))
def pairwise_keys(queries: jnp.ndarray, corpus: jnp.ndarray, metric: Metric,
                  block_q: int = 128, block_c: int = 512,
                  interpret: bool | None = None):
    """(Q, N) order-key matrix (padded internally, cropped on return)."""
    interpret = _resolve_interpret(interpret)
    qn, d = queries.shape
    cn = corpus.shape[0]
    bq, bc = _block_sizes(cn, qn, block_q, block_c)
    qp = _pad_dim(_pad_dim(queries.astype(jnp.float32), LANE, 1), bq, 0)
    cp = _pad_dim(_pad_dim(corpus.astype(jnp.float32), LANE, 1), bc, 0)
    out = pairwise_keys_pallas(qp, cp, metric, block_q=bq, block_c=bc,
                               interpret=interpret)
    return out[:qn, :cn]


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_n", "interpret"))
def fused_scan_topk_batch(corpus: jnp.ndarray, queries: jnp.ndarray, k: int,
                          row_mask: jnp.ndarray | None, metric: Metric,
                          block_q: int = 128, block_n: int = 1024,
                          interpret: bool | None = None,
                          qvalid: jnp.ndarray | None = None):
    """Batched fused scan+filter+top-k: Q queries in one kernel launch.

    ``queries`` is (Q, D); ``row_mask`` is None, a shared (N,) mask, or a
    per-query (Q, N) mask.  Each (q-block, n-block) grid cell runs ONE
    (BLOCK_N, D)·(D, BLOCK_Q) MXU matmul — the per-tile corpus read is
    amortized over BLOCK_Q queries instead of re-streamed per query.
    ``qvalid`` (None | (Q,) bool) marks size-bucket pad queries: an invalid
    query's column folds into the mask layout as a (1, Qpad) lane, so it
    emits no candidates (all ids -1).
    Returns (ids (Q, k), sims raw-metric (Q, k), valid (Q, k))."""
    interpret = _resolve_interpret(interpret)
    n, d = corpus.shape
    qn = queries.shape[0]
    bq, bn = _block_sizes(n, qn, block_q, block_n)
    # the corpus is read in place along N (the kernel's last block may
    # overhang it); only a lane pad of D, a no-op at D = 512, copies it
    with jax.named_scope("chase.flat.pad_corpus"):
        cp = _pad_dim(corpus.astype(jnp.float32), LANE, 1)
    qp = _pad_dim(_pad_dim(queries.astype(jnp.float32), LANE, 1), bq, 0)
    mp = _mask_nq_i8(row_mask, n, qn, bn, bq)
    qv = _qvalid_row_i8(qvalid, qn, bq)
    with jax.named_scope("chase.flat.scan"):
        keys, ids = scan_topk_batch_pallas(cp, qp, mp, qv, k, metric,
                                           block_q=bq, block_n=bn,
                                           interpret=interpret)
    # stage 2: query-major layout, rebase local ids by n-block, merge per row
    with jax.named_scope("chase.flat.merge"):
        num_n = pl.cdiv(n, bn)
        keys = keys.T                                           # (Qpad, nb*k)
        ids = ids.T
        base = (jnp.arange(num_n * k, dtype=jnp.int32) // k) * bn
        gids = jnp.where(ids >= 0, ids + base[None, :], -1)
        out_keys, out_ids = best_first(keys, gids, k)           # row-wise
        valid = jnp.isfinite(out_keys)
        out_ids = jnp.where(valid, out_ids, -1)
        sims = jnp.where(valid,
                         -out_keys if metric.is_similarity() else out_keys,
                         0.0)
    return out_ids[:qn], sims[:qn], valid[:qn]


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_n",
                                             "interpret"))
def fused_range_scan_batch(corpus: jnp.ndarray, queries: jnp.ndarray, radius,
                           row_mask: jnp.ndarray | None, metric: Metric,
                           block_q: int = 128, block_n: int = 1024,
                           interpret: bool | None = None,
                           qvalid: jnp.ndarray | None = None):
    """Batched fused range scan. ``radius`` is a scalar or (Q,) raw values.

    ``qvalid`` (None | (Q,) bool) marks size-bucket pad queries: an invalid
    query registers no hits and a zero count.
    Returns (hit (Q, N), raw sims (Q, N), counts (Q,))."""
    from ..core.expr import order_key
    interpret = _resolve_interpret(interpret)
    n, d = corpus.shape
    qn = queries.shape[0]
    bq, bn = _block_sizes(n, qn, block_q, block_n)
    cp = _pad_dim(corpus.astype(jnp.float32), LANE, 1)   # N read in place
    qp = _pad_dim(_pad_dim(queries.astype(jnp.float32), LANE, 1), bq, 0)
    mp = _mask_nq_i8(row_mask, n, qn, bn, bq)
    qv = _qvalid_row_i8(qvalid, qn, bq)
    rk = order_key(metric, jnp.broadcast_to(
        jnp.asarray(radius, jnp.float32), (qn,)))
    rk = _pad_dim(rk.reshape(1, qn), bq, 1, value=-jnp.inf)  # padded q: no hit
    keys, hits, counts = range_scan_batch_pallas(
        cp, qp, rk, mp, qv, metric, block_q=bq, block_n=bn,
        interpret=interpret)
    keys = keys[:n, :qn].T                                  # (Q, N)
    hit = hits[:n, :qn].T != 0
    raw = jnp.where(hit, -keys if metric.is_similarity() else keys, 0.0)
    return hit, raw, jnp.sum(counts, axis=0)[:qn]


@functools.partial(jax.jit, static_argnames=("metric", "capacity", "block_q",
                                             "block_n", "interpret"))
def fused_range_topk_batch(corpus: jnp.ndarray, queries: jnp.ndarray, radius,
                           row_mask: jnp.ndarray | None, metric: Metric,
                           capacity: int, block_q: int = 128,
                           block_n: int = 1024,
                           interpret: bool | None = None,
                           qvalid: jnp.ndarray | None = None):
    """Fused range scan + per-query compaction to a fixed result buffer.

    The join families' flat lowering: every (masked) left row is one lane of
    the query-tiled range kernel, and each lane's (N,) hit vector compacts to
    its best-``capacity`` results.  ``radius`` is a scalar or (Q,) raw metric
    values; ``row_mask`` follows the (Npad, Qm) normalization of
    :func:`fused_range_scan_batch` (None | shared (N,) | per-query (Q, N));
    ``qvalid`` (None | (Q,) bool) marks size-bucket pad queries (no hits).
    Ordering policy: ascending order key (best first; the IVF range probes
    instead emit probe-discovery order).  Returns (ids (Q, capacity), sims
    raw-metric, valid (Q, capacity), count (Q,) total hits before
    truncation)."""
    from ..core.expr import order_key
    hit, raw, counts = fused_range_scan_batch(
        corpus, queries, radius, row_mask, metric, block_q=block_q,
        block_n=block_n, interpret=interpret, qvalid=qvalid)
    keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
    rows = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    keys, sel = best_first(keys, rows, capacity)             # row-wise
    valid = jnp.isfinite(keys)
    ids = jnp.where(valid, sel, -1)
    sims = jnp.where(valid, jnp.take_along_axis(raw, sel, axis=1), 0.0)
    return ids, sims, valid, counts
