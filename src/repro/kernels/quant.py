"""Quantized corpus scan kernels with fused fp32 rescore (DESIGN.md §13).

The flat batched scan is memory-bandwidth-bound: QPS is set by corpus bytes
streamed through the (BLOCK_N, D)·(D, BLOCK_Q) tiles, not by FLOPs.  These
kernels stream an int8 (per-row symmetric scale) or bf16 twin of the corpus
— 4×/2× fewer bytes — on the same MXU layout the fp32 query-tiled kernels
use (both widen in-register, int8 also rescales — see
:func:`_dequant_block`), and keep results EXACT by re-ranking a small
candidate set against the fp32 originals.

Three ideas make the quantized path both fast and bit-identical:

* **Segmented candidate extraction.**  The per-cell extract-min loop, not
  the matmul, dominates the fp32 kernel at moderate k.  The quantized
  kernel reduces its (B, BQ) key tile to per-``SEG``-row segment minima
  (an 8× smaller array) and extracts the top-(c·k) *segments* per query.
  A row with quantized rank ≤ c·k has at most c·k − 1 rows ahead of it, so
  at most c·k − 1 segments have a smaller minimum — its segment is always
  within the top-(c·k) segments, and expanding each selected segment back
  to its ``SEG`` rows yields a candidate superset of the quantized
  top-(c·k).  The extract loop runs c·k/(k·8) ≈ c/8 of the fp32 work.

* **Same-kernel fp32 replay rescore.**  A dot's reduction order depends
  on the operand shapes and on its lowering (XLA outside a kernel and
  Mosaic inside one differ), so per-query gathered matvecs do NOT
  reproduce the kernel's keys bitwise.  Instead the candidate rows are
  packed into synthetic (BLOCK_N, D) blocks and pushed through the very
  same (BLOCK_N, D)·(D, BLOCK_Q) ``_keys_from_block_batch`` contraction,
  inside a Pallas kernel (``keys_batch_pallas``) — per query block, against
  that block's own query tile — which reproduces the fp32 kernel's keys
  bit-for-bit for every (row, query) pair.
  The final selection orders exact key ties by ascending id
  (``ops.best_first``), as the fp32 path does.

* **Certified candidates.**  The candidate set covers the fp32 top-k only
  if no row outside it is better than the rescored K-th key; the
  per-row error bounds below prove that per query, and a batch with an
  unproven query runs the fp32 kernel instead.  Where the neighbourhood
  is dense (near-duplicate rows) the proof can fail, and the quantized
  lane then costs the fp32 scan on top of its own.

Range queries rescore boundary candidates inside a scale-derived slack
band: per-row dequantization error bounds (``QuantizedCorpus.half_step``)
give |k̂ − k| ≤ slack, so rows with k̂ ≤ radius − slack are certain hits,
rows with k̂ > radius + slack are certain misses, and only the band in
between is replayed in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.schema import Metric
from .ops import (LANE, _block_sizes, _mask_nq_i8, _pad_dim, _qvalid_row_i8,
                  _resolve_interpret, best_first, fused_scan_topk_batch)
from .scan_topk import (_extract_topk_cols, _keys_from_block_batch,
                        keys_batch_pallas)

INF = float("inf")
_I32_MAX = 2 ** 31 - 1

# Segment width of the segmented candidate extraction.  8 divides every
# block size the wrappers emit (block_n >= LANE = 128) and measured best
# on the q13 sweep (16 halves the extract work again but doubles the
# expansion width; the rescore gather then dominates).
SEG = 8


# ---------------------------------------------------------------------------
# Stage 1 kernels: dequantize in-register, quantized keys on the MXU
# ---------------------------------------------------------------------------

def _dequant_block(c_ref, s_ref) -> jnp.ndarray:
    """The corpus tile widened to fp32 for the MXU contraction.

    int8 widens and applies the per-row scales in-register (the MXU has no
    int8 × fp32 contraction with per-row rescale).  bf16 only widens: its
    scales are ones by construction (DESIGN.md §13), and bf16 -> fp32
    conversion is exact.  Mosaic has no mixed bf16 × fp32 contraction, so
    the saving of both twins is the HBM stream, not the matmul."""
    if c_ref.dtype == jnp.bfloat16:
        return c_ref[...].astype(jnp.float32)
    return c_ref[...].astype(jnp.float32) * s_ref[...]


def _quant_topk_batch_kernel(q_ref, qv_ref, c_ref, s_ref, m_ref, keys_out,
                             ids_out, *, s_count: int, metric: Metric):
    """Grid (num_q_blocks, num_n_blocks): quantized keys + segment minima +
    top-``s_count`` SEGMENT extraction per query column.

    ``c_ref`` is the (BLOCK_N, D) int8/bf16 tile; ``s_ref`` the matching
    (BLOCK_N, 1) fp32 per-row scales (unused in bf16 mode, where
    :func:`_dequant_block` only widens the tile).  Emits
    (s_count, BLOCK_Q) blocks of LOCAL segment indices; the wrapper rebases
    by n-block, merges globally, and expands segments back to rows for the
    fp32 replay rescore."""
    block = _dequant_block(c_ref, s_ref)                 # (B, D)
    qs = q_ref[...].astype(jnp.float32)                  # (BQ, D)
    keys = _keys_from_block_batch(block, qs, metric)     # (B, BQ)
    live = (m_ref[...] != 0) & (qv_ref[...] != 0)        # broadcasts (1, BQ)
    keys = jnp.where(live, keys, INF)
    b, bq = keys.shape
    segk = keys.reshape(b // SEG, SEG, bq).min(axis=1)   # (B/SEG, BQ)
    out_keys, out_ids = _extract_topk_cols(segk, s_count)
    keys_out[...] = out_keys
    ids_out[...] = out_ids


@functools.partial(jax.jit,
                   static_argnames=("s_count", "metric", "block_q", "block_n",
                                    "interpret"))
def quant_scan_topk_batch_pallas(qcorpus: jnp.ndarray, scales: jnp.ndarray,
                                 queries: jnp.ndarray, mask_i8: jnp.ndarray,
                                 qvalid_i8: jnp.ndarray, s_count: int,
                                 metric: Metric, block_q: int = 128,
                                 block_n: int = 1024, interpret: bool = True):
    """Stage 1 (Pallas), quantized + segmented: per (q-block, n-block) cell
    the top-``s_count`` segment minima per query.

    Inputs pre-padded by :func:`fused_scan_topk_batch_q`: qcorpus
    (Npad, Dpad) int8/bf16, scales (Npad, 1) fp32, queries (Qpad, Dpad),
    mask (Npad, Qm) int8 with Qm ∈ {1, Qpad}, qvalid (1, Qpad) int8.
    Returns (num_n_blocks*s_count, Qpad) keys and LOCAL segment ids,
    written n-block-major like the fp32 kernel's (Mosaic's tiling rule)."""
    n, d = qcorpus.shape
    qn = queries.shape[0]
    assert n % block_n == 0 and qn % block_q == 0, (n, block_n, qn, block_q)
    assert block_n % SEG == 0, (block_n, SEG)
    num_n = n // block_n
    num_q = qn // block_q
    per_query_mask = mask_i8.shape[1] != 1
    mspec = (pl.BlockSpec((block_n, block_q), lambda i, j: (j, i))
             if per_query_mask
             else pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)))
    kernel = functools.partial(_quant_topk_batch_kernel, s_count=s_count,
                               metric=metric)
    keys, ids = pl.pallas_call(
        kernel,
        grid=(num_q, num_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),   # query tile
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),   # q-valid row
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),   # quant tile
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),   # row scales
            mspec,                                             # mask tile
        ],
        out_specs=[
            pl.BlockSpec((pl.squeezed, s_count, block_q),
                         lambda i, j: (j, 0, i)),
            pl.BlockSpec((pl.squeezed, s_count, block_q),
                         lambda i, j: (j, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_n, s_count, qn), jnp.float32),
            jax.ShapeDtypeStruct((num_n, s_count, qn), jnp.int32),
        ],
        interpret=interpret,
    )(queries, qvalid_i8, qcorpus, scales, mask_i8)
    return (keys.reshape(num_n * s_count, qn),
            ids.reshape(num_n * s_count, qn))


def _quant_keys_batch_kernel(q_ref, qv_ref, c_ref, s_ref, m_ref, keys_out, *,
                             metric: Metric):
    """Grid (num_q_blocks, num_n_blocks): the quantized twin of the fp32
    range kernel's key materialization — masked quantized order keys, no
    radius test (the slack-band classification happens outside)."""
    block = _dequant_block(c_ref, s_ref)
    keys = _keys_from_block_batch(block, q_ref[...].astype(jnp.float32),
                                  metric)
    live = (m_ref[...] != 0) & (qv_ref[...] != 0)
    keys_out[...] = jnp.where(live, keys, INF)


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_n",
                                             "interpret"))
def quant_keys_batch_pallas(qcorpus: jnp.ndarray, scales: jnp.ndarray,
                            queries: jnp.ndarray, mask_i8: jnp.ndarray,
                            qvalid_i8: jnp.ndarray, metric: Metric,
                            block_q: int = 128, block_n: int = 1024,
                            interpret: bool = True):
    """Masked (Npad, Qpad) quantized order keys (INF on dead lanes) — the
    range path's stage 1 (the fp32 range kernel materializes the same
    matrix; the quantized one just streams 4×/2× fewer corpus bytes)."""
    n, d = qcorpus.shape
    qn = queries.shape[0]
    assert n % block_n == 0 and qn % block_q == 0
    num_n = n // block_n
    num_q = qn // block_q
    per_query_mask = mask_i8.shape[1] != 1
    mspec = (pl.BlockSpec((block_n, block_q), lambda i, j: (j, i))
             if per_query_mask
             else pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)))
    kernel = functools.partial(_quant_keys_batch_kernel, metric=metric)
    keys = pl.pallas_call(
        kernel,
        grid=(num_q, num_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            mspec,
        ],
        out_specs=pl.BlockSpec((block_n, block_q), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((n, qn), jnp.float32),
        interpret=interpret,
    )(queries, qvalid_i8, qcorpus, scales, mask_i8)
    return keys


# ---------------------------------------------------------------------------
# Fused fp32 rescore (same-kernel replay — bitwise-exact keys)
# ---------------------------------------------------------------------------

def _replay_keys(corpus: jnp.ndarray, queries_pad: jnp.ndarray,
                 rows: jnp.ndarray, metric: Metric, block_n: int,
                 block_q: int, interpret: bool) -> jnp.ndarray:
    """Exact fp32 order keys for per-query candidate rows, bitwise equal to
    the fp32 batched kernels' keys for the same (row, query) pairs.

    ``rows`` is (Qpad, C) int32 row ids into ``corpus`` (N, Dpad) (callers
    clamp out-of-range ids to 0 and mask afterwards).  Candidates are packed
    into synthetic (block_n, Dpad) blocks and pushed through the SAME
    (block_n, D)·(D, block_q) contraction the kernels run
    (:func:`~repro.kernels.scan_topk.keys_batch_pallas`) — per query block,
    against that block's own (block_q, Dpad) query tile — so the
    shape-dependent accumulation order matches the kernel's exactly.  The
    metric epilogues (row norms on the (block_n, Dpad) block, query norms
    on the (block_q, Dpad) tile) replay on the same shapes too."""
    qn_pad, c = rows.shape
    d = corpus.shape[1]
    assert qn_pad % block_q == 0, (qn_pad, block_q)
    out = []
    for qb in range(qn_pad // block_q):
        q_tile = queries_pad[qb * block_q:(qb + 1) * block_q]   # (BQ, D)
        r = rows[qb * block_q:(qb + 1) * block_q].reshape(-1)   # (BQ*C,)
        gathered = corpus[r]                                    # (BQ*C, D)
        total = block_q * c
        nb = -(-total // block_n)
        pad = nb * block_n - total
        if pad:
            gathered = jnp.concatenate(
                [gathered, jnp.zeros((pad, d), jnp.float32)])
        rep = keys_batch_pallas(gathered, q_tile, metric, block_q=block_q,
                                block_n=block_n,
                                interpret=interpret)[:total]    # (BQ*C, BQ)
        # candidate slot (q-local row i, position j) reads ITS query column
        qcol = jnp.repeat(jnp.arange(block_q, dtype=jnp.int32), c)
        out.append(rep[jnp.arange(total), qcol].reshape(block_q, c))
    return jnp.concatenate(out, axis=0)                         # (Qpad, C)


def _mask_at_rows(row_mask, rows_safe: jnp.ndarray, qn: int,
                  n: int) -> jnp.ndarray:
    """Row-mask values at gathered candidate positions ((Qpad, C) bool).

    Segment expansion can resurrect predicate-masked rows (a masked row
    shares a segment with a surviving one), so the rescore re-applies the
    mask before the final top-k."""
    if row_mask is None:
        return jnp.ones(rows_safe.shape, jnp.bool_)
    if row_mask.ndim == 1:
        return row_mask.astype(jnp.bool_)[rows_safe]
    qn_pad = rows_safe.shape[0]
    m = row_mask.astype(jnp.bool_)
    assert m.shape == (qn, n), (m.shape, qn, n)
    if qn_pad != qn:
        m = jnp.pad(m, ((0, qn_pad - qn), (0, 0)), constant_values=False)
    return jnp.take_along_axis(m, rows_safe, axis=1)


def _topk_slack(metric: Metric, half: jnp.ndarray, l1: jnp.ndarray,
                l2: jnp.ndarray, queries: jnp.ndarray,
                d_true: int) -> jnp.ndarray:
    """Per-query bound on |quantized key − exact key| over EVERY row:
    :func:`_range_slack` at the worst row statistics ((Q, 1) fp32).  Each
    bound grows with ``half`` and ``l1``; the cosine one with
    ``half / l2``, folded here into one ratio over unit norms."""
    if metric == Metric.COSINE:
        h = jnp.max(half / jnp.maximum(l2, 1e-12))
    else:
        h = jnp.max(half)
    one = jnp.ones((1,), jnp.float32)
    return _range_slack(metric, h * one, jnp.max(l1) * one, one, queries,
                        d_true)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "rescore_factor",
                                    "block_q", "block_n", "interpret"))
def fused_scan_topk_batch_q(corpus: jnp.ndarray, qvecs: jnp.ndarray,
                            scales: jnp.ndarray, half: jnp.ndarray,
                            l1: jnp.ndarray, l2: jnp.ndarray,
                            queries: jnp.ndarray, k: int,
                            row_mask: jnp.ndarray | None,
                            metric: Metric, rescore_factor: int = 2,
                            block_q: int = 128, block_n: int = 1024,
                            interpret: bool | None = None,
                            qvalid: jnp.ndarray | None = None):
    """Quantized twin of :func:`~repro.kernels.ops.fused_scan_topk_batch`.

    Streams the int8/bf16 ``qvecs`` (with fp32 per-row ``scales``; ones in
    bf16 mode) through the segmented quantized kernel, merges the per-cell
    segment winners to the global top-(rescore_factor·k) segments per
    query, expands them to rows, and re-ranks those candidates against the
    fp32 ``corpus`` with the same-kernel replay.  ``half``/``l1``/``l2``
    are the twin's per-row error statistics (as in
    :func:`fused_range_topk_batch_q`); they certify the candidate set:
    every row outside it has a quantized key at least the last selected
    segment's, so its exact key is at least that minus the slack bound.
    When that exceeds the rescored K-th key for every query the result is
    the fp32 top-k; otherwise (a dense neighbourhood, coarse scales) the
    batch runs the fp32 kernel itself.  Either way the output is
    bit-identical to the fp32 batched path; only the cost depends on the
    data (a fallback batch pays the quantized scan, the replay AND the fp32
    scan).  Contract (masks, q-valid lane, outputs) identical to the fp32
    wrapper.
    Returns (ids (Q, k), sims raw-metric (Q, k), valid (Q, k),
    fallback () bool — True when the batch ran the fp32 kernel)."""
    interpret = _resolve_interpret(interpret)
    n, d = corpus.shape
    qn = queries.shape[0]
    bq, bn = _block_sizes(n, qn, block_q, block_n)
    # the replay gathers candidate rows (< N) only: no N-padded fp32 copy
    cd = _pad_dim(corpus.astype(jnp.float32), LANE, 1)
    zp = _pad_dim(_pad_dim(qvecs, LANE, 1), bn, 0)        # quant dtype kept
    sp = _pad_dim(scales.astype(jnp.float32).reshape(-1, 1), bn, 0)
    qp = _pad_dim(_pad_dim(queries.astype(jnp.float32), LANE, 1), bq, 0)
    mp = _mask_nq_i8(row_mask, n, qn, bn, bq)
    qv = _qvalid_row_i8(qvalid, qn, bq)
    c = max(1, int(rescore_factor))
    s_count = max(1, min(c * k, bn // SEG))
    keys, ids = quant_scan_topk_batch_pallas(
        zp, sp, qp, mp, qv, s_count, metric, block_q=bq, block_n=bn,
        interpret=interpret)
    # stage 2: query-major, rebase local segment ids, merge the global
    # top-(c·k) segments per query
    num_n = zp.shape[0] // bn
    keys = keys.T                                   # (Qpad, num_n*s_count)
    ids = ids.T
    base = (jnp.arange(num_n * s_count, dtype=jnp.int32) // s_count) \
        * (bn // SEG)
    gseg = jnp.where(ids >= 0, ids + base[None, :], -1)
    s_total = min(c * k, num_n * s_count)
    neg, idx = jax.lax.top_k(-keys, s_total)                    # row-wise
    segsel = jnp.where(jnp.isfinite(-neg),
                       jnp.take_along_axis(gseg, idx, axis=1), -1)
    # expand segments -> rows; ids sorted ascending so that the top_k
    # below picks the lowest ids among exact-key ties at the K-th place
    rows = (segsel[:, :, None] * SEG
            + jnp.arange(SEG, dtype=jnp.int32)[None, None, :])
    rows = jnp.where(segsel[:, :, None] >= 0, rows, _I32_MAX)
    rows = jnp.sort(rows.reshape(rows.shape[0], -1), axis=1)    # (Qpad, C)
    okrow = rows < n
    safe = jnp.where(okrow, rows, 0)
    exact = _replay_keys(cd, qp, safe, metric, bn, bq, interpret)
    exact = jnp.where(okrow & _mask_at_rows(row_mask, safe, qn, n),
                      exact, INF)
    out_keys, out_ids = best_first(exact, rows, k)              # row-wise
    valid = jnp.isfinite(out_keys)
    out_ids = jnp.where(valid, out_ids, -1)
    sims = jnp.where(valid,
                     -out_keys if metric.is_similarity() else out_keys, 0.0)
    # certificate: a row outside the candidates has exact key >= the last
    # selected segment key − slack; strictly above the K-th key, it can
    # neither enter the top-k nor win a lowest-id tie at its boundary.  An
    # INF last key means every live segment was selected.
    last = -neg[:, s_total - 1]
    slack = _topk_slack(metric, half[:n], l1[:n], l2[:n],
                        _pad_dim(queries.astype(jnp.float32), bq, 0), d)
    certified = jnp.isinf(last) | (out_keys[:, k - 1] < last - slack[:, 0])
    fallback = ~jnp.all(certified)
    ids_o, sims_o, valid_o = jax.lax.cond(
        fallback,
        lambda: fused_scan_topk_batch(
            corpus, queries, k, row_mask, metric, block_q=block_q,
            block_n=block_n, interpret=interpret, qvalid=qvalid),
        lambda: (out_ids[:qn], sims[:qn], valid[:qn]))
    return ids_o, sims_o, valid_o, fallback


# ---------------------------------------------------------------------------
# Range: slack-band classification + boundary rescore
# ---------------------------------------------------------------------------

def _range_slack(metric: Metric, half: jnp.ndarray, l1: jnp.ndarray,
                 l2: jnp.ndarray, queries: jnp.ndarray,
                 d_true: int) -> jnp.ndarray:
    """Per-(query, row) upper bound on |quantized key − exact key|.

    With h the per-row componentwise dequantization error bound
    (``QuantizedCorpus.half_step``), x̂ the dequantized row, and q the
    query (DESIGN.md §13 derives these):

    * IP:  |Δ(−q·x)| ≤ h·‖q‖₁
    * L2:  |Δ‖x−q‖²| ≤ 2h(‖x̂‖₁ + ‖q‖₁) + D·h²
    * cos: |Δ| ≤ h·(‖q‖₁/‖q‖₂ + √D) / ‖x̂‖₂

    Returns (Q, N) fp32, widened by a small relative+absolute epsilon for
    fp32 evaluation noise of the bound itself."""
    h = half.reshape(1, -1)                                 # (1, N)
    q_l1 = jnp.sum(jnp.abs(queries), axis=1, keepdims=True)  # (Q, 1)
    if metric == Metric.INNER_PRODUCT:
        slack = h * q_l1
    elif metric == Metric.L2:
        slack = 2.0 * h * (l1.reshape(1, -1) + q_l1) + d_true * h * h
    elif metric == Metric.COSINE:
        q_l2 = jnp.sqrt(jnp.sum(queries * queries, axis=1, keepdims=True))
        num = q_l1 / jnp.maximum(q_l2, 1e-12) + jnp.sqrt(float(d_true))
        slack = h * num / jnp.maximum(l2.reshape(1, -1), 1e-12)
    else:
        raise ValueError(metric)
    return slack * 1.001 + 1e-6


@functools.partial(jax.jit,
                   static_argnames=("metric", "capacity", "rescore_factor",
                                    "block_q", "block_n", "interpret"))
def fused_range_topk_batch_q(corpus: jnp.ndarray, qvecs: jnp.ndarray,
                             scales: jnp.ndarray, half: jnp.ndarray,
                             l1: jnp.ndarray, l2: jnp.ndarray,
                             queries: jnp.ndarray, radius,
                             row_mask: jnp.ndarray | None, metric: Metric,
                             capacity: int, rescore_factor: int = 2,
                             block_q: int = 128, block_n: int = 1024,
                             interpret: bool | None = None,
                             qvalid: jnp.ndarray | None = None):
    """Quantized twin of :func:`~repro.kernels.ops.fused_range_topk_batch`.

    Quantized keys classify every row into certain-hit (k̂ ≤ r − slack),
    certain-miss (k̂ > r + slack), or boundary; only boundary rows and the
    emitted best-``capacity`` candidates are replayed in fp32 (same-kernel
    replay — emitted sims are bitwise the fp32 kernel's).  ``count`` is
    #certain-hits + #(replayed boundary rows that hit exactly).  The
    replay budget is ``rescore_factor·capacity`` rows per query; when a
    slack band overflows it (detected at runtime) the whole corpus is
    replayed instead, so results stay exact unconditionally — only the
    bandwidth saving degrades.  Returns (ids (Q, P), sims, valid,
    count (Q,)) with P = min(capacity, N), contract identical to the fp32
    wrapper (best-first, lowest-id ties)."""
    from ..core.expr import order_key
    interpret = _resolve_interpret(interpret)
    n, d = corpus.shape
    qn = queries.shape[0]
    bq, bn = _block_sizes(n, qn, block_q, block_n)
    cd = _pad_dim(corpus.astype(jnp.float32), LANE, 1)
    zp = _pad_dim(_pad_dim(qvecs, LANE, 1), bn, 0)
    sp = _pad_dim(scales.astype(jnp.float32).reshape(-1, 1), bn, 0)
    qp = _pad_dim(_pad_dim(queries.astype(jnp.float32), LANE, 1), bq, 0)
    mp = _mask_nq_i8(row_mask, n, qn, bn, bq)
    qv = _qvalid_row_i8(qvalid, qn, bq)
    qkeys = quant_keys_batch_pallas(zp, sp, qp, mp, qv, metric, block_q=bq,
                                    block_n=bn, interpret=interpret)
    qkeys = qkeys[:n, :].T                                   # (Qpad, N)
    qn_pad = qkeys.shape[0]
    rk = order_key(metric, jnp.broadcast_to(
        jnp.asarray(radius, jnp.float32), (qn,)))
    rk = _pad_dim(rk.reshape(qn, 1), bq, 0, value=-jnp.inf)  # (Qpad, 1)
    slack = _range_slack(metric, half[:n], l1[:n], l2[:n],
                         _pad_dim(queries.astype(jnp.float32), bq, 0), d)
    certain = qkeys <= rk - slack
    maybe = qkeys <= rk + slack                    # INF lanes: never maybe
    boundary = maybe & ~certain
    live = jnp.isfinite(qkeys)
    cap = min(int(capacity), n)
    w = min(max(1, int(rescore_factor)) * cap, n)

    def rescore(sel_keys):
        """Top-``w`` rows per query by ``sel_keys`` (INF = excluded),
        replayed in fp32.  Returns (rows asc-sorted, in-bounds+selected
        mask, exact keys)."""
        negk, sel = jax.lax.top_k(-sel_keys, w)
        rows = jnp.where(jnp.isfinite(-negk), sel.astype(jnp.int32),
                         _I32_MAX)
        rows = jnp.sort(rows, axis=1)              # fp32 lowest-id ties
        ok = rows < n
        safe = jnp.where(ok, rows, 0)
        return rows, ok, _replay_keys(cd, qp, safe, metric, bn, bq,
                                      interpret)

    def budgeted(_):
        # emission: best-cap exact hits from the top-w maybe rows by k̂
        rows_e, ok_e, exact_e = rescore(jnp.where(maybe, qkeys, INF))
        ekeys = jnp.where(ok_e & (exact_e <= rk), exact_e, INF)
        out_keys, out_ids = best_first(ekeys, rows_e, cap)      # row-wise
        valid = jnp.isfinite(out_keys)
        out_ids = jnp.where(valid, out_ids, -1)
        # count: certain hits + exact hits among replayed boundary rows
        rows_b, ok_b, exact_b = rescore(
            jnp.where(boundary, jnp.abs(qkeys - rk), INF))
        count = jnp.sum(certain, axis=1) + jnp.sum(ok_b & (exact_b <= rk),
                                                   axis=1)
        return out_ids, out_keys, valid, count

    def full(_):
        # slack band wider than the rescore budget (huge radius, coarse
        # scales): replay every row — still bitwise the fp32 kernel keys
        exact_all = keys_batch_pallas(_pad_dim(cd, bn, 0), qp, metric,
                                      block_q=bq, block_n=bn,
                                      interpret=interpret).T[:, :n]
        ekeys = jnp.where(live & (exact_all <= rk), exact_all, INF)
        out_keys, out_ids = best_first(
            ekeys, jax.lax.broadcasted_iota(jnp.int32, ekeys.shape, 1), cap)
        valid = jnp.isfinite(out_keys)
        out_ids = jnp.where(valid, out_ids, -1)
        return out_ids, out_keys, valid, jnp.sum(jnp.isfinite(ekeys),
                                                 axis=1)

    # boundary ⊆ maybe, so one check covers both rescore budgets; when it
    # does NOT trip, every budgeted replay set was complete — so emission
    # AND count are exact unconditionally, not just empirically
    overflow = jnp.max(jnp.sum(maybe, axis=1)) > w
    out_ids, out_keys, valid, count = jax.lax.cond(overflow, full, budgeted,
                                                   None)
    sims = jnp.where(valid,
                     -out_keys if metric.is_similarity() else out_keys, 0.0)
    return (out_ids[:qn], sims[:qn], valid[:qn],
            count[:qn].astype(jnp.int32))
