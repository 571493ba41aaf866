"""Pallas TPU kernel: fused distance + predicate filter + blockwise top-k.

This is the compute hot-spot CHASE optimizes (the map-operator fusion, §5.1):
one pass over the corpus computes similarities on the MXU, applies the
structured-filter mask in-register, and maintains top-k candidates — the full
(N,) score vector is never materialized to HBM, and nothing downstream ever
recomputes a distance.

TPU shape discipline:
* corpus tiles (BLOCK_N, D) stream HBM→VMEM via BlockSpec straight from the
  caller's corpus: N is read in place, never copied to a BLOCK_N multiple,
  so the grid's last block may overhang the corpus.  Those rows' values are
  undefined (zeros or garbage on the chip, NaN in interpret mode); the
  int8 mask, padded with zeros to whole blocks, makes their keys INF
  whatever they hold, so they reach no result.  D is padded to a lane
  multiple (128) by the wrapper.
* a (BLOCK_Q, D) query tile stays in VMEM; keys come from one
  (BLOCK_N, D)·(D, BLOCK_Q) MXU matmul per grid cell with fp32 contraction.
  A single query is a one-query batch (BLOCK_Q = 8 after padding), so there
  is one kernel per query class and no (BLOCK_N, 1) column layout, which
  Mosaic cannot broadcast across lanes.
* per-cell top-k runs as a k-step column-parallel extract-min — small-k
  selection is VPU-friendly; no unsupported `top_k` inside Mosaic.  A second
  `lax.top_k` over (num_blocks × k) candidates runs outside the kernel
  (standard two-stage TPU top-k).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.schema import Metric

INF = float("inf")  # python literal: safe inside kernel bodies (no captured consts)
# fp32 contraction on the MXU (Mosaic's `contract_precision<fp32>`): the
# default would round both operands to bf16, and the engine's flat scans
# are exact.  A no-op for the CPU interpreter, which always contracts fp32.
HIGHEST = jax.lax.Precision.HIGHEST


def _sq_rowvec(x: jnp.ndarray) -> jnp.ndarray:
    """(BQ, D) -> (1, BQ) per-row squared norms, via a dot-general contraction
    (no vector transpose/relayout inside Mosaic)."""
    ones = jnp.ones((1, x.shape[1]), jnp.float32)
    return jax.lax.dot_general(ones, x * x, (((1,), (1,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _keys_from_block_batch(block: jnp.ndarray, qs: jnp.ndarray,
                           metric: Metric) -> jnp.ndarray:
    """(B,D),(BQ,D) -> (B,BQ) order keys. One MXU matmul per corpus tile
    amortized over the whole query tile — the batched-execution hot loop.

    Both operands arrive in fp32 (the quantized kernels widen their tile
    first — Mosaic has no mixed-dtype contraction)."""
    ip = jax.lax.dot_general(block, qs, (((1,), (1,)), ((), ())),
                             precision=HIGHEST,
                             preferred_element_type=jnp.float32)  # (B, BQ)
    if metric == Metric.INNER_PRODUCT:
        return -ip
    if metric == Metric.L2:
        b2 = jnp.sum(block * block, axis=1, keepdims=True)   # (B, 1)
        q2 = _sq_rowvec(qs)                                  # (1, BQ)
        return b2 - 2.0 * ip + q2
    if metric == Metric.COSINE:
        bn = jnp.sqrt(jnp.sum(block * block, axis=1, keepdims=True))
        qn = jnp.sqrt(_sq_rowvec(qs))
        return -(ip / (bn * qn + 1e-12))
    raise ValueError(metric)


def _extract_topk_cols(keys_bq: jnp.ndarray, k: int):
    """(B, BQ) masked keys -> ((k, BQ) smallest keys, (k, BQ) row indices).

    Column-parallel k-step extract-min: every iteration selects one row per
    query column with 6 full-size array passes (min, eq, tie-break where/min,
    select, invalidate) and updates the small (k, BQ) outputs in place — no
    vector transposes, no gathers, per-column state stays in the (1, BQ)
    lane layout throughout (Mosaic-safe).  Invalid (all-INF) columns emit
    INF keys and -1 ids."""
    b, bq = keys_bq.shape
    iota_col = jax.lax.broadcasted_iota(jnp.int32, (b, bq), 0)
    iota_kq = jax.lax.broadcasted_iota(jnp.int32, (k, bq), 0)

    def body(j, carry):
        vals, out_keys, out_ids = carry
        m = jnp.min(vals, axis=0, keepdims=True)                    # (1, BQ)
        idxv = jnp.min(jnp.where(vals == m, iota_col, b), axis=0,
                       keepdims=True)                               # (1, BQ)
        sel = iota_col == idxv
        keep = jnp.isfinite(m)                                      # (1, BQ)
        out_keys = jnp.where(iota_kq == j, jnp.where(keep, m, INF), out_keys)
        out_ids = jnp.where(iota_kq == j, jnp.where(keep, idxv, -1), out_ids)
        vals = jnp.where(sel, INF, vals)
        return vals, out_keys, out_ids

    init = (keys_bq, jnp.full((k, bq), INF),
            jnp.full((k, bq), -1, jnp.int32))
    _, out_keys, out_ids = jax.lax.fori_loop(0, k, body, init)
    return out_keys, out_ids


def _scan_topk_batch_kernel(q_ref, qv_ref, c_ref, m_ref, keys_out, ids_out, *,
                            k: int, metric: Metric):
    """Grid (num_q_blocks, num_n_blocks): one (BLOCK_N, D)·(D, BLOCK_Q) MXU
    matmul per tile, per-query in-register top-k.  Emits (k, BLOCK_Q) blocks
    of LOCAL row indices; the wrapper rebases by n-block and transposes.

    ``qv_ref`` is the (1, BLOCK_Q) per-query valid row (size-bucket padding):
    it folds into the mask layout, so a pad query's column is all-INF and
    emits no candidates — without materializing a (N, Q) mask when the row
    mask is shared."""
    block = c_ref[...].astype(jnp.float32)               # (B, D)
    qs = q_ref[...].astype(jnp.float32)                  # (BQ, D)
    keys = _keys_from_block_batch(block, qs, metric)     # (B, BQ)
    mask = m_ref[...]                                    # (B, BQ) or (B, 1)
    live = (mask != 0) & (qv_ref[...] != 0)              # broadcasts (1, BQ)
    keys = jnp.where(live, keys, INF)
    out_keys, out_ids = _extract_topk_cols(keys, k)      # (k, BQ) each
    keys_out[...] = out_keys
    ids_out[...] = out_ids


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "block_q", "block_n",
                                    "interpret"))
def scan_topk_batch_pallas(corpus: jnp.ndarray, queries: jnp.ndarray,
                           mask_i8: jnp.ndarray, qvalid_i8: jnp.ndarray,
                           k: int, metric: Metric,
                           block_q: int = 128, block_n: int = 1024,
                           interpret: bool = True):
    """Stage 1 (Pallas), query-tiled: per (q-block, n-block) top-k candidates.

    The corpus (N, Dpad) is read in place: the grid runs over
    ``cdiv(N, block_n)`` blocks, and the last may overhang N.  The small
    operands arrive padded by ops.py: queries (Qpad, Dpad), mask (Npad, Qm)
    int8 with Npad = cdiv(N, block_n)·block_n and Qm ∈ {1, Qpad} (shared vs
    per-query masks), zero on the rows past N — which is what keeps the
    overhang's undefined rows out of every result — and qvalid (1, Qpad)
    int8, the per-query valid lane for size-bucket padding.
    Returns (num_n_blocks*k, Qpad) keys and LOCAL ids (ops.py rebases ids by
    n-block and transposes to query-major).  The kernel writes them as
    (num_n_blocks, k, Qpad) with the n-block axis squeezed out of each block,
    so a block's last two dims equal the array's — Mosaic's tiling rule for a
    k that is not a multiple of 8 — and the reshape back is free."""
    n, d = corpus.shape
    qn = queries.shape[0]
    num_n = pl.cdiv(n, block_n)
    num_q = qn // block_q
    assert mask_i8.shape[0] == num_n * block_n, (mask_i8.shape, n, block_n)
    assert qn % block_q == 0, (qn, block_q)
    assert qvalid_i8.shape == (1, qn), (qvalid_i8.shape, qn)
    per_query_mask = mask_i8.shape[1] != 1
    mspec = (pl.BlockSpec((block_n, block_q), lambda i, j: (j, i))
             if per_query_mask
             else pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)))
    kernel = functools.partial(_scan_topk_batch_kernel, k=k, metric=metric)
    keys, ids = pl.pallas_call(
        kernel,
        grid=(num_q, num_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),   # query tile
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),   # q-valid row
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),   # corpus tile
            mspec,                                             # mask tile
        ],
        out_specs=[
            pl.BlockSpec((pl.squeezed, k, block_q), lambda i, j: (j, 0, i)),
            pl.BlockSpec((pl.squeezed, k, block_q), lambda i, j: (j, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_n, k, qn), jnp.float32),
            jax.ShapeDtypeStruct((num_n, k, qn), jnp.int32),
        ],
        interpret=interpret,
    )(queries, qvalid_i8, corpus, mask_i8)
    return keys.reshape(num_n * k, qn), ids.reshape(num_n * k, qn)


def _keys_batch_kernel(q_ref, c_ref, keys_out, *, metric: Metric):
    """Grid (num_q_blocks, num_n_blocks): the unmasked (BLOCK_N, BLOCK_Q)
    order keys of one tile — the contraction the top-k and range kernels
    run, so a replay through it is bitwise their keys."""
    keys_out[...] = _keys_from_block_batch(c_ref[...].astype(jnp.float32),
                                           q_ref[...].astype(jnp.float32),
                                           metric)


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_n",
                                             "interpret"))
def keys_batch_pallas(corpus: jnp.ndarray, queries: jnp.ndarray,
                      metric: Metric, block_q: int = 128, block_n: int = 1024,
                      interpret: bool = True):
    """(Npad, Qpad) fp32 order keys of every (row, query) pair.

    Inputs pre-padded: corpus (Npad, Dpad), queries (Qpad, Dpad).  The
    quantized lanes' fp32 rescore replays candidate rows through this
    kernel at the fp32 kernels' own (block_n, block_q) tile shape, which is
    what makes the rescored keys bitwise the fp32 lane's on the chip as well
    as in the interpreter (an XLA dot outside the kernel is lowered
    differently)."""
    n, d = corpus.shape
    qn = queries.shape[0]
    assert n % block_n == 0 and qn % block_q == 0, (n, block_n, qn, block_q)
    kernel = functools.partial(_keys_batch_kernel, metric=metric)
    return pl.pallas_call(
        kernel,
        grid=(qn // block_q, n // block_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_q), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((n, qn), jnp.float32),
        interpret=interpret,
    )(queries, corpus)
