"""Pallas TPU kernel: fused distance + threshold + predicate range scan.

DR-SF hot path (§5.2): one pass computes order keys on the MXU, applies the
radius test and the structured-filter mask in-register, and emits a compact
per-block hit count plus masked keys.  The (data-dependent) compaction happens
outside the kernel; what the kernel saves is the materialization of raw
scores + a second filtering pass — the paper's fusion argument applied to
Algorithm 1's inner loop.

The corpus is read in place along N, as in ``scan_topk.py``: the grid's last
block may overhang it, its rows past N hold undefined values, and the int8
mask's zero rows past N keep them out of every hit, key and count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.schema import Metric
from .scan_topk import _keys_from_block_batch

INF = float("inf")


def _range_batch_kernel(q_ref, r_ref, qv_ref, c_ref, m_ref, keys_out,
                        hits_out, cnt_out, *, metric: Metric):
    """Grid (num_q_blocks, num_n_blocks): one corpus-tile matmul amortized
    over the query tile; per-query radius row; per-(tile, query) hit counts.

    ``qv_ref`` is the (1, BLOCK_Q) per-query valid row (size-bucket padding):
    a pad query's column registers no hits and a zero count, without
    materializing a (N, Q) mask when the row mask is shared."""
    block = c_ref[...].astype(jnp.float32)               # (B, D)
    qs = q_ref[...].astype(jnp.float32)                  # (BQ, D)
    radius_row = r_ref[...]                              # (1, BQ)
    keys = _keys_from_block_batch(block, qs, metric)     # (B, BQ)
    mask = (m_ref[...] != 0) & (qv_ref[...] != 0)        # (B, BQ) or (B, 1)
    hit = mask & (keys <= radius_row)
    keys_out[...] = jnp.where(hit, keys, INF)
    hits_out[...] = hit.astype(jnp.int8)
    cnt_out[...] = jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_n",
                                             "interpret"))
def range_scan_batch_pallas(corpus: jnp.ndarray, queries: jnp.ndarray,
                            radius_keys: jnp.ndarray, mask_i8: jnp.ndarray,
                            qvalid_i8: jnp.ndarray,
                            metric: Metric, block_q: int = 128,
                            block_n: int = 1024, interpret: bool = True):
    """Query-tiled fused range scan.

    The corpus (N, Dpad) is read in place over ``cdiv(N, block_n)`` blocks,
    the last of which may overhang N.  The small operands arrive padded:
    queries (Qpad, Dpad), radius_keys (1, Qpad) order keys, mask (Npad, Qm)
    int8 with Npad = cdiv(N, block_n)·block_n, Qm ∈ {1, Qpad}, zero past N
    (so the overhang registers no hit), and qvalid (1, Qpad) int8 — the
    per-query valid lane for size-bucket padding.
    Returns ((Npad, Qpad) masked keys, (Npad, Qpad) int8 hits,
    (num_n_blocks, Qpad) per-block per-query hit counts).  Counts are
    written as (num_n_blocks, 1, Qpad), n-block axis squeezed, for the same
    tiling rule as the top-k kernels."""
    n, d = corpus.shape
    qn = queries.shape[0]
    num_n = pl.cdiv(n, block_n)
    num_q = qn // block_q
    npad = num_n * block_n
    assert mask_i8.shape[0] == npad, (mask_i8.shape, n, block_n)
    assert qn % block_q == 0, (qn, block_q)
    assert qvalid_i8.shape == (1, qn), (qvalid_i8.shape, qn)
    per_query_mask = mask_i8.shape[1] != 1
    mspec = (pl.BlockSpec((block_n, block_q), lambda i, j: (j, i))
             if per_query_mask
             else pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)))
    kernel = functools.partial(_range_batch_kernel, metric=metric)
    keys, hits, counts = pl.pallas_call(
        kernel,
        grid=(num_q, num_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_q), lambda i, j: (0, i)),  # q-valid row
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            mspec,
        ],
        out_specs=[
            pl.BlockSpec((block_n, block_q), lambda i, j: (j, i)),
            pl.BlockSpec((block_n, block_q), lambda i, j: (j, i)),
            pl.BlockSpec((pl.squeezed, 1, block_q), lambda i, j: (j, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, qn), jnp.float32),
            jax.ShapeDtypeStruct((npad, qn), jnp.int8),
            jax.ShapeDtypeStruct((num_n, 1, qn), jnp.int32),
        ],
        interpret=interpret,
    )(queries, radius_keys, qvalid_i8, corpus, mask_i8)
    return keys, hits, counts.reshape(num_n, qn)
