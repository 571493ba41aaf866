"""Pallas TPU kernel: blocked pairwise order-key matrix (distance join GEMM).

Q3/Q4 brute paths and the distributed join reduce to a (Q, N) distance matrix.
This is a classic tiled GEMM with a metric epilogue: (BQ, D) × (D, BC) on the
MXU, fp32 accumulation, L2/cosine epilogue in-register — the whole D dimension
is resident in VMEM per tile (D ≤ 1024 after padding ⇒ ≤ 0.5 MB per operand
tile at BQ=BC=128, comfortably inside the ~16 MB v5e VMEM budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.schema import Metric


def _pairwise_kernel(q_ref, c_ref, out_ref, *, metric: Metric):
    qb = q_ref[...].astype(jnp.float32)         # (BQ, D)
    cb = c_ref[...].astype(jnp.float32)         # (BC, D)
    ip = jnp.dot(qb, cb.T, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)            # (BQ, BC)
    if metric == Metric.INNER_PRODUCT:
        out_ref[...] = -ip
    elif metric == Metric.L2:
        q2 = jnp.sum(qb * qb, axis=1, keepdims=True)
        c2 = jnp.sum(cb * cb, axis=1, keepdims=True)
        out_ref[...] = q2 - 2.0 * ip + c2.T
    elif metric == Metric.COSINE:
        qn = jnp.sqrt(jnp.sum(qb * qb, axis=1, keepdims=True))
        cn = jnp.sqrt(jnp.sum(cb * cb, axis=1, keepdims=True))
        out_ref[...] = -(ip / (qn * cn.T + 1e-12))
    else:
        raise ValueError(metric)


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_c",
                                             "interpret"))
def pairwise_keys_pallas(queries: jnp.ndarray, corpus: jnp.ndarray,
                         metric: Metric, block_q: int = 128,
                         block_c: int = 512, interpret: bool = True):
    """(Qpad, Dpad), (Npad, Dpad) -> (Qpad, Npad) order-key matrix."""
    qn, d = queries.shape
    cn, d2 = corpus.shape
    assert d == d2 and qn % block_q == 0 and cn % block_c == 0
    kernel = functools.partial(_pairwise_kernel, metric=metric)
    return pl.pallas_call(
        kernel,
        grid=(qn // block_q, cn // block_c),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.float32),
        interpret=interpret,
    )(queries, corpus)
