"""Sharded-corpus hybrid-query collectives (DESIGN.md §5, §10).

The corpus rows live sharded over one or more mesh axes; each device runs the
*fused* local scan (distance + filter + top-k/range) over its shard, then only
K (id, key) candidate pairs per shard cross the interconnect — the merge wire
cost is K·shards·8 bytes **per query** regardless of corpus size, which is
what makes scale-out hybrid search interconnect-cheap.

Two generations of primitives live here:

* **Single-query** (:func:`distributed_topk` / :func:`distributed_range`):
  one query vector per call, the per-shard scan is a masked matvec.  These
  are the DESIGN.md §5 seed primitives, kept as the simple reference.
* **Query-batched** (:func:`distributed_topk_batch` /
  :func:`distributed_range_batch`, DESIGN.md §10): each device scans its
  shard for ALL Q queries at once through the query-tiled fused Pallas
  kernels (kernels/ops.py), so the shard × query composition amortizes the
  per-shard corpus stream over BLOCK_Q queries.  The size-bucket ``qvalid``
  lane threads through to every shard — a pad query emits no candidates and
  zero counters on every device — and the hierarchical per-query merge
  (``all_gather`` the (Q, K) local winners along the innermost mesh axis,
  column-parallel re-select, repeat outward) moves K·Q pairs per
  participant per level.

Every returned callable is ``shard_map``'d over ``mesh`` and replicates its
outputs; wrap in ``jax.jit`` (or call from a jitted pipeline — the physical
builders do) for execution.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.expr import distance_values, in_range, order_key
from ..core.schema import Metric
from ..index.flat import masked_topk
from ..kernels.ops import best_first


def shard_corpus(mesh: Mesh, corpus: jnp.ndarray,
                 axes: tuple[str, ...] = ("data",)):
    """Row-shard a corpus (and its global row ids) over ``axes``.

    Rows must divide the axes' total size (pad upstream otherwise).
    Returns (sharded corpus, sharded global ids)."""
    n = corpus.shape[0]
    sharding = NamedSharding(mesh, P(axes))
    ids = jnp.arange(n, dtype=jnp.int32)
    return (jax.device_put(corpus, NamedSharding(mesh, P(axes, None))),
            jax.device_put(ids, sharding))


def distributed_topk(mesh: Mesh, metric: Metric, k: int,
                     axes: tuple[str, ...] = ("data",)):
    """Filtered exact top-k over a row-sharded corpus.

    Per-shard fused scan+filter+top-k, then a hierarchical candidate merge:
    all_gather the K local winners across the innermost shard axis, re-select,
    and repeat outward — each level moves only K pairs per participant."""

    def local(corpus, ids, q, mask):
        raw = distance_values(metric, corpus, q)
        keys = order_key(metric, raw)
        sel_keys, sel_ids, _ = masked_topk(keys, ids, mask, k)
        # hierarchical merge: innermost axis first, then outward (pod-level)
        for ax in reversed(axes):
            ck = jax.lax.all_gather(sel_keys, ax, tiled=True)
            ci = jax.lax.all_gather(sel_ids, ax, tiled=True)
            sel_keys, sel_ids, _ = masked_topk(ck, ci, jnp.isfinite(ck), k)
        valid = jnp.isfinite(sel_keys)
        sims = jnp.where(valid,
                         -sel_keys if metric.is_similarity() else sel_keys,
                         0.0)
        return jnp.where(valid, sel_ids, -1), sims, valid

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(), P(axes)),
        out_specs=(P(), P(), P()),
        check_vma=False)


def distributed_range(mesh: Mesh, metric: Metric, capacity: int,
                      axes: tuple[str, ...] = ("data",)):
    """Filtered range query over a row-sharded corpus.

    Each shard emits up to ``capacity`` in-range candidates (compacted
    locally); the gather concatenates per-shard buffers, so the global result
    holds up to capacity*shards hits, ordered best-first per shard."""

    def local(corpus, ids, q, radius, mask):
        raw = distance_values(metric, corpus, q)
        keys = order_key(metric, raw)
        hit = mask & in_range(metric, raw, radius)
        cap = min(capacity, corpus.shape[0])
        sel_keys, sel_ids, _ = masked_topk(keys, ids, hit, cap)
        count = jnp.sum(hit.astype(jnp.int32)).reshape(1)
        for ax in reversed(axes):
            sel_keys = jax.lax.all_gather(sel_keys, ax, tiled=True)
            sel_ids = jax.lax.all_gather(sel_ids, ax, tiled=True)
            count = jax.lax.all_gather(count, ax, tiled=True)
        valid = jnp.isfinite(sel_keys)
        sims = jnp.where(valid,
                         -sel_keys if metric.is_similarity() else sel_keys,
                         0.0)
        return (jnp.where(valid, sel_ids, -1), sims, valid,
                jnp.sum(count))

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(), P(), P(axes)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)


# ---------------------------------------------------------------------------
# Query-batched collectives (DESIGN.md §10): shard rows x tile queries
# ---------------------------------------------------------------------------

def merge_topk_level(metric: Metric,
                     keys_a: jnp.ndarray, gids_a: jnp.ndarray,
                     keys_b: jnp.ndarray, gids_b: jnp.ndarray,
                     k: int):
    """One level of the hierarchical per-query candidate merge, as a plain
    (non-collective) function: concatenate two (Q, k_a)/(Q, k_b) candidate
    sets column-wise and row-wise re-select the best ``k``.

    This is exactly what :func:`_merge_topk` does per mesh axis, with the
    ``all_gather`` replaced by a local ``concatenate`` — the live-corpus
    delta segment (DESIGN.md §12) is merged into the main top-k as one
    extra, device-local "shard level" through this primitive.

    ``keys_*`` are ascending order keys with ``+inf`` on empty lanes;
    ``gids_*`` the matching global ids with ``-1`` on empty lanes.  Ties
    resolve to the lowest concatenated column index (``jax.lax.top_k`` is
    stable), so with A = main and B = delta, an empty delta segment leaves
    A's result bit-identical.  Output is padded/truncated to exactly
    (Q, k).  Returns (ids, sims raw-metric, valid)."""
    keys = jnp.concatenate([keys_a, keys_b], axis=1)
    gids = jnp.concatenate([gids_a, gids_b], axis=1)
    neg, idx = jax.lax.top_k(-keys, min(k, keys.shape[1]))
    keys = -neg
    gids = jnp.take_along_axis(gids, idx, axis=1)
    if keys.shape[1] < k:
        pad = k - keys.shape[1]
        keys = jnp.pad(keys, ((0, 0), (0, pad)), constant_values=jnp.inf)
        gids = jnp.pad(gids, ((0, 0), (0, pad)), constant_values=-1)
    valid = jnp.isfinite(keys)
    sims = jnp.where(valid, -keys if metric.is_similarity() else keys, 0.0)
    return jnp.where(valid, gids, -1), sims, valid


def _merge_topk(metric: Metric, keys: jnp.ndarray, gids: jnp.ndarray,
                k: int, axes: tuple[str, ...]):
    """Hierarchical per-query candidate merge (runs INSIDE shard_map).

    ``keys``/``gids`` are this shard's (Q, k_local) winners (order keys
    ascending, +inf on empty lanes; global row ids, -1 on empty lanes).
    Per mesh axis, innermost first: ``all_gather`` the candidate columns
    (tiled along axis 1 — K·Q pairs per participant), row-wise re-select
    the best ``k``, repeat outward.  Returns replicated
    (ids (Q, k), sims raw-metric, valid)."""
    for ax in reversed(axes):
        keys = jax.lax.all_gather(keys, ax, axis=1, tiled=True)
        gids = jax.lax.all_gather(gids, ax, axis=1, tiled=True)
        # clamp per level: an early level's gathered width can undercut k
        # when per-shard buffers are capacity-starved (keeping everything is
        # lossless; later levels widen back past k — see the range merge)
        keys, gids = best_first(keys, gids, min(k, keys.shape[1]))
    valid = jnp.isfinite(keys)
    sims = jnp.where(valid, -keys if metric.is_similarity() else keys, 0.0)
    return jnp.where(valid, gids, -1), sims, valid


def _mask_spec(axes: tuple[str, ...], per_query_mask: bool):
    """shard_map in_spec for the row mask: (Q, Npad) per-query masks shard
    along dim 1; a shared (Npad,) mask (the no-predicate case — only the
    divisibility-pad rows are excluded) shards along its only dim and never
    materializes a (Q, N) array."""
    return P(None, axes) if per_query_mask else P(axes)


def distributed_topk_batch(mesh: Mesh, metric: Metric, k: int,
                           axes: tuple[str, ...] = ("data",),
                           interpret: bool | None = None,
                           per_query_mask: bool = True):
    """Batched filtered exact top-k over a row-sharded corpus.

    The shard × tile composition: each device runs the query-tiled fused
    scan (``kernels.ops.fused_scan_topk_batch`` — distance + filter + top-k
    in one kernel) over its shard for ALL Q queries, then the hierarchical
    per-query merge re-selects K winners per mesh axis (innermost first).
    Only K·Q (id, key) pairs per shard cross the interconnect per level.

    Returns a ``shard_map``'d callable
    ``fn(sh_corpus, sh_ids, qs, sh_mask, qvalid) -> (ids, sims, valid)``:

    * ``sh_corpus`` (Npad, d) rows sharded over ``axes``; ``sh_ids`` (Npad,)
      the matching global row ids (-1 on divisibility-pad rows) — both as
      laid out by :class:`~repro.dist.sharding.ShardedCorpus`;
    * ``qs`` (Q, d) replicated query batch;
    * ``sh_mask`` — the fused predicate of the scan, pad rows False: a
      (Q, Npad) bool per-query mask (``per_query_mask=True``), or, for
      plans with NO row predicate, a shared (Npad,) bool mask
      (``per_query_mask=False`` — typically ``row_ids >= 0``, so no
      (Q, N) array is ever materialized or moved);
    * ``qvalid`` (Q,) bool — the size-bucket pad-query lane: an invalid
      query emits no candidates (all ids -1) and no hits on ANY shard.

    Outputs are (Q, k), replicated.  At shards=1 the merge is an identity
    re-selection over an already-sorted candidate list, so results are
    bit-identical to a single-device ``fused_scan_topk_batch`` call."""

    def local(corpus, ids, qs, mask, qvalid):
        from ..kernels.ops import fused_scan_topk_batch
        lids, lsims, lvalid = fused_scan_topk_batch(
            corpus, qs, k, mask, metric, interpret=interpret, qvalid=qvalid)
        gids = jnp.where(lvalid, ids[jnp.maximum(lids, 0)], -1)
        keys = jnp.where(lvalid, order_key(metric, lsims), jnp.inf)
        return _merge_topk(metric, keys, gids, k, axes)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None, None),
                  _mask_spec(axes, per_query_mask), P(None)),
        out_specs=(P(None, None), P(None, None), P(None, None)),
        check_vma=False)


def distributed_topk_batch_q(mesh: Mesh, metric: Metric, k: int,
                             axes: tuple[str, ...] = ("data",),
                             interpret: bool | None = None,
                             per_query_mask: bool = True,
                             rescore_factor: int = 2):
    """Quantized twin of :func:`distributed_topk_batch` (DESIGN.md §13).

    Each device streams its int8/bf16 shard through the quantized
    segmented kernel and rescores its own top-(rescore_factor·k)
    candidates against its fp32 shard LOCALLY — so the (id, key) pairs
    entering the hierarchical merge are already exact fp32 keys, bitwise
    the keys the fp32 path would ship, and the merge (and its shards=1
    bit-identity guarantee) is unchanged.  The interconnect still moves
    only K·Q pairs per shard per level; the bandwidth saving is on the
    per-device HBM corpus stream.

    Returns a ``shard_map``'d callable ``fn(sh_corpus, sh_qvecs,
    sh_scales, sh_half, sh_l1, sh_l2, sh_ids, qs, sh_mask, qvalid) ->
    (ids, sims, valid)`` with ``sh_qvecs`` … ``sh_l2`` the row-sharded
    :class:`~repro.data.quantized.QuantizedCorpus` arrays (same row
    layout as ``sh_corpus``) and everything else as in the fp32 twin."""

    def local(corpus, qvecs, scales, half, l1, l2, ids, qs, mask, qvalid):
        from ..kernels.quant import fused_scan_topk_batch_q
        lids, lsims, lvalid, _ = fused_scan_topk_batch_q(
            corpus, qvecs, scales, half, l1, l2, qs, k, mask, metric,
            rescore_factor=rescore_factor, interpret=interpret,
            qvalid=qvalid)
        gids = jnp.where(lvalid, ids[jnp.maximum(lids, 0)], -1)
        keys = jnp.where(lvalid, order_key(metric, lsims), jnp.inf)
        return _merge_topk(metric, keys, gids, k, axes)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None), P(axes),
                  P(axes), P(axes), P(axes),
                  P(None, None), _mask_spec(axes, per_query_mask), P(None)),
        out_specs=(P(None, None), P(None, None), P(None, None)),
        check_vma=False)


def distributed_range_batch(mesh: Mesh, metric: Metric, capacity: int,
                            axes: tuple[str, ...] = ("data",),
                            interpret: bool | None = None,
                            per_query_mask: bool = True):
    """Batched filtered range query over a row-sharded corpus.

    Each device runs the query-tiled fused range scan + per-query
    compaction (``kernels.ops.fused_range_topk_batch``) over its shard,
    emitting up to ``min(capacity, shard_rows)`` best-first in-range
    candidates per query; the hierarchical merge then re-truncates the
    concatenated per-shard buffers back to the best ``capacity`` per query
    at every mesh axis.  Because each shard's buffer is a superset of its
    contribution to the global best-``capacity`` set, the merged result is
    EXACTLY the global best-first truncation — the result shape (Q,
    capacity) is shard-count-independent, and per-query hit counts are
    ``psum``'d so ``count`` stays exact even past capacity truncation.

    Returns a ``shard_map``'d callable
    ``fn(sh_corpus, sh_ids, qs, radius, sh_mask, qvalid) ->
    (ids, sims, valid, count)`` with ``radius`` a (Q,) raw-metric vector
    and the other arguments/layouts (including the shared-mask
    ``per_query_mask=False`` form) as in :func:`distributed_topk_batch`.
    ``count`` is (Q,) total in-range hits BEFORE truncation (0 for invalid
    queries).  At shards=1 results are bit-identical to a single-device
    ``fused_range_topk_batch`` call."""

    def local(corpus, ids, qs, radius, mask, qvalid):
        from ..kernels.ops import fused_range_topk_batch
        cap_local = min(capacity, corpus.shape[0])
        lids, lsims, lvalid, lcount = fused_range_topk_batch(
            corpus, qs, radius, mask, metric, cap_local,
            interpret=interpret, qvalid=qvalid)
        gids = jnp.where(lvalid, ids[jnp.maximum(lids, 0)], -1)
        keys = jnp.where(lvalid, order_key(metric, lsims), jnp.inf)
        out_ids, sims, valid = _merge_topk(metric, keys, gids, capacity, axes)
        count = lcount
        for ax in reversed(axes):
            count = jax.lax.psum(count, ax)
        return out_ids, sims, valid, count

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None, None), P(None),
                  _mask_spec(axes, per_query_mask), P(None)),
        out_specs=(P(None, None), P(None, None), P(None, None), P(None)),
        check_vma=False)


def distributed_range_batch_q(mesh: Mesh, metric: Metric, capacity: int,
                              axes: tuple[str, ...] = ("data",),
                              interpret: bool | None = None,
                              per_query_mask: bool = True,
                              rescore_factor: int = 2):
    """Quantized twin of :func:`distributed_range_batch` (DESIGN.md §13).

    Per-shard slack-band classification + local fp32 boundary rescore
    (``kernels.quant.fused_range_topk_batch_q``), so the merged candidate
    keys AND the ``psum``'d hit counts are exact — bitwise what the fp32
    twin ships at shards=1.  Signature adds the quantized per-row arrays:
    ``fn(sh_corpus, sh_qvecs, sh_scales, sh_half, sh_l1, sh_l2, sh_ids,
    qs, radius, sh_mask, qvalid) -> (ids, sims, valid, count)``."""

    def local(corpus, qvecs, scales, half, l1, l2, ids, qs, radius, mask,
              qvalid):
        from ..kernels.quant import fused_range_topk_batch_q
        cap_local = min(capacity, corpus.shape[0])
        lids, lsims, lvalid, lcount = fused_range_topk_batch_q(
            corpus, qvecs, scales, half, l1, l2, qs, radius, mask, metric,
            cap_local, rescore_factor=rescore_factor, interpret=interpret,
            qvalid=qvalid)
        gids = jnp.where(lvalid, ids[jnp.maximum(lids, 0)], -1)
        keys = jnp.where(lvalid, order_key(metric, lsims), jnp.inf)
        out_ids, sims, valid = _merge_topk(metric, keys, gids, capacity, axes)
        count = lcount
        for ax in reversed(axes):
            count = jax.lax.psum(count, ax)
        return out_ids, sims, valid, count

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes, None), P(axes),
                  P(axes), P(axes), P(axes), P(None, None), P(None),
                  _mask_spec(axes, per_query_mask), P(None)),
        out_specs=(P(None, None), P(None, None), P(None, None), P(None)),
        check_vma=False)
