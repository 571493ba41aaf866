"""Physical operators + engine modes (CHASE §5) and their lowering to JAX.

Each builder returns a pure function ``fn(arrays, binds) -> outputs`` that the
compiler jits — the data-centric codegen step (§6): one XLA computation per
pipeline, no operator boundaries at runtime.

Engine modes reproduce the paper's comparison systems *as query plans* (the
inefficiencies are plan-structural, so they are faithfully reproducible):

* ``chase``  — rewritten plan: fused predicate probes, similarity from the
               scan reused by sort/rank (map operator), updateState early stop.
* ``vbase``  — incremental ANN probes (relaxed monotonicity) but similarity is
               RECOMPUTED by the sort operator above the scan (Fig. 1c), and
               structured filtering happens between scan and sort.
* ``pase``   — K' = oversample·K unfiltered ANN fetch, post-filter, no
               re-sort needed (index order) but heavy redundant compute and
               recall loss under selective filters (Fig. 1b).
* ``brute``  — compiled, fused, index-less full scan (the LingoDB-V analogue).

For window families (Q4-Q6) the paper's baselines cannot use the ANN index at
all (§2.4); their mode falls back to the brute plan of Fig. 5a (per-partition
full sort), which we also lower faithfully (``brute_sort``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..index.flat import FlatIndex, masked_topk
from ..index.ivf import (IVFIndex, ProbeConfig, ivf_range, ivf_range_batch,
                         ivf_range_category, ivf_range_category_batch,
                         ivf_topk, ivf_topk_batch)
from .expr import (Bindings, Column, Const, Cmp, BoolOp, Arith, Distance,
                   Expr, Param, distance_values, evaluate, in_range, order_key)
from .schema import Catalog, ColumnKind, Metric, Table
from .semantics import Analysis, QueryClass


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Compile-time engine configuration (every field shapes compilation;
    see :meth:`fingerprint`).  ``engine`` selects the plan shape of one of
    the paper's comparison systems (module docstring)."""
    engine: str = "chase"          # chase | vbase | pase | brute | brute_sort
    # default_factory, NOT a shared ProbeConfig() instance: a class-level
    # default dataclass would be one object aliased across every
    # EngineOptions ever constructed (both frozen, so mutation can't bite
    # today — but identity-based caches and dataclasses.replace patterns
    # must never observe cross-caller sharing).
    probe: ProbeConfig = dataclasses.field(default_factory=ProbeConfig)
    pase_oversample: int = 10      # K' = oversample * K
    use_pallas: bool = False       # fused Pallas kernel for flat scans
    max_pairs: int = 512           # per-left-row buffer for join families
    # None -> kernels.default_interpret(): interpret on CPU, compiled Mosaic
    # kernels on TPU/GPU, without callers threading the flag.
    interpret_pallas: bool | None = None
    # Q3-Q6 physical lowering: 'batch' treats the left rows as ONE query
    # batch on the batched kernels/probes (DESIGN.md §7); 'perleft' keeps the
    # legacy per-left-row scan loop (and forces the vmap-of-scalar
    # execute_batch fallback) — the measured baseline in benchmarks/q34.
    join_lowering: str = "batch"   # batch | perleft
    # Multi-device sharded scan (DESIGN.md §10): a
    # repro.dist.sharding.DistSpec row-shards the scanned corpus over its
    # mesh and lowers EVERY query class onto the distributed fused flat
    # scan (shard rows x tile queries + hierarchical per-query merge).
    # Fingerprint-affecting: a mesh change misses the plan cache.  Exact —
    # index probes are bypassed (a row-sharded corpus has no co-sharded IVF
    # gather yet), so only engines 'chase' and 'brute' compose with it.
    dist: "DistSpec | None" = None
    # Quantized corpus scan (DESIGN.md §13): stream the int8 (per-row
    # symmetric scale) or bf16 twin of the scanned column through the
    # quantized Pallas kernels and re-rank the top-(rescore_factor·K)
    # candidates against the fp32 originals — 4×/2× fewer corpus bytes,
    # results bit-identical to the fp32 path.  Requires use_pallas; only
    # engines 'chase' and 'brute' compose (IVF probes stay fp32-exact —
    # their key-dependent early-stop would be perturbed by quantized
    # keys).  Fingerprint-affecting, like every field here.
    quant: str | None = None       # None | 'int8' | 'bf16'
    # Candidate multiple c for the fused fp32 rescore: the quantized scan
    # keeps c·K candidates per query (c·capacity boundary rows for range).
    # 2 is bit-exact on every parity suite; raise for adversarial
    # near-tie corpora (ExecutionHints.rescore_factor folds in here).
    rescore_factor: int = 2

    def fingerprint(self) -> str:
        """Stable serialization for the plan-cache key: every field shapes
        compilation, so any change must miss the cache.  Frozen dataclass
        repr covers all fields (including the nested ProbeConfig and the
        DistSpec mesh description)."""
        return repr(self)


def probe_ceiling(options: "EngineOptions") -> int:
    """Effective probe-budget ceiling of plans compiled under ``options`` —
    what the adaptive optimizer clamps predicted budgets to (DESIGN.md
    §14).  0 means the lowering has no probe lane: flat/brute scans and the
    sharded distributed scan execute in one pass, so a runtime
    ``probe_budget`` is inert and effort bucketing is pure overhead."""
    if options.engine not in ("chase", "vbase", "pase"):
        return 0
    if options.dist is not None:
        return 0
    return int(options.probe.max_probes)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _metric_of(catalog: Catalog, table: str, column: str) -> Metric:
    return catalog.table(table).schema[column].metric


def _static_int(v, binds: Bindings, what: str) -> int:
    if isinstance(v, int):
        return v
    if isinstance(v, str) and v in binds:
        return int(binds[v])
    raise ValueError(f"{what} must be statically resolvable, got {v!r}")


def _row_mask_fn(pred: Expr | None, table: Table):
    """Predicate -> (binds -> (N,) bool) or None."""
    if pred is None:
        return None

    def fn(binds: Bindings) -> jnp.ndarray:
        return evaluate(pred, table, binds)

    return fn


def _owner_fn(ltab: Table, rtab: Table, lalias: str | None,
              ralias: str | None):
    def owner(col: Column) -> str:
        if col.table in (lalias, ltab.name):
            return "l"
        if col.table in (ralias, rtab.name):
            return "r"
        inl = col.name in ltab.schema
        inr = col.name in rtab.schema
        if inl and inr:
            raise ValueError(f"ambiguous column {col.name}")
        return "l" if inl else "r"

    return owner


def _eval_join_pred(pred: Expr, owner, ev_left, ev_right,
                    binds: Bindings) -> jnp.ndarray:
    """One interpreter for both join-mask lowerings; ``ev_left``/``ev_right``
    decide the column shape (scalar-at-lidx vs (L, 1) / (N,) vs (1, N))."""
    def ev(e: Expr):
        if isinstance(e, Column):
            return ev_left(e.name) if owner(e) == "l" else ev_right(e.name)
        if isinstance(e, Const):
            return jnp.asarray(e.value)
        if isinstance(e, Param):
            return jnp.asarray(binds[e.name])
        if isinstance(e, Cmp):
            lo, hi = ev(e.lhs), ev(e.rhs)
            return {"<": lambda: lo < hi, "<=": lambda: lo <= hi,
                    ">": lambda: lo > hi, ">=": lambda: lo >= hi,
                    "=": lambda: lo == hi, "<>": lambda: lo != hi}[e.op]()
        if isinstance(e, BoolOp):
            if e.op == "not":
                return ~ev(e.operands[0])
            vals = [ev(o) for o in e.operands]
            out = vals[0]
            for v in vals[1:]:
                out = (out & v) if e.op == "and" else (out | v)
            return out
        if isinstance(e, Arith):
            lo, hi = ev(e.lhs), ev(e.rhs)
            return {"+": lambda: lo + hi, "-": lambda: lo - hi,
                    "*": lambda: lo * hi, "/": lambda: lo / hi}[e.op]()
        raise TypeError(f"unsupported join-predicate node {type(e)}")

    return ev(pred)


def _join_mask_fn(pred: Expr | None, ltab: Table, rtab: Table,
                  lalias: str | None, ralias: str | None):
    """Residual join predicate -> (left_row_idx, binds) -> (Nright,) bool.

    Left columns resolve to scalars at ``left_row_idx`` (vmap lane), right
    columns to full arrays — the per-left-row filter of the KnnSubquery."""
    if pred is None:
        return None
    owner = _owner_fn(ltab, rtab, lalias, ralias)

    def fn(lidx, binds: Bindings) -> jnp.ndarray:
        m = _eval_join_pred(pred, owner,
                            lambda name: ltab[name][lidx],
                            lambda name: rtab[name], binds)
        return jnp.broadcast_to(m, (rtab.num_rows,))

    return fn


def _join_mask_batch_fn(pred: Expr | None, ltab: Table, rtab: Table,
                        lalias: str | None, ralias: str | None):
    """Residual join predicate -> (binds) -> (L, Nright) bool, ALL left rows.

    The batch-native twin of :func:`_join_mask_fn`: left columns evaluate as
    (L, 1), right columns as (1, N), and broadcasting produces every
    (left row, right row) pair's mask in one columnar pass — the (Q, N) mask
    layout the batched kernels/probes consume, with the left rows playing Q."""
    if pred is None:
        return None
    owner = _owner_fn(ltab, rtab, lalias, ralias)

    def fn(binds: Bindings) -> jnp.ndarray:
        m = _eval_join_pred(pred, owner,
                            lambda name: ltab[name][:, None],
                            lambda name: rtab[name][None, :], binds)
        return jnp.broadcast_to(m, (ltab.num_rows, rtab.num_rows))

    return fn


def _resort_redundant(metric: Metric, corpus, q, ids, valid, k):
    """VBASE's Fig.1c inefficiency: the sort operator recomputes
    vec <*> query for tuples the scan already scored."""
    safe = jnp.maximum(ids, 0)
    vecs = corpus[safe]
    raw = distance_values(metric, vecs, q)          # REDUNDANT distance evals
    keys = jnp.where(valid, order_key(metric, raw), jnp.inf)
    neg, idx = jax.lax.top_k(-keys, k)
    keys2 = -neg
    ids2 = ids[idx]
    valid2 = jnp.isfinite(keys2)
    sims = jnp.where(valid2, -keys2 if metric.is_similarity() else keys2, 0.0)
    return jnp.where(valid2, ids2, -1), sims, valid2


def _flat_topk(opts: EngineOptions, flat: FlatIndex, q, k, row_mask):
    if opts.use_pallas:
        from ..kernels.ops import fused_scan_topk
        return fused_scan_topk(flat.vectors, q, k, row_mask, flat.metric,
                               interpret=opts.interpret_pallas)
    return flat.topk(q, k, row_mask)


def _flat_topk_batch(opts: EngineOptions, arrays, metric: Metric, corpus,
                     qs, k: int, row_mask, qvalid=None):
    """Fused flat batched top-k; routes through the quantized lowering
    (DESIGN.md §13) when ``EngineOptions.quant`` is set — the quantized
    twin's arrays ride the plan's ``arrays`` dict (``qvecs``/``qscales``),
    so Catalog re-registrations re-bind with zero retraces.  Returns (ids,
    sims, valid, stats); the quantized lane's stats add ``fp32_fallback``,
    1 for each query whose batch failed the top-k certificate and ran the
    fp32 kernel as well (the bucketed executor counts it in
    ``BucketedExecutor.quant_topk``; every surface drops it from the
    output tree)."""
    m, n = qs.shape[0], corpus.shape[0]
    stats = {"probes": jnp.zeros((m,), jnp.int32),
             "distance_evals": _flat_evals(qvalid, m, n)}
    if opts.quant is not None:
        from ..kernels.quant import fused_scan_topk_batch_q
        ids, sims, valid, fallback = fused_scan_topk_batch_q(
            corpus, arrays["qvecs"], arrays["qscales"], arrays["qhalf"],
            arrays["ql1"], arrays["ql2"], qs, k, row_mask, metric,
            rescore_factor=opts.rescore_factor,
            interpret=opts.interpret_pallas, qvalid=qvalid)
        fb = jnp.full((m,), fallback, jnp.int32)
        stats["fp32_fallback"] = fb if qvalid is None else jnp.where(
            qvalid, fb, 0)
        return ids, sims, valid, stats
    from ..kernels.ops import fused_scan_topk_batch
    ids, sims, valid = fused_scan_topk_batch(corpus, qs, k, row_mask, metric,
                                             interpret=opts.interpret_pallas,
                                             qvalid=qvalid)
    return ids, sims, valid, stats


def _flat_evals(qvalid, m: int, n: int) -> jnp.ndarray:
    """Per-query flat-scan distance-eval counters; size-bucket pad queries
    (qvalid False) contribute zero."""
    evals = jnp.full((m,), n, jnp.int32)
    return evals if qvalid is None else jnp.where(qvalid, evals, 0)


def _flat_range_topk_batch(opts: EngineOptions, metric: Metric, corpus,
                           qs, radius, row_mask, capacity: int,
                           qvalid=None, arrays=None):
    """Flat range scan over a (M, d) query batch, compacted to ``capacity``.

    Dispatch: the quantized Pallas kernel (``opts.quant``, slack-band
    boundary rescore — needs the plan ``arrays`` for the quantized twin),
    the query-tiled fp32 Pallas kernel (``use_pallas``), or a vmapped
    exact scan.  ``radius`` is a scalar or (M,); ``row_mask`` None, shared
    (N,) (a live validity lane), or per-query (M, N);
    ``qvalid`` None or (M,) bool (size-bucket pad queries register no hits
    and zero counters).  Results are ordered best-first (ascending order
    key).  Returns (ids (M, P), sims, valid, count (M,), per-row stats) with
    P = min(capacity, N)."""
    m, n = qs.shape[0], corpus.shape[0]
    cap = min(int(capacity), n)
    radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (m,))
    if opts.use_pallas and opts.quant is not None:
        from ..kernels.quant import fused_range_topk_batch_q
        ids, sims, valid, count = fused_range_topk_batch_q(
            corpus, arrays["qvecs"], arrays["qscales"], arrays["qhalf"],
            arrays["ql1"], arrays["ql2"], qs, radius, row_mask, metric,
            cap, rescore_factor=opts.rescore_factor,
            interpret=opts.interpret_pallas, qvalid=qvalid)
    elif opts.use_pallas:
        from ..kernels.ops import fused_range_topk_batch
        ids, sims, valid, count = fused_range_topk_batch(
            corpus, qs, radius, row_mask, metric, cap,
            interpret=opts.interpret_pallas, qvalid=qvalid)
    else:
        flat = FlatIndex(metric, corpus)
        if row_mask is None:
            hit, raw = jax.vmap(lambda q, r: flat.range_mask(q, r, None))(
                qs, radius)
        elif row_mask.ndim == 1:
            hit, raw = jax.vmap(
                lambda q, r: flat.range_mask(q, r, row_mask))(qs, radius)
        else:
            hit, raw = jax.vmap(flat.range_mask)(qs, radius, row_mask)
        if qvalid is not None:
            hit = hit & qvalid[:, None]
        keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
        neg, sel = jax.lax.top_k(-keys, cap)                   # row-wise
        valid = jnp.isfinite(-neg)
        ids = jnp.where(valid, sel.astype(jnp.int32), -1)
        sims = jnp.where(valid, jnp.take_along_axis(raw, sel, axis=1), 0.0)
        count = jnp.sum(hit, axis=1)
    stats = {"probes": jnp.zeros((m,), jnp.int32),
             "distance_evals": _flat_evals(qvalid, m, n)}
    return ids, sims, valid, count, stats


def _stacked_batch_size(binds: dict) -> int:
    """Leading Q axis of stacked binds (static at trace time)."""
    dims = [v.shape[0] for v in binds.values()
            if hasattr(v, "ndim") and v.ndim >= 1]
    if not dims:
        raise ValueError("batched join execution needs at least one stacked "
                         "bind to carry the batch size; use binds_list")
    return dims[0]


def _flatten_left_batch(lvec, binds: dict, mask_b):
    """(Q bind sets x L left rows) -> ONE kernel query batch.

    Replicates the (L, d) left block per bind set and evaluates the per-bind
    join masks into the flattened (Q·L, N) layout (q-major, matching
    ``reshape`` on the outputs).  On the flat path the replication recomputes
    (L, N) distances Q-fold — bind sets only vary radius/masks, applied
    post-matmul — acceptable for parameter batches (Q small); a
    share-the-matmul flat fast path is future work."""
    nleft, d = lvec.shape
    qn = _stacked_batch_size(binds)
    qs = jnp.broadcast_to(lvec[None], (qn, nleft, d)).reshape(-1, d)
    rm = (jax.vmap(mask_b)(binds).reshape(qn * nleft, -1)
          if mask_b else None)
    return qn, nleft, qs, rm


def _flatten_valid_budget(qvalid, probe_budget, qn: int, nleft: int):
    """Expand per-bind-set ``qvalid`` (Q,) and ``probe_budget`` (scalar |
    (Q,) | (Q, L)) to the flattened (Q·L,) query-batch layout."""
    fq = (None if qvalid is None
          else jnp.repeat(jnp.asarray(qvalid, jnp.bool_), nleft))
    if probe_budget is None:
        fb = None
    else:
        b = jnp.asarray(probe_budget, jnp.int32)
        if b.ndim == 1:
            b = b[:, None]
        fb = jnp.broadcast_to(b, (qn, nleft)).reshape(-1)
    return fq, fb


# ---------------------------------------------------------------------------
# Sharded lowering (DESIGN.md §10) — selected by EngineOptions.dist
# ---------------------------------------------------------------------------
#
# A DistSpec row-shards the scanned corpus over a device mesh; each device
# runs the query-tiled fused scan for ALL Q queries, then a hierarchical
# per-query merge (dist/collectives.py).  The lowering is EXACT and
# engine-independent: index probes are bypassed (a row-sharded corpus has no
# co-sharded IVF gather yet — ROADMAP item), so at shards=1 results are
# bit-identical to the single-device fused flat path (engine='brute',
# use_pallas=True) for every query class.  The q-valid lane threads through
# to every shard: a size-bucket pad query emits no candidates and zero
# counters on any device.


def _dist_mask(arrays, rm, per_query_mask: bool) -> jnp.ndarray:
    """Normalize the row mask to what the distributed collectives consume.

    With a per-query mask (``rm`` (Q, N), a plan with a row predicate) the
    divisibility-pad columns (beyond the real N — see
    ``ShardedCorpus.build``) pad False to (Q, Npad).  Without one, the
    shared (Npad,) ``row_ids >= 0`` mask excludes exactly the pad rows and
    no (Q, N) array is ever materialized — predicate-free scans at
    production N would otherwise ship Q·Npad mask bytes per batch."""
    if not per_query_mask:
        assert rm is None
        return arrays["drow_ids"] >= 0
    n = arrays["corpus"].shape[0]
    npad = arrays["dcorpus"].shape[0]
    m = rm.astype(jnp.bool_)
    if npad != n:
        m = jnp.pad(m, ((0, 0), (0, npad - n)), constant_values=False)
    return m


def _dist_qvalid(qvalid, qn: int) -> jnp.ndarray:
    """Materialize the per-query valid lane ((Q,) bool; None -> all valid)."""
    return (jnp.ones((qn,), jnp.bool_) if qvalid is None
            else jnp.asarray(qvalid, jnp.bool_))


def _dist_topk_core(opts: EngineOptions, metric: Metric, k: int,
                    per_query_mask: bool):
    """Build ``(arrays, qs, rm, qvalid) -> (ids, sims, valid, stats)``: the
    sharded twin of the fused flat top-k batch (exact; counters match the
    single-device flat path — N distance evals per valid query, 0 probes).
    ``per_query_mask`` is static per plan: whether this plan evaluates a
    row predicate into a (Q, N) mask (see :func:`_dist_mask`)."""
    from ..dist.collectives import (distributed_topk_batch,
                                    distributed_topk_batch_q)
    from ..dist.sharding import resolve_mesh
    spec = opts.dist
    if opts.quant is not None:
        dfn = distributed_topk_batch_q(resolve_mesh(spec), metric, k,
                                       spec.axes,
                                       interpret=opts.interpret_pallas,
                                       per_query_mask=per_query_mask,
                                       rescore_factor=opts.rescore_factor)
    else:
        dfn = distributed_topk_batch(resolve_mesh(spec), metric, k, spec.axes,
                                     interpret=opts.interpret_pallas,
                                     per_query_mask=per_query_mask)

    def run(arrays, qs, rm, qvalid=None):
        qn, n = qs.shape[0], arrays["corpus"].shape[0]
        mask = _dist_mask(arrays, rm, per_query_mask)
        qv = _dist_qvalid(qvalid, qn)
        if opts.quant is not None:
            ids, sims, valid = dfn(arrays["dcorpus"], arrays["dqvecs"],
                                   arrays["dqscales"], arrays["dqhalf"],
                                   arrays["dql1"], arrays["dql2"],
                                   arrays["drow_ids"], qs, mask, qv)
        else:
            ids, sims, valid = dfn(arrays["dcorpus"], arrays["drow_ids"], qs,
                                   mask, qv)
        stats = {"probes": jnp.zeros((qn,), jnp.int32),
                 "distance_evals": _flat_evals(qvalid, qn, n)}
        return ids, sims, valid, stats

    return run


def _dist_range_core(opts: EngineOptions, metric: Metric, capacity: int,
                     n_rows: int, per_query_mask: bool):
    """Build ``(arrays, qs, radius, rm, qvalid) -> (ids, sims, valid, count,
    stats)``: the sharded twin of :func:`_flat_range_topk_batch`.  The
    result buffer is ``min(capacity, n_rows)`` wide regardless of shard
    count (per-shard buffers concatenate and re-truncate best-first at each
    merge level); ``count`` stays exact past truncation (psum of per-shard
    hit counts).  ``per_query_mask`` as in :func:`_dist_topk_core`."""
    from ..dist.collectives import (distributed_range_batch,
                                    distributed_range_batch_q)
    from ..dist.sharding import resolve_mesh
    spec = opts.dist
    cap = min(int(capacity), int(n_rows))
    if opts.quant is not None:
        dfn = distributed_range_batch_q(resolve_mesh(spec), metric, cap,
                                        spec.axes,
                                        interpret=opts.interpret_pallas,
                                        per_query_mask=per_query_mask,
                                        rescore_factor=opts.rescore_factor)
    else:
        dfn = distributed_range_batch(resolve_mesh(spec), metric, cap,
                                      spec.axes,
                                      interpret=opts.interpret_pallas,
                                      per_query_mask=per_query_mask)

    def run(arrays, qs, radius, rm, qvalid=None):
        qn, n = qs.shape[0], arrays["corpus"].shape[0]
        radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (qn,))
        mask = _dist_mask(arrays, rm, per_query_mask)
        qv = _dist_qvalid(qvalid, qn)
        if opts.quant is not None:
            ids, sims, valid, count = dfn(
                arrays["dcorpus"], arrays["dqvecs"], arrays["dqscales"],
                arrays["dqhalf"], arrays["dql1"], arrays["dql2"],
                arrays["drow_ids"], qs, radius, mask, qv)
        else:
            ids, sims, valid, count = dfn(arrays["dcorpus"],
                                          arrays["drow_ids"], qs, radius,
                                          mask, qv)
        stats = {"probes": jnp.zeros((qn,), jnp.int32),
                 "distance_evals": _flat_evals(qvalid, qn, n)}
        return ids, sims, valid, count, stats

    return run


# ---------------------------------------------------------------------------
# Live-corpus lowering (DESIGN.md §12) — selected by an attached LiveCorpus
# ---------------------------------------------------------------------------
#
# When catalog.live_for(scan table, scan column) is attached, the batched
# builders swap two things into the standard pipeline and leave everything
# else untouched:
#
# 1. Masks come from the LIVE arrays: the main-segment validity lane (the
#    tombstone bitmap) ANDed with the predicate evaluated over the live
#    scalar columns — the same (Q, N) row-mask layout every kernel and IVF
#    probe path already threads, so a tombstoned row is inert exactly the
#    way a pad row is.  The delta segment gets the same treatment at its
#    own width ((Q, delta_cap)).
# 2. After the main-segment result (IVF / flat / sharded — unchanged code),
#    the delta segment is scanned by the flat batched machinery and merged
#    in as one extra, device-local level of the hierarchical per-query
#    merge (index/delta.py + dist.collectives.merge_topk_level).  Merged
#    ids >= cap_main name delta slots (LiveCorpus.user_ids maps back).
#
# Live mode composes with the exact engines only (chase / brute — see
# compiler._validate_live); the single-query path reuses the batched
# lowering at Q=1 (compiler._single_via_batch), so no single builder needs
# a live branch.  NOTE on ordering: the delta merge re-sorts each query's
# buffer best-first, so live IVF range results are best-first even at zero
# deltas (fresh-attach live plans — the parity reference — share this code
# and therefore this order; frozen IVF plans keep probe-discovery order).


class _ColsTable:
    """Dict-of-arrays stand-in for :class:`Table` inside ``evaluate()``
    (expression evaluation only reads ``table[name]``), letting predicates
    run against the live segment columns without a frozen Table."""

    def __init__(self, cols: dict):
        self._cols = cols

    def __getitem__(self, name: str):
        return self._cols[name]


def _as_per_query(m, qn: int):
    """Broadcast a shared 1-D live mask to the (Q, N) layout for consumers
    without a shared-mask fast path (IVF probes, the sharded core)."""
    if m is None or m.ndim == 2:
        return m
    return jnp.broadcast_to(m[None], (qn,) + m.shape)


def _live_scan_masks(pred: Expr | None, arrays, binds, qn: int):
    """Live (main, delta) row masks for the scan classes (Q1/Q2/Q5).

    With a structured predicate each is per-query 2-D — (Q, cap_main) /
    (Q, delta_cap) — combining the segment validity lane (tombstones +
    unoccupied slots) with the predicate evaluated over the live scalar
    columns.  Without one the validity lanes are returned UNBROADCAST
    (1-D): the fused kernels take the shared-mask fast path, which keeps
    the zero-delta live scan at frozen-scan cost (the (Q, N) mask alone
    costs ~25% on the b64 flat workload)."""
    mv, dv = arrays["live_main_valid"], arrays["live_delta_valid"]
    n, dn = mv.shape[0], dv.shape[0]
    if pred is None:
        return mv, dv

    def seg(cols, seg_valid, seg_n):
        m = jax.vmap(lambda b: jnp.broadcast_to(
            evaluate(pred, _ColsTable(cols), b), (seg_n,)))(binds)
        return m & seg_valid[None, :]

    return (seg(arrays["live_cols"], mv, n),
            seg(arrays["live_dcols"], dv, dn))


def _live_join_masks(pred: Expr | None, ltab: Table, rtab: Table,
                     lalias: str | None, ralias: str | None,
                     arrays, binds, qn: int, nleft: int):
    """Live (main, delta) masks for the join classes, in the flattened
    (Q·L, seg) layout of :func:`_flatten_left_batch`.

    The twin of :func:`_join_mask_batch_fn` with right columns read from
    the live segment arrays instead of the frozen right table (the left
    side stays frozen — only the scanned column is live)."""
    mv, dv = arrays["live_main_valid"], arrays["live_delta_valid"]
    n, dn = mv.shape[0], dv.shape[0]
    if pred is None:
        return (jnp.broadcast_to(mv[None], (qn * nleft, n)),
                jnp.broadcast_to(dv[None], (qn * nleft, dn)))
    owner = _owner_fn(ltab, rtab, lalias, ralias)

    def seg(cols, seg_valid, seg_n):
        def per_bind(b):
            m = _eval_join_pred(pred, owner,
                                lambda name: ltab[name][:, None],
                                lambda name: cols[name][None, :], b)
            return jnp.broadcast_to(m, (nleft, seg_n))

        m = jax.vmap(per_bind)(binds).reshape(qn * nleft, seg_n)
        return m & seg_valid[None, :]

    return (seg(arrays["live_cols"], mv, n),
            seg(arrays["live_dcols"], dv, dn))


def _merge_delta_topk(opts: EngineOptions, metric: Metric, arrays, qs,
                      k: int, dmask, qvalid, ids, sims, valid, stats):
    """Merge the delta-segment top-k into a main-segment (Q, k) result.

    Main candidates go in as merge side A (ties resolve main-first —
    ``jax.lax.top_k`` stability), so an empty delta leaves the main result
    bit-identical — which licenses the runtime ``lax.cond`` below: with no
    live delta row the whole scan+merge is skipped (the merge alone costs
    ~20% of the b64 flat workload, and zero-delta is the steady state
    between compactions).  Top-k main results are already best-first, so
    the skip branch is the identity.  The delta scan adds delta_cap
    distance evals per valid query to the counters only when it runs (it
    IS a flat scan of the segment)."""
    from ..index.delta import delta_topk_batch
    from ..dist.collectives import merge_topk_level
    offset = arrays["corpus"].shape[0]
    has_delta = jnp.any(arrays["live_delta_valid"])

    def merged(main):
        ids, sims, valid = main
        # the delta segment is delta_cap rows by construction: the jnp scan
        # is a trivial (Q, delta_cap) matmul, while a second Pallas launch
        # per execute costs more than the whole segment (worst in interpret
        # mode)
        dkeys, dgids = delta_topk_batch(
            metric, arrays["live_delta_vec"], qs, k, dmask, qvalid, offset,
            use_pallas=False)
        mkeys = jnp.where(valid, order_key(metric, sims), jnp.inf)
        mgids = jnp.where(valid, ids, -1)
        return merge_topk_level(metric, mkeys, mgids, dkeys, dgids, k)

    ids, sims, valid = jax.lax.cond(has_delta, merged, lambda main: main,
                                    (ids, sims, valid))
    stats = dict(stats)
    stats["distance_evals"] = stats["distance_evals"] + jnp.where(
        has_delta,
        _flat_evals(qvalid, qs.shape[0], arrays["live_delta_vec"].shape[0]),
        0)
    return ids, sims, valid, stats


def _merge_delta_range(opts: EngineOptions, metric: Metric, arrays, qs,
                       radius, capacity: int, dmask, qvalid,
                       ids, sims, valid, count, stats):
    """Merge the delta-segment range hits into a main-segment result batch.

    The merged buffer is ``min(capacity, main width + delta width)`` wide
    best-first; ``count`` stays exact past truncation (main count + exact
    delta hit count).  Counter accounting as in :func:`_merge_delta_topk`,
    but NO empty-delta runtime skip: the merge is what re-sorts IVF range
    hits (probe-discovery order) best-first, an ordering the live range
    classes promise at any delta fill — and none of them is on the gated
    zero-delta flat workload."""
    from ..index.delta import delta_range_batch
    from ..dist.collectives import merge_topk_level
    offset = arrays["corpus"].shape[0]
    dkeys, dgids, dcount = delta_range_batch(
        metric, arrays["live_delta_vec"], qs, radius, dmask, qvalid, offset,
        int(capacity), use_pallas=False)  # tiny segment: see delta_topk note
    mkeys = jnp.where(valid, order_key(metric, sims), jnp.inf)
    mgids = jnp.where(valid, ids, -1)
    w = min(int(capacity), ids.shape[1] + dkeys.shape[1])
    ids, sims, valid = merge_topk_level(metric, mkeys, mgids, dkeys, dgids,
                                        w)
    stats = dict(stats)
    stats["distance_evals"] = stats["distance_evals"] + _flat_evals(
        qvalid, qs.shape[0], arrays["live_delta_vec"].shape[0])
    return ids, sims, valid, count + dcount.astype(count.dtype), stats


# ---------------------------------------------------------------------------
# Q1 — VKNN-SF
# ---------------------------------------------------------------------------

def build_vknn_sf(a: Analysis, catalog: Catalog, opts: EngineOptions,
                  binds_static: Bindings) -> Callable:
    """Q1 (VKNN-SF) single-query pipeline: filtered top-k by engine mode."""
    table = catalog.table(a.table)
    metric = _metric_of(catalog, a.table, a.vector_column)
    k = _static_int(a.k, binds_static, "K")
    mask_fn = _row_mask_fn(a.structured_predicate, table)
    qparam = a.query_expr
    assert isinstance(qparam, Param), "VKNN-SF query must be a parameter"
    index = catalog.index_for(a.table, a.vector_column)
    cfg = opts.probe

    def fn(arrays, binds):
        corpus = arrays["corpus"]
        q = jnp.asarray(binds[qparam.name])
        row_mask = mask_fn(binds) if mask_fn else None
        stats = {}
        if opts.engine == "chase" and index is not None:
            idx: IVFIndex = arrays["index"]
            ids, sims, valid, stats = ivf_topk(idx, corpus, q, k, row_mask, cfg)
        elif opts.engine == "vbase" and index is not None:
            idx = arrays["index"]
            ids, _sims, valid, stats = ivf_topk(idx, corpus, q, k, row_mask, cfg)
            ids, sims, valid = _resort_redundant(metric, corpus, q, ids,
                                                 valid, k)
            stats = dict(stats)
            stats["distance_evals"] = stats["distance_evals"] + k
        elif opts.engine == "pase" and index is not None:
            idx = arrays["index"]
            kk = min(opts.pase_oversample * k, corpus.shape[0])
            ids_o, sims_o, valid_o, stats = ivf_topk(idx, corpus, q, kk, None,
                                                     cfg)
            if row_mask is not None:
                valid_o = valid_o & jnp.where(
                    ids_o >= 0, row_mask[jnp.maximum(ids_o, 0)], False)
            # keep first k surviving (index order is already ascending key)
            keep = jnp.cumsum(valid_o) <= k
            valid_o = valid_o & keep
            keys = jnp.where(valid_o, order_key(metric, sims_o), jnp.inf)
            neg, sel = jax.lax.top_k(-keys, k)
            valid = jnp.isfinite(-neg)
            ids = jnp.where(valid, ids_o[sel], -1)
            sims = jnp.where(valid, sims_o[sel], 0.0)
        else:  # brute (LingoDB-V analogue) or missing index
            flat = FlatIndex(metric, corpus)
            ids, sims, valid = _flat_topk(opts, flat, q, k, row_mask)
            stats = {"probes": jnp.int32(0),
                     "distance_evals": jnp.int32(corpus.shape[0])}
        return {"ids": ids, "sim": sims, "valid": valid, "stats": stats}

    return fn


# ---------------------------------------------------------------------------
# Q2 — DR-SF
# ---------------------------------------------------------------------------

def build_dr_sf(a: Analysis, catalog: Catalog, opts: EngineOptions,
                binds_static: Bindings) -> Callable:
    """Q2 (DR-SF) single-query pipeline: filtered range scan by engine."""
    table = catalog.table(a.table)
    metric = _metric_of(catalog, a.table, a.vector_column)
    mask_fn = _row_mask_fn(a.structured_predicate, table)
    qparam = a.query_expr
    index = catalog.index_for(a.table, a.vector_column)
    cfg = opts.probe
    radius_expr = a.radius

    def radius_of(binds):
        return evaluate(radius_expr, table, binds)

    def fn(arrays, binds):
        corpus = arrays["corpus"]
        q = jnp.asarray(binds[qparam.name])
        radius = radius_of(binds)
        row_mask = mask_fn(binds) if mask_fn else None
        if opts.engine == "chase" and index is not None:
            idx = arrays["index"]
            ids, sims, valid, count, stats = ivf_range(idx, corpus, q, radius,
                                                       row_mask, cfg)
        elif opts.engine == "vbase" and index is not None:
            idx = arrays["index"]
            # scan without fused predicate; filter as a separate operator,
            # whose predicate re-evaluates similarity for the range check
            ids, _sims, valid, count, stats = ivf_range(idx, corpus, q, radius,
                                                        None, cfg)
            safe = jnp.maximum(ids, 0)
            raw = distance_values(metric, corpus[safe], q)    # REDUNDANT
            valid = valid & in_range(metric, raw, radius)
            if row_mask is not None:
                valid = valid & row_mask[safe]
            sims = jnp.where(valid, raw, 0.0)
            count = jnp.sum(valid)
            stats = dict(stats)
            stats["distance_evals"] = stats["distance_evals"] + cfg.capacity
        else:
            # PASE/pgvector cannot route range queries to the ANN index (§2.3)
            flat = FlatIndex(metric, corpus)
            hit, raw = flat.range_mask(q, radius, row_mask)
            capacity = cfg.capacity
            keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
            neg, sel = jax.lax.top_k(-keys, min(capacity, corpus.shape[0]))
            valid = jnp.isfinite(-neg)
            ids = jnp.where(valid, sel.astype(jnp.int32), -1)
            sims = jnp.where(valid, raw[sel], 0.0)
            count = jnp.sum(hit)
            stats = {"probes": jnp.int32(0),
                     "distance_evals": jnp.int32(corpus.shape[0])}
        return {"ids": ids, "sim": sims, "valid": valid, "count": count,
                "stats": stats}

    return fn


# ---------------------------------------------------------------------------
# Q3 — distance join
# ---------------------------------------------------------------------------
#
# Batch-native lowering (the default): the left side of a vector join IS a
# query batch, so the (masked) left embeddings are gathered into one (L, d)
# batch and pushed through ivf_range_batch / the query-tiled range kernel in
# a single shot — per-left-row join predicates become the (L, N) mask the
# batched operators already consume, and stats come back as per-left (L,)
# arrays (``benchmarks.counters.per_left_amortized`` reports them).  The
# legacy per-left-row loop survives behind join_lowering='perleft' as the
# measured baseline.  Ordering policy: flat plans emit best-first per left
# row; IVF plans emit probe-discovery order (identical to the per-left loop
# with probe_batch=1).


def _dist_join_core(a: Analysis, catalog: Catalog, opts: EngineOptions):
    """(arrays, qs (M,d), radius, rm (M,N)|None) -> Q3 result batch."""
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    index = catalog.index_for(a.right_table, a.right_vector)
    cfg = dataclasses.replace(opts.probe, capacity=opts.max_pairs)
    live = catalog.live_for(a.right_table, a.right_vector) is not None
    sharded = (_dist_range_core(opts, metric, opts.max_pairs,
                                catalog.table(a.right_table).num_rows,
                                per_query_mask=(a.join_predicate is not None
                                                or live))
               if opts.dist is not None else None)

    def core(arrays, qs, radius, rm, qvalid=None, probe_budget=None,
             dmask=None):
        corpus = arrays["corpus"]
        m = qs.shape[0]
        radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (m,))

        def out(ids, sims, valid, count, stats):
            if not live:
                return ids, sims, valid, count, stats
            return _merge_delta_range(opts, metric, arrays, qs, radius,
                                      opts.max_pairs, dmask, qvalid,
                                      ids, sims, valid, count, stats)

        if sharded is not None:
            return out(*sharded(arrays, qs, radius, rm, qvalid))
        if opts.engine in ("chase", "vbase") and index is not None:
            idx = arrays["index"]
            if opts.engine == "chase":
                ids, sims, valid, count, stats = ivf_range_batch(
                    idx, corpus, qs, radius, rm, cfg,
                    probe_budget=probe_budget, qvalid=qvalid)
            else:
                ids, _s, valid, count, stats = ivf_range_batch(
                    idx, corpus, qs, radius, None, cfg,
                    probe_budget=probe_budget, qvalid=qvalid)
                safe = jnp.maximum(ids, 0)
                raw = distance_values(metric, corpus[safe],
                                      qs[:, None, :])          # REDUNDANT
                valid = valid & in_range(metric, raw, radius[:, None])
                if rm is not None:
                    valid = valid & jnp.take_along_axis(rm, safe, axis=1)
                sims = jnp.where(valid, raw, 0.0)
                count = jnp.sum(valid, axis=1)
                # legacy-parity quirk: the per-left Q3 vbase plan never
                # counted its redundant re-check evals; keep counters
                # identical across lowerings
            return out(ids, sims, valid, count, stats)
        return out(*_flat_range_topk_batch(opts, metric, corpus, qs, radius,
                                           rm, opts.max_pairs,
                                           qvalid=qvalid, arrays=arrays))

    return core


def build_dist_join(a: Analysis, catalog: Catalog, opts: EngineOptions,
                    binds_static: Bindings) -> Callable:
    """Q3 (distance join): left rows ride ONE query batch (see section
    comment above; ``join_lowering='perleft'`` keeps the legacy loop)."""
    if opts.join_lowering == "perleft":
        return _build_dist_join_perleft(a, catalog, opts, binds_static)
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    mask_b = _join_mask_batch_fn(a.join_predicate, ltab, rtab, a.left_alias,
                                 a.right_alias)
    core = _dist_join_core(a, catalog, opts)
    radius_expr = a.radius

    def fn(arrays, binds):
        lvec = arrays["left"]                                  # (L, d)
        nleft = lvec.shape[0]
        radius = evaluate(radius_expr, rtab, binds)
        rm = mask_b(binds) if mask_b else None                 # (L, N)
        ids, sims, valid, counts, stats = core(arrays, lvec, radius, rm)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[:, None], ids.shape),
                "tid": ids, "sim": sims, "valid": valid, "count": counts,
                "stats": stats}

    return fn


def build_dist_join_batch(a: Analysis, catalog: Catalog, opts: EngineOptions,
                          binds_static: Bindings) -> Callable:
    """Q bind sets x L left rows, flattened into ONE kernel query batch."""
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    mask_b = _join_mask_batch_fn(a.join_predicate, ltab, rtab, a.left_alias,
                                 a.right_alias)
    live = catalog.live_for(a.right_table, a.right_vector) is not None
    core = _dist_join_core(a, catalog, opts)
    radius_expr = a.radius

    def fn(arrays, binds, qvalid=None, probe_budget=None):
        if live:
            qn, nleft, qs, _ = _flatten_left_batch(arrays["left"], binds,
                                                   None)
            rm, dmask = _live_join_masks(a.join_predicate, ltab, rtab,
                                         a.left_alias, a.right_alias,
                                         arrays, binds, qn, nleft)
        else:
            qn, nleft, qs, rm = _flatten_left_batch(arrays["left"], binds,
                                                    mask_b)
            dmask = None
        fq, fb = _flatten_valid_budget(qvalid, probe_budget, qn, nleft)
        radius = jnp.broadcast_to(
            jax.vmap(lambda b: evaluate(radius_expr, rtab, b))(binds), (qn,))
        ids, sims, valid, counts, stats = core(
            arrays, qs, jnp.repeat(radius, nleft), rm, qvalid=fq,
            probe_budget=fb, dmask=dmask)
        pairs = ids.shape[1]
        shape = (qn, nleft, pairs)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[None, :, None], shape),
                "tid": ids.reshape(shape), "sim": sims.reshape(shape),
                "valid": valid.reshape(shape),
                "count": counts.reshape(qn, nleft),
                "stats": jax.tree.map(lambda v: v.reshape(qn, nleft), stats)}

    return fn


def _build_dist_join_perleft(a: Analysis, catalog: Catalog,
                             opts: EngineOptions,
                             binds_static: Bindings) -> Callable:
    """Legacy lowering: one scan/probe per left row (vmapped matvecs)."""
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    pair_mask = _join_mask_fn(a.join_predicate, ltab, rtab, a.left_alias,
                              a.right_alias)
    index = catalog.index_for(a.right_table, a.right_vector)
    cfg = dataclasses.replace(opts.probe, capacity=opts.max_pairs)
    radius_expr = a.radius

    def fn(arrays, binds):
        lvec = arrays["left"]
        corpus = arrays["corpus"]
        radius = evaluate(radius_expr, rtab, binds)
        nleft = lvec.shape[0]

        def per_left(i):
            q = lvec[i]
            rm = pair_mask(i, binds) if pair_mask else None
            if opts.engine in ("chase", "vbase") and index is not None:
                idx = arrays["index"]
                if opts.engine == "chase":
                    ids, sims, valid, count, stats = ivf_range(
                        idx, corpus, q, radius, rm, cfg)
                else:
                    ids, _s, valid, count, stats = ivf_range(
                        idx, corpus, q, radius, None, cfg)
                    safe = jnp.maximum(ids, 0)
                    raw = distance_values(metric, corpus[safe], q)  # REDUNDANT
                    valid = valid & in_range(metric, raw, radius)
                    if rm is not None:
                        valid = valid & rm[safe]
                    sims = jnp.where(valid, raw, 0.0)
                    count = jnp.sum(valid)
            else:
                if opts.use_pallas:
                    # one kernel launch per left row: the per-query
                    # baseline the query-tiled lowering replaces
                    from ..kernels.ops import fused_range_scan
                    hit, raw, _cnt = fused_range_scan(
                        corpus, q, radius, rm, metric,
                        interpret=opts.interpret_pallas)
                else:
                    flat = FlatIndex(metric, corpus)
                    hit, raw = flat.range_mask(q, radius, rm)
                keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
                neg, sel = jax.lax.top_k(-keys, opts.max_pairs)
                valid = jnp.isfinite(-neg)
                ids = jnp.where(valid, sel.astype(jnp.int32), -1)
                sims = jnp.where(valid, raw[sel], 0.0)
                count = jnp.sum(hit)
                stats = {"probes": jnp.int32(0),
                         "distance_evals": jnp.int32(corpus.shape[0])}
            return ids, sims, valid, count, stats

        ids, sims, valid, counts, stats = jax.vmap(per_left)(
            jnp.arange(nleft, dtype=jnp.int32))
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[:, None], ids.shape),
                "tid": ids, "sim": sims, "valid": valid, "count": counts,
                "stats": stats}

    return fn


# ---------------------------------------------------------------------------
# Q4 — entity-centric KNN join
# ---------------------------------------------------------------------------

def _knn_join_core(a: Analysis, catalog: Catalog, opts: EngineOptions,
                   k: int):
    """(arrays, qs (M,d), rm (M,N)|None) -> (ids, sims, valid, stats)."""
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    index = catalog.index_for(a.right_table, a.right_vector)
    cfg = opts.probe
    live = catalog.live_for(a.right_table, a.right_vector) is not None
    sharded = (_dist_topk_core(opts, metric, k,
                               per_query_mask=(a.join_predicate is not None
                                               or live))
               if opts.dist is not None else None)

    def core(arrays, qs, rm, qvalid=None, probe_budget=None, dmask=None):
        corpus = arrays["corpus"]
        m, n = qs.shape[0], corpus.shape[0]
        if sharded is not None:
            ids, sims, valid, stats = sharded(arrays, qs, rm, qvalid)
        elif opts.engine == "chase" and index is not None:
            # R2: ANN top-k, all left rows in one probe batch — the 7500x
            # path with the matvec loop batched away
            ids, sims, valid, stats = ivf_topk_batch(
                arrays["index"], corpus, qs, k, rm, cfg,
                probe_budget=probe_budget, qvalid=qvalid)
        elif opts.engine == "brute_sort":
            # Fig. 5a plan: window sorts the WHOLE partition (|B| log |B|)
            # per left row — the full sort is the measured inefficiency
            raw = distance_values(metric, corpus[None], qs[:, None, :])
            keys = order_key(metric, raw)                     # (M, N)
            if rm is not None:
                keys = jnp.where(rm, keys, jnp.inf)
            if qvalid is not None:
                keys = jnp.where(qvalid[:, None], keys, jnp.inf)
            perm = jnp.argsort(keys, axis=1)       # full sort, on purpose
            sel = perm[:, :k]
            skeys = jnp.take_along_axis(keys, sel, axis=1)
            valid = jnp.isfinite(skeys)
            ids = jnp.where(valid, sel.astype(jnp.int32), -1)
            sims = jnp.where(valid,
                             -skeys if metric.is_similarity() else skeys,
                             0.0)
            stats = {"probes": jnp.zeros((m,), jnp.int32),
                     "distance_evals": _flat_evals(qvalid, m, n)}
        elif opts.use_pallas:  # brute (compiled top-k; LingoDB-V-like)
            ids, sims, valid, stats = _flat_topk_batch(
                opts, arrays, metric, corpus, qs, k, rm, qvalid=qvalid)
        else:
            flat = FlatIndex(metric, corpus)
            if rm is None:
                ids, sims, valid = jax.vmap(
                    lambda q: flat.topk(q, k, None))(qs)
            else:
                ids, sims, valid = jax.vmap(
                    lambda q, r: flat.topk(q, k, r))(qs, rm)
            if qvalid is not None:
                valid = valid & qvalid[:, None]
                ids = jnp.where(valid, ids, -1)
                sims = jnp.where(valid, sims, 0.0)
            stats = {"probes": jnp.zeros((m,), jnp.int32),
                     "distance_evals": _flat_evals(qvalid, m, n)}
        if live:
            ids, sims, valid, stats = _merge_delta_topk(
                opts, metric, arrays, qs, k, dmask, qvalid,
                ids, sims, valid, stats)
        return ids, sims, valid, stats

    return core


def build_knn_join(a: Analysis, catalog: Catalog, opts: EngineOptions,
                   binds_static: Bindings) -> Callable:
    """Q4 (entity-centric KNN join): per-left top-k as one query batch."""
    if opts.join_lowering == "perleft":
        return _build_knn_join_perleft(a, catalog, opts, binds_static)
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    k = _static_int(a.k, binds_static, "K")
    mask_b = _join_mask_batch_fn(a.join_predicate, ltab, rtab, a.left_alias,
                                 a.right_alias)
    core = _knn_join_core(a, catalog, opts, k)

    def fn(arrays, binds):
        lvec = arrays["left"]                                  # (L, d)
        nleft = lvec.shape[0]
        rm = mask_b(binds) if mask_b else None                 # (L, N)
        ids, sims, valid, stats = core(arrays, lvec, rm)
        ranks = jnp.broadcast_to(jnp.arange(1, k + 1, dtype=jnp.int32)[None],
                                 ids.shape)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[:, None], ids.shape),
                "tid": ids, "sim": sims, "valid": valid, "rank": ranks,
                "stats": stats}

    return fn


def build_knn_join_batch(a: Analysis, catalog: Catalog, opts: EngineOptions,
                         binds_static: Bindings) -> Callable:
    """Q bind sets x L left rows, flattened into ONE kernel query batch."""
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    k = _static_int(a.k, binds_static, "K")
    mask_b = _join_mask_batch_fn(a.join_predicate, ltab, rtab, a.left_alias,
                                 a.right_alias)
    live = catalog.live_for(a.right_table, a.right_vector) is not None
    core = _knn_join_core(a, catalog, opts, k)

    def fn(arrays, binds, qvalid=None, probe_budget=None):
        if live:
            qn, nleft, qs, _ = _flatten_left_batch(arrays["left"], binds,
                                                   None)
            rm, dmask = _live_join_masks(a.join_predicate, ltab, rtab,
                                         a.left_alias, a.right_alias,
                                         arrays, binds, qn, nleft)
        else:
            qn, nleft, qs, rm = _flatten_left_batch(arrays["left"], binds,
                                                    mask_b)
            dmask = None
        fq, fb = _flatten_valid_budget(qvalid, probe_budget, qn, nleft)
        ids, sims, valid, stats = core(arrays, qs, rm, qvalid=fq,
                                       probe_budget=fb, dmask=dmask)
        shape = (qn, nleft, k)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[None, :, None], shape),
                "tid": ids.reshape(shape), "sim": sims.reshape(shape),
                "valid": valid.reshape(shape),
                "rank": jnp.broadcast_to(
                    jnp.arange(1, k + 1, dtype=jnp.int32)[None, None], shape),
                "stats": jax.tree.map(lambda v: v.reshape(qn, nleft), stats)}

    return fn


def _build_knn_join_perleft(a: Analysis, catalog: Catalog,
                            opts: EngineOptions,
                            binds_static: Bindings) -> Callable:
    """Legacy lowering: one scan/probe per left row (vmapped matvecs)."""
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    k = _static_int(a.k, binds_static, "K")
    pair_mask = _join_mask_fn(a.join_predicate, ltab, rtab, a.left_alias,
                              a.right_alias)
    index = catalog.index_for(a.right_table, a.right_vector)
    cfg = opts.probe

    def fn(arrays, binds):
        lvec = arrays["left"]
        corpus = arrays["corpus"]
        nleft = lvec.shape[0]

        def per_left(i):
            q = lvec[i]
            rm = pair_mask(i, binds) if pair_mask else None
            if opts.engine == "chase" and index is not None:
                # R2: ANN top-k per left row — the 7500x path
                idx = arrays["index"]
                ids, sims, valid, stats = ivf_topk(idx, corpus, q, k, rm, cfg)
            elif opts.engine == "brute_sort":
                # Fig. 5a plan: window sorts the WHOLE partition (|B| log |B|)
                raw = distance_values(metric, corpus, q)
                keys = order_key(metric, raw)
                if rm is not None:
                    keys = jnp.where(rm, keys, jnp.inf)
                perm = jnp.argsort(keys)               # full sort, on purpose
                sel = perm[:k]
                skeys = keys[perm[:k]]
                valid = jnp.isfinite(skeys)
                ids = jnp.where(valid, sel.astype(jnp.int32), -1)
                sims = jnp.where(valid,
                                 -skeys if metric.is_similarity() else skeys,
                                 0.0)
                stats = {"probes": jnp.int32(0),
                         "distance_evals": jnp.int32(corpus.shape[0])}
            else:  # brute (compiled top-k; LingoDB-V-like)
                flat = FlatIndex(metric, corpus)
                ids, sims, valid = _flat_topk(opts, flat, q, k, rm)
                stats = {"probes": jnp.int32(0),
                         "distance_evals": jnp.int32(corpus.shape[0])}
            return ids, sims, valid, stats

        ids, sims, valid, stats = jax.vmap(per_left)(
            jnp.arange(nleft, dtype=jnp.int32))
        ranks = jnp.broadcast_to(jnp.arange(1, k + 1, dtype=jnp.int32)[None],
                                 ids.shape)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[:, None], ids.shape),
                "tid": ids, "sim": sims, "valid": valid, "rank": ranks,
                "stats": stats}

    return fn


# ---------------------------------------------------------------------------
# Q5 / Q6 — category-driven
# ---------------------------------------------------------------------------

def _rank_per_category(metric: Metric, ids, keys, valid, cats, C: int, K: int):
    """Buffer -> per-category top-K (the window operator over probe output).
    Consumes the scan's similarity via `keys` — map-operator contract."""
    def per_cat(c):
        m = valid & (cats == c)
        return masked_topk(keys, ids, m, K)

    ck, cids, cvalid = jax.vmap(per_cat)(jnp.arange(C, dtype=jnp.int32))
    sims = jnp.where(cvalid, -ck if metric.is_similarity() else ck, 0.0)
    return cids, sims, cvalid


def _rank_per_category_batch(metric: Metric, ids, keys, valid, cats,
                             C: int, K: int):
    """Vectorized window rank: (M, P) probe buffers -> (M, C, K) results.

    One (M, C, P) masked top-k over the whole batch — the category ranking
    runs for every left row / bind set at once instead of per query."""
    return jax.vmap(lambda i, k2, v, c: _rank_per_category(
        metric, i, k2, v, c, C, K))(ids, keys, valid, cats)


def _category_core(opts: EngineOptions, metric: Metric, index,
                   C: int, k: int, vbase_extra_evals: bool,
                   n_rows: int = 0, per_query_mask: bool = True,
                   live: bool = False, cat_col: str | None = None):
    """(arrays, qs (M,d), radius, rm (M,N)|None) -> (M, C, K) ranked batch.

    Shared by the Q5 bind-batch lowering and the Q6 left-row batch: probe a
    (M, d) query batch (Algorithm 2's record table batched when updateState
    applies), then run the window rank for all M queries at once.
    ``n_rows`` (the scanned table's row count) sizes the sharded range
    buffer when ``opts.dist`` selects the distributed lowering.  Under
    ``live``, the delta segment is merged in LOSSLESSLY (main + delta
    buffer widths) before the window rank, and merged ids >= cap_main read
    their category from the live delta columns (``cat_col``)."""
    cfg = dataclasses.replace(opts.probe, num_categories=C, k_per_category=k)
    use_update_state = opts.engine == "chase"
    sharded = (_dist_range_core(opts, metric, cfg.capacity, n_rows,
                                per_query_mask=per_query_mask)
               if opts.dist is not None else None)

    def core(arrays, qs, radius, rm, qvalid=None, probe_budget=None,
             dmask=None):
        corpus = arrays["corpus"]
        cats = arrays["categories"]
        m = qs.shape[0]
        radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (m,))
        if sharded is not None:
            ids, sims, valid, count, stats = sharded(arrays, qs, radius,
                                                     _as_per_query(rm, m),
                                                     qvalid)
        elif index is not None and opts.engine in ("chase", "vbase",
                                                   "chase_no_updatestate"):
            idx = arrays["index"]
            rm = _as_per_query(rm, m)
            if use_update_state:
                ids, sims, valid, count, stats = ivf_range_category_batch(
                    idx, corpus, cats, qs, radius, rm, cfg,
                    probe_budget=probe_budget, qvalid=qvalid)
            else:
                ids, sims, valid, count, stats = ivf_range_batch(
                    idx, corpus, qs, radius, rm, cfg,
                    probe_budget=probe_budget, qvalid=qvalid)
            if opts.engine == "vbase":
                safe = jnp.maximum(ids, 0)
                raw = distance_values(metric, corpus[safe],
                                      qs[:, None, :])          # REDUNDANT
                sims = jnp.where(valid, raw, 0.0)
                if vbase_extra_evals:
                    extra = (cfg.capacity if qvalid is None
                             else jnp.where(qvalid, cfg.capacity, 0))
                    stats = dict(stats)
                    stats["distance_evals"] = stats["distance_evals"] + extra
        else:
            ids, sims, valid, count, stats = _flat_range_topk_batch(
                opts, metric, corpus, qs, radius, rm, cfg.capacity,
                qvalid=qvalid, arrays=arrays)
        if live:
            # lossless merge width (main + delta buffers): the window rank
            # below consumes the WHOLE buffer, so truncating here would
            # drop per-category candidates the frozen plan would keep
            dcap = arrays["live_delta_vec"].shape[0]
            ids, sims, valid, count, stats = _merge_delta_range(
                opts, metric, arrays, qs, radius, ids.shape[1] + dcap,
                dmask, qvalid, ids, sims, valid, count, stats)
            n = corpus.shape[0]
            dcats = arrays["live_dcols"][cat_col]
            bcats = jnp.where(
                valid,
                jnp.where(ids < n, cats[jnp.clip(ids, 0, n - 1)],
                          dcats[jnp.clip(ids - n, 0, dcap - 1)]),
                -1)
        else:
            bcats = jnp.where(valid, cats[jnp.maximum(ids, 0)], -1)
        keys = jnp.where(valid, order_key(metric, sims), jnp.inf)
        cids, csims, cvalid = _rank_per_category_batch(
            metric, ids, keys, valid, bcats, C, k)
        return cids, csims, cvalid, stats

    return core


def build_category_partition(a: Analysis, catalog: Catalog,
                             opts: EngineOptions,
                             binds_static: Bindings) -> Callable:
    """Q5 (category-driven, single table): range probe + per-category rank
    (updateState early stop under the chase engine)."""
    table = catalog.table(a.table)
    metric = _metric_of(catalog, a.table, a.vector_column)
    k = _static_int(a.k, binds_static, "K")
    cat_col = a.category_column.name
    C = table.schema[cat_col].num_categories
    assert C, f"category column {cat_col} needs num_categories"
    mask_fn = _row_mask_fn(a.structured_predicate, table)
    qparam = a.query_expr
    index = catalog.index_for(a.table, a.vector_column)
    cfg = dataclasses.replace(opts.probe, num_categories=C, k_per_category=k)
    radius_expr = a.radius
    use_update_state = opts.engine == "chase"

    def fn(arrays, binds):
        corpus = arrays["corpus"]
        cats = arrays["categories"]
        q = jnp.asarray(binds[qparam.name])
        radius = evaluate(radius_expr, table, binds)
        row_mask = mask_fn(binds) if mask_fn else None
        if index is not None and opts.engine in ("chase", "vbase",
                                                 "chase_no_updatestate"):
            idx = arrays["index"]
            if use_update_state:
                ids, sims, valid, count, stats = ivf_range_category(
                    idx, corpus, cats, q, radius, row_mask, cfg)
            else:
                ids, sims, valid, count, stats = ivf_range(
                    idx, corpus, q, radius, row_mask, cfg)
            if opts.engine == "vbase":
                safe = jnp.maximum(ids, 0)
                raw = distance_values(metric, corpus[safe], q)  # REDUNDANT
                sims = jnp.where(valid, raw, 0.0)
                stats = dict(stats)
                stats["distance_evals"] = stats["distance_evals"] + cfg.capacity
        else:
            flat = FlatIndex(metric, corpus)
            hit, raw = flat.range_mask(q, radius, row_mask)
            keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
            neg, sel = jax.lax.top_k(-keys, cfg.capacity)
            valid = jnp.isfinite(-neg)
            ids = jnp.where(valid, sel.astype(jnp.int32), -1)
            sims = jnp.where(valid, raw[sel], 0.0)
            stats = {"probes": jnp.int32(0),
                     "distance_evals": jnp.int32(corpus.shape[0])}
        keys = jnp.where(valid, order_key(metric, sims), jnp.inf)
        bcats = jnp.where(valid, cats[jnp.maximum(ids, 0)], -1)
        cids, csims, cvalid = _rank_per_category(metric, ids, keys, valid,
                                                 bcats, C, k)
        return {"ids": cids, "sim": csims, "valid": cvalid,
                "category": jnp.broadcast_to(
                    jnp.arange(C, dtype=jnp.int32)[:, None], cids.shape),
                "stats": stats}

    return fn


def build_category_partition_batch(a: Analysis, catalog: Catalog,
                                   opts: EngineOptions,
                                   binds_static: Bindings) -> Callable:
    """Q5 over Q bind sets: one batched category probe + one window rank."""
    table = catalog.table(a.table)
    metric = _metric_of(catalog, a.table, a.vector_column)
    k = _static_int(a.k, binds_static, "K")
    cat_col = a.category_column.name
    C = table.schema[cat_col].num_categories
    assert C, f"category column {cat_col} needs num_categories"
    mask_fn = _row_mask_fn(a.structured_predicate, table)
    qparam = a.query_expr
    index = catalog.index_for(a.table, a.vector_column)
    live = catalog.live_for(a.table, a.vector_column) is not None
    core = _category_core(opts, metric, index, C, k, vbase_extra_evals=True,
                          n_rows=table.num_rows,
                          per_query_mask=mask_fn is not None or live,
                          live=live, cat_col=cat_col)
    radius_expr = a.radius

    def fn(arrays, binds, qvalid=None, probe_budget=None):
        qs = jnp.asarray(binds[qparam.name])                      # (Q, D)
        qn = qs.shape[0]
        radius = jnp.broadcast_to(
            jax.vmap(lambda b: evaluate(radius_expr, table, b))(binds), (qn,))
        dmask = None
        if live:
            row_mask, dmask = _live_scan_masks(a.structured_predicate,
                                               arrays, binds, qn)
        else:
            row_mask = jax.vmap(mask_fn)(binds) if mask_fn else None  # (Q, N)
        cids, csims, cvalid, stats = core(arrays, qs, radius, row_mask,
                                          qvalid=qvalid,
                                          probe_budget=probe_budget,
                                          dmask=dmask)
        return {"ids": cids, "sim": csims, "valid": cvalid,
                "category": jnp.broadcast_to(
                    jnp.arange(C, dtype=jnp.int32)[None, :, None],
                    cids.shape),
                "stats": stats}

    return fn


def build_category_join(a: Analysis, catalog: Catalog, opts: EngineOptions,
                        binds_static: Bindings) -> Callable:
    """Q6 (category-driven join): Q5's probe+rank per left row, batched."""
    if opts.join_lowering == "perleft":
        return _build_category_join_perleft(a, catalog, opts, binds_static)
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    k = _static_int(a.k, binds_static, "K")
    cat_col = a.category_column.name
    C = rtab.schema[cat_col].num_categories
    assert C, f"category column {cat_col} needs num_categories"
    mask_b = _join_mask_batch_fn(a.join_predicate, ltab, rtab, a.left_alias,
                                 a.right_alias)
    index = catalog.index_for(a.right_table, a.right_vector)
    # legacy-parity quirk: the per-left Q6 vbase plan never counted its
    # redundant re-sort evals — keep counters identical across lowerings
    core = _category_core(opts, metric, index, C, k, vbase_extra_evals=False,
                          n_rows=rtab.num_rows,
                          per_query_mask=a.join_predicate is not None)
    radius_expr = a.radius

    def fn(arrays, binds):
        lvec = arrays["left"]                                  # (L, d)
        nleft = lvec.shape[0]
        radius = evaluate(radius_expr, rtab, binds)
        rm = mask_b(binds) if mask_b else None                 # (L, N)
        cids, csims, cvalid, stats = core(arrays, lvec, radius, rm)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[:, None, None],
                    cids.shape),
                "tid": cids, "sim": csims, "valid": cvalid,
                "category": jnp.broadcast_to(
                    jnp.arange(C, dtype=jnp.int32)[None, :, None],
                    cids.shape),
                "stats": stats}

    return fn


def build_category_join_batch(a: Analysis, catalog: Catalog,
                              opts: EngineOptions,
                              binds_static: Bindings) -> Callable:
    """Q bind sets x L left rows, flattened into ONE kernel query batch."""
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    k = _static_int(a.k, binds_static, "K")
    cat_col = a.category_column.name
    C = rtab.schema[cat_col].num_categories
    assert C, f"category column {cat_col} needs num_categories"
    mask_b = _join_mask_batch_fn(a.join_predicate, ltab, rtab, a.left_alias,
                                 a.right_alias)
    index = catalog.index_for(a.right_table, a.right_vector)
    live = catalog.live_for(a.right_table, a.right_vector) is not None
    core = _category_core(opts, metric, index, C, k, vbase_extra_evals=False,
                          n_rows=rtab.num_rows,
                          per_query_mask=(a.join_predicate is not None
                                          or live),
                          live=live, cat_col=cat_col)
    radius_expr = a.radius

    def fn(arrays, binds, qvalid=None, probe_budget=None):
        if live:
            qn, nleft, qs, _ = _flatten_left_batch(arrays["left"], binds,
                                                   None)
            rm, dmask = _live_join_masks(a.join_predicate, ltab, rtab,
                                         a.left_alias, a.right_alias,
                                         arrays, binds, qn, nleft)
        else:
            qn, nleft, qs, rm = _flatten_left_batch(arrays["left"], binds,
                                                    mask_b)
            dmask = None
        fq, fb = _flatten_valid_budget(qvalid, probe_budget, qn, nleft)
        radius = jnp.broadcast_to(
            jax.vmap(lambda b: evaluate(radius_expr, rtab, b))(binds), (qn,))
        cids, csims, cvalid, stats = core(
            arrays, qs, jnp.repeat(radius, nleft), rm, qvalid=fq,
            probe_budget=fb, dmask=dmask)
        shape = (qn, nleft, C, k)
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[None, :, None, None],
                    shape),
                "tid": cids.reshape(shape), "sim": csims.reshape(shape),
                "valid": cvalid.reshape(shape),
                "category": jnp.broadcast_to(
                    jnp.arange(C, dtype=jnp.int32)[None, None, :, None],
                    shape),
                "stats": jax.tree.map(lambda v: v.reshape(qn, nleft), stats)}

    return fn


def _build_category_join_perleft(a: Analysis, catalog: Catalog,
                                 opts: EngineOptions,
                                 binds_static: Bindings) -> Callable:
    """Legacy lowering: one category probe per left row (vmapped matvecs)."""
    ltab, rtab = catalog.table(a.left_table), catalog.table(a.right_table)
    metric = _metric_of(catalog, a.right_table, a.right_vector)
    k = _static_int(a.k, binds_static, "K")
    cat_col = a.category_column.name
    C = rtab.schema[cat_col].num_categories
    assert C, f"category column {cat_col} needs num_categories"
    pair_mask = _join_mask_fn(a.join_predicate, ltab, rtab, a.left_alias,
                              a.right_alias)
    index = catalog.index_for(a.right_table, a.right_vector)
    cfg = dataclasses.replace(opts.probe, num_categories=C, k_per_category=k)
    radius_expr = a.radius
    use_update_state = opts.engine == "chase"

    def fn(arrays, binds):
        lvec = arrays["left"]
        corpus = arrays["corpus"]
        cats = arrays["categories"]
        radius = evaluate(radius_expr, rtab, binds)
        nleft = lvec.shape[0]

        def per_left(i):
            q = lvec[i]
            rm = pair_mask(i, binds) if pair_mask else None
            if index is not None and opts.engine in ("chase", "vbase",
                                                     "chase_no_updatestate"):
                idx = arrays["index"]
                if use_update_state:
                    ids, sims, valid, count, stats = ivf_range_category(
                        idx, corpus, cats, q, radius, rm, cfg)
                else:
                    ids, sims, valid, count, stats = ivf_range(
                        idx, corpus, q, radius, rm, cfg)
                if opts.engine == "vbase":
                    safe = jnp.maximum(ids, 0)
                    raw = distance_values(metric, corpus[safe], q)  # REDUNDANT
                    sims = jnp.where(valid, raw, 0.0)
            else:
                flat = FlatIndex(metric, corpus)
                hit, raw = flat.range_mask(q, radius, rm)
                keys = jnp.where(hit, order_key(metric, raw), jnp.inf)
                neg, sel = jax.lax.top_k(-keys, cfg.capacity)
                valid = jnp.isfinite(-neg)
                ids = jnp.where(valid, sel.astype(jnp.int32), -1)
                sims = jnp.where(valid, raw[sel], 0.0)
                stats = {"probes": jnp.int32(0),
                         "distance_evals": jnp.int32(corpus.shape[0])}
            keys = jnp.where(valid, order_key(metric, sims), jnp.inf)
            bcats = jnp.where(valid, cats[jnp.maximum(ids, 0)], -1)
            cids, csims, cvalid = _rank_per_category(metric, ids, keys, valid,
                                                     bcats, C, k)
            return cids, csims, cvalid, stats

        cids, csims, cvalid, stats = jax.vmap(per_left)(
            jnp.arange(nleft, dtype=jnp.int32))
        return {"qid": jnp.broadcast_to(
                    jnp.arange(nleft, dtype=jnp.int32)[:, None, None],
                    cids.shape),
                "tid": cids, "sim": csims, "valid": cvalid,
                "category": jnp.broadcast_to(
                    jnp.arange(C, dtype=jnp.int32)[None, :, None], cids.shape),
                "stats": stats}

    return fn


# ---------------------------------------------------------------------------
# Batched execution path — parameter-only batches (same plan, Q bind vectors)
# ---------------------------------------------------------------------------
#
# Batch builders receive ``binds`` whose every value carries a leading Q axis
# (the compiler stacks/broadcasts them) and lower onto the NATIVE batched
# operators: the query-tiled Pallas scans and the multi-cluster IVF probes.
# Structured predicates evaluate per query via vmap, producing a (Q, N) mask
# the fused kernels consume directly.  Query classes without a native batched
# builder fall back to a vmap of their single-query pipeline in the compiler.

def build_vknn_sf_batch(a: Analysis, catalog: Catalog, opts: EngineOptions,
                        binds_static: Bindings) -> Callable:
    """Q1 batched: Q bind sets on the query-tiled kernels / batched probes
    (uniform batch_fn signature — see :class:`CompiledPlan`)."""
    table = catalog.table(a.table)
    metric = _metric_of(catalog, a.table, a.vector_column)
    k = _static_int(a.k, binds_static, "K")
    mask_fn = _row_mask_fn(a.structured_predicate, table)
    qparam = a.query_expr
    assert isinstance(qparam, Param), "VKNN-SF query must be a parameter"
    index = catalog.index_for(a.table, a.vector_column)
    cfg = opts.probe
    live = catalog.live_for(a.table, a.vector_column) is not None
    dist = (_dist_topk_core(opts, metric, k,
                            per_query_mask=mask_fn is not None or live)
            if opts.dist is not None else None)
    # the fused flat lane (brute engine, or no index) names its mask step
    flat_lane = (dist is None and opts.use_pallas and not (
        opts.engine in ("chase", "vbase", "pase") and index is not None))
    mask_scope = (functools.partial(jax.named_scope, "chase.flat.mask")
                  if flat_lane else contextlib.nullcontext)

    def fn(arrays, binds, qvalid=None, probe_budget=None):
        corpus = arrays["corpus"]
        n = corpus.shape[0]
        qs = jnp.asarray(binds[qparam.name])                     # (Q, D)
        qn = qs.shape[0]
        dmask = None
        with mask_scope():
            if live:
                row_mask, dmask = _live_scan_masks(a.structured_predicate,
                                                   arrays, binds, qn)
            else:                                               # (Q, N)
                row_mask = jax.vmap(mask_fn)(binds) if mask_fn else None
        if dist is not None:
            ids, sims, valid, stats = dist(arrays, qs,
                                           _as_per_query(row_mask, qn),
                                           qvalid)
        elif opts.engine == "chase" and index is not None:
            idx: IVFIndex = arrays["index"]
            ids, sims, valid, stats = ivf_topk_batch(
                idx, corpus, qs, k, _as_per_query(row_mask, qn), cfg,
                probe_budget=probe_budget, qvalid=qvalid)
        elif opts.engine == "vbase" and index is not None:
            idx = arrays["index"]
            ids, _sims, valid, stats = ivf_topk_batch(
                idx, corpus, qs, k, _as_per_query(row_mask, qn), cfg,
                probe_budget=probe_budget, qvalid=qvalid)
            ids, sims, valid = jax.vmap(
                lambda q, i, v: _resort_redundant(metric, corpus, q, i, v, k)
            )(qs, ids, valid)
            extra = k if qvalid is None else jnp.where(qvalid, k, 0)
            stats = dict(stats)
            stats["distance_evals"] = stats["distance_evals"] + extra
        elif opts.engine == "pase" and index is not None:
            idx = arrays["index"]
            kk = min(opts.pase_oversample * k, n)
            ids_o, sims_o, valid_o, stats = ivf_topk_batch(
                idx, corpus, qs, kk, None, cfg,
                probe_budget=probe_budget, qvalid=qvalid)

            def post(ids_q, sims_q, valid_q, rm_q):
                if rm_q is not None:
                    valid_q = valid_q & jnp.where(
                        ids_q >= 0, rm_q[jnp.maximum(ids_q, 0)], False)
                keep = jnp.cumsum(valid_q) <= k
                valid_q = valid_q & keep
                keys = jnp.where(valid_q, order_key(metric, sims_q), jnp.inf)
                neg, sel = jax.lax.top_k(-keys, k)
                v = jnp.isfinite(-neg)
                return (jnp.where(v, ids_q[sel], -1),
                        jnp.where(v, sims_q[sel], 0.0), v)

            if row_mask is None:
                ids, sims, valid = jax.vmap(
                    lambda i, s, v: post(i, s, v, None))(ids_o, sims_o,
                                                         valid_o)
            else:
                ids, sims, valid = jax.vmap(post)(
                    ids_o, sims_o, valid_o, _as_per_query(row_mask, qn))
        elif opts.use_pallas:  # brute (LingoDB-V analogue) or missing index
            ids, sims, valid, stats = _flat_topk_batch(
                opts, arrays, metric, corpus, qs, k, row_mask, qvalid=qvalid)
        else:
            flat = FlatIndex(metric, corpus)
            if row_mask is None:
                ids, sims, valid = jax.vmap(
                    lambda q: flat.topk(q, k, None))(qs)
            elif row_mask.ndim == 1:                # shared live validity lane
                ids, sims, valid = jax.vmap(
                    lambda q: flat.topk(q, k, row_mask))(qs)
            else:
                ids, sims, valid = jax.vmap(
                    lambda q, rm: flat.topk(q, k, rm))(qs, row_mask)
            if qvalid is not None:
                valid = valid & qvalid[:, None]
                ids = jnp.where(valid, ids, -1)
                sims = jnp.where(valid, sims, 0.0)
            stats = {"probes": jnp.zeros((qn,), jnp.int32),
                     "distance_evals": _flat_evals(qvalid, qn, n)}
        if live:
            ids, sims, valid, stats = _merge_delta_topk(
                opts, metric, arrays, qs, k, dmask, qvalid,
                ids, sims, valid, stats)
        return {"ids": ids, "sim": sims, "valid": valid, "stats": stats}

    return fn


def build_dr_sf_batch(a: Analysis, catalog: Catalog, opts: EngineOptions,
                      binds_static: Bindings) -> Callable:
    """Q2 batched: Q bind sets on the batched range kernels / probes."""
    table = catalog.table(a.table)
    metric = _metric_of(catalog, a.table, a.vector_column)
    mask_fn = _row_mask_fn(a.structured_predicate, table)
    qparam = a.query_expr
    index = catalog.index_for(a.table, a.vector_column)
    cfg = opts.probe
    radius_expr = a.radius
    live = catalog.live_for(a.table, a.vector_column) is not None
    dist = (_dist_range_core(opts, metric, cfg.capacity, table.num_rows,
                             per_query_mask=mask_fn is not None or live)
            if opts.dist is not None else None)

    def radius_of(binds):
        return evaluate(radius_expr, table, binds)

    def fn(arrays, binds, qvalid=None, probe_budget=None):
        corpus = arrays["corpus"]
        n = corpus.shape[0]
        qs = jnp.asarray(binds[qparam.name])                      # (Q, D)
        qn = qs.shape[0]
        radius = jnp.broadcast_to(jax.vmap(radius_of)(binds), (qn,))
        dmask = None
        if live:
            row_mask, dmask = _live_scan_masks(a.structured_predicate,
                                               arrays, binds, qn)
        else:
            row_mask = jax.vmap(mask_fn)(binds) if mask_fn else None  # (Q, N)
        if dist is not None:
            ids, sims, valid, count, stats = dist(arrays, qs, radius,
                                                  _as_per_query(row_mask, qn),
                                                  qvalid)
        elif opts.engine == "chase" and index is not None:
            idx = arrays["index"]
            ids, sims, valid, count, stats = ivf_range_batch(
                idx, corpus, qs, radius, _as_per_query(row_mask, qn), cfg,
                probe_budget=probe_budget, qvalid=qvalid)
        elif opts.engine == "vbase" and index is not None:
            idx = arrays["index"]
            ids, _sims, valid, count, stats = ivf_range_batch(
                idx, corpus, qs, radius, None, cfg,
                probe_budget=probe_budget, qvalid=qvalid)

            def post(q, ids_q, valid_q, r_q, rm_q):
                safe = jnp.maximum(ids_q, 0)
                raw = distance_values(metric, corpus[safe], q)    # REDUNDANT
                v = valid_q & in_range(metric, raw, r_q)
                if rm_q is not None:
                    v = v & rm_q[safe]
                return jnp.where(v, raw, 0.0), v

            if row_mask is None:
                sims, valid = jax.vmap(
                    lambda q, i, v, r: post(q, i, v, r, None))(
                        qs, ids, valid, radius)
            else:
                sims, valid = jax.vmap(post)(qs, ids, valid, radius,
                                             _as_per_query(row_mask, qn))
            count = jnp.sum(valid, axis=1)
            extra = (cfg.capacity if qvalid is None
                     else jnp.where(qvalid, cfg.capacity, 0))
            stats = dict(stats)
            stats["distance_evals"] = stats["distance_evals"] + extra
        else:
            # PASE/pgvector cannot route range queries to the ANN index (§2.3)
            ids, sims, valid, count, stats = _flat_range_topk_batch(
                opts, metric, corpus, qs, radius, row_mask, cfg.capacity,
                qvalid=qvalid, arrays=arrays)
        if live:
            ids, sims, valid, count, stats = _merge_delta_range(
                opts, metric, arrays, qs, radius, cfg.capacity, dmask,
                qvalid, ids, sims, valid, count, stats)
        return {"ids": ids, "sim": sims, "valid": valid, "count": count,
                "stats": stats}

    return fn


BUILDERS = {
    QueryClass.VKNN_SF: build_vknn_sf,
    QueryClass.DR_SF: build_dr_sf,
    QueryClass.DIST_JOIN: build_dist_join,
    QueryClass.KNN_JOIN: build_knn_join,
    QueryClass.CATEGORY_PARTITION: build_category_partition,
    QueryClass.CATEGORY_JOIN: build_category_join,
}

# Every hybrid class now has a NATIVE batched lowering.  Join families
# flatten (bind sets x left rows) into one kernel-level query batch; the
# vmap-of-scalar fallback remains only for join_lowering='perleft'
# (core/compiler.py gates it — the measured baseline).
BATCH_BUILDERS = {
    QueryClass.VKNN_SF: build_vknn_sf_batch,
    QueryClass.DR_SF: build_dr_sf_batch,
    QueryClass.DIST_JOIN: build_dist_join_batch,
    QueryClass.KNN_JOIN: build_knn_join_batch,
    QueryClass.CATEGORY_PARTITION: build_category_partition_batch,
    QueryClass.CATEGORY_JOIN: build_category_join_batch,
}

# the join classes whose lowering obeys opts.join_lowering: 'perleft' swaps
# their single-call builder for the legacy loop AND forces the vmap
# execute_batch fallback.  Q5 (CATEGORY_PARTITION) has no per-left loop, so
# the flag never touches it — its bind-batch builder is always native.
JOIN_LOWERING_FAMILIES = frozenset({
    QueryClass.DIST_JOIN, QueryClass.KNN_JOIN, QueryClass.CATEGORY_JOIN,
})
