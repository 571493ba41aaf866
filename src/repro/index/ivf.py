"""IVF index: the TPU-native adaptation of CHASE's ANN layer.

HNSW (the paper's index) is a pointer-chasing graph walk — hostile to the MXU.
IVF preserves the property the paper's algorithms actually rely on —
*monotone outward expansion from the query's neighborhood* — while turning
every step into dense batched compute:

* probe order   = ascending centroid order-key (a `Q·Cᵀ` matmul + argsort),
* cluster scan  = padded gather + blocked distance matmul + predicate mask,
* Algorithm 1's per-tuple ``outRangeCounter`` becomes a per-*cluster* counter
  inside a ``jax.lax.while_loop`` (§DESIGN.md 2),
* Algorithm 2's hash record-table becomes dense per-category state arrays.

Beyond-paper addition: each cluster stores its radius (max member-centroid
distance), giving a *sound lower bound* on any unprobed member's order key.
``termination='bound'`` uses it for exact early termination (the paper's R2
shrinkage made provable); ``termination='counter'`` is the faithful heuristic.

All probes return raw similarity values alongside ids — the physical layer's
contract with the **map operator** (§5.1): similarity computed during the scan
is *never* recomputed downstream.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..core.expr import distance_values, order_key
from ..core.schema import Metric
from .kmeans import assign, kmeans

INF = jnp.float32(jnp.inf)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["centroids", "lists", "list_sizes", "radii", "centroid_sq"],
    meta_fields=["metric", "nlist", "cap"],
)
@dataclasses.dataclass
class IVFIndex:
    """Inverted-file index: k-means centroids with fixed-capacity member
    lists (-1 padded) plus per-list radii for the geometric probe-pruning
    bound.  A pytree — probe kernels trace over the arrays."""
    metric: Metric
    centroids: jnp.ndarray     # (nlist, d)
    lists: jnp.ndarray         # (nlist, cap) int32 row ids, -1 padded
    list_sizes: jnp.ndarray    # (nlist,) int32
    radii: jnp.ndarray         # (nlist,) max ||member - centroid||
    centroid_sq: jnp.ndarray   # (nlist,) ||c||^2 (L2 fast path)
    nlist: int
    cap: int


@dataclasses.dataclass(frozen=True)
class ProbeConfig:
    """Static probe parameters (the engine's physical-operator knobs)."""
    max_probes: int = 64            # hard cap on clusters visited
    min_probes: int = 4             # converge-first phase (Alg.1 lines 2-3)
    stop_after_no_improve: int = 4  # top-k adaptive-queue stop (VBASE analogue)
    out_range_stop: int = 2         # Alg.1 `IsAboveN` N, cluster-granular
    capacity: int = 4096            # range-probe result buffer
    termination: str = "counter"    # 'counter' (faithful) | 'bound' (exact)
    probe_batch: int = 1            # clusters gathered per while_loop round
    no_new_category_stop: int = 2   # Alg.2: clusters w/o new category
    num_categories: int = 0         # static category cardinality (Alg.2)
    k_per_category: int = 10        # Alg.2 K
    # per-query cluster budget for the BATCHED probes (0 = unlimited): the
    # user-facing straggler valve — a query that exhausts its budget freezes
    # with its best-so-far results instead of holding the lock-step batch
    # hostage.  A runtime ``probe_budget`` argument (scalar or (Q,)) overrides
    # this static default per call.
    probe_budget: int = 0


def build_ivf(key: jax.Array, vectors: jnp.ndarray, nlist: int,
              metric: Metric = Metric.INNER_PRODUCT, iters: int = 8,
              cap_multiple: int = 4, cap: int | None = None) -> IVFIndex:
    """Train centroids, bucket rows into padded inverted lists.

    ``cap`` pins the inverted-list capacity instead of deriving it from the
    actual max cluster size.  ``cap`` (with ``nlist``/``metric``) is STATIC
    index metadata — it shapes the compiled probe loops — so live-corpus
    compaction (DESIGN.md §12) rebuilds with a fixed ``cap`` to keep
    re-bound plans at zero retraces."""
    import numpy as np
    n, d = vectors.shape
    centroids = kmeans(key, vectors, nlist, iters=iters)
    a = np.asarray(assign(vectors, centroids))
    counts = np.bincount(a, minlength=nlist)
    derived = int(counts.max())
    derived = max(8, -(-derived // 8) * 8)  # round up for lane alignment
    if cap is None:
        cap = derived
    elif cap < derived:
        raise ValueError(f"fixed cap {cap} < max cluster size "
                         f"{int(counts.max())}")
    lists = np.full((nlist, cap), -1, dtype=np.int32)
    cursor = np.zeros(nlist, dtype=np.int64)
    order = np.argsort(a, kind="stable")
    for row in order:
        c = a[row]
        lists[c, cursor[c]] = row
        cursor[c] += 1
    # cluster radii: max ||x - centroid|| per cluster
    vec_np = np.asarray(vectors, dtype=np.float32)
    cent_np = np.asarray(centroids, dtype=np.float32)
    diffs = vec_np - cent_np[a]
    norms = np.linalg.norm(diffs, axis=1)
    radii = np.zeros(nlist, dtype=np.float32)
    np.maximum.at(radii, a, norms)
    return IVFIndex(
        metric=metric,
        centroids=jnp.asarray(centroids),
        lists=jnp.asarray(lists),
        list_sizes=jnp.asarray(counts.astype(np.int32)),
        radii=jnp.asarray(radii),
        centroid_sq=jnp.sum(jnp.asarray(centroids) ** 2, axis=1),
        nlist=nlist,
        cap=cap,
    )


# ---------------------------------------------------------------------------
# shared probe plumbing
# ---------------------------------------------------------------------------

def _max_probes(index: IVFIndex, cfg: ProbeConfig) -> int:
    """Cluster cap for the sequential probes: ``max_probes`` bounded by the
    index size, tightened by the ``probe_budget`` knob when set (the same
    per-query budget semantics as the batched probes' runtime argument)."""
    cap = min(cfg.max_probes, index.nlist)
    if cfg.probe_budget > 0:
        cap = min(cap, cfg.probe_budget)
    return cap


def _cluster_order(index: IVFIndex, q: jnp.ndarray):
    """Clusters sorted by ascending centroid order-key; returns (order, keys,
    bound_keys) where bound_keys[i] lower-bounds any member of order[i]."""
    raw = distance_values(index.metric, index.centroids, q)
    keys = order_key(index.metric, raw)
    if index.metric == Metric.L2:
        # members within radius r of c: sqdist >= max(0, ||q-c|| - r)^2
        dist = jnp.sqrt(jnp.maximum(keys, 0.0))
        bound = jnp.maximum(dist - index.radii, 0.0) ** 2
    elif index.metric == Metric.INNER_PRODUCT:
        # x·q <= c·q + r*||q||  =>  key = -x·q >= -(c·q) - r||q||
        qn = jnp.linalg.norm(q)
        bound = keys - index.radii * qn
    else:  # cosine: |cos(x,q) - cos-ish bound|; use conservative -1 shift
        bound = keys - index.radii
    order = jnp.argsort(keys)
    # suffix-min of bounds: bound_sufmin[p] lower-bounds every member of every
    # cluster from probe position p onward (bounds are NOT monotone in probe
    # order, so the exact-termination test needs the suffix minimum).
    bound_sufmin = jnp.flip(jax.lax.cummin(jnp.flip(bound[order])))
    return order, keys[order], bound_sufmin


def _scan_cluster(index: IVFIndex, corpus: jnp.ndarray, q: jnp.ndarray,
                  cluster: jnp.ndarray, row_mask: jnp.ndarray | None):
    """Gather one inverted list and compute masked order-keys.

    Returns (ids (cap,), keys (cap,), valid (cap,), n_distance_evals)."""
    ids = index.lists[cluster]                       # (cap,)
    pad = ids >= 0
    safe = jnp.maximum(ids, 0)
    vecs = corpus[safe]                              # (cap, d)
    raw = distance_values(index.metric, vecs, q)
    keys = order_key(index.metric, raw)
    valid = pad
    if row_mask is not None:
        valid = valid & row_mask[safe]
    return ids, jnp.where(pad, keys, INF), valid, jnp.sum(pad)


def _merge_topk(best_keys, best_ids, cand_keys, cand_ids, cand_valid, k):
    keys = jnp.concatenate([best_keys, jnp.where(cand_valid, cand_keys, INF)])
    ids = jnp.concatenate([best_ids, cand_ids])
    neg, idx = jax.lax.top_k(-keys, k)
    return -neg, ids[idx]


# ---------------------------------------------------------------------------
# Top-k probe (VKNN-SF physical operator, §5.1)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "cfg"))
def ivf_topk(index: IVFIndex, corpus: jnp.ndarray, q: jnp.ndarray, k: int,
             row_mask: jnp.ndarray | None = None,
             cfg: ProbeConfig = ProbeConfig()):
    """Filtered top-k with the adaptive probe queue.

    VBASE's relaxed-monotonicity insight, IVF-shaped: instead of fetching a
    conservative K' ≫ K (PASE), keep extending the probe frontier until K
    *filtered* results are held AND the frontier stops improving the heap
    ('counter'), or provably cannot ('bound').  Returns
    (ids(k,), sims(k,), valid(k,), stats)."""
    order, _, bounds = _cluster_order(index, q)
    max_probes = _max_probes(index, cfg)

    def cond(state):
        p, bk, bi, no_imp, evals = state
        have_k = jnp.isfinite(bk[k - 1])
        kth = bk[k - 1]
        if cfg.termination == "bound":
            next_bound = bounds[jnp.minimum(p, index.nlist - 1)]
            done = have_k & (next_bound > kth)
        else:
            done = have_k & (no_imp >= cfg.stop_after_no_improve)
        done = done & (p >= cfg.min_probes)
        return (p < max_probes) & ~done

    def body(state):
        p, bk, bi, no_imp, evals = state
        ids, keys, valid, n = _scan_cluster(index, corpus, q, order[p], row_mask)
        old_kth = bk[k - 1]
        bk2, bi2 = _merge_topk(bk, bi, keys, ids, valid, k)
        improved = (bk2[k - 1] < old_kth) | (~jnp.isfinite(old_kth)
                                             & jnp.isfinite(bk2[k - 1]))
        no_imp2 = jnp.where(improved, 0, no_imp + 1)
        return (p + 1, bk2, bi2, no_imp2, evals + n)

    init = (jnp.int32(0), jnp.full((k,), INF), jnp.full((k,), -1, jnp.int32),
            jnp.int32(0), jnp.int32(0))
    p, bk, bi, _, evals = jax.lax.while_loop(cond, body, init)
    valid = jnp.isfinite(bk)
    sims = jnp.where(valid, -bk if index.metric.is_similarity() else bk, 0.0)
    stats = {"probes": p, "distance_evals": evals}
    return jnp.where(valid, bi, -1), sims, valid, stats


# ---------------------------------------------------------------------------
# Range probe — Algorithm 1, cluster-granular
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def ivf_range(index: IVFIndex, corpus: jnp.ndarray, q: jnp.ndarray,
              radius, row_mask: jnp.ndarray | None = None,
              cfg: ProbeConfig = ProbeConfig()):
    """DR-SF physical operator (paper Algorithm 1).

    Probes clusters by ascending centroid key; a probe round with in-range hits
    sets ``hasInRange``; after entering the range, ``out_range_stop``
    consecutive empty rounds end the scan ('counter'), or the radius-vs-bound
    test ends it exactly ('bound').  Returns (ids(capacity,), sims, valid,
    count, stats)."""
    order, _, bounds = _cluster_order(index, q)
    max_probes = _max_probes(index, cfg)
    radius_key = order_key(index.metric, jnp.asarray(radius, jnp.float32))
    capacity = cfg.capacity

    def cond(state):
        p, *_rest, has_in, out_cnt, evals = state
        if cfg.termination == "bound":
            next_bound = bounds[jnp.minimum(p, index.nlist - 1)]
            done = next_bound > radius_key
        else:
            done = has_in & (out_cnt >= cfg.out_range_stop)
        done = done & (p >= cfg.min_probes)
        return (p < max_probes) & ~done

    def body(state):
        p, out_ids, out_keys, count, has_in, out_cnt, evals = state
        ids, keys, valid, n = _scan_cluster(index, corpus, q, order[p], None)
        in_range_hit = valid & (keys <= radius_key)     # pre-filter (Alg.1's
        # hasInRange tracks the RANGE only; the structured filter must not
        # starve the termination signal at low selectivity)
        hit = in_range_hit
        if row_mask is not None:
            hit = hit & row_mask[jnp.maximum(ids, 0)]
        n_range = jnp.sum(in_range_hit)
        n_hits = jnp.sum(hit)
        # compact-append filtered hits into the fixed buffer
        pos = count + jnp.cumsum(hit) - 1
        ok = hit & (pos < capacity)
        safe_pos = jnp.where(ok, pos, capacity)        # capacity row = scratch
        out_ids = out_ids.at[safe_pos].set(jnp.where(ok, ids, -1), mode="drop")
        out_keys = out_keys.at[safe_pos].set(jnp.where(ok, keys, INF),
                                             mode="drop")
        count2 = jnp.minimum(count + n_hits, capacity)
        has_in2 = has_in | (n_range > 0)
        out_cnt2 = jnp.where(n_range > 0, 0, jnp.where(has_in, out_cnt + 1, 0))
        return (p + 1, out_ids, out_keys, count2, has_in2, out_cnt2, evals + n)

    init = (jnp.int32(0),
            jnp.full((capacity,), -1, jnp.int32),
            jnp.full((capacity,), INF),
            jnp.int32(0), jnp.bool_(False), jnp.int32(0), jnp.int32(0))
    p, out_ids, out_keys, count, _, _, evals = jax.lax.while_loop(cond, body, init)
    valid = out_ids >= 0
    sims = jnp.where(valid,
                     -out_keys if index.metric.is_similarity() else out_keys,
                     0.0)
    stats = {"probes": p, "distance_evals": evals}
    return out_ids, sims, valid, count, stats


# ---------------------------------------------------------------------------
# Category probe — Algorithm 2 (updateState) fused into the range scan
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def ivf_range_category(index: IVFIndex, corpus: jnp.ndarray,
                       categories: jnp.ndarray, q: jnp.ndarray, radius,
                       row_mask: jnp.ndarray | None = None,
                       cfg: ProbeConfig = ProbeConfig(num_categories=8)):
    """Category-driven probe: range scan + the updateState record table.

    The paper's hash table T becomes dense arrays over the static category
    universe: per-category hit counts (``filteredK_c``), a per-category best-K
    key heap (the 'search queue'), and a seen mask.  A category *converges*
    when it holds K hits whose kth key beats the probe frontier (the
    monotonicity check of Alg. 2 line 6, made sound by cluster radii under
    'bound' termination).  The scan stops early when every seen category has
    converged and ``no_new_category_stop`` rounds brought no new category —
    i.e. the dynamic R2 < R1 range shrinkage of §4.3.

    Returns (ids, sims, valid, count, stats)."""
    C = cfg.num_categories
    K = cfg.k_per_category
    assert C > 0, "category probe needs static num_categories"
    order, _, bounds = _cluster_order(index, q)
    max_probes = _max_probes(index, cfg)
    radius_key = order_key(index.metric, jnp.asarray(radius, jnp.float32))
    capacity = cfg.capacity

    def cond(state):
        (p, _oi, _ok, _cnt, has_in, out_cnt, seen, counts, kth, no_new,
         evals) = state
        frontier = bounds[jnp.minimum(p, index.nlist - 1)] \
            if cfg.termination == "bound" else radius_key
        # Alg.2: converged_c = filteredK_c >= K and queue monotonic past kth
        converged = (counts >= K) & (kth[:, K - 1] <= frontier)
        rest = jnp.sum(seen & ~converged)            # T.restElements
        cat_done = (rest == 0) & (no_new >= cfg.no_new_category_stop) \
            & jnp.any(seen)
        if cfg.termination == "bound":
            range_done = bounds[jnp.minimum(p, index.nlist - 1)] > radius_key
        else:
            range_done = has_in & (out_cnt >= cfg.out_range_stop)
        done = (cat_done | range_done) & (p >= cfg.min_probes)
        return (p < max_probes) & ~done

    def body(state):
        (p, out_ids, out_keys, count, has_in, out_cnt, seen, counts, kth,
         no_new, evals) = state
        ids, keys, valid, n = _scan_cluster(index, corpus, q, order[p], None)
        in_range_hit = valid & (keys <= radius_key)   # range only (Alg.1)
        hit = in_range_hit
        if row_mask is not None:
            hit = hit & row_mask[jnp.maximum(ids, 0)]
        n_range = jnp.sum(in_range_hit)
        n_hits = jnp.sum(hit)
        safe = jnp.maximum(ids, 0)
        cats = jnp.where(hit, categories[safe], -1)  # (cap,)

        onehot = (cats[:, None] == jnp.arange(C)[None, :])   # (cap, C)
        cat_hits = jnp.sum(onehot, axis=0)                   # (C,)
        new_seen = seen | (cat_hits > 0)
        n_new_cats = jnp.sum(new_seen) - jnp.sum(seen)
        counts2 = counts + cat_hits
        # per-category best-K merge ('search queue' update, Alg.2 line 5)
        cand = jnp.where(onehot, keys[:, None], INF)         # (cap, C)
        merged = jnp.concatenate([kth, cand.T], axis=1)      # (C, K+cap)
        kth2 = -jax.lax.top_k(-merged, K)[0]                 # smallest K keys

        pos = count + jnp.cumsum(hit) - 1
        ok = hit & (pos < capacity)
        safe_pos = jnp.where(ok, pos, capacity)
        out_ids = out_ids.at[safe_pos].set(jnp.where(ok, ids, -1), mode="drop")
        out_keys = out_keys.at[safe_pos].set(jnp.where(ok, keys, INF),
                                             mode="drop")
        count2 = jnp.minimum(count + n_hits, capacity)
        has_in2 = has_in | (n_range > 0)
        out_cnt2 = jnp.where(n_range > 0, 0,
                             jnp.where(has_in, out_cnt + 1, 0))
        no_new2 = jnp.where(n_new_cats > 0, 0, no_new + 1)
        return (p + 1, out_ids, out_keys, count2, has_in2, out_cnt2,
                new_seen, counts2, kth2, no_new2, evals + n)

    init = (jnp.int32(0),
            jnp.full((capacity,), -1, jnp.int32),
            jnp.full((capacity,), INF),
            jnp.int32(0), jnp.bool_(False), jnp.int32(0),
            jnp.zeros((C,), jnp.bool_), jnp.zeros((C,), jnp.int32),
            jnp.full((C, K), INF), jnp.int32(0), jnp.int32(0))
    (p, out_ids, out_keys, count, _hi, _oc, seen, counts, _kth, _nn,
     evals) = jax.lax.while_loop(cond, body, init)
    valid = out_ids >= 0
    sims = jnp.where(valid,
                     -out_keys if index.metric.is_similarity() else out_keys,
                     0.0)
    stats = {"probes": p, "distance_evals": evals,
             "categories_seen": jnp.sum(seen)}
    return out_ids, sims, valid, count, stats


# ---------------------------------------------------------------------------
# Batched probes — Q queries, ``probe_batch`` clusters per while_loop round
# ---------------------------------------------------------------------------
#
# The per-query loop above gathers ONE inverted list per round: a (cap, d)
# gather followed by a matvec — MXU-hostile.  The batched path amortizes both
# axes at once: Q queries advance in lock-step (merged per-query termination
# state decides who still probes) and each round gathers ``probe_batch``
# clusters into one (B·cap, d) block per query, so every round is one dense
# batched matmul.  Per-query early termination is preserved at ROUND
# granularity: a finished query's state freezes (``active`` mask) while
# stragglers keep probing — with probe_batch=1 the probe sequence, merges, and
# counters are bit-identical to the sequential functions.
#
# Lock-step straggler tradeoff (DESIGN.md §6/§7): when the batch mixes
# heterogeneous queries — e.g. join left rows whose structured masks have very
# different selectivity — the while_loop runs until the SLOWEST query
# terminates.  The guarantees that keep this sound rather than wasteful:
#   * frozen queries do no work that is observable: their buffers, counters,
#     and stats stop advancing the round they terminate, so per-query
#     ``probes`` / ``distance_evals`` counters report each query's OWN
#     termination point, not the batch's wall-clock round count;
#   * counters advance in CLUSTER units (a round adds ``n_probed``), so the
#     ``stop_after_no_improve`` / ``out_range_stop`` / ``no_new_category_stop``
#     knobs stay calibrated for any probe_batch: a query's batched probe count
#     exceeds its sequential count by at most one round's rounding,
#     ``ceil(sequential / B) * B``;
#   * an optional per-query ``probe_budget`` (cluster units) caps heavy
#     queries individually, so one adversarial left row cannot hold the whole
#     batch hostage — light rows still freeze at their own termination and a
#     budgeted row freezes at its cap (tests/test_join_batched.py).
# The wall-clock cost of stragglers is real (every round gathers B·cap rows
# for the LIVE queries); the ROADMAP's dynamic batch scheduler (size/effort
# bucketing) is the planned systemic fix.


def _apply_budget(active, probes, probe_budget, qn: int):
    """Freeze queries that exhausted their per-query cluster budget."""
    if probe_budget is None:
        return active
    budget = jnp.broadcast_to(jnp.asarray(probe_budget, jnp.int32), (qn,))
    return active & (probes < budget)


def _resolve_budget(probe_budget, cfg: ProbeConfig):
    """Runtime budget argument wins; else the cfg.probe_budget knob
    (0 = unlimited -> None)."""
    if probe_budget is not None:
        return probe_budget
    return cfg.probe_budget if cfg.probe_budget > 0 else None


def _active_init(qvalid, qn: int):
    """Initial per-query active mask: size-bucket pad queries (qvalid False)
    never probe — their buffers, counters, and stats stay at zero."""
    if qvalid is None:
        return jnp.ones((qn,), jnp.bool_)
    return jnp.asarray(qvalid, jnp.bool_).reshape(qn)

def _round_schedule(index: IVFIndex, cfg: ProbeConfig):
    """(B, n_rounds, max_probes) for the round-granular probe loop."""
    max_probes = min(cfg.max_probes, index.nlist)
    B = max(1, min(cfg.probe_batch, max_probes))
    n_rounds = -(-max_probes // B)
    return B, n_rounds, max_probes


def round_width(index: IVFIndex, cfg: ProbeConfig) -> int:
    """Clusters each batched probe round gathers per active query."""
    return _round_schedule(index, cfg)[0]


@jax.named_scope("chase.ivf.order")
def _order_pad_batch(index: IVFIndex, qs: jnp.ndarray, B: int, n_rounds: int,
                     max_probes: int):
    """Per-query probe order padded to n_rounds*B with -1 sentinels."""
    order, _, bounds = jax.vmap(lambda q: _cluster_order(index, q))(qs)
    order = order[:, :max_probes]
    pad = n_rounds * B - max_probes
    if pad:
        order = jnp.pad(order, ((0, 0), (0, pad)), constant_values=-1)
    return order, bounds


@jax.named_scope("chase.ivf.gather")
def _scan_clusters_batch(index: IVFIndex, corpus: jnp.ndarray,
                         qs: jnp.ndarray, clusters: jnp.ndarray,
                         row_mask: jnp.ndarray | None):
    """Gather B inverted lists per query, one batched matmul for the keys.

    clusters: (Q, B) with -1 sentinels.  Returns (ids (Q, B·cap),
    keys (Q, B·cap), valid, rm_hit (row-mask lookup), n_evals (Q,))."""
    qn, bsz = clusters.shape
    safe_cl = jnp.maximum(clusters, 0)
    ids = index.lists[safe_cl]                          # (Q, B, cap)
    ids = jnp.where(clusters[..., None] >= 0, ids, -1)
    ids = ids.reshape(qn, bsz * index.cap)
    pad = ids >= 0
    safe = jnp.maximum(ids, 0)
    vecs = corpus[safe]                                 # (Q, B·cap, d)
    raw = distance_values(index.metric, vecs, qs[:, None, :])
    keys = order_key(index.metric, raw)
    if row_mask is None:
        rm_hit = pad
    elif row_mask.ndim == 1:
        rm_hit = row_mask[safe]
    else:
        rm_hit = jnp.take_along_axis(row_mask, safe, axis=1)
    return ids, jnp.where(pad, keys, INF), pad, rm_hit, jnp.sum(pad, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "cfg"))
def ivf_topk_batch(index: IVFIndex, corpus: jnp.ndarray, qs: jnp.ndarray,
                   k: int, row_mask: jnp.ndarray | None = None,
                   cfg: ProbeConfig = ProbeConfig(),
                   probe_budget: jnp.ndarray | None = None,
                   qvalid: jnp.ndarray | None = None):
    """Batched filtered top-k: (Q, d) queries, multi-cluster probe rounds.

    ``row_mask`` is None, a shared (N,) mask, or per-query (Q, N).  Returns
    (ids (Q, k), sims (Q, k), valid (Q, k), stats with per-query (Q,) arrays).
    With ``cfg.probe_batch == 1`` results match :func:`ivf_topk` exactly
    (same probe prefix, same merges); with B > 1 each query probes a prefix
    that is a superset of its sequential prefix, so its kth key can only
    improve.  ``probe_budget`` optionally caps each query's probed clusters
    individually (scalar or (Q,) int; defaults to cfg.probe_budget when > 0),
    the straggler valve for heterogeneous batches — a budgeted query freezes
    with its best-so-far results.  ``qvalid`` (None | (Q,) bool) marks
    size-bucket pad queries: they start with ``active=False``, so they never
    probe and their counters stay zero."""
    qn = qs.shape[0]
    probe_budget = _resolve_budget(probe_budget, cfg)
    B, n_rounds, max_probes = _round_schedule(index, cfg)
    order, bounds = _order_pad_batch(index, qs, B, n_rounds, max_probes)

    def cond(state):
        r, *_rest, active = state
        return (r < n_rounds) & jnp.any(active)

    @jax.named_scope("chase.ivf.probe_round")
    def body(state):
        r, bk, bi, no_imp, probes, evals, active = state
        cl = jax.lax.dynamic_slice_in_dim(order, r * B, B, axis=1)
        ids, keys, valid, rm_hit, nev = _scan_clusters_batch(
            index, corpus, qs, cl, row_mask)
        valid = valid & rm_hit
        old_kth = bk[:, k - 1]
        with jax.named_scope("chase.ivf.merge"):
            merged_k, merged_i = jax.vmap(
                lambda a, b, c, d, e: _merge_topk(a, b, c, d, e, k))(
                    bk, bi, keys, ids, valid)
            bk2 = jnp.where(active[:, None], merged_k, bk)
            bi2 = jnp.where(active[:, None], merged_i, bi)
        improved = (bk2[:, k - 1] < old_kth) | (~jnp.isfinite(old_kth)
                                                & jnp.isfinite(bk2[:, k - 1]))
        n_probed = jnp.minimum(B, max_probes - r * B)
        # the no-improvement counter advances per CLUSTER, not per round: a
        # non-improving round means all n_probed clusters failed to improve
        # (kth only tightens), keeping stop_after_no_improve calibrated in
        # cluster units for any probe_batch
        no_imp2 = jnp.where(active,
                            jnp.where(improved, 0, no_imp + n_probed),
                            no_imp)
        probes2 = probes + jnp.where(active, n_probed, 0)
        evals2 = evals + jnp.where(active, nev, 0)
        p_next = (r + 1) * B
        have_k = jnp.isfinite(bk2[:, k - 1])
        if cfg.termination == "bound":
            nb = bounds[:, jnp.minimum(p_next, index.nlist - 1)]
            done = have_k & (nb > bk2[:, k - 1])
        else:
            done = have_k & (no_imp2 >= cfg.stop_after_no_improve)
        done = done & (p_next >= cfg.min_probes)
        active2 = active & ~done & (p_next < max_probes)
        active2 = _apply_budget(active2, probes2, probe_budget, qn)
        return (r + 1, bk2, bi2, no_imp2, probes2, evals2, active2)

    init = (jnp.int32(0),
            jnp.full((qn, k), INF), jnp.full((qn, k), -1, jnp.int32),
            jnp.zeros((qn,), jnp.int32), jnp.zeros((qn,), jnp.int32),
            jnp.zeros((qn,), jnp.int32), _active_init(qvalid, qn))
    _, bk, bi, _, probes, evals, _ = jax.lax.while_loop(cond, body, init)
    valid = jnp.isfinite(bk)
    sims = jnp.where(valid, -bk if index.metric.is_similarity() else bk, 0.0)
    stats = {"probes": probes, "distance_evals": evals}
    return jnp.where(valid, bi, -1), sims, valid, stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def ivf_range_batch(index: IVFIndex, corpus: jnp.ndarray, qs: jnp.ndarray,
                    radius, row_mask: jnp.ndarray | None = None,
                    cfg: ProbeConfig = ProbeConfig(),
                    probe_budget: jnp.ndarray | None = None,
                    qvalid: jnp.ndarray | None = None):
    """Batched DR-SF probe (Algorithm 1 over a query batch).

    ``radius`` is a scalar or per-query (Q,) raw metric values.  Returns
    (ids (Q, capacity), sims, valid, count (Q,), stats with (Q,) arrays).
    probe_batch=1 matches :func:`ivf_range` per query exactly.
    ``probe_budget`` (scalar or (Q,) clusters; defaults to cfg.probe_budget
    when > 0) individually caps stragglers; ``qvalid`` marks size-bucket pad
    queries (inert: empty buffers, zero counters); results are ordered by
    probe discovery, not by key."""
    qn = qs.shape[0]
    probe_budget = _resolve_budget(probe_budget, cfg)
    B, n_rounds, max_probes = _round_schedule(index, cfg)
    order, bounds = _order_pad_batch(index, qs, B, n_rounds, max_probes)
    radius_key = order_key(index.metric, jnp.broadcast_to(
        jnp.asarray(radius, jnp.float32), (qn,)))
    capacity = cfg.capacity

    def cond(state):
        r, *_rest, active = state
        return (r < n_rounds) & jnp.any(active)

    @jax.named_scope("chase.ivf.probe_round")
    def body(state):
        (r, out_ids, out_keys, count, has_in, out_cnt, probes, evals,
         active) = state
        cl = jax.lax.dynamic_slice_in_dim(order, r * B, B, axis=1)
        ids, keys, valid, rm_hit, nev = _scan_clusters_batch(
            index, corpus, qs, cl, row_mask)
        in_range_hit = valid & (keys <= radius_key[:, None])
        hit = in_range_hit & rm_hit & active[:, None]
        n_range = jnp.sum(in_range_hit, axis=1)
        n_hits = jnp.sum(hit, axis=1)
        pos = count[:, None] + jnp.cumsum(hit, axis=1) - 1
        ok = hit & (pos < capacity)
        safe_pos = jnp.where(ok, pos, capacity)

        def append(oi, ok_, okr, sp, idsr, keysr):
            oi = oi.at[sp].set(jnp.where(ok_, idsr, -1), mode="drop")
            okr = okr.at[sp].set(jnp.where(ok_, keysr, INF), mode="drop")
            return oi, okr

        out_ids2, out_keys2 = jax.vmap(append)(out_ids, ok, out_keys,
                                               safe_pos, ids, keys)
        count2 = jnp.where(active, jnp.minimum(count + n_hits, capacity),
                           count)
        has_in2 = jnp.where(active, has_in | (n_range > 0), has_in)
        n_probed = jnp.minimum(B, max_probes - r * B)
        # out-of-range counter in CLUSTER units (see ivf_topk_batch): an
        # empty round is n_probed consecutive empty cluster probes
        out_cnt2 = jnp.where(
            active,
            jnp.where(n_range > 0, 0,
                      jnp.where(has_in, out_cnt + n_probed, 0)),
            out_cnt)
        probes2 = probes + jnp.where(active, n_probed, 0)
        evals2 = evals + jnp.where(active, nev, 0)
        p_next = (r + 1) * B
        if cfg.termination == "bound":
            done = bounds[:, jnp.minimum(p_next, index.nlist - 1)] > radius_key
        else:
            done = has_in2 & (out_cnt2 >= cfg.out_range_stop)
        done = done & (p_next >= cfg.min_probes)
        active2 = active & ~done & (p_next < max_probes)
        active2 = _apply_budget(active2, probes2, probe_budget, qn)
        return (r + 1, out_ids2, out_keys2, count2, has_in2, out_cnt2,
                probes2, evals2, active2)

    init = (jnp.int32(0),
            jnp.full((qn, capacity), -1, jnp.int32),
            jnp.full((qn, capacity), INF),
            jnp.zeros((qn,), jnp.int32), jnp.zeros((qn,), jnp.bool_),
            jnp.zeros((qn,), jnp.int32), jnp.zeros((qn,), jnp.int32),
            jnp.zeros((qn,), jnp.int32), _active_init(qvalid, qn))
    (_, out_ids, out_keys, count, _hi, _oc, probes, evals,
     _a) = jax.lax.while_loop(cond, body, init)
    valid = out_ids >= 0
    sims = jnp.where(valid,
                     -out_keys if index.metric.is_similarity() else out_keys,
                     0.0)
    stats = {"probes": probes, "distance_evals": evals}
    return out_ids, sims, valid, count, stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def ivf_range_category_batch(index: IVFIndex, corpus: jnp.ndarray,
                             categories: jnp.ndarray, qs: jnp.ndarray,
                             radius, row_mask: jnp.ndarray | None = None,
                             cfg: ProbeConfig = ProbeConfig(num_categories=8),
                             probe_budget: jnp.ndarray | None = None,
                             qvalid: jnp.ndarray | None = None):
    """Batched category probe (Algorithm 2 over a query batch).

    The updateState record table gains a leading Q axis: per-query seen mask
    (Q, C), per-category hit counts (Q, C), and the per-category best-K key
    queues (Q, C, K).  Category convergence / dynamic range shrinkage decide
    termination per query; as everywhere on the batched path the ``active``
    mask freezes finished queries at ROUND granularity and counters advance
    in CLUSTER units.  probe_batch=1 matches :func:`ivf_range_category` per
    query exactly.  ``probe_budget`` defaults to cfg.probe_budget when > 0;
    ``qvalid`` marks size-bucket pad queries (inert).  Returns
    (ids (Q, capacity), sims, valid, count (Q,), stats with per-query (Q,)
    arrays)."""
    C = cfg.num_categories
    K = cfg.k_per_category
    assert C > 0, "category probe needs static num_categories"
    qn = qs.shape[0]
    probe_budget = _resolve_budget(probe_budget, cfg)
    B, n_rounds, max_probes = _round_schedule(index, cfg)
    order, bounds = _order_pad_batch(index, qs, B, n_rounds, max_probes)
    radius_key = order_key(index.metric, jnp.broadcast_to(
        jnp.asarray(radius, jnp.float32), (qn,)))
    capacity = cfg.capacity

    def cond(state):
        r, *_rest, active = state
        return (r < n_rounds) & jnp.any(active)

    @jax.named_scope("chase.ivf.probe_round")
    def body(state):
        (r, out_ids, out_keys, count, has_in, out_cnt, seen, counts, kth,
         no_new, probes, evals, active) = state
        cl = jax.lax.dynamic_slice_in_dim(order, r * B, B, axis=1)
        ids, keys, valid, rm_hit, nev = _scan_clusters_batch(
            index, corpus, qs, cl, row_mask)
        in_range_hit = valid & (keys <= radius_key[:, None])  # range only
        hit = in_range_hit & rm_hit & active[:, None]
        n_range = jnp.sum(in_range_hit, axis=1)
        n_hits = jnp.sum(hit, axis=1)
        safe = jnp.maximum(ids, 0)
        cats = jnp.where(hit, categories[safe], -1)           # (Q, B·cap)

        # record-table update — hits of frozen queries are already masked out,
        # so the category state freezes automatically with ``active``
        onehot = cats[..., None] == jnp.arange(C)[None, None, :]  # (Q,Bc,C)
        cat_hits = jnp.sum(onehot, axis=1)                    # (Q, C)
        seen2 = seen | (cat_hits > 0)
        n_new = jnp.sum(seen2, axis=1) - jnp.sum(seen, axis=1)
        counts2 = counts + cat_hits
        cand = jnp.where(onehot, keys[..., None], INF)        # (Q, B·cap, C)
        merged = jnp.concatenate([kth, jnp.swapaxes(cand, 1, 2)], axis=2)
        kth2 = -jax.lax.top_k(-merged, K)[0]                  # (Q, C, K)

        pos = count[:, None] + jnp.cumsum(hit, axis=1) - 1
        ok = hit & (pos < capacity)
        safe_pos = jnp.where(ok, pos, capacity)

        def append(oi, okeys, ok_, sp, idsr, keysr):
            oi = oi.at[sp].set(jnp.where(ok_, idsr, -1), mode="drop")
            okeys = okeys.at[sp].set(jnp.where(ok_, keysr, INF), mode="drop")
            return oi, okeys

        out_ids2, out_keys2 = jax.vmap(append)(out_ids, out_keys, ok,
                                               safe_pos, ids, keys)
        count2 = jnp.where(active, jnp.minimum(count + n_hits, capacity),
                           count)
        has_in2 = jnp.where(active, has_in | (n_range > 0), has_in)
        n_probed = jnp.minimum(B, max_probes - r * B)
        out_cnt2 = jnp.where(
            active,
            jnp.where(n_range > 0, 0,
                      jnp.where(has_in, out_cnt + n_probed, 0)),
            out_cnt)
        no_new2 = jnp.where(active,
                            jnp.where(n_new > 0, 0, no_new + n_probed),
                            no_new)
        probes2 = probes + jnp.where(active, n_probed, 0)
        evals2 = evals + jnp.where(active, nev, 0)
        p_next = (r + 1) * B
        next_bound = bounds[:, jnp.minimum(p_next, index.nlist - 1)]
        frontier = next_bound if cfg.termination == "bound" else radius_key
        converged = (counts2 >= K) & (kth2[:, :, K - 1] <= frontier[:, None])
        rest = jnp.sum(seen2 & ~converged, axis=1)            # T.restElements
        cat_done = ((rest == 0) & (no_new2 >= cfg.no_new_category_stop)
                    & jnp.any(seen2, axis=1))
        if cfg.termination == "bound":
            range_done = next_bound > radius_key
        else:
            range_done = has_in2 & (out_cnt2 >= cfg.out_range_stop)
        done = (cat_done | range_done) & (p_next >= cfg.min_probes)
        active2 = active & ~done & (p_next < max_probes)
        active2 = _apply_budget(active2, probes2, probe_budget, qn)
        return (r + 1, out_ids2, out_keys2, count2, has_in2, out_cnt2,
                seen2, counts2, kth2, no_new2, probes2, evals2, active2)

    init = (jnp.int32(0),
            jnp.full((qn, capacity), -1, jnp.int32),
            jnp.full((qn, capacity), INF),
            jnp.zeros((qn,), jnp.int32), jnp.zeros((qn,), jnp.bool_),
            jnp.zeros((qn,), jnp.int32),
            jnp.zeros((qn, C), jnp.bool_), jnp.zeros((qn, C), jnp.int32),
            jnp.full((qn, C, K), INF), jnp.zeros((qn,), jnp.int32),
            jnp.zeros((qn,), jnp.int32), jnp.zeros((qn,), jnp.int32),
            _active_init(qvalid, qn))
    (_, out_ids, out_keys, count, _hi, _oc, seen, _cn, _kth, _nn, probes,
     evals, _a) = jax.lax.while_loop(cond, body, init)
    valid = out_ids >= 0
    sims = jnp.where(valid,
                     -out_keys if index.metric.is_similarity() else out_keys,
                     0.0)
    stats = {"probes": probes, "distance_evals": evals,
             "categories_seen": jnp.sum(seen, axis=1)}
    return out_ids, sims, valid, count, stats
