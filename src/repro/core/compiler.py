"""Query compilation — CHASE §6, XLA edition.

LingoDB lowers relalg -> subop -> LLVM IR -> machine code.  Here the analogue
chain is: logical plan -> (semantic analysis + rewrite) -> physical builder ->
traced JAX function -> jaxpr -> XLA HLO -> machine code.  CSE / DCE / constant
folding (§6's "general passes") happen inside XLA.  One pipeline = one fused
XLA computation; there is no operator interpretation at runtime.

The compilation product is split in two (the size-bucketed execution stack,
DESIGN.md §8):

* :class:`CompiledPlan` — the shape-independent plan artifact: analysis,
  plans, options, and the traced-but-unjitted single/batch pipeline
  functions.  §6's "one plan, one executable" claim generalizes to "one
  plan, one executable *per batch shape*" under serving traffic — which is
  exactly the problem, because every distinct request-batch size Q retraces.
* :class:`BucketedExecutor` — the runtime half: a lazy per-power-of-two
  bucket executor cache.  A batch of Q queries pads up to the enclosing
  bucket, runs the bucket's (single, reused) executable with a per-query
  ``valid`` mask that makes pad queries inert at every layer (kernel mask
  lanes, IVF ``active`` state), and slices outputs back to Q.

:class:`CompiledQuery` remains the user-facing handle tying the two
together (plus the exact-shape ``execute_batch`` used as the bit-parity
reference and by callers with a fixed batch size).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import hashlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .expr import BoolOp, Bindings, Expr, Param
from .physical import (BATCH_BUILDERS, BUILDERS, JOIN_LOWERING_FAMILIES,
                       EngineOptions)
from .plan import PlanNode
from .rewriter import rewrite
from .schema import Catalog
from .semantics import Analysis, QueryClass, analyze
from .sql import parse_sql


class StalePlanError(RuntimeError):
    """A compiled plan's catalog registrations changed in a way that cannot
    be re-bound in place (DESIGN.md §11).

    Raised when a table was re-registered (the builders close over its
    predicate columns) or an index appeared/disappeared after compilation
    (index *presence* selects the lowering at build time).  Recovery is a
    re-prepare: the session API does it transparently
    (:meth:`repro.api.Statement` re-prepares through the plan cache); legacy
    ``compile_query`` callers must compile fresh."""


def _scan_of(a: Analysis) -> tuple[str, str]:
    """The (table, vector column) pair a plan's corpus scan reads — the
    pair live-corpus / index / sharded registrations key on."""
    if a.query_class in (QueryClass.VKNN_SF, QueryClass.DR_SF,
                         QueryClass.CATEGORY_PARTITION):
        return a.table, a.vector_column
    return a.right_table, a.right_vector


def _catalog_dep_keys(a: Analysis, catalog: Catalog,
                      options: EngineOptions) -> tuple:
    """The catalog registration keys a compiled plan captures — what
    :meth:`CompiledQuery.ensure_fresh` watches for version bumps."""
    qc = a.query_class
    scan = _scan_of(a)
    if qc in (QueryClass.VKNN_SF, QueryClass.DR_SF,
              QueryClass.CATEGORY_PARTITION):
        keys = [("table", a.table), ("index",) + scan]
    else:
        keys = [("table", a.left_table), ("table", a.right_table),
                ("index",) + scan]
    if options.dist is not None:
        keys.append(("sharded",) + scan)
    if options.quant is not None and catalog.live_for(*scan) is None:
        # frozen quantized twin: a re-registered same-shape twin re-binds
        # in place (live twins instead ride the live key — mutations bump
        # it, and the twin caches on the LiveCorpus device dict)
        keys.append(("quantized",) + scan)
    if catalog.live_for(*scan) is not None:
        # every insert/delete/compact bumps this key: mutations become
        # visible through the in-place array re-bind, zero retraces
        keys.append(("live",) + scan)
    return tuple(keys)


# ---------------------------------------------------------------------------
# plan fingerprinting (the normalized plan-cache key, DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# Two SQL texts that parse to the same logical plan modulo (a) whitespace,
# (b) parameter names, and (c) the order of commutative AND/OR conjuncts
# must share one CompiledPlan — plan reuse across requests is the dominant
# serving cost, and prepared statements arrive in every textual variant.
#
# Canonicalization: parameters are renamed positionally (?0, ?1, ... in
# canonical traversal order) and commutative BoolOp operands are sorted by
# their *name-erased* serialization (params rendered as a bare "?"), so the
# operand order and the positional assignment are both stable across
# variants.  The fingerprint is the canonical serialization; the canonical
# parameter order is returned alongside so a cache hit can translate the
# statement's own bind names onto the cached plan's names.

def _param_slot(params: list, name: str) -> int:
    if name not in params:
        params.append(name)
    return params.index(name)


def _fp_value(v: Any, params: list | None) -> str:
    if isinstance(v, (Expr, PlanNode)):
        return _fp_node(v, params)
    if isinstance(v, tuple):
        return "(" + ",".join(_fp_value(x, params) for x in v) + ")"
    return repr(v)


def _fp_node(n: Any, params: list | None) -> str:
    """Serialize one plan/expr node; ``params is None`` => name-erased mode
    (every parameter renders as "?" — the commutative-sort key)."""
    if isinstance(n, Param):
        return "?" if params is None else f"?{_param_slot(params, n.name)}"
    parts = []
    for f in dataclasses.fields(n):
        v = getattr(n, f.name)
        # Limit.k (and the rewritten nodes' k) may hold a *param name* string
        if f.name == "k" and isinstance(v, str):
            parts.append("?" if params is None
                         else f"?{_param_slot(params, v)}")
            continue
        if (isinstance(n, BoolOp) and f.name == "operands"
                and n.op in ("and", "or")):
            erased = [_fp_node(o, None) for o in n.operands]
            order = sorted(range(len(erased)), key=erased.__getitem__)
            parts.append("(" + ",".join(
                _fp_node(n.operands[i], params) for i in order) + ")")
            continue
        parts.append(_fp_value(v, params))
    return type(n).__name__ + "[" + ";".join(parts) + "]"


def plan_fingerprint(plan: PlanNode) -> tuple[str, tuple[str, ...]]:
    """Canonical fingerprint of a logical plan.

    Returns ``(fingerprint, param_order)``: the fingerprint is identical for
    whitespace / parameter-rename / AND-OR-operand-order variants of the same
    SQL, and ``param_order[i]`` is THIS plan's original name for canonical
    parameter slot ``i`` (two variant plans align slot-by-slot)."""
    params: list[str] = []
    fp = _fp_node(plan, params)
    return fp, tuple(params)


def fingerprint_digest(fp: str) -> str:
    """Short stable digest of a plan fingerprint (for explain/report keys)."""
    return hashlib.sha256(fp.encode()).hexdigest()[:12]


@dataclasses.dataclass
class CompiledPlan:
    """Shape-independent compilation artifact (one per SQL + options).

    ``batch_fn`` has the uniform signature
    ``(arrays, binds, qvalid=None, probe_budget=None)``: every value in
    ``binds`` carries a leading Q axis, ``qvalid`` is an optional (Q,) bool
    marking size-bucket pad queries (inert: no results, zero counters), and
    ``probe_budget`` is an optional per-query IVF cluster budget (the
    straggler valve; ignored by index-less plans)."""
    sql: str
    analysis: Analysis
    logical_plan: PlanNode
    rewritten_plan: PlanNode
    options: EngineOptions
    fn: Callable
    batch_fn: Callable
    batch_native: bool
    batch_reason: str


def _bucket_for(qn: int) -> int:
    """Enclosing power-of-two size bucket (1, 2, 4, 8, ...)."""
    if qn < 1:
        raise ValueError(f"batch size must be >= 1, got {qn}")
    return 1 << (qn - 1).bit_length()


def _pad_leading(v, bucket: int) -> np.ndarray:
    """Edge-pad the leading Q axis up to ``bucket`` (pad rows repeat the last
    real row, so they are well-formed binds — correctness never depends on
    their values; the ``valid`` mask makes them inert).

    Host-side numpy on purpose: op-by-op jnp padding would compile a tiny
    XLA program per DISTINCT Q, re-introducing exactly the per-batch-size
    compile latency the bucket cache exists to kill."""
    v = np.asarray(v)
    pad = bucket - v.shape[0]
    if pad == 0:
        return v
    return np.concatenate(
        [v, np.broadcast_to(v[-1:], (pad,) + v.shape[1:])])


# -- profiler spans of the serving path --------------------------------------
# ``chase.drain`` (opened by the serving scheduler) encloses one drain; its
# children ``chase.stack`` / ``chase.pad`` / ``chase.dispatch`` /
# ``chase.fetch`` / ``chase.slice`` carry the same ``drain`` id.  A
# ``TraceAnnotation`` records nothing unless a profiler session is open.

_DRAIN_ID: contextvars.ContextVar[int] = contextvars.ContextVar(
    "chase_drain", default=-1)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span of the serving path, tagged with the drain it runs in
    (-1 outside a drain)."""
    return jax.profiler.TraceAnnotation(name, drain=_DRAIN_ID.get())


@contextlib.contextmanager
def drain_span(drain: int, size: int, bucket: int):
    """The ``chase.drain`` span: ``size`` requests run in ``bucket``; the
    spans opened inside it carry ``drain`` as their id."""
    token = _DRAIN_ID.set(drain)
    try:
        with jax.profiler.TraceAnnotation("chase.drain", drain=drain,
                                          size=size, bucket=bucket):
            yield
    finally:
        _DRAIN_ID.reset(token)


FALLBACK_STAT = "fp32_fallback"


def _without_fallback(out):
    """``out`` without the quantized top-k lane's per-query
    ``fp32_fallback`` stat (traced or on the host), so that every surface
    returns the same output tree in every lane; the bucketed executor
    counts the flag first (:meth:`BucketedExecutor.note_fallback`)."""
    stats = out.get("stats") if isinstance(out, dict) else None
    if not isinstance(stats, dict) or FALLBACK_STAT not in stats:
        return out
    return {**out, "stats": {k: v for k, v in stats.items()
                             if k != FALLBACK_STAT}}


class BucketedExecutor:
    """Lazy per-(plan, bucket) executor cache — the serving execution tier.

    One jitted executable exists per power-of-two bucket actually seen;
    ``trace_counts[bucket]`` counts how many times that bucket's function was
    (re)traced, so tests can assert the compile-once contract.  A batch of Q
    requests pads to ``_bucket_for(Q)``, executes with ``valid[q] = q < Q``,
    and slices every output leaf back to Q.  Pad queries are inert by
    construction (kernel mask lanes / IVF ``active`` freeze), so bucketed
    results are bit-identical to an exact-shape ``execute_batch``.
    """

    def __init__(self, plan: CompiledPlan, arrays: Any):
        self.plan = plan
        self.arrays = arrays
        self._cache: dict[int, Any] = {}
        self.trace_counts: dict[int, int] = {}
        # persistent AOT plan cache (DESIGN.md §15): binding + loaded/
        # exported executables keyed (bucket, argument signature)
        self._aot = None
        self._aot_exec: dict[tuple[int, str], Any] = {}
        self.aot_loaded: dict[int, int] = {}
        # quantized top-k lane (DESIGN.md §13): executions, and those whose
        # certificate failed so that the batch ran the fp32 kernel as well
        self.quant_topk = {"batches": 0, "fp32_fallbacks": 0}

    def note_fallback(self, out):
        """Count a bucketed execution of the quantized top-k lane in
        :attr:`quant_topk` (reading its ``fp32_fallback`` flag on the host)
        and return the output tree without the flag."""
        stats = out.get("stats") if isinstance(out, dict) else None
        if isinstance(stats, dict) and FALLBACK_STAT in stats:
            fell_back = bool(np.asarray(stats[FALLBACK_STAT]).any())
            self.quant_topk["batches"] += 1
            self.quant_topk["fp32_fallbacks"] += int(fell_back)
        return _without_fallback(out)

    def attach_aot(self, binding) -> None:
        """Route this executor through a persistent AOT plan cache
        (:class:`repro.core.aot.AOTPlanCache`, DESIGN.md §15).

        Once attached, every bucket executable is loaded from disk when a
        valid entry exists (zero traces — ``trace_counts`` stays honest)
        and exported + persisted write-through when it does not.  Failure
        anywhere in the persistence path degrades to the plain in-memory
        jit path with a typed :class:`~repro.core.aot.AOTCacheWarning`."""
        self._aot = binding

    def bucket_for(self, qn: int) -> int:
        """Enclosing power-of-two bucket a batch of ``qn`` queries runs in."""
        return _bucket_for(qn)

    @property
    def buckets(self) -> list[int]:
        """Buckets with a compiled executable (sorted)."""
        return sorted(self._cache)

    def executable(self, bucket: int):
        """The (lazily jitted) executable for one bucket."""
        if bucket not in self._cache:
            self.trace_counts.setdefault(bucket, 0)

            def run(arrays, binds, qvalid, probe_budget, _b=bucket):
                self.trace_counts[_b] += 1      # advances only on (re)trace
                return self.plan.batch_fn(arrays, binds, qvalid=qvalid,
                                          probe_budget=probe_budget)

            self._cache[bucket] = jax.jit(run)
        return self._cache[bucket]

    def run_padded(self, binds: dict, qn: int, probe_budget=None):
        """Execute at bucket granularity WITHOUT slicing outputs back.

        Returns (padded outputs, bucket, valid): every output leaf keeps its
        leading bucket axis, so tests (and debuggers) can observe that pad
        rows are inert — empty results, zero probe/distance counters."""
        bucket = _bucket_for(qn)
        with span("chase.pad"):
            padded = {k: _pad_leading(v, bucket) for k, v in binds.items()}
            valid = np.arange(bucket) < qn
            if probe_budget is not None:
                budget = np.asarray(probe_budget, np.int32)
                if budget.ndim >= 1 and budget.shape[0] == qn:
                    budget = _pad_leading(budget, bucket)
                probe_budget = budget
        args = (self.arrays, padded, valid, probe_budget)
        with span("chase.dispatch"):
            if self._aot is not None:
                out = self._aot_call(bucket, args)
            else:
                out = self.executable(bucket)(*args)
        return self.note_fallback(out), bucket, valid

    # -- persistent AOT plan cache (DESIGN.md §15) --------------------------

    def _aot_call(self, bucket: int, args: tuple):
        """Dispatch one bucket execution through the persistent cache.

        Keyed by (bucket, argument signature): a live-corpus delta growth
        or index replacement that changes leaf shapes gets a new entry,
        exactly as the plain jit path would retrace."""
        from . import aot as _aot
        sig = _aot.args_signature(args)
        key = (bucket, sig)
        fn = self._aot_exec.get(key)
        if fn is None:
            fn = self._aot.cache.load(self._aot, bucket, sig)
            if fn is not None:
                # disk hit: executable restored without tracing anything
                self.trace_counts.setdefault(bucket, 0)
                self.aot_loaded[bucket] = self.aot_loaded.get(bucket, 0) + 1
            else:
                fn = self._aot_compile(bucket, sig, args)
            self._aot_exec[key] = fn
        return fn(args)

    def _aot_compile(self, bucket: int, sig: str, args: tuple):
        """Cold path under an attached cache: trace once via ``jax.export``,
        persist (portable StableHLO + native annex), return the compiled
        callable.  An unserializable plan restores the trace-count snapshot
        and falls back to the plain in-memory jit executable."""
        from . import aot as _aot
        binding = self._aot
        self.trace_counts.setdefault(bucket, 0)
        snapshot = self.trace_counts[bucket]
        leaves, treedef = jax.tree.flatten(args)

        def flat_run(lvs, _b=bucket, _td=treedef):
            self.trace_counts[_b] += 1      # advances only on (re)trace
            arrays, binds, qvalid, probe_budget = jax.tree.unflatten(_td, lvs)
            return self.plan.batch_fn(arrays, binds, qvalid=qvalid,
                                      probe_budget=probe_budget)

        try:
            exported = _aot.export_flat(flat_run, leaves)
            portable = exported.serialize()
        except Exception as exc:                       # noqa: BLE001
            # the failed export may have traced already: keep the count
            # honest before the plain path's own first-call trace
            self.trace_counts[bucket] = snapshot
            binding.cache.note_unserializable(binding.plan_key, exc)
            return lambda a, _b=bucket: self.executable(_b)(*a)
        compiled, annex = _aot.native_annex(exported, leaves)
        binding.cache.save(binding, bucket, sig, portable, annex)
        if compiled is not None:
            return lambda a: compiled(jax.tree.leaves(a))
        jitted = jax.jit(exported.call)
        return lambda a: jitted(jax.tree.leaves(a))

    def __call__(self, binds: dict, probe_budget=None):
        """Bucketed execution: pad -> run bucket executable -> slice to Q.

        Output slicing happens on host (numpy): a jnp slice would compile
        one tiny executable per distinct Q — see :func:`_pad_leading`."""
        qn = _stacked_qn(binds)
        out, _bucket, _valid = self.run_padded(binds, qn, probe_budget)
        with span("chase.fetch"):     # the host waits for the device here
            return jax.tree.map(lambda v: np.asarray(v)[:qn], out)


def _stacked_qn(binds: dict) -> int:
    dims = [v.shape[0] for v in binds.values()
            if hasattr(v, "ndim") and v.ndim >= 1]
    if not dims:
        raise ValueError("stacked binds carry no leading batch axis")
    return dims[0]


@dataclasses.dataclass
class CompiledQuery:
    """User-facing handle: plan artifact + per-bucket executor cache.

    ``__call__`` runs the single-query executable; ``execute_batch`` runs the
    exact-shape batch executable (one trace per distinct Q — the bit-parity
    reference); ``execute_bucketed`` runs the size-bucketed serving path
    (one executable per power-of-two bucket, any Q)."""
    plan: CompiledPlan
    _jitted: Any
    _arrays: Any
    _batch_jitted: Any
    executor: BucketedExecutor
    # catalog-version invalidation (DESIGN.md §11): the catalog, the
    # registration keys this plan captured, and their versions at bind time
    _catalog: Any = None
    _dep_keys: tuple = ()
    _bound_versions: tuple = ()
    rebinds: int = 0

    # -- plan delegation (back-compat surface) ------------------------------
    @property
    def sql(self) -> str:
        """The statement's original SQL text."""
        return self.plan.sql

    @property
    def analysis(self) -> Analysis:
        """Semantic analysis (query class + extracted slots)."""
        return self.plan.analysis

    @property
    def logical_plan(self) -> PlanNode:
        """The parsed (pre-rewrite) logical plan."""
        return self.plan.logical_plan

    @property
    def rewritten_plan(self) -> PlanNode:
        """The CHASE-rewritten logical plan (R1-R3 applied)."""
        return self.plan.rewritten_plan

    @property
    def options(self) -> EngineOptions:
        """The EngineOptions this plan compiled under."""
        return self.plan.options

    @property
    def batch_native(self) -> bool:
        """True when execute_batch lowers natively (no vmap fallback)."""
        return self.plan.batch_native

    def ensure_fresh(self) -> bool:
        """Re-bind this plan to the catalog's current registrations.

        Called at execute time by every surface (single / exact-shape /
        bucketed, and by the session API / scheduler).  Compares the
        captured registration versions against the catalog clock:

        * unchanged — no-op (a few dict lookups);
        * an index / sharded-handle replacement — re-gathers the plan's
          device ``arrays`` in place (the jitted pipelines take arrays as an
          *argument*, so a same-shape replacement costs zero retraces) and
          returns True;
        * a table re-registration, or index presence flipping — raises
          :class:`StalePlanError` (the builders' closures hold stale state;
          only a re-prepare can fix it).
        """
        if self._catalog is None:
            return False
        current = self._catalog.version_snapshot(self._dep_keys)
        if current == self._bound_versions:
            return False
        stale_tables = [
            k[1] for k, old, new in zip(self._dep_keys, self._bound_versions,
                                        current)
            if old != new and k[0] == "table"]
        if stale_tables:
            raise StalePlanError(
                f"table(s) {stale_tables} were re-registered after this plan "
                f"compiled; the plan's predicate columns are frozen at the "
                f"old table — re-prepare the statement")
        new_arrays = _gather_arrays(self.analysis, self._catalog,
                                    self.options)
        if set(new_arrays) != set(self._arrays):
            raise StalePlanError(
                f"catalog registration change altered the plan's array set "
                f"({sorted(self._arrays)} -> {sorted(new_arrays)}); index "
                f"presence selects the lowering at compile time — "
                f"re-prepare the statement")
        # in place: the BucketedExecutor holds THE SAME dict object
        self._arrays.clear()
        self._arrays.update(new_arrays)
        self._bound_versions = self._catalog.version_snapshot(self._dep_keys)
        self.rebinds += 1
        return True

    def __call__(self, **binds):
        self.ensure_fresh()
        return self._jitted(self._arrays, dict(binds))

    def execute_batch(self, binds_list: list[dict] | None = None, **stacked):
        """Execute a parameter-only batch: ONE compiled pipeline, Q bind sets.

        Accepts either ``binds_list`` (a list of per-query bind dicts, which
        get stacked) or keyword binds already stacked with a leading Q axis
        (scalars broadcast).  Every hybrid class has a native batched
        lowering: VKNN-SF / DR-SF run the query-tiled kernels and
        multi-cluster IVF probes directly, and the join families (Q3-Q6)
        flatten (bind sets x left rows) into ONE kernel-level query batch.
        The vmap-of-scalar fallback survives only under
        ``join_lowering='perleft'`` (the benchmark baseline).  Every output
        gains a leading Q axis; stats report per-query counters (per
        (bind set, left row) for joins).

        NOTE: each distinct Q traces a fresh executable.  Serving traffic
        with varying batch sizes should use :meth:`execute_bucketed`."""
        self.ensure_fresh()
        binds = self._stack_binds(binds_list, stacked)
        return self._batch_jitted(self._arrays, binds)

    def execute_bucketed(self, binds_list: list[dict] | None = None,
                         probe_budget=None, **stacked):
        """Size-bucketed batch execution (the serving path).

        Semantically identical to :meth:`execute_batch` (bit-identical
        outputs) but pads Q up to the enclosing power-of-two bucket and
        reuses ONE compiled executable per bucket, so arbitrary request-batch
        sizes cost at most log2(max_batch) compilations.  ``probe_budget``
        (scalar or (Q,) int, cluster units) optionally caps each query's IVF
        probes — the effort-bucket valve used by serving/scheduler.py."""
        self.ensure_fresh()
        binds = self._stack_binds(binds_list, stacked)
        return self.executor(binds, probe_budget=probe_budget)

    def _stack_binds(self, binds_list, stacked) -> dict:
        if binds_list is not None:
            if stacked:
                raise TypeError("pass binds_list OR keyword binds, not both")
            if not binds_list:
                raise ValueError("binds_list is empty")
            keys = binds_list[0].keys()
            for i, b in enumerate(binds_list):
                missing = keys - b.keys()
                extra = b.keys() - keys
                if missing or extra:
                    offending = sorted(missing | extra)[0]
                    kind = "missing" if offending in missing else "unexpected"
                    raise ValueError(
                        f"ragged binds_list: binds_list[{i}] has {kind} key "
                        f"{offending!r} (binds_list[0] keys: "
                        f"{sorted(keys)})")
            # host-side stack: a jnp.stack over N request arrays compiles a
            # fresh concatenate per DISTINCT N — per-batch-size compile
            # latency the bucketed serving path exists to kill
            with span("chase.stack"):
                return {k: np.stack([np.asarray(b[k]) for b in binds_list])
                        for k in keys}
        binds = {k: jnp.asarray(v) for k, v in stacked.items()}
        qe = self.analysis.query_expr
        if isinstance(qe, Param) and qe.name in binds:
            qv = binds[qe.name]
            if qv.ndim != 2:
                raise ValueError(
                    f"execute_batch needs a stacked (Q, D) query vector for "
                    f"${{{qe.name}}}, got shape {qv.shape}; pass a single "
                    f"query through __call__ instead")
            qn = qv.shape[0]
        else:
            dims = [v.shape[0] for v in binds.values() if v.ndim >= 1]
            if not dims:
                raise ValueError("cannot infer batch size from scalar binds; "
                                 "use binds_list")
            qn = dims[0]
        bad = {k: v.shape for k, v in binds.items()
               if v.ndim >= 1 and v.shape[0] != qn}
        if bad:
            raise ValueError(f"stacked binds disagree on batch size {qn}: "
                             f"{bad}")
        # scalar broadcast on host (numpy): jnp.broadcast_to would compile
        # one tiny executable per distinct Q
        return {k: (np.broadcast_to(np.asarray(v), (qn,)) if v.ndim == 0
                    else v)
                for k, v in binds.items()}

    def lower(self, **binds):
        """AOT lowering for inspection (HLO text, cost analysis)."""
        return self._jitted.lower(self._arrays, dict(binds))

    def lower_batch(self, binds_list: list[dict] | None = None, **stacked):
        """AOT lowering of the BATCHED executable (HLO text, cost
        analysis) — what ``execute_batch`` would run at this Q."""
        self.ensure_fresh()
        binds = self._stack_binds(binds_list, stacked)
        return self._batch_jitted.lower(self._arrays, binds)

    def export_batch(self, binds_list: list[dict] | None = None,
                     **stacked) -> bytes:
        """Serialize the batched executable at this Q to portable
        ``jax.export`` StableHLO bytes (DESIGN.md §15).

        The round-trip partner is :meth:`deserialize_batch`: the returned
        bytes restore — in this or any later process on the same backend —
        a callable taking the same ``(arrays, binds)`` the batched
        executable takes, bit-identical to :meth:`execute_batch`.  The
        full persistent cache (:mod:`repro.core.aot`) layers keying,
        validation, and the native annex on top of this primitive."""
        from . import aot as _aot
        self.ensure_fresh()
        binds = self._stack_binds(binds_list, stacked)
        args = (self._arrays, binds)
        leaves, treedef = jax.tree.flatten(args)

        def flat(lvs, _td=treedef):
            arrays, b = jax.tree.unflatten(_td, lvs)
            return _without_fallback(self.plan.batch_fn(arrays, b))

        return _aot.export_flat(flat, leaves).serialize()

    @staticmethod
    def deserialize_batch(data: bytes):
        """Restore an :meth:`export_batch` payload to a callable taking
        ``(arrays, binds)`` (re-pays the XLA compile, not the trace)."""
        from . import aot as _aot
        fn = _aot.load_portable(data)
        return lambda arrays, binds: fn((arrays, binds))

    def explain(self) -> str:
        """Engine/class/lowering summary plus both plan trees, as text."""
        out = [f"-- engine: {self.options.engine}",
               f"-- class:  {self.analysis.query_class.value}",
               f"-- batch:  {self.plan.batch_reason}",
               "-- logical plan:", self.logical_plan.pretty(),
               "-- rewritten plan:", self.rewritten_plan.pretty()]
        return "\n".join(out)


def _gather_arrays(a: Analysis, catalog: Catalog,
                   options: EngineOptions | None = None) -> dict:
    """Collect the device arrays a compiled pipeline closes over.

    For distributed plans (``options.dist``) the scanned corpus is
    additionally row-sharded over the spec's mesh: a matching
    :class:`~repro.dist.sharding.ShardedCorpus` registered on the catalog
    is reused (the registry is keyed per (table, column, mesh spec), so
    handles for different meshes coexist); otherwise one is built and
    registered."""
    arrays: dict[str, Any] = {}
    qc = a.query_class
    scan_table, scan_column = _scan_of(a)
    live = catalog.live_for(scan_table, scan_column)
    if qc in (QueryClass.VKNN_SF, QueryClass.DR_SF,
              QueryClass.CATEGORY_PARTITION):
        tab = catalog.table(a.table)
        arrays["corpus"] = tab[a.vector_column]
        idx = catalog.index_for(a.table, a.vector_column)
        if idx is not None:
            arrays["index"] = idx
        if qc == QueryClass.CATEGORY_PARTITION:
            arrays["categories"] = tab[a.category_column.name]
    else:
        ltab = catalog.table(a.left_table)
        rtab = catalog.table(a.right_table)
        arrays["left"] = ltab[a.left_vector]
        arrays["corpus"] = rtab[a.right_vector]
        idx = catalog.index_for(a.right_table, a.right_vector)
        if idx is not None:
            arrays["index"] = idx
        if qc == QueryClass.CATEGORY_JOIN:
            arrays["categories"] = rtab[a.category_column.name]
    if live is not None:
        # the live segment arrays REPLACE the frozen corpus: padded main
        # segment + validity (tombstone bitmap), delta segment, and the
        # live scalar columns predicates evaluate against (DESIGN.md §12)
        arrays.update(live.plan_arrays())
        if "categories" in arrays:
            arrays["categories"] = arrays["live_cols"][a.category_column.name]
    if options is not None and options.dist is not None:
        from ..dist.sharding import ShardedCorpus, resolve_mesh
        if live is not None:
            # keyed off the live device cache, which compaction clears (the
            # only mutation that moves main-segment vectors) — catalog
            # sharded registration would go stale silently
            key = f"sharded:{options.dist!r}"
            sharded = live._dev.get(key)
            if sharded is None:
                sharded = ShardedCorpus.build(resolve_mesh(options.dist),
                                              arrays["corpus"],
                                              options.dist.axes)
                live._dev[key] = sharded
        else:
            sharded = catalog.sharded_for(scan_table, scan_column,
                                          options.dist)
            if sharded is None:
                sharded = ShardedCorpus.build(resolve_mesh(options.dist),
                                              arrays["corpus"],
                                              options.dist.axes)
                catalog.register_sharded(scan_table, scan_column, sharded)
        arrays["dcorpus"] = sharded.corpus
        arrays["drow_ids"] = sharded.row_ids
    if options is not None and options.quant is not None:
        from ..data.quantized import quantize_corpus
        if live is not None:
            # keyed off the live device cache: compaction (the only
            # mutation that moves main-segment vectors) clears it, so the
            # twin re-quantizes exactly when the fp32 source moved; the
            # delta segment stays fp32 (it is small and mutation-hot)
            key = f"quant:{options.quant}"
            quant = live._dev.get(key)
            if quant is None:
                quant = quantize_corpus(arrays["corpus"], options.quant)
                live._dev[key] = quant
        else:
            quant = catalog.quantized_for(scan_table, scan_column,
                                          options.quant)
            if quant is None:
                quant = quantize_corpus(arrays["corpus"], options.quant)
                catalog.register_quantized(scan_table, scan_column, quant)
        arrays.update(quant.plan_arrays())
        if options.dist is not None:
            arrays.update(_sharded_quant(catalog, live, options, arrays,
                                         scan_table, scan_column)
                          .plan_arrays(prefix="d"))
    return arrays


def _sharded_quant(catalog, live, options, arrays, scan_table: str,
                   scan_column: str):
    """The quantized twin of the SHARDED corpus (divisibility-padded rows
    included — all-zero pads quantize to zero and are masked by row_id=-1),
    each per-row array device_put onto the dist mesh with the same row
    sharding as ``dcorpus``.  Cached like the sharded handle itself:
    per-(mode, spec) on the catalog, or on the live device cache."""
    from jax.sharding import NamedSharding, PartitionSpec
    from ..data.quantized import QuantizedCorpus, quantize_corpus
    from ..dist.sharding import resolve_mesh
    if live is not None:
        key = f"quant:{options.quant}:dist:{options.dist!r}"
        dq = live._dev.get(key)
    else:
        dq = catalog.quantized_for(scan_table, scan_column,
                                   (options.quant, options.dist))
    if dq is None:
        raw = quantize_corpus(arrays["dcorpus"], options.quant)
        mesh = resolve_mesh(options.dist)
        rows = NamedSharding(mesh, PartitionSpec(options.dist.axes, None))
        lane = NamedSharding(mesh, PartitionSpec(options.dist.axes))
        dq = QuantizedCorpus(
            mode=raw.mode,
            qvecs=jax.device_put(raw.qvecs, rows),
            scales=jax.device_put(raw.scales, rows),
            half_step=jax.device_put(raw.half_step, lane),
            row_l1=jax.device_put(raw.row_l1, lane),
            row_l2=jax.device_put(raw.row_l2, lane))
        if live is not None:
            live._dev[f"quant:{options.quant}:dist:{options.dist!r}"] = dq
        else:
            catalog.register_quantized(scan_table, scan_column, dq,
                                       key=(options.quant, options.dist))
    return dq


def _vmap_fallback(fn: Callable) -> Callable:
    """vmap-of-scalar batch fallback with the uniform batch_fn signature.

    Pad queries cannot be skipped here (the scalar pipeline has no valid
    lane), so inertness is enforced on the way out: invalid queries report
    zero counters and all-False validity.  ``probe_budget`` has no lane
    either and is ignored — callers that depend on it (effort bucketing)
    must check ``batch_native`` first (serving/scheduler.py does)."""

    def bfn(arrs, binds, qvalid=None, probe_budget=None):
        out = jax.vmap(lambda b: fn(arrs, b))(binds)
        if qvalid is None:
            return out
        masked = {}
        for key, v in out.items():
            if key in ("stats", "count"):
                masked[key] = jax.tree.map(
                    lambda s: jnp.where(
                        qvalid.reshape((-1,) + (1,) * (s.ndim - 1)), s, 0),
                    v)
            elif hasattr(v, "dtype") and v.dtype == jnp.bool_:
                masked[key] = v & qvalid.reshape(
                    (-1,) + (1,) * (v.ndim - 1))
            else:
                masked[key] = v
        return masked

    return bfn


def _batch_lowering(a: Analysis, options: EngineOptions):
    """(batch_builder | None, batch_native, human-readable reason)."""
    qc = a.query_class
    batch_builder = BATCH_BUILDERS.get(qc)
    if batch_builder is None:
        return None, False, (f"vmap-of-scalar fallback (no native batch "
                             f"builder registered for class {qc.value})")
    if options.dist is not None:
        spec = options.dist
        mesh = dict(zip(spec.axes, spec.mesh_shape))
        return batch_builder, True, (
            f"native sharded (distributed fused flat scan: "
            f"{spec.num_shards} shard(s) over mesh {mesh}, "
            f"merge depth {spec.merge_depth})")
    if options.join_lowering == "perleft" and qc in JOIN_LOWERING_FAMILIES:
        return None, False, "vmap-of-scalar fallback (perleft join lowering)"
    if qc in JOIN_LOWERING_FAMILIES:
        return batch_builder, True, ("native (bind sets x left rows "
                                     "flattened into one kernel-level "
                                     "query batch)")
    return batch_builder, True, ("native (query-tiled kernels / "
                                 "multi-cluster probes)")


def _validate_dist(options: EngineOptions) -> None:
    """Reject option combinations the sharded lowering cannot honor.

    The distributed lowering is the exact fused flat scan (index probes are
    bypassed — DESIGN.md §10), so the approximate comparison engines
    (pase / vbase / brute_sort), whose measured inefficiency lives in the
    bypassed plan structure, and the perleft join baseline cannot compose
    with it."""
    if options.dist is None:
        return
    if options.engine not in ("chase", "brute"):
        raise ValueError(
            f"EngineOptions.dist runs the exact distributed flat scan and "
            f"only composes with engine 'chase' or 'brute', not "
            f"{options.engine!r} (the comparison engines' plan-structural "
            f"inefficiencies would be silently bypassed)")
    if options.join_lowering != "batch":
        raise ValueError(
            "EngineOptions.dist requires join_lowering='batch': the sharded "
            "lowering IS a query-batched scan (left rows ride the shard x "
            "tile composition); the perleft loop has no sharded twin")


def _validate_live(a: Analysis, catalog: Catalog,
                   options: EngineOptions) -> None:
    """Reject option combinations the live-corpus lowering cannot honor.

    The delta merge composes with the exact paths only: the comparison
    engines (pase / vbase / brute_sort) model *plan-structural*
    inefficiencies of the frozen lowering, and the perleft join baseline
    has no delta twin — same restriction (and same reasoning) as the
    distributed lowering (:func:`_validate_dist`)."""
    if catalog.live_for(*_scan_of(a)) is None:
        return
    if options.engine not in ("chase", "brute"):
        raise ValueError(
            f"a live corpus is attached to {'.'.join(_scan_of(a))} and only "
            f"composes with engine 'chase' or 'brute', not "
            f"{options.engine!r}")
    if options.join_lowering != "batch":
        raise ValueError(
            "a live corpus requires join_lowering='batch': the delta merge "
            "rides the query-batched lowering; the perleft loop has no "
            "live twin")


def _validate_quant(options: EngineOptions) -> None:
    """Reject option combinations the quantized lowering cannot honor.

    The quantized scan IS the fused batched kernel path (DESIGN.md §13):
    no jnp twin exists, and the comparison engines' plan-structural
    inefficiencies would be silently bypassed — same restriction (and
    same reasoning) as the distributed lowering (:func:`_validate_dist`).
    IVF probes stay fp32-exact under quant (their key-dependent
    early-stop would be perturbed), so engine 'chase' composes: flat
    scans quantize, probes do not."""
    if options.quant is None:
        if options.rescore_factor < 1:
            raise ValueError(
                f"EngineOptions.rescore_factor must be >= 1, got "
                f"{options.rescore_factor}")
        return
    from ..data.quantized import MODES
    if options.quant not in MODES:
        raise ValueError(
            f"EngineOptions.quant must be one of {MODES} (or None), got "
            f"{options.quant!r}")
    if not options.use_pallas:
        raise ValueError(
            "EngineOptions.quant requires use_pallas=True: the quantized "
            "lowering IS the fused kernel path (no jnp twin)")
    if options.engine not in ("chase", "brute"):
        raise ValueError(
            f"EngineOptions.quant is exact (fused fp32 rescore) and only "
            f"composes with engine 'chase' or 'brute', not "
            f"{options.engine!r}")
    if options.join_lowering != "batch":
        raise ValueError(
            "EngineOptions.quant requires join_lowering='batch': the "
            "quantized kernels are query-batched; the perleft loop has no "
            "quantized twin")
    if options.rescore_factor < 1:
        raise ValueError(
            f"EngineOptions.rescore_factor must be >= 1, got "
            f"{options.rescore_factor}")


def _single_via_batch(bfn: Callable) -> Callable:
    """Single-query front for distributed / live / quantized plans.

    These plans have ONE lowering — the query-batched scan — so the
    single-query pipeline runs it at Q=1 and slices the leading axis off
    every output leaf (bit-identical to a one-element batch; no separate
    single-query shard_map to compile or maintain)."""

    def fn(arrays, binds):
        stacked = {k: jnp.asarray(v)[None] for k, v in binds.items()}
        out = _without_fallback(bfn(arrays, stacked))
        return jax.tree.map(lambda v: v[0], out)

    return fn


def compile_query(sql: str, catalog: Catalog,
                  options: EngineOptions | None = None,
                  **static_binds) -> CompiledQuery:
    """Parse, analyze, rewrite, select physical operators, and jit.

    ``static_binds`` resolve parameters that shape the computation (K values).
    Runtime parameters (query vectors, radii, filter constants) are passed at
    call time and are traced, so re-running with a new query vector reuses the
    compiled executable — the production serving pattern.

    This is the legacy one-shot front door; the session API
    (:func:`repro.api.connect`) routes through :func:`compile_plan` with a
    normalized plan cache in front, so textual variants of one query share
    one compilation.  Each ``compile_query`` call compiles fresh."""
    options = options or EngineOptions()
    plan = parse_sql(sql)
    return compile_plan(sql, plan, catalog, options, static_binds)


def compile_plan(sql: str, plan: PlanNode, catalog: Catalog,
                 options: EngineOptions, static_binds: dict) -> CompiledQuery:
    """Compile an already-parsed logical plan (the plan-cache entry point —
    ``Database.prepare`` parses once for fingerprinting, then compiles the
    same tree only on a cache miss)."""
    a = analyze(plan, catalog)
    if a.query_class == QueryClass.NON_HYBRID:
        raise NotImplementedError(
            "plan did not match a hybrid pattern; use the interpreter engine")
    _validate_dist(options)
    _validate_live(a, catalog, options)
    _validate_quant(options)
    rewritten = rewrite(a)
    arrays = _gather_arrays(a, catalog, options)
    batch_builder, batch_native, batch_reason = _batch_lowering(a, options)
    if (options.dist is not None or options.quant is not None
            or catalog.live_for(*_scan_of(a)) is not None):
        # one lowering per dist, live, OR quant plan: the batched pipeline
        # (which carries the delta merge / shard composition / quantized
        # rescore) serves the single-query path at Q=1 (_single_via_batch)
        bfn = batch_builder(a, catalog, options, Bindings(static_binds))
        fn = _single_via_batch(bfn)
    else:
        builder = BUILDERS[a.query_class]
        fn = builder(a, catalog, options, Bindings(static_binds))
        if batch_native:
            bfn = batch_builder(a, catalog, options, Bindings(static_binds))
        else:
            bfn = _vmap_fallback(fn)
    compiled_plan = CompiledPlan(sql, a, plan, rewritten, options, fn, bfn,
                                 batch_native, batch_reason)
    executor = BucketedExecutor(compiled_plan, arrays)
    # snapshot AFTER _gather_arrays: gathering a dist plan may itself
    # register a sharded handle (a version bump this plan must not see as
    # staleness on its first execute)
    dep_keys = _catalog_dep_keys(a, catalog, options)
    return CompiledQuery(compiled_plan, jax.jit(fn), arrays,
                         jax.jit(lambda arrs, binds: _without_fallback(
                             bfn(arrs, binds))),
                         executor, _catalog=catalog, _dep_keys=dep_keys,
                         _bound_versions=catalog.version_snapshot(dep_keys))
