"""Dynamic batch scheduler — the serving front-end of the size-bucketed
execution stack (DESIGN.md §8).

Serving traffic does not arrive in fixed-size batches: requests trickle in,
and every distinct batch size Q used to cost a fresh trace while lock-step
IVF rounds made every query in a batch pay for its slowest straggler.  This
module closes both gaps on top of :class:`~repro.core.compiler.BucketedExecutor`:

* **Coalescing** (:class:`BatchScheduler`): arriving requests queue until the
  batch fills (``max_batch``) or the OLDEST queued request has waited
  ``max_wait_ms`` — the deadline rule — then the whole batch drains into the
  bucketed executor (padded to the enclosing power-of-two bucket, outputs
  sliced per request).  Any traffic pattern touches at most
  log2(max_batch)+1 executables per plan.
* **Effort bucketing** (:func:`run_effort_bucketed`): a two-phase defense
  against lock-step straggler coupling.  Phase 1 runs the whole batch with a
  small per-query ``probe_budget`` (the pilot); queries that terminate
  *naturally* under the pilot are final (a budget can only freeze a query at
  or past its budget, so ``probes < pilot`` proves natural termination, and
  per-query probe state is independent — phase-1 results for light queries
  are bit-identical to a full run).  Phase 2 re-runs only the heavy
  remainder — a smaller batch, so its extra rounds no longer drag the light
  majority through ``Q x B x cap`` gathers.  The merged result is
  bit-identical to the lock-step run.  Join plans effort-bucket at bind-set
  granularity through this API; heterogeneous join LEFT rows effort-bucket
  in their query-batch form (the PR-2 flattening: left rows ARE the query
  batch — benchmarks/q8_sched_qps.py measures exactly that shape).

Resilience (DESIGN.md §11): requests may carry **deadlines** and
**priorities**.  Expired requests are shed *before* compilation/execution
(:class:`~repro.serving.resilience.DeadlineExceededError` — no kernel time
is spent on a result nobody can use), a forming batch never waits past its
tightest member's deadline, and an execution that raises is contained to
its own batch — every member fails with the error, the queue keeps
draining.  :class:`ResilientScheduler` adds graceful degradation (a
:class:`~repro.serving.resilience.LoadController` stepping probe budgets
down under queue pressure) and fault-injection hooks on top.

A virtual-clock queueing simulation (:meth:`BatchScheduler.simulate`) backs
benchmarks/q8_sched_qps.py: arrivals advance on a virtual clock, service
times are measured wall-clock of the real batch executions.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Any, Callable

import jax

import numpy as np

from ..core.compiler import drain_span, span
from ..index.ivf import round_width
from .resilience import DeadlineExceededError, LoadController

# process-wide drain ids, so that the ``chase.drain`` spans of several
# schedulers in one trace stay distinct
_DRAIN_SEQ = itertools.count()


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Coalescing + effort-bucketing + deadline knobs.

    ``max_wait_ms`` bounds the queueing latency the scheduler may add: a
    request never waits more than ``max_wait_ms`` for co-batched company
    before execution starts (it may still wait for the server to free up).
    ``pilot_budget`` > 0 enables two-phase effort-bucketed IVF execution
    (cluster units; a sensible pilot is ``ProbeConfig.min_probes`` plus a
    few rounds' worth of clusters).  ``default_deadline_ms`` stamps every
    request submitted without an explicit deadline (None = no deadline);
    ``deadline_margin_ms`` drains a forming batch that much *before* its
    tightest member deadline (headroom for service time)."""
    max_batch: int = 64
    max_wait_ms: float = 2.0
    pilot_budget: int = 0
    default_deadline_ms: float | None = None
    deadline_margin_ms: float = 0.0


@dataclasses.dataclass
class _Request:
    """One queued request: binds + arrival/deadline/priority metadata."""
    rid: int
    binds: dict
    arrival: float
    deadline: float | None = None     # absolute, clock units (seconds)
    priority: int = 0                 # higher drains first


@dataclasses.dataclass
class SimRecord:
    """One simulated request's timeline (seconds, virtual clock)."""
    rid: int
    arrival: float
    start: float
    finish: float
    batch_size: int

    @property
    def latency(self) -> float:
        """Request latency (finish - arrival) in virtual-clock seconds."""
        return self.finish - self.arrival


def _leading_probes(stats: dict) -> np.ndarray:
    """Per-bind-set probe counters: joins report (Q, L) — reduce to the
    per-bind-set maximum (a bind set is heavy if ANY of its left rows is)."""
    probes = np.asarray(stats["probes"])
    if probes.ndim > 1:
        probes = probes.max(axis=tuple(range(1, probes.ndim)))
    return probes


def _pilot_info(pilot) -> "int | dict":
    """JSON-able form of a pilot budget (scalar int or array summary)."""
    if np.ndim(pilot) == 0:
        return int(pilot)
    arr = np.asarray(pilot)
    return {"min": int(arr.min()), "max": int(arr.max()),
            "shape": list(arr.shape)}


def run_effort_bucketed(compiled, binds: dict, pilot_budget=0, *,
                        advisor=None):
    """Two-phase effort-bucketed execution of a stacked bind batch.

    Returns ``(out, info)`` where ``out`` is bit-identical to
    ``compiled.execute_bucketed`` on the same binds (lock-step) and ``info``
    reports the phase split: ``n_light`` queries finished in the pilot,
    ``n_heavy`` re-ran in the (smaller) phase-2 batch.

    ``pilot_budget`` may be a scalar (the classic static pilot), a (Q,)
    per-bind-set array, or — for join plans — a (Q, L) per-left array (the
    runtime ``probe_budget`` lane of the compiled bucket executables, so no
    shape retraces beyond the first).  A bind set is heavy if ANY of its
    queries/left rows hit its own budget; phase 2 re-runs those sets
    unbudgeted, preserving bit-exactness unconditionally.

    With ``advisor`` (a :class:`~repro.opt.advisor.LoweringAdvisor`), the
    pilot comes from the stats-driven predictor instead (DESIGN.md §14): a
    cold or probe-less plan runs single-phase lock-step, a warmed plan gets
    a predicted scalar pilot or per-left budgets, and the merged counters
    are folded back into the advisor's stats store either way.  ``compiled``
    may be a core ``CompiledQuery`` or a session-API ``Statement``."""
    inner = getattr(compiled, "compiled", compiled)
    executor = compiled.executor
    decision = None
    if advisor is not None and getattr(advisor, "enabled", True):
        decision = advisor.advise_batch(inner, binds)
        pilot_budget = (decision.pilot if decision.pilot is not None else 0)
    scalar_pilot = np.ndim(pilot_budget) == 0
    if scalar_pilot and pilot_budget <= 0 and advisor is None:
        raise ValueError("pilot_budget must be positive")
    t0 = time.perf_counter()
    if not compiled.batch_native:
        # the vmap-of-scalar fallback has no probe_budget lane: a pilot run
        # would execute the FULL unbudgeted batch and classify every query
        # heavy — strictly more work than lock-step.  Run single-phase.
        out = executor(binds)
        qn = _leading_probes(out["stats"]).shape[0]
        info = {"n_light": qn, "n_heavy": 0,
                "pilot_budget": _pilot_info(pilot_budget),
                "skipped": "plan has no native batched lowering"}
        return _observed(advisor, inner, decision, out, t0, info)
    if scalar_pilot and pilot_budget <= 0:
        # advisor-driven lock-step (cold plan, or no probe lane): one
        # phase, but the counters still feed the stats store
        out = executor(binds)
        qn = _leading_probes(out["stats"]).shape[0]
        info = {"n_light": qn, "n_heavy": 0, "pilot_budget": 0}
        return _observed(advisor, inner, decision, out, t0, info)
    if scalar_pilot:
        budget = int(pilot_budget)
    else:
        budget = np.asarray(pilot_budget, np.int32)
    out1 = executor(binds, probe_budget=budget)
    probes = np.asarray(out1["stats"]["probes"])
    limit = budget
    if not scalar_pilot and probes.ndim == 2 and np.ndim(budget) == 1:
        limit = np.asarray(budget)[:, None]   # per-bind-set vs (Q, L) stats
    hit = probes >= limit
    if hit.ndim > 1:
        hit = hit.any(axis=tuple(range(1, hit.ndim)))
    heavy = np.nonzero(hit)[0]
    qn = probes.shape[0]
    info = {"n_light": int(qn - heavy.size), "n_heavy": int(heavy.size),
            "pilot_budget": _pilot_info(budget)}
    if heavy.size == 0:
        return _observed(advisor, inner, decision, out1, t0, info)
    # host-side gather: a jnp fancy-index would compile per heavy-set shape
    sub = {k: np.asarray(v)[heavy] for k, v in binds.items()}
    out2 = executor(sub)
    out1 = jax.tree.map(np.asarray, out1)
    out2 = jax.tree.map(np.asarray, out2)

    def scatter(a, b):
        merged = np.array(a)
        merged[heavy] = b
        return merged

    merged = jax.tree.map(scatter, out1, out2)
    return _observed(advisor, inner, decision, merged, t0, info)


def _observed(advisor, inner, decision, out, t0: float, info: dict):
    """Fold the finished execution into the advisor (if any) and attach the
    decision summary to ``info`` under ``"opt"``."""
    if advisor is not None and decision is not None:
        latency_ms = (time.perf_counter() - t0) * 1e3
        advisor.observe(inner, decision, out, latency_ms)
        info["opt"] = decision.summary()
    return out, info


class BatchScheduler:
    """Coalesce arriving requests into size-bucketed batch executions.

    Online surface: ``submit(**binds)`` enqueues and returns a request id;
    ``poll()`` drains a batch when due (full, or the oldest request's
    ``max_wait_ms`` deadline expired); ``flush()`` drains everything;
    ``result(rid)`` returns that request's sliced outputs.  One scheduler
    serves one compiled plan (the serving deployment unit).

    ``compiled`` is anything exposing the execution contract —
    ``_stack_binds`` / ``executor`` / ``batch_native`` — i.e. a legacy
    :class:`~repro.core.compiler.CompiledQuery` or a session-API
    :class:`~repro.api.Statement` (``Database.serve`` constructs the latter;
    a Statement additionally translates renamed bind parameters onto the
    cached plan before stacking)."""

    def __init__(self, compiled, config: SchedulerConfig | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 advisor=None):
        self.compiled = compiled
        # None-sentinel, NOT a `config=SchedulerConfig()` default: a
        # class-level default dataclass would be one shared instance across
        # every scheduler ever constructed.
        self.config = config if config is not None else SchedulerConfig()
        self.clock = clock
        # optional repro.opt.LoweringAdvisor: replaces the static
        # pilot_budget with the stats-driven predictor (DESIGN.md §14)
        self.advisor = advisor
        self._queue: collections.deque[_Request] = collections.deque()
        self._results: dict[int, Any] = {}
        self._next_rid = 0
        # ``wait_s``: summed queue wait (take - submit, on ``clock``) of the
        # executed requests; ``probe_rounds`` / ``rows_gathered`` /
        # ``rows_scored``: lock-step IVF rounds, rows those rounds gathered
        # (pad and frozen queries included) and rows actually scored
        self.counters = {"submitted": 0, "executed": 0, "batches": 0,
                         "shed_deadline": 0, "failed": 0, "wait_s": 0.0,
                         "probe_rounds": 0, "rows_gathered": 0,
                         "rows_scored": 0}

    # -- online API ---------------------------------------------------------

    def submit(self, **binds) -> int:
        """Enqueue a request with default deadline/priority (back-compat
        surface; see :meth:`submit_request` for the full contract)."""
        return self.submit_request(binds)

    def submit_request(self, binds: dict, *, deadline_ms: float | None = None,
                       deadline: float | None = None,
                       priority: int = 0) -> int:
        """Enqueue a request and return its id.

        ``deadline_ms`` is relative to now; ``deadline`` is absolute in
        clock units (seconds) and wins when both are given.  Without either,
        ``config.default_deadline_ms`` applies (None = never expires).
        Higher ``priority`` drains first; ties drain in arrival order."""
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock()
        if deadline is None:
            if deadline_ms is None:
                deadline_ms = self.config.default_deadline_ms
            if deadline_ms is not None:
                deadline = now + deadline_ms * 1e-3
        self._queue.append(_Request(rid, binds, now, deadline, priority))
        self.counters["submitted"] += 1
        return rid

    def pending(self) -> int:
        """Number of requests queued (submitted, not yet drained/shed)."""
        return len(self._queue)

    def due(self, now: float | None = None) -> bool:
        """Drain rule: full batch, OR the oldest request waited out its
        ``max_wait_ms`` coalescing window, OR the tightest queued deadline
        is within ``deadline_margin_ms`` — a batch never idles past the
        point where one of its members would expire."""
        if not self._queue:
            return False
        if len(self._queue) >= self.config.max_batch:
            return True
        now = self.clock() if now is None else now
        oldest = self._queue[0].arrival
        if (now - oldest) * 1e3 >= self.config.max_wait_ms:
            return True
        deadlines = [r.deadline for r in self._queue if r.deadline is not None]
        if deadlines:
            margin = self.config.deadline_margin_ms * 1e-3
            return now >= min(deadlines) - margin
        return False

    def shed_expired(self, now: float | None = None) -> list[int]:
        """Drop every queued request whose deadline has passed (strict
        ``now > deadline`` — a drain at exactly the deadline still serves).
        Each shed rid completes with a stored
        :class:`~repro.serving.resilience.DeadlineExceededError` that
        :meth:`result` re-raises; no kernel time is spent on them."""
        if not self._queue:
            return []
        now = self.clock() if now is None else now
        shed: list[int] = []
        keep: collections.deque[_Request] = collections.deque()
        for r in self._queue:
            if r.deadline is not None and now > r.deadline:
                self._results[r.rid] = DeadlineExceededError(
                    r.rid, (now - r.deadline) * 1e3)
                shed.append(r.rid)
            else:
                keep.append(r)
        if shed:
            self._queue = keep
            self.counters["shed_deadline"] += len(shed)
        return shed

    def poll(self, now: float | None = None) -> list[int]:
        """Shed expired requests, then drain ONE batch if due; returns the
        completed request ids (shed rids included — their results raise)."""
        now = self.clock() if now is None else now
        done = self.shed_expired(now)
        if self.due(now):
            done.extend(self._drain(now))
        return done

    def flush(self, now: float | None = None) -> list[int]:
        """Drain everything queued, one max_batch execution at a time."""
        now = self.clock() if now is None else now
        done = self.shed_expired(now)
        while self._queue:
            done.extend(self._drain(now))
        return done

    def result(self, rid: int):
        """Pop the request's outcome: sliced outputs, or — for a shed or
        failed request — re-raise its stored exception."""
        out = self._results.pop(rid)
        if isinstance(out, BaseException):
            raise out
        return out

    # -- execution ----------------------------------------------------------

    def _take(self) -> list[_Request]:
        """Pop up to max_batch requests, highest priority first (arrival
        order within a priority level, and the all-default-priority path
        stays pure FIFO)."""
        take = min(len(self._queue), self.config.max_batch)
        if any(r.priority for r in self._queue):
            ordered = sorted(self._queue,
                             key=lambda r: (-r.priority, r.arrival, r.rid))
            chosen = {r.rid for r in ordered[:take]}
            entries = [r for r in self._queue if r.rid in chosen]
            self._queue = collections.deque(
                r for r in self._queue if r.rid not in chosen)
            return entries
        return [self._queue.popleft() for _ in range(take)]

    def _drain(self, now: float | None = None) -> list[int]:
        now = self.clock() if now is None else now
        done = self.shed_expired(now)
        if not self._queue:
            return done
        entries = self._take()
        taken = self.clock()
        bucket = self.compiled.executor.bucket_for(len(entries))
        with drain_span(next(_DRAIN_SEQ), len(entries), bucket):
            try:
                out = self.execute([r.binds for r in entries])
            except Exception as e:
                # fault containment: the failure is scoped to this batch —
                # every member completes with the error, the queue keeps
                # draining, and nothing is left dangling (no hangs).
                for r in entries:
                    self._results[r.rid] = e
                self.counters["failed"] += len(entries)
            else:
                with span("chase.slice"):
                    for i, r in enumerate(entries):
                        self._results[r.rid] = self._slice(out, i)
                self._count(entries, taken, out, bucket)
        return done + [r.rid for r in entries]

    def _count(self, entries: list[_Request], taken: float, out,
               bucket: int) -> None:
        """Counters of one executed drain.  The IVF probes advance
        lock-step by one round width per round until the batch's slowest
        query stops, so the rounds are its probes over the width."""
        c = self.counters
        c["executed"] += len(entries)
        c["batches"] += 1
        c["wait_s"] += sum(taken - r.arrival for r in entries)
        data = getattr(out, "data", out)
        stats = data.get("stats") if isinstance(data, dict) else None
        index = self.compiled.executor.arrays.get("index")
        if not stats or "probes" not in stats or index is None:
            return
        probes = np.asarray(stats["probes"])
        width = round_width(index, self.compiled.executor.plan.options.probe)
        rounds = -(-int(probes.max(initial=0)) // width)
        if rounds == 0:
            return                      # the plan probed no IVF index
        lanes = bucket * (probes.size // max(probes.shape[0], 1))
        c["probe_rounds"] += rounds
        c["rows_gathered"] += rounds * lanes * width * index.cap
        c["rows_scored"] += int(np.asarray(stats["distance_evals"]).sum())

    def _slice(self, out, i: int):
        """Extract request ``i``'s view of a batch output (overridable —
        :class:`ResilientScheduler` slices structured ResultBatch)."""
        return jax.tree.map(lambda v: v[i], out)

    def execute(self, binds_list: list[dict]):
        """Execute one coalesced batch through the bucketed executor
        (effort-bucketed when ``pilot_budget`` > 0; advisor-predicted
        budgets replace the static pilot when an ``advisor`` is attached)."""
        binds = self.compiled._stack_binds(binds_list, {})
        if self.advisor is not None:
            out, _info = run_effort_bucketed(self.compiled, binds,
                                             self.config.pilot_budget,
                                             advisor=self.advisor)
            return out
        if self.config.pilot_budget > 0:
            out, _info = run_effort_bucketed(self.compiled, binds,
                                             self.config.pilot_budget)
            return out
        return self.compiled.executor(binds)

    def warm(self, sample_binds: dict, batch_sizes: list[int]) -> None:
        """Pre-trace the bucket executables a traffic mix will touch (keeps
        compile time out of latency measurements and first requests).

        With ``pilot_budget`` > 0 both per-bucket variants are traced — the
        budgeted phase-1 executable AND the unbudgeted phase-2 one — since
        whether a drain reaches phase 2 depends on the data (all-identical
        warm batches may never produce a heavy remainder)."""
        for b in sorted({self.compiled.executor.bucket_for(s)
                         for s in batch_sizes}):
            stacked = self.compiled._stack_binds([sample_binds] * b, {})
            self.compiled.executor(stacked)
            if self.config.pilot_budget > 0 and self.compiled.batch_native:
                self.compiled.executor(stacked,
                                       probe_budget=self.config.pilot_budget)

    # -- virtual-clock simulation -------------------------------------------

    def simulate(self, arrivals: np.ndarray,
                 binds_list: list[dict]) -> list[SimRecord]:
        """Single-server queueing simulation of the coalescing policy.

        ``arrivals`` are request arrival times in seconds (sorted ascending,
        virtual clock); ``binds_list`` the matching per-request binds.  Batch
        formation follows the deadline rule; service time is the measured
        wall-clock of the REAL batch execution (warm the buckets first).
        Returns per-request :class:`SimRecord` timelines."""
        n = len(arrivals)
        assert len(binds_list) == n
        wait_s = self.config.max_wait_ms * 1e-3
        server_free = 0.0
        records: list[SimRecord] = []
        i = 0
        while i < n:
            deadline = arrivals[i] + wait_s
            close = max(deadline, server_free)
            j = i
            while (j < n and arrivals[j] <= close
                   and (j - i) < self.config.max_batch):
                j += 1
            if j - i >= self.config.max_batch:
                # the batch filled before the window closed
                start = max(server_free, float(arrivals[j - 1]))
            else:
                start = close
            t0 = time.perf_counter()
            out = self.execute(binds_list[i:j])
            jax.block_until_ready(jax.tree.leaves(getattr(out, "data", out))[0])
            exec_s = time.perf_counter() - t0
            finish = start + exec_s
            for r in range(i, j):
                records.append(SimRecord(r, float(arrivals[r]), start,
                                         finish, j - i))
            server_free = finish
            i = j
        return records


class ResilientScheduler(BatchScheduler):
    """Deadline scheduler + graceful degradation + fault injection.

    Serves a session-API :class:`~repro.api.Statement` (required — the
    structured-result surface is what carries degraded-mode reporting).
    On every drain the :class:`~repro.serving.resilience.LoadController`
    observes the pre-drain queue depth and picks an effort level; level
    L > 0 caps batched IVF executions at the policy's per-query
    ``probe_budget`` (trading recall for goodput) and the served results'
    ``explain()`` reports ``degraded``.  A
    :class:`~repro.serving.faults.FaultInjector`, when wired, wraps each
    batch execution (latency spikes, kernel errors, catalog bumps) —
    injected kernel errors are contained per batch like any real failure.
    """

    def __init__(self, statement, config: SchedulerConfig | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 policy=None, faults=None):
        super().__init__(statement, config, clock)
        self.load = LoadController(policy)
        self.faults = faults

    @property
    def statement(self):
        """The served Statement (alias of the scheduler's compiled slot)."""
        return self.compiled

    def execute(self, binds_list: list[dict]):
        # local import: repro.api imports this module at package init
        from ..api.hints import ExecutionHints
        from ..api.result import ResultBatch

        depth = self.pending() + len(binds_list)  # pre-drain queue depth
        level = self.load.observe(depth)
        budget = self.load.probe_budget()
        if budget is not None and self.compiled.batch_native:
            hints = ExecutionHints(probe_budget=budget)
        elif self.config.pilot_budget > 0:
            hints = ExecutionHints(pilot_budget=self.config.pilot_budget)
        else:
            hints = None
        run = lambda bl: self.compiled.execute(bl, hints=hints)
        if self.faults is not None:
            run = self.faults.wrap(run)
        out = run(binds_list)
        if level > 0 and isinstance(out, ResultBatch):
            info = {"level": level, "probe_budget": budget}
            base_fn = out._explain_fn
            out = ResultBatch(out.data,
                              lambda: dataclasses.replace(base_fn(),
                                                          degraded=info),
                              len(out))
        return out

    def _slice(self, out, i: int):
        if hasattr(out, "query"):
            return out.query(i)
        return super()._slice(out, i)

    def warm(self, sample_binds: dict, batch_sizes: list[int]) -> None:
        """Also pre-trace the probe-budgeted executables degraded drains
        run (a load transition must not pay a compile on the hot path —
        that latency spike is exactly what degradation is fighting)."""
        super().warm(sample_binds, batch_sizes)
        if self.load.policy.steps and self.compiled.batch_native:
            budget = self.load.policy.steps[-1][1]
            ex = self.compiled.executor
            for b in sorted({ex.bucket_for(s) for s in batch_sizes}):
                stacked = self.compiled._stack_binds([sample_binds] * b, {})
                ex(stacked, probe_budget=budget)

    def snapshot(self) -> dict:
        """Scheduler counters + load-controller state (+ fault counters)."""
        snap = {**self.counters, "load": self.load.snapshot()}
        if self.faults is not None:
            snap["faults"] = self.faults.snapshot()
        return snap


def latency_stats(records: list[SimRecord]) -> dict:
    """p50/p95/mean latency (ms) + throughput (QPS) of a simulation run."""
    lats = np.asarray([r.latency for r in records]) * 1e3
    span = max(r.finish for r in records) - min(r.arrival for r in records)
    return {"p50_ms": round(float(np.percentile(lats, 50)), 3),
            "p95_ms": round(float(np.percentile(lats, 95)), 3),
            "mean_ms": round(float(lats.mean()), 3),
            "qps": round(len(records) / span, 1) if span > 0 else float("inf")}
