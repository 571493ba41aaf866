"""One benchmark cell, one seed, one measured window.

``run.py`` is the command; this module is its body, kept importable so that
the CPU rehearsal tests can drive it with the device check stubbed and the
sizes shrunk.  A cell is found by name: ``BENCHMARK.json`` names its
configuration and traffic mix, ``bench/configs/<config>.json`` holds the
deployment, ``bench/traffic/<traffic>.json`` the mix, and each metric is
read by ``bench/metrics/<metric>.py``.  Nothing here names a cell.

A run: make the data on the device from the configuration's ``data_seed``,
build the index the configuration asks for, prepare the statement, open
``Database.serve``, make every request (ordered by the run's seed), warm
the mix's buckets (all of this is ``setup_s``), then drive ``submit`` /
``poll`` / ``result`` from one thread for the window, answer what is still
queued, read the device's peak memory, free the program, and compare a
seeded sample of the answers with the plain reference under
``bench/checks/``.  Nothing here names a bind, a column or an answer key
but ``probes``: the mix file and its check module hold what a statement
binds and answers.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoDevice(RuntimeError):
    """No TPU, too few chips, or Pallas in interpret mode."""


def check_device(chips: int) -> list:
    """The devices to run on; raises :class:`NoDevice` rather than fall
    back to the CPU or to interpreted kernels."""
    import jax
    from repro.kernels import default_interpret
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    if default_interpret():
        raise NoDevice("Pallas kernels would run in interpret mode")
    return devices[:chips]


# ---------------------------------------------------------------------------
# finding a cell by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    root: str               # the checkout whose bench/ files it came from
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, root, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def _load(path: str, key: str):
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    return _load(os.path.join(root, "bench", kind, name + ".py"),
                 f"bench_{kind}_{name.replace('.', '_')}_{abs(hash(root))}")


def own(name: str):
    """``bench/<name>.py`` by its path (``trace`` would otherwise meet the
    standard library's module of that name)."""
    return _load(os.path.join(BENCH, name + ".py"), f"bench_{name}")


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class GcClock:
    """Counts the garbage collector's passes and their longest pause."""

    def __init__(self):
        self.count = 0
        self.longest_s = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.count += 1
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._on_gc)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations, and counts
    the events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.count += 1


def seed_key(seed: int):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    import jax
    state = np.random.SeedSequence(seed).generate_state(1)[0]
    return jax.random.key(int(state) & 0x7FFFFFFF)


@dataclasses.dataclass
class World:
    dataset: object
    server: object          # Database.serve over the prepared statement
    requests: object
    setup: dict             # set-up seconds by part


def build_world(cell: Cell, seed: int, seconds: float,
                clock: CompileClock) -> World:
    import jax
    from repro.api import connect
    from repro.core import EngineOptions
    from repro.index import build_ivf
    from repro.index.ivf import ProbeConfig
    traffic_gen = own("traffic")

    cfg, tr = cell.config, cell.traffic
    parts = {}
    # one corpus per configuration, as a deployment holds
    key = seed_key(cfg["data_seed"])
    t = time.perf_counter()
    ds = load_module("datasets", cfg["dataset"], cell.root).build(cfg, key)
    jax.block_until_ready(ds.corpus)
    parts["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if cfg.get("index"):
        ix = cfg["index"]
        if ix["kind"] != "ivf":
            raise ValueError(f"unknown index kind {ix['kind']!r}")
        index = build_ivf(jax.random.fold_in(key, 1), ds.corpus,
                          nlist=ix["nlist"], iters=ix["kmeans_iters"])
        jax.block_until_ready(index.lists)
        _say(f"index ivf nlist={index.nlist} cap={index.cap} "
             f"mean_list={cfg['rows'] / index.nlist}")
        for table, column in ix["on"]:
            ds.catalog.register_index(table, column, index)
    parts["index_s"] = time.perf_counter() - t
    t = time.perf_counter()
    requests = traffic_gen.generate(tr, ds, cfg["data_seed"], seed, seconds)
    parts["requests_s"] = time.perf_counter() - t
    t = time.perf_counter()
    probe = {"probe": ProbeConfig(**cfg["probe"])} if "probe" in cfg else {}
    options = EngineOptions(**probe, **cfg["engine"])
    db = connect(ds.catalog, options)
    server = db.serve(db.prepare(tr["sql"]), **tr["server"])
    server.warm(requests.binds[0], tr["warm_batches"])
    gc.collect()        # set-up's garbage, before drive() freezes the rest
    parts["prepare_warm_s"] = time.perf_counter() - t
    return World(ds, server, requests, parts)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Timeline:
    """What the window did, on the host's ``perf_counter`` clock."""
    t0: float
    end: float
    due: np.ndarray         # per attempted request
    sent: np.ndarray
    start: np.ndarray       # start of the drain that answered it
    done: np.ndarray        # its sliced result on the host (nan: none)
    ok: np.ndarray
    answers: dict           # per attempted request: each array of the
                            # answer but stats, and probes
    drains: list            # (start, end, requests) of drains in the window
    counters: tuple         # scheduler counters at the open and the close
    compiles: int           # compile events inside the window


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _answer_slots(out: dict, n: int, answers: dict) -> list:
    """An (n, ...) array in ``answers`` for each array-valued key of the
    first answer but ``stats``, shaped and typed as that answer's value;
    rows of requests that get no answer stay zero."""
    slots = []
    for key, value in out.items():
        if key != "stats" and hasattr(value, "shape"):
            answers[key] = np.zeros((n, *value.shape), value.dtype)
            slots.append((key, answers[key]))
    return slots


def drive(world: World, cell: Cell, seconds: float,
          clock: CompileClock) -> Timeline:
    server, req = world.server, world.requests
    n = len(req.binds)
    due = np.full(n, np.nan)
    sent, start, done = (np.full(n, np.nan) for _ in range(3))
    ok = np.zeros(n, bool)
    probes = np.zeros(n, np.int64)
    answers = {"probes": probes}
    slots: list = []            # (key, its (n, ...) array) of every answer
    rid_of: dict = {}           # queued request id -> request, oldest first
    drains: list = []
    max_wait_s = cell.traffic["server"]["max_wait_ms"] * 1e-3
    nxt = 0

    def submit(i: int, due_t: float):
        due[i] = due_t
        rid_of[server.submit(**req.binds[i])] = i
        sent[i] = time.perf_counter()

    def collect(rids, t_start: float) -> list:
        answered = []
        with _annotate("bench.result"):
            for rid in rids:
                i = rid_of.pop(rid)
                start[i] = t_start
                try:
                    out = server.result(rid)
                except Exception as e:          # noqa: BLE001 -- counted
                    print(f"request {i} failed: {e!r}", file=sys.stderr)
                else:
                    if not slots:
                        slots.extend(_answer_slots(out, n, answers))
                    for key, column in slots:
                        column[i] = out[key]
                    stats = out.get("stats", {})
                    probes[i] = int(stats.get("probes", 0))
                    ok[i] = True
                done[i] = time.perf_counter()
                answered.append(i)
        return answered

    # what set-up made lives through the window: out of the collector's
    # reach, so that no full collection walks it while requests wait
    gc.freeze()
    compiles0 = clock.count
    counters0 = dict(server.counters)
    gc_clock = GcClock()
    closed = req.offsets is None
    with _annotate("bench.window"):
        t0 = time.perf_counter()
        end = t0 + seconds
        if closed:
            with _annotate("bench.submit"):
                for _ in range(req.clients):
                    submit(nxt, t0)
                    nxt += 1
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if not closed and nxt < n and t0 + req.offsets[nxt] <= now:
                with _annotate("bench.submit"):
                    while nxt < n and t0 + req.offsets[nxt] <= now:
                        submit(nxt, t0 + req.offsets[nxt])
                        nxt += 1
            if server.due():
                with _annotate("bench.drain"):
                    t_start = time.perf_counter()
                    rids = server.poll()
                t_end = time.perf_counter()
                drains.append((t_start, t_end, len(rids)))
                answered = collect(rids, t_start)
                if closed:
                    # each client sends its next request once answered
                    if nxt + len(answered) > n:
                        raise RuntimeError("the request pool ran out: raise "
                                           "pool_per_s in the traffic file")
                    with _annotate("bench.submit"):
                        for i in answered:
                            submit(nxt, done[i])
                            nxt += 1
            else:
                # until the next arrival, or until the oldest queued
                # request's coalescing wait runs out
                wake = end
                if not closed and nxt < n:
                    wake = min(wake, t0 + req.offsets[nxt])
                if rid_of:
                    wake = min(wake, sent[next(iter(rid_of.values()))]
                               + max_wait_s)
                with _annotate("bench.sleep"):
                    time.sleep(max(0.0, wake - time.perf_counter()))
    counters1 = dict(server.counters)
    compiles = clock.count - compiles0
    gc_clock.close()
    _say(f"window_gc passes={gc_clock.count} "
         f"longest_ms={gc_clock.longest_s * 1e3}")
    # requests due inside the window that the generator had not sent yet
    if not closed:
        while nxt < n and req.offsets[nxt] < seconds:
            submit(nxt, t0 + req.offsets[nxt])
            nxt += 1
    t_start = time.perf_counter()
    collect(server.flush(), t_start)
    gc.unfreeze()
    m = nxt
    return Timeline(t0, end, due[:m], sent[:m], start[:m], done[:m], ok[:m],
                    {key: column[:m] for key, column in answers.items()},
                    drains, (counters0, counters1), compiles)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _sample(timeline: Timeline, n: int, seed: int) -> np.ndarray:
    """A seeded sample of the answered requests (every one where fewer)."""
    answered = np.flatnonzero(timeline.ok)
    if answered.size <= n:
        return answered
    rng = np.random.default_rng([seed, 0x5A3B1E])
    return np.sort(rng.choice(answered, n, replace=False))


def sampled_binds(requests, pick: np.ndarray) -> dict:
    """Each bind name -> its values stacked over the sampled requests."""
    return {name: np.stack([requests.binds[i][name] for i in pick])
            for name in requests.binds[0]}


def check(world: World, cell: Cell, timeline: Timeline, seed: int):
    """The compared numbers of a seeded sample, and their judgement.

    The mix's ``check`` names a module under ``bench/checks/`` that holds
    ``compare_run(dataset, binds, served, spec, guarantee, limits)`` (the
    compared numbers of the sampled answers), ``control_run(dataset,
    binds, spec, guarantee, precision, limits)`` (the same numbers of its
    reference computed one precision below, for ``readings.py``) and
    ``judge(numbers, limits)``.  ``binds`` and ``served`` map each bind
    name and answer key to its values over the sample; ``spec`` is the
    mix's ``check`` object; ``limits`` the configuration's."""
    spec = cell.traffic["check"]
    checker = load_module("checks", spec["module"], cell.root)
    pick = _sample(timeline, spec["sample"], seed)
    served = {k: v[pick] for k, v in timeline.answers.items()}
    numbers = checker.compare_run(world.dataset,
                                  sampled_binds(world.requests, pick),
                                  served, spec, cell.config["guarantee"],
                                  cell.config["limits"])
    numbers["failed"] = int((~timeline.ok).sum())
    limits = {"failed": {"max": 0}, **cell.config["limits"]}
    return numbers, checker.judge(numbers, limits)


@dataclasses.dataclass
class Record:
    """What a metric reader reads."""
    cell: Cell
    timeline: Timeline
    setup_s: float
    numbers: dict           # the compared numbers (recall, ...)
    trace: object           # trace.Reduced, or None without --trace 1
    peaks: dict             # bench/peaks.py row of the device


def read_metrics(record: Record, entries: list) -> dict:
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"],
                            record.cell.root).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _say(text: str):
    print(text, file=sys.stderr, flush=True)


def run(name: str, seed: int, seconds: float, trace: bool,
        root: str = ROOT, device_check=check_device,
        adjust=None) -> dict:
    """One run of one cell; returns the result line's object.

    ``adjust(cell)`` may change the loaded cell in place before anything is
    built (the CPU rehearsal tests shrink the sizes through it)."""
    import jax
    peak_table, trace_mod = own("peaks"), own("trace")

    cell = load_cell(name, root)
    if adjust is not None:
        adjust(cell)
    devices = device_check(cell.chips)
    clock = CompileClock()
    t_setup = time.perf_counter()
    c_setup = clock.seconds
    world = build_world(cell, seed, seconds, clock)
    setup_s = time.perf_counter() - t_setup
    parts = " ".join(f"{k}={v}" for k, v in world.setup.items())
    _say(f"setup_s={setup_s} {parts} compile_s={clock.seconds - c_setup} "
         f"requests={len(world.requests.binds)}")
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        timeline = drive(world, cell, seconds, clock)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak = _peak_bytes(devices)
    late = timeline.sent - timeline.due
    _say(f"window_compiles={timeline.compiles} attempted={timeline.ok.size} "
         f"drains={len(timeline.drains)} generator_late_ms "
         f"p50={np.median(late) * 1e3} p99={np.quantile(late, 0.99) * 1e3} "
         f"max={late.max() * 1e3}")
    probes = timeline.answers["probes"][timeline.ok]
    _say(f"probes max={probes.max(initial=0)} "
         f"over_min={np.count_nonzero(probes > probes.min(initial=0))}")
    longest = sorted(range(len(timeline.drains)),
                     key=lambda j: timeline.drains[j][0]
                     - timeline.drains[j][1])[:3]
    words = []
    for j in longest:
        s, e, n = timeline.drains[j]
        mine = timeline.ok & (timeline.start == s)
        words.append(f"at={s - timeline.t0:.3f}s took={(e - s) * 1e3:.1f}ms "
                     f"size={n} max_probes="
                     f"{timeline.answers['probes'][mine].max(initial=0)}")
    _say("longest_drains " + " ".join(words))
    _say(f"memory_peak_bytes={peak}")
    # the program's state goes before the reference runs
    world.server = world.dataset.catalog = None
    gc.collect()
    numbers, verdict = check(world, cell, timeline, seed)
    reduced = trace_mod.load(TRACE_DIR) if trace else None
    if reduced is not None and reduced.drains:
        top = sorted(reduced.drains, key=lambda d: d[0] - d[1])[:3]
        _say("traced_longest_drains " + " ".join(
            f"took={(e - s) * 1e-6:.1f}ms busy={b * 1e-6:.1f}ms"
            for s, e, b in top))
    kind = devices[0].device_kind
    record = Record(cell, timeline, setup_s, numbers, reduced,
                    peak_table.peaks_for(kind) if trace else {})
    metrics = read_metrics(record, cell.per_layer if trace
                           else cell.end_to_end)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(ok for *_, ok in verdict),
              "attempted": int(timeline.ok.size),
              "failed": int(numbers["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = trace_mod.breakdown(reduced)
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim, _ in verdict}
    for n, v, lim, ok in verdict:
        _say(f"check {n} = {v} (limit {lim}) {'ok' if ok else 'FAILED'}")
    return result
