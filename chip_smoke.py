"""Chip smoke test: the hybrid-query engine's main path on a TPU, at the
paper's §7.1 LAION scale (1,000,000 rows x 512-d CLIP-shaped embeddings,
inner product, IVF at ``configs/chase_laion.py``'s nlist / ProbeConfig).

One chip (default):

    python chip_smoke.py [--rows N] [--seed S]

builds the catalog from the seed, then runs through the front door
(``connect -> prepare -> Statement.execute`` and ``Database.serve``):

* Q1 (filtered VKNN, K=50, price selectivity 1.0 and 0.03), Q2 (range at
  the ~120-match radius), Q3 (distance join, queries x images) and Q5
  (category partition, K=10) for the 64 rows of the queries table, at b1
  (one request per query; for Q3 a one-row left table) and at b64 (one
  request), in three lanes:
  ``chase`` (default XLA lowering with IVF probes), ``pallas`` (brute
  flat scan on the fused Pallas kernels) and ``int8`` (the same on the
  int8 twin with fp32 rescore);
* a few requests through ``db.serve(stmt)`` (submit / flush / result);
* one AOT phase: a second session on the same ``aot_cache_path``
  re-prepares the Pallas-lane statement with zero traces.

Every lane is checked against a plain NumPy brute force over the same host
arrays: the pallas lane matches it (ids wherever the K-th/(K+1)-th margin
exceeds 1e-5, sims at rtol 1e-5), the int8 lane is bit-identical to the
pallas lane, and the IVF lane's recall@K is at least 0.9.  After the
phases the device itself is checked: a TPU, Pallas not in interpret mode,
and a Mosaic kernel (``tpu_custom_call``) in a compiled Pallas-lane bucket.

Four chips:

    python chip_smoke.py --chips 4

shards the same corpus over ``DistSpec(mesh_shape=(4,))`` and runs only Q1
and Q2 at b64 in the pallas lane, compared with the ``DistSpec((1,))`` plan,
the single-device plan and NumPy.

CPU rehearsal (every phase at a small size, Pallas in interpret mode; exits
non-zero at the device check):

    JAX_PLATFORMS=cpu python chip_smoke.py --rows 20000

The last line of stdout, printed only when every check passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.api import DistSpec, connect                         # noqa: E402
from repro.compile_cache import enable_compile_cache            # noqa: E402
from repro.configs.chase_laion import bench_config              # noqa: E402
from repro.core import EngineOptions                            # noqa: E402
from repro.data import make_laion_catalog                       # noqa: E402
from repro.index import build_ivf                               # noqa: E402
from repro.kernels import default_interpret                     # noqa: E402

PAPER_ROWS = 1_000_000      # §7.1: laion1m
B64 = 64                    # the batched bucket; also the queries table size
K_TOP, K_CAT = 50, 10       # §7.1: Q1/Q4 K, Q5/Q6 K
SELECTIVITIES = (1.0, 0.03)
EXCLUDED_CUISINE = 3
MARGIN = 1e-5               # id sets must match where the K/K+1 gap exceeds it
RTOL, ATOL = 1e-5, 1e-6     # sims vs NumPy
MIN_RECALL = 0.9            # IVF lane
AOT_DIR = os.path.join(ROOT, ".aot_cache")

Q1 = ("SELECT sample_id FROM products WHERE price < ${p} "
      "ORDER BY DISTANCE(embedding, ${qv}) LIMIT %d" % K_TOP)
Q2 = "SELECT sample_id FROM products WHERE DISTANCE(embedding, ${qv}) <= ${r}"
Q3 = """
SELECT {left}.id AS qid, images.sample_id AS tid
FROM {left} JOIN images
ON DISTANCE({left}.embedding, images.embedding) <= ${{r}}
AND images.capture_date > {left}.capture_date
"""
Q5 = """
SELECT qid, category FROM (
 SELECT sample_id AS qid, calorie_level AS category,
 RANK() OVER (PARTITION BY calorie_level
   ORDER BY DISTANCE(embedding, ${qv})) AS rank
 FROM recipes
 WHERE DISTANCE(embedding, ${qv}) <= ${r} AND cuisine <> ${ex}
) AS ranked WHERE ranked.rank <= %d
""" % K_CAT

LANES = {
    "chase": dict(engine="chase"),
    "pallas": dict(engine="brute", use_pallas=True),
    "int8": dict(engine="brute", use_pallas=True, quant="int8"),
}


# ---------------------------------------------------------------------------
# the catalog and its NumPy reference
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class World:
    cfg: object
    catalog: object
    corpus: np.ndarray          # (N, D) host copy of the device corpus
    qvecs: np.ndarray           # (64, D) the queries table's embeddings
    sims: np.ndarray            # (64, N) NumPy inner products
    price: np.ndarray
    capture: np.ndarray
    qcapture: np.ndarray
    cuisine: np.ndarray
    calorie: np.ndarray
    radius: float               # ~120 matches per query (§7.1)
    thresholds: dict            # selectivity -> price bound


def build_world(rows: int, seed: int, ivf: bool = True) -> World:
    """The catalog (with its IVF index unless ``ivf`` is False: only the
    ``chase`` lane probes it) and the NumPy reference arrays."""
    cfg = dataclasses.replace(bench_config(), n_rows=rows, n_queries=B64,
                              seed=seed)
    cat = make_laion_catalog(
        n_rows=cfg.n_rows, n_queries=cfg.n_queries, dim=cfg.dim,
        n_modes=cfg.n_modes, num_categories=cfg.num_categories,
        seed=cfg.seed, metric=cfg.metric)
    vec = cat.table("laion")["vec"]
    if ivf:
        idx = build_ivf(jax.random.key(cfg.seed), vec, nlist=cfg.nlist,
                        metric=cfg.metric, iters=cfg.kmeans_iters)
        for name in ("laion", "products", "images", "recipes", "movies"):
            for column in ("vec", "embedding"):
                cat.register_index(name, column, idx)
    # b1 join: a one-row left side (the join's left rows are its batch)
    cat.register("queries1", cat.table("queries").take(jnp.arange(1)))
    laion, queries = cat.table("laion"), cat.table("queries")
    corpus = np.asarray(vec)
    qvecs = np.asarray(queries["embedding"])
    sims = qvecs @ corpus.T
    target = cfg.range_match_target
    kth = np.partition(sims, -target, axis=1)[:, -target]
    price = np.asarray(laion["price"])
    return World(
        cfg, cat, corpus, qvecs, sims, price,
        np.asarray(laion["capture_date"]),
        np.asarray(queries["capture_date"]),
        np.asarray(laion["cuisine"]), np.asarray(laion["calorie_level"]),
        float(np.median(kth)),
        {s: (float("inf") if s >= 1.0 else float(np.quantile(price, s)))
         for s in SELECTIVITIES})


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

class Checks:
    def __init__(self):
        self.failures: list[str] = []

    def expect(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            print(f"FAIL {what}", flush=True)
        return ok


def topk_matches(ref: np.ndarray, mask: np.ndarray, k: int, ids, sims,
                 valid) -> bool:
    """One query's top-k against the NumPy reference row."""
    s = np.where(mask, ref, -np.inf)
    order = np.argsort(-s, kind="stable")[:k + 1]
    n = int(np.isfinite(s[order[:k]]).sum())
    got = np.asarray(ids)[np.asarray(valid)]
    if got.size != n or not mask[got].all():
        return False
    got_sims = np.asarray(sims)[np.asarray(valid)]
    if not (np.allclose(got_sims, ref[got], rtol=RTOL, atol=ATOL)
            and np.allclose(got_sims, s[order[:n]], rtol=RTOL, atol=ATOL)):
        return False
    if n == k and k < s.size and np.isfinite(s[order[k]]):
        if s[order[k - 1]] - s[order[k]] <= MARGIN:
            return True             # a near-tie at the boundary: sims suffice
    return set(got.tolist()) == set(order[:n].tolist())


def range_matches(ref: np.ndarray, mask: np.ndarray, r: float, cap: int,
                  ids, sims, valid, count) -> bool:
    """One query's range result against the NumPy reference row; rows
    within MARGIN of the radius may fall either way."""
    hit = mask & (ref >= r)
    near = mask & (np.abs(ref - r) <= MARGIN)
    got = np.asarray(ids)[np.asarray(valid)]
    if not (hit | near)[got].all() or len(set(got.tolist())) != got.size:
        return False
    if not np.allclose(np.asarray(sims)[np.asarray(valid)], ref[got],
                       rtol=RTOL, atol=ATOL):
        return False
    if abs(int(count) - int(hit.sum())) > int(near.sum()):
        return False
    if hit.sum() <= cap:
        return not (set(np.flatnonzero(hit & ~near).tolist())
                    - set(got.tolist()))
    return got.size == cap


def recall(got: set, want: set) -> float:
    return 1.0 if not want else len(got & want) / len(want)


# ---------------------------------------------------------------------------
# query classes: (statement SQL, binds at batch b, per-query checks)
# ---------------------------------------------------------------------------

def q1_binds(w: World, sel: float) -> list:
    return [{"qv": q, "p": w.thresholds[sel]} for q in w.qvecs]


def q1_check(w: World, out, sel: float, lane: str):
    mask = w.price < w.thresholds[sel]
    oks, recs = [], []
    for i in range(len(w.qvecs)):
        ids, sims, valid = out["ids"][i], out["sim"][i], out["valid"][i]
        if lane == "chase":
            s = np.where(mask, w.sims[i], -np.inf)
            top = np.argsort(-s)[:K_TOP]
            want = set(top[np.isfinite(s[top])].tolist())
            recs.append(recall(set(ids[valid].tolist()), want))
        else:
            oks.append(topk_matches(w.sims[i], mask, K_TOP, ids, sims, valid))
    return oks, recs


def q2_binds(w: World) -> list:
    return [{"qv": q, "r": w.radius} for q in w.qvecs]


def q2_check(w: World, out, lane: str):
    cap = w.cfg.probe.capacity
    mask = np.ones(w.price.shape, bool)
    oks, recs = [], []
    for i in range(len(w.qvecs)):
        ids, valid = out["ids"][i], out["valid"][i]
        if lane == "chase":
            want = set(np.flatnonzero(w.sims[i] >= w.radius).tolist())
            recs.append(recall(set(ids[valid].tolist()), want))
        else:
            oks.append(range_matches(w.sims[i], mask, w.radius, cap, ids,
                                     out["sim"][i], valid, out["count"][i]))
    return oks, recs


def q3_check(w: World, out, nleft: int, lane: str):
    """Join output of one bind set: (L, max_pairs) per left row."""
    cap = EngineOptions().max_pairs
    oks, recs = [], []
    for i in range(nleft):
        mask = w.capture > w.qcapture[i]
        ids, valid = out["tid"][0, i], out["valid"][0, i]
        if lane == "chase":
            want = set(np.flatnonzero(mask & (w.sims[i] >= w.radius))
                       .tolist())
            recs.append(recall(set(ids[valid].tolist()), want))
        else:
            oks.append(range_matches(w.sims[i], mask, w.radius, cap, ids,
                                     out["sim"][0, i], valid,
                                     out["count"][0, i]))
    return oks, recs


def q5_binds(w: World) -> list:
    return [{"qv": q, "r": w.radius, "ex": EXCLUDED_CUISINE}
            for q in w.qvecs]


def q5_check(w: World, out, lane: str):
    base = w.cuisine != EXCLUDED_CUISINE
    oks, recs = [], []
    for i in range(len(w.qvecs)):
        inrange = base & (w.sims[i] >= w.radius)
        for c in range(w.cfg.num_categories):
            mask = inrange & (w.calorie == c)
            ids, valid = out["ids"][i, c], out["valid"][i, c]
            if lane == "chase":
                s = np.where(mask, w.sims[i], -np.inf)
                top = np.argsort(-s)[:K_CAT]
                want = set(top[np.isfinite(s[top])].tolist())
                recs.append(recall(set(ids[valid].tolist()), want))
            else:
                # a row within MARGIN of the radius may fall either way
                cat = base & (w.calorie == c)
                oks.append(any(
                    topk_matches(w.sims[i], m, K_CAT, ids, out["sim"][i, c],
                                 valid)
                    for m in (mask, cat & (w.sims[i] >= w.radius - MARGIN),
                              cat & (w.sims[i] >= w.radius + MARGIN))))
    return oks, recs


def host(result) -> dict:
    """A result's output tree as NumPy (waits for the device)."""
    return jax.tree.map(np.asarray, dict(result.data))


def execute(stmt, binds: list, b: int) -> dict:
    """All of ``binds`` at batch size ``b``: one b64 request, or one b1
    request per bind set (recall@K is a mean over the query set)."""
    if b == B64:
        return host(stmt.execute(binds))
    outs = [host(stmt.execute([bi])) for bi in binds]
    return jax.tree.map(lambda *xs: np.concatenate(xs), *outs)


def same_bits(a: dict, b: dict) -> bool:
    """Bitwise equality of two output trees (counters excluded)."""
    keys = sorted(set(a) - {"stats"})
    return keys == sorted(set(b) - {"stats"}) and all(
        np.array_equal(a[k], b[k]) for k in keys)


def report(checks: Checks, name: str, oks: list, recs: list, lane: str):
    if lane == "chase":
        mean = float(np.mean(recs))
        print(f"recall {name}: {mean}", flush=True)
        checks.expect(mean >= MIN_RECALL,
                      f"{name}: IVF recall {mean} < {MIN_RECALL}")
    else:
        print(f"numpy_parity {name}: {sum(oks)}/{len(oks)}", flush=True)
        checks.expect(all(oks), f"{name}: {len(oks) - sum(oks)} of "
                                f"{len(oks)} queries differ from NumPy")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(w: World, checks: Checks):
    opts = {lane: EngineOptions(probe=w.cfg.probe, **kw)
            for lane, kw in LANES.items()}
    dbs = {lane: connect(w.catalog, o) for lane, o in opts.items()}
    outs = {}                          # (query, batch, lane) -> host tree
    for lane, db in dbs.items():
        q1 = db.prepare(Q1)
        q2 = db.prepare(Q2)
        q5 = db.prepare(Q5)
        q3 = {1: db.prepare(Q3.format(left="queries1")),
              B64: db.prepare(Q3.format(left="queries"))}
        for b in (1, B64):
            for sel in SELECTIVITIES:
                name = f"q1_sel{sel}/{lane}/b{b}"
                out = execute(q1, q1_binds(w, sel), b)
                report(checks, name, *q1_check(w, out, sel, lane), lane)
                outs[("q1", sel, b, lane)] = out
            name = f"q2/{lane}/b{b}"
            out = execute(q2, q2_binds(w), b)
            report(checks, name, *q2_check(w, out, lane), lane)
            outs[("q2", b, lane)] = out
            name = f"q3/{lane}/b{b}"
            out = host(q3[b].execute([{"r": w.radius}]))
            report(checks, name, *q3_check(w, out, b, lane), lane)
            outs[("q3", b, lane)] = out
            name = f"q5/{lane}/b{b}"
            out = execute(q5, q5_binds(w), b)
            report(checks, name, *q5_check(w, out, lane), lane)
            outs[("q5", b, lane)] = out
        if lane == "int8":
            # Q1 executions whose top-k certificate failed (fp32 rerun)
            print(f"int8 q1 quant_topk={q1.executor.quant_topk}", flush=True)
    for key in [k for k in outs if k[-1] == "int8"]:
        same = same_bits(outs[key], outs[key[:-1] + ("pallas",)])
        print(f"int8_vs_pallas {key[:-1]}: bit_identical={same}", flush=True)
        checks.expect(same, f"int8 lane differs from pallas lane at "
                            f"{key[:-1]}")

    pallas = dbs["pallas"].prepare(Q1)
    single = host(pallas.execute(q1_binds(w, 1.0)[0]))
    bucket = {k: v[0] for k, v in outs[("q1", 1.0, 1, "pallas")].items()
              if k != "stats"}
    same = same_bits(single, bucket)
    print(f"single_dict_vs_bucket1 q1: bit_identical={same}", flush=True)
    checks.expect(same, "q1 single-dict path differs from bucket 1")

    server = dbs["pallas"].serve(pallas, max_batch=B64)
    binds = q1_binds(w, 0.03)[:5]
    rids = [server.submit(**bi) for bi in binds]
    server.flush()
    answered = 0
    for i, rid in enumerate(rids):
        res = server.result(rid)
        answered += int(np.array_equal(
            np.asarray(res["ids"]),
            outs[("q1", 0.03, B64, "pallas")]["ids"][i]))
    print(f"serve answered={answered}/{len(rids)}", flush=True)
    checks.expect(answered == len(rids),
                  "served requests differ from the batch results")

    shutil.rmtree(AOT_DIR, ignore_errors=True)
    binds = q1_binds(w, 0.03)
    cold_db = connect(w.catalog, opts["pallas"], aot_cache_path=AOT_DIR)
    cold = cold_db.prepare(Q1)
    cold_out = host(cold.execute(binds))
    warm_db = connect(w.catalog, opts["pallas"], aot_cache_path=AOT_DIR)
    warm = warm_db.prepare(Q1)
    warm_out = host(warm.execute(binds))
    traces = sum(warm.executor.trace_counts.values())
    errors = (cold_db.cache_info().aot["errors"]
              + warm_db.cache_info().aot["errors"])
    same = (same_bits(warm_out, cold_out)
            and same_bits(warm_out, outs[("q1", 0.03, B64, "pallas")]))
    print(f"aot cold_traces={sum(cold.executor.trace_counts.values())} "
          f"warm_traces={traces} errors={errors} "
          f"loaded={dict(warm.executor.aot_loaded)} "
          f"bit_identical={same}", flush=True)
    checks.expect(traces == 0, f"AOT warm prepare traced {traces} times")
    checks.expect(errors == 0, f"AOT cache reported {errors} errors")
    checks.expect(same, "AOT warm results differ")
    return pallas


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(w: World, checks: Checks):
    checks.expect(len(jax.devices()) >= 4,
                  f"--chips 4 needs 4 devices, have {len(jax.devices())}")
    lane = dict(probe=w.cfg.probe, **LANES["pallas"])
    plans = {"single": EngineOptions(**lane),
             "dist1": EngineOptions(dist=DistSpec(mesh_shape=(1,)), **lane),
             "dist4": EngineOptions(dist=DistSpec(mesh_shape=(4,)), **lane)}
    cases = [(f"q1_sel{sel}", Q1, q1_binds(w, sel))
             for sel in SELECTIVITIES] + [("q2", Q2, q2_binds(w))]
    outs = {}
    for plan, o in plans.items():
        db = connect(w.catalog, o)
        for name, sql, binds in cases:
            outs[(name, plan)] = host(db.prepare(sql).execute(binds))
    for name, _, _ in cases:
        ref = outs[(name, "single")]
        for plan in ("dist1", "dist4"):
            got = outs[(name, plan)]
            exact = all(np.array_equal(got[k], ref[k])
                        for k in ("ids", "valid", "count") if k in ref)
            close = np.allclose(got["sim"], ref["sim"], rtol=RTOL, atol=ATOL)
            print(f"sharded {name} {plan}_vs_single: ids_valid_count_equal="
                  f"{exact} sims_close={close}", flush=True)
            checks.expect(exact and close, f"{name}: {plan} differs from "
                                           f"the single-device plan")
    for sel in SELECTIVITIES:
        report(checks, f"q1_sel{sel}/dist4/b{B64}",
               *q1_check(w, outs[(f"q1_sel{sel}", "dist4")], sel, "dist4"),
               "dist4")
    report(checks, f"q2/dist4/b{B64}",
           *q2_check(w, outs[("q2", "dist4")], "dist4"), "dist4")
    sharded = w.catalog.sharded_for("products", "embedding",
                                    DistSpec(mesh_shape=(4,)))
    devices = sharded.corpus.sharding.device_set
    rows = sorted(s.data.shape[0] for s in sharded.corpus.addressable_shards)
    print(f"sharded corpus: devices={len(devices)} rows_per_device={rows}",
          flush=True)
    checks.expect(len(devices) == 4, f"corpus spans {len(devices)} devices")
    checks.expect(len(rows) == 4 and rows[-1] - rows[0] <= 1
                  and rows[-1] <= -(-sharded.num_rows // 4) + 1,
                  f"corpus rows per device {rows} are not a quarter each")
    return None


# ---------------------------------------------------------------------------

def device_check(checks: Checks, pallas_stmt, w: World) -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}",
          flush=True)
    checks.expect(dev.platform == "tpu", f"ran on {dev.platform}, not a TPU")
    checks.expect(default_interpret() is False,
                  "Pallas kernels ran in interpret mode")
    if pallas_stmt is not None:
        binds = q1_binds(w, 0.03)
        text = pallas_stmt.compiled.lower_batch(binds).compile().as_text()
        checks.expect("tpu_custom_call" in text,
                      "no Mosaic kernel in the compiled Pallas-lane bucket")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"corpus rows (default: the paper's {PAPER_ROWS}; "
                         f"smaller sizes are CPU rehearsals)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded phase and its comparisons")
    args = ap.parse_args(argv)
    if args.rows is None and jax.default_backend() == "cpu":
        print("no accelerator: the paper-scale run needs a TPU (rehearse on "
              "CPU with --rows 20000)", file=sys.stderr)
        return 2
    enable_compile_cache()
    checks = Checks()
    w = build_world(args.rows or PAPER_ROWS, args.seed,
                    ivf=args.chips == 1)
    print(f"rows={w.corpus.shape[0]} dim={w.corpus.shape[1]} "
          f"queries={w.qvecs.shape[0]} "
          f"nlist={w.cfg.nlist if args.chips == 1 else None} "
          f"radius={w.radius} seed={args.seed}", flush=True)
    if args.chips == 4:
        pallas_stmt = run_four_chips(w, checks)
    else:
        pallas_stmt = run_one_chip(w, checks)
    info = device_check(checks, pallas_stmt, w)
    if checks.failures:
        print(f"{len(checks.failures)} check(s) failed:", file=sys.stderr)
        for f in checks.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
