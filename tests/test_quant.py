"""Quantized corpus scans (DESIGN.md §13): bit-parity with the fp32 path.

The EXACTNESS INVARIANT under test — ``EngineOptions.quant`` ('int8' /
'bf16') changes how many bytes the flat scan moves, never what it returns:

* **Q1-Q6 parity**: every query class, on both exact engines (brute and
  chase — IVF probes stay fp32, flat scans quantize), is BIT-identical to
  the fp32 path across batch sizes, the bucketed (pad-query) path, the
  exact-shape path, and the single-query front (which runs the batch
  lowering at Q=1 — so its reference is the fp32 *batched* execution);
* **adversarial corpora**: exact duplicates quantize identically and keep
  the fp32 lowest-id tie-break; near-tie rows whose differences vanish
  under quantization (sub-resolution for BOTH int8 and bf16) are ordered
  by the fused fp32 rescore, not by the quantized keys;
* **composition parity**: the sharded lowering at shards=1 and the
  live-delta lowering (insert / delete / compact — the main segment scans
  its quantized twin, the delta stays fp32) stay bit-identical to fp32;
* **zero-retrace rebind**: a re-registered twin and every live mutation
  re-bind through ``ensure_fresh`` without compiling anything
  (``trace_counts`` asserted);
* ``ExecutionHints.rescore_factor`` is compile-affecting (its own plan
  cache entry) and a wider replay changes nothing on an exact result;
* ``quantize_corpus`` honors the per-row contract (scale, half_step,
  all-zero rows, dequantized norms) and bad option combinations fail
  loud at compile time (``_validate_quant``).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionHints, connect
from repro.core import EngineOptions, Metric, compile_query
from repro.core.schema import Table
from repro.data import make_laion_catalog
from repro.data.mutations import attach_live
from repro.data.quantized import quantize_corpus
from repro.dist import DistSpec
from repro.index import build_ivf
from repro.index.ivf import ProbeConfig

PROBE = ProbeConfig(max_probes=16, capacity=128, termination="bound",
                    probe_batch=2)
SPEC1 = DistSpec(mesh_shape=(1,), axes=("data",))
MODES = ("int8", "bf16")

Q1 = ("SELECT sample_id FROM products WHERE price < ${p} "
      "ORDER BY DISTANCE(embedding, ${qv}) LIMIT 4")
Q2 = ("SELECT sample_id FROM images "
      "WHERE DISTANCE(embedding, ${qv}) <= ${r} AND capture_date > ${d}")
Q3 = """
SELECT queries.id AS qid, images.sample_id AS tid
FROM queries JOIN images
ON DISTANCE(queries.embedding, images.embedding) <= ${r}
AND images.capture_date > queries.capture_date
"""
Q4 = """
SELECT qid, tid FROM (
 SELECT users.id AS qid, movies.sample_id AS tid,
 RANK() OVER (PARTITION BY users.id
   ORDER BY DISTANCE(users.embedding, movies.embedding)) AS rank
 FROM users JOIN movies ON users.preferred_rating = movies.rating
 AND movies.release_year >= ${y}
) AS ranked WHERE ranked.rank <= 4
"""
Q5 = """
SELECT qid, category FROM (
 SELECT sample_id AS qid, calorie_level AS category,
 RANK() OVER (PARTITION BY calorie_level
   ORDER BY DISTANCE(embedding, ${qv})) AS rank
 FROM recipes WHERE DISTANCE(embedding, ${qv}) <= ${r}
) AS ranked WHERE ranked.rank <= 3
"""
Q6 = """
SELECT qid, category, tid FROM (
 SELECT queries.id AS qid, recipes.sample_id AS tid,
 recipes.calorie_level AS category,
 RANK() OVER (PARTITION BY queries.id, recipes.calorie_level
   ORDER BY DISTANCE(queries.embedding, recipes.embedding)) AS rank
 FROM queries JOIN recipes
 ON DISTANCE(queries.embedding, recipes.embedding) <= ${r}
 AND queries.cuisine <> recipes.cuisine
) AS ranked WHERE ranked.rank <= 3
"""
ALL_SQL = {"q1": Q1, "q2": Q2, "q3": Q3, "q4": Q4, "q5": Q5, "q6": Q6}

DIM = 16


@pytest.fixture(scope="module")
def env():
    cat = make_laion_catalog(n_rows=900, n_queries=4, dim=DIM, n_modes=8,
                             num_categories=4, seed=0)
    idx = build_ivf(jax.random.key(0), cat.table("laion")["vec"], nlist=16,
                    metric=Metric.INNER_PRODUCT, iters=3)
    for name in ("laion", "products", "images", "recipes", "movies"):
        cat.register_index(name, "vec", idx)
        cat.register_index(name, "embedding", idx)
    sims = (np.asarray(cat.table("queries")["embedding"])
            @ np.asarray(cat.table("laion")["vec"]).T)
    radius = float(np.median(np.partition(sims, -30, axis=1)[:, -30]))
    return cat, radius


@pytest.fixture(scope="module")
def dbs(env):
    """One Database per (engine, quant mode), shared across tests so
    repeated prepares hit the plan cache instead of recompiling."""
    cat, _ = env
    cache = {}

    def get(engine, quant=None):
        key = (engine, quant)
        if key not in cache:
            cache[key] = connect(cat, EngineOptions(
                engine=engine, probe=PROBE, use_pallas=True, quant=quant))
        return cache[key]

    return get


def _qvecs(cat, qn):
    base = np.asarray(cat.table("queries")["embedding"])
    rng = np.random.default_rng(3)
    reps = -(-qn // base.shape[0])
    qs = np.tile(base, (reps, 1))[:qn]
    return (qs + 0.01 * rng.standard_normal(qs.shape)).astype(np.float32)


def _binds_for(case, cat, radius, qn):
    rng = np.random.default_rng(7)
    price = np.asarray(cat.table("laion")["price"])
    dates = np.asarray(cat.table("laion")["capture_date"])
    years = np.asarray(cat.table("movies")["release_year"])
    qs = _qvecs(cat, qn)
    out = []
    for i in range(qn):
        if case == "q1":
            out.append({"qv": qs[i],
                        "p": np.float32(np.quantile(
                            price, rng.uniform(0.3, 1.0)))})
        elif case == "q2":
            out.append({"qv": qs[i],
                        "r": np.float32(radius * rng.uniform(0.95, 1.0)),
                        "d": np.int32(np.quantile(
                            dates, rng.uniform(0.2, 0.8)))})
        elif case in ("q3", "q6"):
            out.append({"r": np.float32(radius * rng.uniform(0.95, 1.0))})
        elif case == "q4":
            out.append({"y": np.int32(np.quantile(
                years, rng.uniform(0.1, 0.6)))})
        elif case == "q5":
            out.append({"qv": qs[i],
                        "r": np.float32(radius * rng.uniform(0.95, 1.0))})
    return out


def _trees_equal(a, b, ctx=""):
    a = jax.tree.map(np.asarray, dict(a))
    b = jax.tree.map(np.asarray, dict(b))
    assert set(a.keys()) == set(b.keys()), ctx
    import jax.tree_util as jtu
    la = jtu.tree_leaves_with_path(a)
    lb = jtu.tree_leaves_with_path(b)
    for (pa, x), (_pb, y) in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{ctx} leaf {jtu.keystr(pa)}")


# ---------------------------------------------------------------------------
# Q1-Q6 bit-parity: both exact engines x both modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("engine", ["brute", "chase"])
@pytest.mark.parametrize("case", sorted(ALL_SQL))
def test_parity_every_class(env, dbs, case, engine, mode):
    cat, radius = env
    binds = _binds_for(case, cat, radius, 5)       # bucketed: pads 5 -> 8
    want = dbs(engine).prepare(ALL_SQL[case]).execute(binds)
    got = dbs(engine, mode).prepare(ALL_SQL[case]).execute(binds)
    _trees_equal(want.data, got.data, ctx=f"{case}/{engine}/{mode}")


@pytest.mark.parametrize("mode", MODES)
def test_batch_sizes_pad_queries_and_exact_shape(env, dbs, mode):
    """Parity across batch sizes (1, 3-padded-to-4, 8) on the bucketed AND
    exact-shape paths — the q-valid pad lane must stay inert under quant."""
    cat, radius = env
    exact = ExecutionHints(exact_shape=True)
    for case in ("q1", "q5"):
        for qn in (1, 3, 8):
            binds = _binds_for(case, cat, radius, qn)
            ctx = f"{case}/qn={qn}/{mode}"
            want = dbs("brute").prepare(ALL_SQL[case])
            got = dbs("brute", mode).prepare(ALL_SQL[case])
            _trees_equal(want.execute(binds).data,
                         got.execute(binds).data, ctx=ctx)
            _trees_equal(want.execute(binds, hints=exact).data,
                         got.execute(binds, hints=exact).data,
                         ctx=ctx + "/exact_shape")


@pytest.mark.parametrize("mode", MODES)
def test_single_query_front_matches_fp32_batch(env, dbs, mode):
    """The quant single-query front IS the batch lowering at Q=1
    (``_single_via_batch``), so its bitwise reference is the fp32 BATCHED
    execution of one bind, sliced — not the fp32 single-query matvec."""
    cat, radius = env
    binds = _binds_for("q1", cat, radius, 1)
    got = dbs("brute", mode).prepare(Q1).execute(binds[0])     # Result
    want = dbs("brute").prepare(Q1).execute(
        binds, hints=ExecutionHints(exact_shape=True))         # batch of 1
    sliced = jax.tree.map(lambda v: np.asarray(v)[0], dict(want.data))
    _trees_equal(sliced, got.data, ctx=f"single/{mode}")


# ---------------------------------------------------------------------------
# adversarial corpora: ties the quantized keys cannot see
# ---------------------------------------------------------------------------

def _adversarial_catalog():
    """512-row corpus whose interesting rows sit mid-corpus (segments 32+):

    * rows 256..263 — EIGHT exact duplicates of the unit query direction u
      (identical quantization, identical fp32 keys: the lowest-id
      tie-break must survive the rescore's candidate reordering);
    * rows 264..279 — sixteen near-ties ``0.9*u + eps_i*e1`` with eps_i
      strictly increasing but SUB-RESOLUTION for both int8 (per-row scale
      step ~6e-3) and bf16 (ulp ~1.4e-3): their quantized keys tie
      exactly, so only the fused fp32 rescore can order them;
    * everything else — 0.1-scale noise, clearly outside the top-k.
    """
    n = 512
    cat = make_laion_catalog(n_rows=n, n_queries=4, dim=DIM, n_modes=8,
                             num_categories=4, seed=0)
    raw = np.linspace(1.0, 0.2, DIM).astype(np.float32)
    u = raw / np.linalg.norm(raw)
    rng = np.random.default_rng(5)
    vecs = 0.1 * rng.standard_normal((n, DIM)).astype(np.float32)
    vecs /= np.maximum(np.linalg.norm(vecs, axis=1, keepdims=True), 1e-6)
    vecs *= 0.1
    vecs[256:264] = u
    eps = (1e-6 * np.arange(1, 17)).astype(np.float32)
    near = np.tile(0.9 * u, (16, 1))
    near[:, 1] += eps
    vecs[264:280] = near
    tab = cat.table("laion")
    cols = {name: tab[name] for name in tab.schema.names()}
    cols["vec"] = cols["embedding"] = jnp.asarray(vecs)
    fresh = Table(tab.schema, cols)
    for name in ("laion", "products", "images", "recipes", "movies"):
        cat.register(name, fresh)
    return cat, u


@pytest.mark.parametrize("mode", MODES)
def test_adversarial_ties_and_duplicates(mode):
    cat, u = _adversarial_catalog()
    ksql = ("SELECT sample_id FROM products WHERE price < ${p} "
            "ORDER BY DISTANCE(embedding, ${qv}) LIMIT ${K}")
    binds = [{"qv": u.astype(np.float32), "p": np.float32(1e9)}] * 2
    fdb = connect(cat, EngineOptions(engine="brute", use_pallas=True))
    qdb = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                     quant=mode))
    want = fdb.prepare(ksql, K=12).execute(binds)
    got = qdb.prepare(ksql, K=12).execute(binds)
    _trees_equal(want.data, got.data, ctx=f"adversarial/{mode}")
    ids = np.asarray(got.data["ids"])[0].tolist()
    # duplicates: exact-tie keys resolve to the lowest ids, in id order
    assert ids[:8] == list(range(256, 264)), ids
    # near-ties: strictly-increasing eps under INNER_PRODUCT means the
    # LAST rows win ranks 9..12 — an ordering only fp32 can see
    assert ids[8:] == [279, 278, 277, 276], ids


@pytest.mark.parametrize("mode", MODES)
def test_dense_neighbourhood_falls_back_to_fp32(mode):
    """A tight cluster whose spread is below the quantization step: the
    quantized ranking inside it is noise, so the smallest candidate set
    (rescore_factor=1) misses true top-k rows, the certificate fails, and
    the batch must still equal the fp32 path bit for bit."""
    n, k = 1024, 16
    cat = make_laion_catalog(n_rows=n, n_queries=4, dim=DIM, n_modes=8,
                             num_categories=4, seed=0)
    rng = np.random.default_rng(11)
    u = rng.standard_normal(DIM).astype(np.float32)
    u /= np.linalg.norm(u)
    vecs = 0.1 * rng.standard_normal((n, DIM)).astype(np.float32)
    vecs[::2] = u + 2e-3 * rng.standard_normal((n // 2, DIM))
    tab = cat.table("laion")
    cols = {name: tab[name] for name in tab.schema.names()}
    cols["vec"] = cols["embedding"] = jnp.asarray(vecs)
    cat.register("products", Table(tab.schema, cols))
    ksql = ("SELECT sample_id FROM products WHERE price < ${p} "
            "ORDER BY DISTANCE(embedding, ${qv}) LIMIT %d" % k)
    qs = u + 1e-2 * rng.standard_normal((4, DIM)).astype(np.float32)
    binds = [{"qv": q, "p": np.float32(1e9)} for q in qs]
    want = connect(cat, EngineOptions(engine="brute", use_pallas=True)
                   ).prepare(ksql).execute(binds)
    stmt = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                      quant=mode, rescore_factor=1)
                   ).prepare(ksql)
    got = stmt.execute(binds)
    _trees_equal(want.data, got.data, ctx=f"dense/{mode}")
    assert stmt.executor.quant_topk == {"batches": 1, "fp32_fallbacks": 1}


@pytest.mark.parametrize("mode", MODES)
def test_separated_neighbourhood_certifies(mode):
    """The opposite of the dense case: each query's top-k rows stand far
    above the rest of the corpus, so the certificate holds, the batch
    never runs the fp32 kernel, and the result still equals the fp32 path
    bit for bit."""
    n, k = 1024, 4
    cat = make_laion_catalog(n_rows=n, n_queries=4, dim=DIM, n_modes=8,
                             num_categories=4, seed=0)
    rng = np.random.default_rng(12)
    qs = rng.standard_normal((4, DIM)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    vecs = 0.05 * rng.standard_normal((n, DIM)).astype(np.float32)
    for i, q in enumerate(qs):
        rows = slice(64 * i, 64 * i + k)
        vecs[rows] = q + 1e-2 * rng.standard_normal((k, DIM))
    tab = cat.table("laion")
    cols = {name: tab[name] for name in tab.schema.names()}
    cols["vec"] = cols["embedding"] = jnp.asarray(vecs)
    cat.register("products", Table(tab.schema, cols))
    ksql = ("SELECT sample_id FROM products "
            "ORDER BY DISTANCE(embedding, ${qv}) LIMIT %d" % k)
    binds = [{"qv": q} for q in qs]
    want = connect(cat, EngineOptions(engine="brute", use_pallas=True)
                   ).prepare(ksql).execute(binds)
    stmt = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                      quant=mode)).prepare(ksql)
    got = stmt.execute(binds)
    _trees_equal(want.data, got.data, ctx=f"separated/{mode}")
    assert stmt.executor.quant_topk == {"batches": 1, "fp32_fallbacks": 0}


# ---------------------------------------------------------------------------
# composition: sharded shards=1, live-delta, re-registered twins
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", ["q1", "q2"])
def test_sharded_shards1_parity(env, case, mode):
    """quant + dist at shards=1 == plain fp32 flat path, bitwise — the
    per-shard local rescore happens before the (identity) merge."""
    cat, radius = env
    ref = compile_query(ALL_SQL[case], cat,
                        EngineOptions(engine="brute", use_pallas=True))
    q = compile_query(ALL_SQL[case], cat,
                      EngineOptions(engine="brute", use_pallas=True,
                                    quant=mode, dist=SPEC1))
    binds = _binds_for(case, cat, radius, 3)
    stacked = {k: np.stack([np.asarray(b[k]) for b in binds])
               for k in binds[0]}
    _trees_equal(ref.execute_bucketed(**stacked),
                 q.execute_bucketed(**stacked), ctx=f"dist/{case}/{mode}")


@pytest.mark.parametrize("mode", MODES)
def test_live_delta_parity_and_zero_retrace(tmp_path, mode):
    """Live mutations under quant: the main segment scans its quantized
    twin, the delta stays fp32, and insert/delete/compact stay bitwise
    equal to an identically-mutated fp32 plan — with ZERO retraces."""

    def mk():
        return make_laion_catalog(n_rows=240, n_queries=4, dim=DIM,
                                  n_modes=8, num_categories=4, seed=0)

    kw = dict(delta_cap=16, cap_main=304)
    cat, ref_cat = mk(), mk()
    live = attach_live(cat, "products", "embedding",
                       os.fspath(tmp_path / "a"), **kw)
    ref_live = attach_live(ref_cat, "products", "embedding",
                           os.fspath(tmp_path / "b"), **kw)
    qdb = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                     quant=mode))
    fdb = connect(ref_cat, EngineOptions(engine="brute", use_pallas=True))
    qs = np.asarray(cat.table("queries")["embedding"]).astype(np.float32)
    binds = [{"qv": qs[i], "p": np.float32(1e9)} for i in range(3)]
    stmt, ref = qdb.prepare(Q1), fdb.prepare(Q1)
    _trees_equal(ref.execute(binds).data, stmt.execute(binds).data,
                 ctx=f"live/pre/{mode}")
    traces = dict(stmt.executor.trace_counts)
    assert traces                                   # compiled exactly once

    rng = np.random.default_rng(2)
    v = rng.standard_normal((3, DIM)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    for lv in (live, ref_live):
        lv.insert([9000, 9001, 9002], v,
                  {"price": np.full(3, 1.0, np.float32)})
    _trees_equal(ref.execute(binds).data, stmt.execute(binds).data,
                 ctx=f"live/insert/{mode}")
    for lv in (live, ref_live):
        lv.delete([9001, 17])
    _trees_equal(ref.execute(binds).data, stmt.execute(binds).data,
                 ctx=f"live/delete/{mode}")
    for lv in (live, ref_live):
        lv.compact()                 # canonical swap re-quantizes the main
    _trees_equal(ref.execute(binds).data, stmt.execute(binds).data,
                 ctx=f"live/compact/{mode}")
    # every mutation re-bound in place: zero new executables
    assert dict(stmt.executor.trace_counts) == traces


def test_requantized_twin_rebinds_zero_retraces():
    cat = make_laion_catalog(n_rows=240, n_queries=4, dim=DIM, n_modes=8,
                             num_categories=4, seed=0)
    db = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                    quant="int8"))
    stmt = db.prepare(Q1)
    qs = np.asarray(cat.table("queries")["embedding"]).astype(np.float32)
    binds = [{"qv": qs[i], "p": np.float32(1e9)} for i in range(3)]
    before = stmt.execute(binds)
    traces = dict(stmt.executor.trace_counts)
    # re-register a same-shape twin: ensure_fresh re-binds, nothing retraces
    twin = quantize_corpus(
        np.asarray(cat.table("products")["embedding"]), "int8")
    cat.register_quantized("products", "embedding", twin)
    after = stmt.execute(binds)
    assert dict(stmt.executor.trace_counts) == traces
    _trees_equal(before.data, after.data, ctx="requantize")


def test_rescore_factor_hint_is_compile_affecting(env, dbs):
    cat, radius = env
    db = connect(cat, EngineOptions(engine="brute", use_pallas=True,
                                    quant="int8"))
    stmt = db.prepare(Q1)
    binds = _binds_for("q1", cat, radius, 3)
    base = stmt.execute(binds)
    assert db.cache_info().entries == 1
    wide = stmt.execute(binds, hints=ExecutionHints(rescore_factor=3))
    # a distinct options fingerprint -> its own cache entry; the original
    # statement keeps its compiled default
    assert db.cache_info().entries == 2
    assert stmt.compiled.options.rescore_factor != 3
    # a wider replay on an already-exact result changes nothing
    _trees_equal(base.data, wide.data, ctx="rescore_factor")
    with pytest.raises(ValueError, match="rescore_factor"):
        ExecutionHints(rescore_factor=0)


# ---------------------------------------------------------------------------
# quantize_corpus unit contract + option validation
# ---------------------------------------------------------------------------

def test_quantize_corpus_int8_contract():
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((32, DIM)).astype(np.float32)
    vecs[5] = 0.0                                    # all-zero row
    qc = quantize_corpus(vecs, "int8")
    assert qc.qvecs.dtype == jnp.int8
    assert qc.scales.shape == (32, 1)
    deq = np.asarray(qc.qvecs, np.float32) * np.asarray(qc.scales)
    half = np.asarray(qc.half_step)
    assert np.all(np.abs(vecs - deq) <= half[:, None] + 1e-7)
    # all-zero row: scale pinned to 1, zero error bound, zero norms
    assert float(np.asarray(qc.scales)[5, 0]) == 1.0
    assert float(half[5]) == 0.0
    np.testing.assert_allclose(np.asarray(qc.row_l1),
                               np.abs(deq).sum(axis=1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(qc.row_l2),
                               np.linalg.norm(deq, axis=1), rtol=1e-6)


def test_quantize_corpus_bf16_contract():
    rng = np.random.default_rng(1)
    vecs = rng.standard_normal((8, DIM)).astype(np.float32)
    qc = quantize_corpus(vecs, "bf16")
    assert qc.qvecs.dtype == jnp.bfloat16
    # scales are EXACT ones: 1.0 * x is a bitwise identity, so ONE kernel
    # serves both modes
    assert np.all(np.asarray(qc.scales) == 1.0)
    deq = np.asarray(qc.qvecs, np.float32)
    half = np.max(np.abs(vecs), axis=1) * 2.0 ** -8
    np.testing.assert_allclose(np.asarray(qc.half_step), half, rtol=1e-6)
    assert np.all(np.abs(vecs - deq) <= half[:, None] + 1e-7)


def test_quantize_corpus_validation():
    vecs = np.ones((4, DIM), np.float32)
    with pytest.raises(ValueError, match="mode"):
        quantize_corpus(vecs, "fp8")
    with pytest.raises(ValueError, match="expected"):
        quantize_corpus(vecs[0], "int8")
    # plan_arrays carries the ensure_fresh re-bind keys, prefix included
    qc = quantize_corpus(vecs, "int8")
    assert set(qc.plan_arrays("m_")) == {
        "m_qvecs", "m_qscales", "m_qhalf", "m_ql1", "m_ql2"}


def test_quant_option_validation(env):
    cat, _ = env
    with pytest.raises(ValueError, match="use_pallas"):
        compile_query(Q1, cat, EngineOptions(
            engine="brute", use_pallas=False, quant="int8"))
    with pytest.raises(ValueError, match="chase"):
        compile_query(Q1, cat, EngineOptions(
            engine="vbase", use_pallas=True, quant="int8", probe=PROBE))
    with pytest.raises(ValueError, match="one of"):
        compile_query(Q1, cat, EngineOptions(
            engine="brute", use_pallas=True, quant="fp8"))
    with pytest.raises(ValueError, match="join_lowering"):
        compile_query(Q1, cat, EngineOptions(
            engine="brute", use_pallas=True, quant="int8",
            join_lowering="perleft"))
    with pytest.raises(ValueError, match=">= 1"):
        compile_query(Q1, cat, EngineOptions(
            engine="brute", use_pallas=True, quant="int8",
            rescore_factor=0))
