"""Q13 — quantized flat-scan QPS (int8 / bf16 corpus, fused fp32 rescore).

The quantized scan kernels (DESIGN.md §13) stream an int8 or bf16 corpus
tile through the same MXU layout as the fp32 batch kernel and rescore the
top-(c·K) candidates in fp32, so the result is BIT-IDENTICAL to the fp32
scan while the corpus read moves 4x (int8) or 2x (bf16) fewer bytes.
This bench sweeps batch ∈ {1, 8, 64, 256} over the BENCH_batch flat
workload for fp32 / bf16 / int8 and, for every (mode, batch) point,
hard-asserts recall == 1.0 against the fp32 run BEFORE timing — a
quantized row that is not exact never gets a QPS number.

Bandwidth accounting: each row carries the model bytes the scan must move
(corpus + scales + queries + fp32 rescore gather), the achieved GB/s at
the measured time, and that as a fraction of TPU v5e HBM peak
(``roofline/hw.py``); the b64 rows additionally run the compiled HLO
through ``roofline/hlo_analyzer`` and publish a v5e roofline bound
(``roofline/analysis.roofline_terms``).  Interpret-mode caveat: on CPU
emulation the achieved fractions are honest but tiny — the model-bytes
column is the machine-independent part, and is what shrinks 4x.

Writes ``BENCH_quant.json``.  The acceptance gate (scripts/bench_gate.py)
holds every (mode, batch) QPS within tolerance of the committed baseline
AND requires int8 b64 >= 1.5x fp32 b64 within one run.

Standalone:  PYTHONPATH=src python -m benchmarks.q13_quant_qps [--full]
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro.core import EngineOptions, compile_query
from repro.roofline import analysis as roofline_analysis
from repro.roofline import hlo_analyzer
from repro.roofline.hw import TPU_V5E

from .common import BenchEnv, Row, timeit

BATCHES = (1, 8, 64, 256)
MODES = ("fp32", "bf16", "int8")
RESCORE_FACTOR = 3   # c=2 (the engine default) loses one candidate in
                     # 2560 on this 16k-row corpus at b256; c=3 restores
                     # exactness while keeping the fp32 replay (whose cost
                     # scales with c·K·SEG rows per query) small next to
                     # the corpus stream
SQL = ("SELECT sample_id FROM products "
       "ORDER BY DISTANCE(embedding, ${qv}) LIMIT {K}")
OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_quant.json")

FLAT_ROWS = 16384  # deliberately LARGER than q7's 2000-row flat catalog:
                   # the quantized scan's win is corpus BYTES MOVED, so the
                   # corpus must not fit in cache (at 2k rows x 64 dims the
                   # fp32 corpus is 512 KB and every mode runs at cache
                   # speed, hiding the 4x int8 traffic saving the gate
                   # asserts; at 16k rows the fp32 stream is 4 MB and the
                   # int8 kernel wins >= 1.5x even on the CPU emulation)

_ITEMSIZE = {"fp32": 4, "bf16": 2, "int8": 1}


def _queries(base: np.ndarray, q: int) -> np.ndarray:
    rng = np.random.default_rng(7)
    reps = -(-q // base.shape[0])
    qs = np.tile(base, (reps, 1))[:q]
    return (qs + 0.01 * rng.standard_normal(qs.shape)).astype(np.float32)


def _model_bytes(mode: str, n: int, dim: int, q: int, k: int) -> int:
    """Bytes the flat scan must move per execution: the quantized (or
    fp32) corpus stream, per-row scales, the query tile, and — for the
    quantized modes — the fp32 gather of the c*K rescore candidates."""
    b = n * dim * _ITEMSIZE[mode] + q * dim * 4
    if mode != "fp32":
        b += n * 4                                  # per-row scales
        b += RESCORE_FACTOR * k * q * dim * 4       # fp32 rescore gather
    return b


def _recall(out, ref, k: int) -> float:
    """Mean top-k id recall of ``out`` against the fp32 reference."""
    ids = np.atleast_2d(np.asarray(out["ids"]))
    rds = np.atleast_2d(np.asarray(ref["ids"]))
    v = np.atleast_2d(np.asarray(ref["valid"]))
    hits = tot = 0
    for i in range(ids.shape[0]):
        want = set(rds[i][v[i]].tolist())
        if not want:
            continue
        hits += len(want & set(ids[i].tolist()))
        tot += len(want)
    return hits / tot if tot else 1.0


def _hlo_roofline(q, qs, model_flops: float) -> dict | None:
    """Compiled-HLO cost of the b64 executable -> v5e roofline terms."""
    try:
        text = q.lower_batch(qv=qs).compile().as_text()
        cost = hlo_analyzer.analyze(text)
        terms = roofline_analysis.roofline_terms(
            {"flops": cost.flops, "bytes accessed": cost.bytes},
            {}, chips=1, model_flops=model_flops)
        return {"hlo_gflops": round(cost.flops / 1e9, 3),
                "hlo_gbytes": round(cost.bytes / 1e9, 3),
                "v5e_step_us": round(1e6 * terms.step_time_lower_bound_s, 3),
                "v5e_dominant": terms.dominant}
    except Exception as e:                           # interpret-mode HLO can
        return {"error": type(e).__name__}          # defeat the parser; the
                                                    # model columns still land


def run(env: BenchEnv, rows: list, batches=BATCHES) -> dict:
    from repro.data import make_laion_catalog

    K = min(env.cfg.k_top, 10)
    sql = SQL.replace("{K}", str(K))
    n = FLAT_ROWS        # NOT min(env.n_rows, ...): see FLAT_ROWS comment
    cat = make_laion_catalog(n_rows=n, n_queries=8, dim=env.cfg.dim,
                             n_modes=16, seed=env.cfg.seed)
    qvecs = np.asarray(cat.table("queries")["embedding"])
    dim = env.cfg.dim
    report: dict = {"n_rows": n, "dim": dim, "k": K,
                    "rescore_factor": RESCORE_FACTOR, "workloads": {},
                    "hbm_peak_gbps": round(TPU_V5E.hbm_bw / 1e9, 1)}

    compiled = {}
    for mode in MODES:
        opts = EngineOptions(engine="brute", use_pallas=True,
                             quant=None if mode == "fp32" else mode,
                             rescore_factor=RESCORE_FACTOR)
        compiled[mode] = compile_query(sql, cat, opts)

    for mode in MODES:
        q = compiled[mode]
        entries = []
        for b in batches:
            qs = _queries(qvecs, b)
            if b == 1:
                out = q(qv=qs[0])
                ref = compiled["fp32"](qv=qs[0])
            else:
                out = q.execute_batch(qv=qs)
                ref = compiled["fp32"].execute_batch(qv=qs)
            # exactness is the contract, not a tolerance: no QPS number
            # without recall 1.0 against the fp32 scan
            recall = _recall(out, ref, K)
            assert recall == 1.0, (
                f"quantized scan lost exactness: mode={mode} batch={b} "
                f"recall={recall:.4f} (must be 1.0)")
            if b == 1:
                ms = timeit(lambda: q(qv=qs[0]), repeats=9)
            else:
                ms = timeit(lambda: q.execute_batch(qv=qs), repeats=3)
            qps = 1e3 * b / ms
            mb = _model_bytes(mode, n, dim, b, K)
            achieved = mb / (ms / 1e3) / 1e9
            entry = {"batch": b, "ms": round(ms, 3), "qps": round(qps, 1),
                     "recall": recall,
                     "model_mbytes": round(mb / 1e6, 3),
                     "achieved_gbps": round(achieved, 3),
                     "frac_hbm_peak": round(achieved * 1e9
                                            / TPU_V5E.hbm_bw, 6)}
            if b == 64:
                flops = 2.0 * n * dim * b
                if mode != "fp32":
                    flops += 2.0 * RESCORE_FACTOR * K * dim * b
                entry["roofline"] = _hlo_roofline(q, qs, flops)
            entries.append(entry)
            rows.append(Row(f"q13_{mode}_b{b}", ms, qps=entry["qps"]))
        report["workloads"][mode] = entries

    def b64(mode):
        return next(e["qps"] for e in report["workloads"][mode]
                    if e["batch"] == 64)

    report["speedup_b64"] = {m: round(b64(m) / b64("fp32"), 2)
                             for m in MODES if m != "fp32"}
    # share of the batches whose top-k certificate failed, so that they
    # ran the fp32 kernel too (DESIGN.md §13): one bucketed execution of
    # each timed batch, which the executor counts
    report["fp32_fallback_share"] = {}
    for mode in MODES:
        if mode != "fp32":
            for b in batches:
                compiled[mode].execute_bucketed(qv=_queries(qvecs, b))
            counts = compiled[mode].executor.quant_topk
            report["fp32_fallback_share"][mode] = (counts["fp32_fallbacks"]
                                                   / counts["batches"])
    with open(OUT_JSON, "w") as f:
        json.dump(report, f, indent=2)
    return report


if __name__ == "__main__":
    import argparse

    from .common import get_env

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-scale catalog (default: smoke)")
    args = ap.parse_args()
    env = get_env(smoke=not args.full)
    rows: list[Row] = []
    report = run(env, rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(r.csv())
    print("speedup_b64:", report["speedup_b64"])
