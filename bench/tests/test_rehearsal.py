"""Each cell end to end at a toy size on the CPU (Pallas in interpret
mode), with the device check stubbed in the test: the result meets the
contract, a broken served path comes out as not correct, files dropped in
are found by name, and run.py refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import harness
from repro.core import compiler

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**33 + 12345            # wider than 32 bits, as run seeds may be


def shrink(cell):
    """Toy sizes, set here and not through any option of the harness."""
    cell.config.update(rows=3000, modes=8)
    if cell.config.get("index"):
        cell.config["index"].update(nlist=8, kmeans_iters=3)
    tr = cell.traffic
    tr["check"]["sample"] = 48
    if tr["loop"] == "open":
        tr["rate_per_s"] = 150.0
    else:
        tr.update(clients=16, pool_per_s=4000)
        tr["server"]["max_batch"] = 8
        tr["warm_batches"] = [8]


def on_cpu(chips):
    return jax.devices()[:chips]


def run(cell, seconds=1.5, root=ROOT, adjust=shrink):
    return harness.run(cell, SEED, seconds, False, root=root,
                       device_check=on_cpu, adjust=adjust)


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_meets_the_contract(cell):
    result = run(cell)
    line = json.loads(json.dumps(result))
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"]: m["unit"] for m in SPEC["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    dev = line["device"]
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert dev["count"] == 1
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"}


def _break_answers(monkeypatch, how):
    """Break the served path underneath the scheduler."""
    real = compiler.BucketedExecutor.__call__

    def broken(self, binds, probe_budget=None):
        out = real(self, binds, probe_budget)
        ids = np.array(out["ids"])
        if how == "answer_altered":
            ids[:, 0] = (ids[:, 0] + 1) % 3000
        elif how == "half_batch_left_out":
            half = (ids.shape[0] + 1) // 2
            for key in ("ids", "sim", "valid"):
                a = np.array(out[key])
                a[half:] = a[:ids.shape[0] - half]
                out[key] = a
            return out
        elif how == "row_moved_to_another_slot":
            # a partitioned answer's best row of category 0 shown again in
            # category 1's slot, its score and validity with it
            for key in ("ids", "sim", "valid"):
                a = np.array(out[key])
                a[:, 1, 0] = a[:, 0, 0]
                out[key] = a
            return out
        out["ids"] = ids
        return out

    monkeypatch.setattr(compiler.BucketedExecutor, "__call__", broken)


@pytest.mark.parametrize("how", ["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_served_path_is_not_correct(cell, how, monkeypatch):
    _break_answers(monkeypatch, how)
    result = run(cell)
    assert result["correct"] is False, result["checks"]


def test_the_check_interface_reads_as_filtered_knn_did():
    """On one rehearsal record, ``compare_run`` through the harness gives
    the numbers that ``compare`` gives on the arguments the harness used
    to build for it, and the answers keep Q1's shapes and types."""
    import jax.numpy as jnp
    cell = harness.load_cell(CELLS[0])
    shrink(cell)
    clock = harness.CompileClock()
    world = harness.build_world(cell, SEED, 1.0, clock)
    tl = harness.drive(world, cell, 1.0, clock)
    numbers, _ = harness.check(world, cell, tl, SEED)
    spec = cell.traffic["check"]
    k = spec["k"]
    for key, dtype in (("ids", np.int32), ("sim", np.float32),
                       ("valid", bool)):
        assert tl.answers[key].shape == (tl.ok.size, k)
        assert tl.answers[key].dtype == dtype
    pick = harness._sample(tl, spec["sample"], SEED)
    req = world.requests
    qv = np.stack([req.binds[i][spec["vector_bind"]] for i in pick])
    bounds = np.asarray([req.binds[i][spec["bound_bind"]] for i in pick],
                        np.float32)
    col = world.dataset.columns[spec["column"]]
    served = {key: tl.answers[key][pick] for key in ("ids", "sim", "valid")}
    before = harness.load_module("checks", "filtered_knn").compare(
        world.dataset.corpus, col, jnp.asarray(col), qv, bounds, served, k,
        cell.config["guarantee"])
    assert {n: v for n, v in numbers.items() if n != "failed"} == before


def _small_checkout(tmp_path, traffic: str, mix: dict):
    """A copy of the benchmark that gains the configuration
    ``laion_small_flat``, the mix ``traffic`` and their cell by new files
    and a BENCHMARK.json entry alone; returns (root, spec, cell name)."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.loads((root / "bench/configs/laion1m_flat.json").read_text())
    cfg["name"] = "laion_small_flat"
    cfg["rows"] = 2500
    (root / "bench/configs/laion_small_flat.json").write_text(
        json.dumps(cfg))
    (root / f"bench/traffic/{traffic}.json").write_text(json.dumps(mix))
    name = f"laion_small_flat.{traffic}"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "laion_small_flat", "source": "x",
                            "file": "bench/configs/laion_small_flat.json",
                            "reduced": ["rows"], "why": "test"})
    spec["workloads"].append({"name": name, "config": "laion_small_flat",
                              "traffic": traffic, "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, spec, name


def tiny_dim(cell):
    cell.config["modes"] = 8


def test_files_dropped_in_are_found_by_name(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    metric and a cell by new files and a BENCHMARK.json entry alone."""
    with open(os.path.join(ROOT, "bench/traffic/q1_closed128.json")) as f:
        tr = json.load(f)
    tr["clients"] = 8
    tr["server"]["max_batch"] = 4
    tr["warm_batches"] = [4]
    tr["check"]["sample"] = 32
    root, spec, name = _small_checkout(tmp_path, "q1_closed8", tr)
    (root / "bench/metrics/drains_per_s.py").write_text(
        "def read(record):\n"
        "    tl = record.timeline\n"
        "    return len(tl.drains) / (tl.end - tl.t0)\n")
    spec["end_to_end"].append({"name": "drains_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    result = run(name, root=str(root), adjust=tiny_dim)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["drains_per_s"]["value"] > 0
    assert result["metrics"]["drains_per_s"]["unit"] == "1/s"


# CHASE's Q5 (the paper's Fig. 8), K = 10: per calorie level, the ten rows
# nearest the query among those within the radius and not of cuisine ex
SQL_Q5 = (
    "SELECT qid, category FROM ("
    " SELECT sample_id AS qid, calorie_level AS category,"
    " RANK() OVER (PARTITION BY calorie_level"
    "   ORDER BY DISTANCE(embedding, ${qv})) AS rank"
    " FROM recipes"
    " WHERE DISTANCE(embedding, ${qv}) <= ${r} AND cuisine <> ${ex}"
    ") AS ranked WHERE ranked.rank <= 10")

Q5_MIX = {
    "sql": SQL_Q5,
    "binds": {"qv": {"kind": "query_vector"},
              # on the toy corpus about 10 rows a category qualify, so
              # some slots fill and some come short
              "r": {"kind": "constant", "value": 0.935},
              "ex": {"kind": "constant", "value": 3}},
    "loop": "closed", "clients": 8, "pool_per_s": 4000,
    "server": {"max_batch": 4, "max_wait_ms": 2.0},
    "warm_batches": [4],
    "check": {"module": "partition_topk", "k": 10, "sample": 32,
              "vector_bind": "qv", "radius_bind": "r", "exclude_bind": "ex",
              "exclude_column": "cuisine",
              "partition_column": "calorie_level"}}


@pytest.mark.parametrize("how", [None, "answer_altered",
                                 "half_batch_left_out",
                                 "row_moved_to_another_slot"])
def test_a_q5_cell_dropped_in(tmp_path, monkeypatch, how):
    """A category-partition top-k cell comes from new files alone: its
    answers of shape (C, K), constant binds and its own check module; it
    is correct, and each broken served path makes it not correct."""
    root, _, name = _small_checkout(tmp_path, "q5_closed8", Q5_MIX)
    if how is not None:
        _break_answers(monkeypatch, how)
    result = run(name, root=str(root), adjust=tiny_dim)
    assert result["correct"] is (how is None), result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no result" in proc.stderr


def test_run_py_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_sweep_reports_each_offered_rate():
    import sweep
    cell = next(c for c in CELLS
                if harness.load_cell(c).traffic["loop"] == "open")
    rows = sweep.sweep(cell, [SEED, SEED + 1], 1.0, [20.0, 60.0],
                       device_check=on_cpu, adjust=shrink)
    assert [r["rate_per_s"] for r in rows] == [20.0, 20.0, 60.0, 60.0]
    for r in rows:
        assert r["answered_per_s"] > 0 and r["window_compiles"] == 0
        assert r["p95_ms"] >= r["p50_ms"] > 0
        assert len(r["backlog_quarters"]) == 4
