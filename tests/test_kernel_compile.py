"""Compile checks of the Pallas kernels for a described TPU v5e chip.

The rest of the suite runs every kernel in Pallas interpret mode on the
CPU, which checks what the kernels compute but not that the TPU compiler
(Mosaic) accepts their block layouts, in-kernel ops and VMEM budget.  These
tests compile the kernel wrappers with ``interpret=False`` for one chip of
a *described* v5e:2x2 topology — jaxlib ships the TPU compiler, and a
described chip needs no attached one — at the paper's §7.1 scale
(N = 1,000,000 rows, D = 512, inner product, K ∈ {10, 50}), and check that
each compiled program holds a Mosaic kernel (``tpu_custom_call``) and fits
the chip's 16 GB of HBM.  Nothing executes: a compile that passes is not a
chip run (``chip_smoke.py`` is).

The topology is described inside a fixture, never while a module imports:
only one process may load the TPU library at a time, and under pytest-xdist
every worker imports this file while only the one that runs it may load it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Metric
from repro.kernels.ops import (fused_range_scan, fused_range_scan_batch,
                               fused_scan_topk, fused_scan_topk_batch)
from repro.kernels.quant import (fused_range_topk_batch_q,
                                 fused_scan_topk_batch_q)

N, D = 1_000_000, 512           # §7.1: laion1m, 512-d CLIP embeddings
METRIC = Metric.INNER_PRODUCT
CAPACITY = 4096                 # configs/chase_laion.py range buffer
HBM_BYTES = 16 * 10 ** 9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """One described v5e:2x2 host, with the persistent compilation cache off
    (an entry compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except RuntimeError as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> an argument shape placed on one chip."""
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("q", [1, 64, 128])
@pytest.mark.parametrize("k", [10, 50])
def test_topk_batch_compiles(shape, k, q):
    _compile(lambda c, qs: fused_scan_topk_batch(c, qs, k, None, METRIC,
                                                 interpret=False),
             shape((N, D)), shape((q, D)))


@pytest.mark.parametrize("mask", ["shared", "per_query"])
def test_topk_batch_masked_compiles(shape, mask):
    dims = (N,) if mask == "shared" else (64, N)
    _compile(lambda c, qs, m: fused_scan_topk_batch(c, qs, 50, m, METRIC,
                                                    interpret=False),
             shape((N, D)), shape((64, D)), shape(dims, jnp.bool_))


def test_single_topk_compiles(shape):
    _compile(lambda c, q: fused_scan_topk(c, q, 50, None, METRIC,
                                          interpret=False),
             shape((N, D)), shape((D,)))


@pytest.mark.parametrize("q", [1, 64])
def test_range_batch_compiles(shape, q):
    _compile(lambda c, qs, r: fused_range_scan_batch(c, qs, r, None, METRIC,
                                                     interpret=False),
             shape((N, D)), shape((q, D)), shape((q,)))


def test_single_range_compiles(shape):
    _compile(lambda c, q, r: fused_range_scan(c, q, r, None, METRIC,
                                              interpret=False),
             shape((N, D)), shape((D,)), shape(()))


@pytest.mark.parametrize("k", [10, 50])
@pytest.mark.parametrize("mode", [jnp.int8, jnp.bfloat16])
def test_quant_topk_compiles(shape, mode, k):
    _compile(lambda c, z, s, h, l1, l2, qs: fused_scan_topk_batch_q(
                 c, z, s, h, l1, l2, qs, k, None, METRIC, interpret=False),
             shape((N, D)), shape((N, D), mode), shape((N, 1)),
             shape((N,)), shape((N,)), shape((N,)), shape((64, D)))


def test_int8_range_compiles(shape):
    _compile(lambda c, z, s, h, l1, l2, qs, r: fused_range_topk_batch_q(
                 c, z, s, h, l1, l2, qs, r, None, METRIC, CAPACITY,
                 interpret=False),
             shape((N, D)), shape((N, D), jnp.int8), shape((N, 1)),
             shape((N,)), shape((N,)), shape((N,)), shape((64, D)),
             shape((64,)))


@pytest.mark.parametrize("q,mask", [(1, None), (64, None), (1, "per_query"),
                                    (64, "per_query"), (1, "range"),
                                    (64, "range")])
def test_flat_scan_reads_corpus_in_place(shape, q, mask):
    """The flat kernels read the 2.048 GB corpus in place: no executable may
    hold a corpus-sized temporary (a copy padded to a block multiple; with
    it the top-k executables held 2.18 GB, the range ones 2.7-2.8 GB)."""
    corpus_bytes = N * D * 4
    bound = corpus_bytes // 2
    if mask == "range":
        compiled = _compile(
            lambda c, qs, r: fused_range_scan_batch(c, qs, r, None, METRIC,
                                                    interpret=False),
            shape((N, D)), shape((q, D)), shape((q,)))
        if q == 64:
            # its own (N, 64) keys and hits, lane-padded to 128 columns and
            # transposed to (64, N), take 1.15 GB: bounded by the corpus
            bound = corpus_bytes
    elif mask is None:
        compiled = _compile(
            lambda c, qs: fused_scan_topk_batch(c, qs, 50, None, METRIC,
                                                interpret=False),
            shape((N, D)), shape((q, D)))
    else:
        compiled = _compile(
            lambda c, qs, m: fused_scan_topk_batch(c, qs, 50, m, METRIC,
                                                   interpret=False),
            shape((N, D)), shape((q, D)), shape((q, N), jnp.bool_))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < bound, (temp, bound)
