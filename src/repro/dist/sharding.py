"""Logical-axis sharding rules plus the engine's corpus-sharding handles.

Two independent facilities live here:

* **Logical-axis rules** (`logical_axis_rules` / `constrain`): model code
  names axes ("batch", "heads", ...) and the launch layer binds those names
  to physical mesh axes via a rules dict.  Without an active mesh every
  helper is a no-op passthrough, so single-device smoke tests and the query
  engine never pay a sharding tax.  The rules dict maps logical name ->
  mesh axis (str), tuple of mesh axes, or None (replicated); see
  ``launch.shardspec.rules_for`` for the production tables.
* **Corpus sharding for distributed hybrid queries** (DESIGN.md §10):
  :class:`DistSpec` is the *fingerprintable* mesh description that rides
  ``EngineOptions.dist`` (a plan compiled for one mesh must miss the plan
  cache on any other mesh), :func:`resolve_mesh` turns a spec into a live
  ``jax.sharding.Mesh``, and :class:`ShardedCorpus` is the row-sharded
  corpus handle the catalog can register so every plan compiled against a
  (table, column) reuses ONE device placement.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_STATE = threading.local()


def _stack() -> list:
    if not hasattr(_STATE, "stack"):
        _STATE.stack = []
    return _STATE.stack


@contextlib.contextmanager
def logical_axis_rules(rules: Mapping[str, Any], mesh: Mesh | None = None):
    """Activate a logical->mesh axis mapping for the enclosed region."""
    _stack().append((dict(rules), mesh))
    try:
        yield
    finally:
        _stack().pop()


def current_rules() -> dict | None:
    """The innermost active logical-axis rules dict, or None."""
    s = _stack()
    return s[-1][0] if s else None


def current_mesh() -> Mesh | None:
    """The innermost active mesh bound by logical_axis_rules, or None."""
    s = _stack()
    return s[-1][1] if s else None


def logical_to_spec(logical_axes: Sequence, rules: Mapping[str, Any]) -> tuple:
    """Map logical axis names through the rules to PartitionSpec entries."""
    out = []
    for name in logical_axes:
        entry = rules.get(name) if name is not None else None
        if isinstance(entry, (list, tuple)):
            entry = tuple(entry) if entry else None
        out.append(entry)
    return tuple(out)


def _entry_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        size = 1
        for a in entry:
            size *= mesh.shape[a]
        return size
    return mesh.shape[entry]


def constrain(x, logical_axes: Sequence):
    """``with_sharding_constraint`` by logical names; passthrough when no
    rules/mesh are active or an axis size does not divide the dim."""
    s = _stack()
    if not s:
        return x
    rules, mesh = s[-1]
    if rules is None or mesh is None:
        return x
    spec = list(logical_to_spec(logical_axes, rules))
    while len(spec) < x.ndim:
        spec.append(None)
    fixed = []
    for dim, entry in zip(x.shape, spec[: x.ndim]):
        size = _entry_size(mesh, entry)
        fixed.append(entry if (size > 1 and dim % size == 0) else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*fixed)))


# ---------------------------------------------------------------------------
# Corpus sharding for distributed hybrid queries (DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistSpec:
    """Fingerprintable mesh description for ``EngineOptions.dist``.

    A live ``jax.sharding.Mesh`` holds device objects and cannot key a plan
    cache; ``DistSpec`` captures exactly what shapes compilation — the mesh
    shape and the axis names the corpus rows shard over — with a stable
    ``repr`` that folds into ``EngineOptions.fingerprint()``.  Changing the
    mesh (shape OR axis names) therefore misses the normalized plan cache
    and compiles fresh sharded executables (tests/test_dist_batch.py).

    ``mesh_shape[i]`` is the device count along ``axes[i]``; the total shard
    count is their product.  Hierarchical merges run innermost axis first
    (``axes[-1]``), then outward — ``merge_depth`` is ``len(axes)``."""
    mesh_shape: tuple[int, ...] = (1,)
    axes: tuple[str, ...] = ("data",)

    def __post_init__(self):
        object.__setattr__(self, "mesh_shape", tuple(self.mesh_shape))
        object.__setattr__(self, "axes", tuple(self.axes))
        if len(self.mesh_shape) != len(self.axes):
            raise ValueError(
                f"mesh_shape {self.mesh_shape} and axes {self.axes} must "
                f"have the same length")
        if not self.axes:
            raise ValueError("DistSpec needs at least one mesh axis")
        if len(set(self.axes)) != len(self.axes):
            raise ValueError(f"duplicate mesh axis names: {self.axes}")
        if any((not isinstance(s, int)) or s < 1 for s in self.mesh_shape):
            raise ValueError(
                f"mesh_shape entries must be ints >= 1, got {self.mesh_shape}")

    @property
    def num_shards(self) -> int:
        """Total corpus shard count (product of the mesh axis sizes)."""
        return math.prod(self.mesh_shape)

    @property
    def merge_depth(self) -> int:
        """Hierarchical-merge levels: one per mesh axis (innermost first)."""
        return len(self.axes)


@functools.lru_cache(maxsize=None)
def resolve_mesh(spec: DistSpec) -> Mesh:
    """Build (once per spec) the live mesh a :class:`DistSpec` describes.

    Uses the first ``spec.num_shards`` local devices; raises with the
    ``xla_force_host_platform_device_count`` hint when the host has fewer
    (CI simulates shard counts with fake CPU devices — see
    benchmarks/q10_sharded_qps.py).  Cached so every plan compiled against
    one spec shares one mesh object (and device placement)."""
    n = spec.num_shards
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"DistSpec {spec} needs {n} devices, have {len(devs)} — run "
            f"under XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"to simulate shards on CPU")
    return Mesh(np.array(devs[:n]).reshape(spec.mesh_shape), spec.axes)


@dataclasses.dataclass(frozen=True)
class ShardedCorpus:
    """A row-sharded corpus + its global row ids, pinned to one mesh.

    The handle the catalog registers per (table, vector column)
    (``Catalog.register_sharded``) so that every plan compiled with a
    matching ``EngineOptions.dist`` reuses ONE device placement instead of
    re-slicing the corpus per prepare.  Rows are zero-padded up to a
    multiple of the shard count (``num_rows`` keeps the real count); pad
    rows carry ``row_id = -1`` and are masked out of every scan by the
    distributed collectives' mask normalization."""
    mesh: Mesh
    axes: tuple[str, ...]
    corpus: jnp.ndarray        # (Npad, d), rows sharded over ``axes``
    row_ids: jnp.ndarray       # (Npad,), global ids; -1 on pad rows
    num_rows: int              # real (pre-padding) row count

    @classmethod
    def build(cls, mesh: Mesh, corpus, axes: Sequence[str] = ("data",)
              ) -> "ShardedCorpus":
        """Row-shard ``corpus`` over ``axes``, zero-padding to divisibility.

        Padding happens in host memory and each device receives only its
        own rows: the whole corpus is never materialized on one device
        (a corpus sized for the mesh need not fit on its first chip)."""
        axes = tuple(axes)
        shards = math.prod(mesh.shape[a] for a in axes)
        n = int(corpus.shape[0])
        pad = (-n) % shards
        arr = np.asarray(corpus, np.float32)
        ids = np.arange(n, dtype=np.int32)
        if pad:
            arr = np.concatenate(
                [arr, np.zeros((pad, arr.shape[1]), arr.dtype)])
            ids = np.concatenate([ids, np.full((pad,), -1, np.int32)])
        return cls(
            mesh, axes,
            jax.device_put(arr, NamedSharding(mesh, PartitionSpec(axes, None))),
            jax.device_put(ids, NamedSharding(mesh, PartitionSpec(axes))),
            n)

    @property
    def num_shards(self) -> int:
        """Corpus shard count (product of this handle's axis sizes)."""
        return math.prod(self.mesh.shape[a] for a in self.axes)

    @property
    def padded_rows(self) -> int:
        """Row count after divisibility padding (``corpus.shape[0]``)."""
        return int(self.corpus.shape[0])

    @property
    def spec(self) -> DistSpec:
        """The :class:`DistSpec` this handle's mesh corresponds to (the
        catalog's registry key — engine dist meshes are dedicated, so the
        handle's axes must be exactly the mesh's axes)."""
        return DistSpec(tuple(int(s) for s in self.mesh.devices.shape),
                        tuple(self.mesh.axis_names))

    def matches(self, spec: DistSpec) -> bool:
        """True iff this handle's mesh is the one ``spec`` describes."""
        return (self.axes == spec.axes
                and tuple(self.mesh.devices.shape) == spec.mesh_shape
                and tuple(self.mesh.axis_names) == spec.axes)
