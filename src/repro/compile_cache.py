"""JAX's persistent compilation cache for the repository's entry points.

Scripts, the server and the examples call :func:`enable_compile_cache` once
before they compile anything; the library itself never turns the cache on,
so importing it (as the tests do) leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
import pathlib

import jax

# A fixed path inside the checkout (git-ignored): the directory is part of
# the cache key, so a path built from a temp dir, pid or clock never hits.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here overrides it.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.

    Entries are keyed on the programs' metadata too: a profile names each
    device op by the ``jax.named_scope`` path in its executable's
    metadata, and with that left out of the key an executable cached
    before a scope was added or moved would bring stale names into every
    trace."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
