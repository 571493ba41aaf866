"""The one traffic generator: it reads a mix file, ``bench/traffic/<name>.json``.

A mix file states the statement (SQL and the compared result), how its
binds are drawn, the loop (``closed`` with a number of clients, ``open``
with Poisson arrivals at a fixed rate, or ``onoff`` with Poisson bursts),
the server's settings and the batch sizes to warm.  Keys:

* ``sql``: the statement, prepared once through ``Database.prepare``;
* ``binds``: bind name -> one of
  ``{"kind": "query_vector"}``: a fresh vector from the dataset's mixture
  for every request;
  ``{"kind": "quantile_below", "column": c, "shares": [...]}``: the bound
  under which that share of the column lies, +inf for a share of 1; shares
  come in shuffled blocks that hold each share once;
  ``{"kind": "constant", "value": v}``: ``v`` for every request, float32
  for a float and int32 for an integer; it draws nothing, so adding one
  leaves every other bind's draws as they were;
* ``loop``: ``closed`` (``clients``; each sends its next request when the
  last one is answered, so ``pool_per_s`` caps the requests made ahead),
  ``open`` (``rate_per_s``; Poisson arrivals: independent exponential gaps)
  or ``onoff`` (``rate_per_s`` the mean, ``on_s``, ``off_s``: Poisson
  arrivals at ``rate_per_s * (on_s + off_s) / on_s`` while on, none while
  off; the window opens at the start of an on period);
* ``server``: keyword arguments of ``Database.serve``;
* ``warm_batches``: the batch sizes whose buckets set-up compiles;
* ``check``: the module under ``bench/checks/`` that decides ``correct``
  (``module``), the sample it compares (``sample``), and what else that
  module reads (``k``, the binds and columns it names).

One policy for every cell: the requests (vectors, shares and, in an open
or on/off loop, gaps) are drawn from the configuration's ``data_seed``, as
its corpus is, and the run's seed only orders them, shuffling within
consecutive blocks of 64.  So every seed sends the same requests at the
same arrival rate, in an order of its own, and any prefix holds the same
requests bar its last block.

Everything is drawn before the window opens.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


DEAL_BLOCK = 64


@dataclasses.dataclass
class Requests:
    binds: list             # per-request bind dicts of host NumPy values
    offsets: np.ndarray | None   # open loop: due time after the window opens
    clients: int | None     # closed loop: concurrent clients


def _share_blocks(rng: np.random.Generator, shares, n: int) -> np.ndarray:
    blocks = -(-n // len(shares))
    return np.concatenate([rng.permutation(shares)
                           for _ in range(blocks)])[:n]


def _dealt(rng: np.random.Generator, n: int) -> np.ndarray:
    """0..n-1 shuffled within consecutive blocks of DEAL_BLOCK, so that any
    prefix holds the same requests whatever the seed, bar its last block."""
    return np.concatenate([s + rng.permutation(min(DEAL_BLOCK, n - s))
                           for s in range(0, n, DEAL_BLOCK)])


def _constant_dtype(value):
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        raise ValueError(f"a constant bind is a number, not {value!r}")
    return np.int32 if isinstance(value, int) else np.float32


def generate(traffic: dict, dataset, data_seed: int, seed: int,
             seconds: float) -> Requests:
    """Every request of one run: drawn from ``data_seed``, ordered by
    ``seed``."""
    draw = np.random.default_rng([data_seed, 0x7AFF1C])
    offsets = clients = None
    loop = traffic["loop"]
    if loop in ("open", "onoff"):
        rate, busy = float(traffic["rate_per_s"]), seconds
        if loop == "onoff":
            on_s, off_s = float(traffic["on_s"]), float(traffic["off_s"])
            rate *= (on_s + off_s) / on_s
            periods, rest = divmod(seconds, on_s + off_s)
            busy = periods * on_s + min(rest, on_s)     # on inside the window
        pool = int(rate * busy * 1.25) + 2 * DEAL_BLOCK
        gaps = draw.exponential(1.0 / rate, pool)
    elif loop == "closed":
        clients = int(traffic["clients"])
        pool = clients + int(traffic["pool_per_s"] * seconds)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    columns = {}
    for name, spec in traffic["binds"].items():
        if spec["kind"] == "query_vector":
            key = jax.random.key(int(draw.integers(2**31)))
            columns[name] = dataset.queries(key, pool)
        elif spec["kind"] == "quantile_below":
            col = dataset.columns[spec["column"]]
            shares = _share_blocks(draw, np.asarray(spec["shares"]), pool)
            bounds = {s: (np.inf if s >= 1.0 else np.quantile(col, s))
                      for s in set(spec["shares"])}
            columns[name] = np.asarray([bounds[s] for s in shares],
                                       np.float32)
        elif spec["kind"] == "constant":
            columns[name] = np.full(pool, spec["value"], _constant_dtype(
                spec["value"]))
        else:
            raise ValueError(f"unknown bind kind {spec['kind']!r}")
    order = _dealt(np.random.default_rng([seed, 0x7AFF1C]), pool)
    n = pool
    if clients is None:
        offsets = np.cumsum(gaps[order])
        if loop == "onoff":
            # the arrivals' own clock runs only while on
            periods, within = np.divmod(offsets, on_s)
            offsets = periods * (on_s + off_s) + within
        n = int(np.searchsorted(offsets, seconds))
        offsets = offsets[:n]
    binds = [{k: v[i] for k, v in columns.items()} for i in order[:n]]
    return Requests(binds, offsets, clients)
