"""The one traffic generator: requests drawn from the configuration's
data seed, ordered by the run's seed, with Poisson arrivals in an open
loop."""
import types

import numpy as np
import pytest

import harness

traffic = harness.own("traffic")

MIX = {"binds": {"qv": {"kind": "query_vector"},
                 "p": {"kind": "quantile_below", "column": "price",
                       "shares": [1.0, 0.5, 0.03]}}}


def _dataset():
    price = np.random.default_rng(0).lognormal(3.5, 1.0, 5000)

    def queries(key, n):
        import jax
        return np.asarray(jax.random.normal(key, (n, 4)))
    return types.SimpleNamespace(columns={"price": price}, queries=queries)


def _open(rate=200.0):
    return {**MIX, "loop": "open", "rate_per_s": rate}


def _key(binds):
    return (float(binds["qv"][0]), float(binds["p"]))


def test_same_seeds_same_requests():
    ds = _dataset()
    a = traffic.generate(_open(), ds, 5, 2**40 + 1, 4.0)
    b = traffic.generate(_open(), ds, 5, 2**40 + 1, 4.0)
    assert [_key(x) for x in a.binds] == [_key(x) for x in b.binds]
    np.testing.assert_array_equal(a.offsets, b.offsets)


def test_the_run_seed_only_orders_the_requests():
    ds = _dataset()
    a = traffic.generate(_open(), ds, 5, 11, 4.0)
    b = traffic.generate(_open(), ds, 5, 12, 4.0)
    assert [_key(x) for x in a.binds] != [_key(x) for x in b.binds]
    whole = (min(len(a.binds), len(b.binds)) // traffic.DEAL_BLOCK
             * traffic.DEAL_BLOCK)
    assert sorted(map(_key, a.binds[:whole])) == sorted(
        map(_key, b.binds[:whole]))
    c = traffic.generate(_open(), ds, 6, 11, 4.0)
    assert not set(map(_key, a.binds)) & set(map(_key, c.binds))


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_one_drawing_path_for_both_loops(loop):
    ds = _dataset()
    mix = (_open() if loop == "open"
           else {**MIX, "loop": "closed", "clients": 8, "pool_per_s": 100})
    req = traffic.generate(mix, ds, 5, 3, 2.0)
    shares = np.asarray([x["p"] for x in req.binds])
    assert np.isinf(shares).sum() == pytest.approx(len(shares) / 3, abs=2)


def test_open_loop_gaps_are_exponential():
    ds = _dataset()
    rate, seconds = 400.0, 50.0
    req = traffic.generate(_open(rate), ds, 5, 9, seconds)
    gaps = np.diff(req.offsets)
    assert len(req.binds) == pytest.approx(rate * seconds, rel=0.03)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.03)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # the arrival count per second wanders as a Poisson count does
    per_s = np.bincount(req.offsets.astype(int), minlength=int(seconds))
    assert per_s.var() / per_s.mean() == pytest.approx(1.0, abs=0.5)
