"""The one traffic generator: requests drawn from the configuration's
data seed, ordered by the run's seed, with Poisson arrivals in an open
loop and in the on periods of an on/off one; constant binds; and the
committed mixes' requests pinned."""
import hashlib
import types

import numpy as np
import pytest

import harness
from test_rehearsal import CELLS, SPEC

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


def test_a_constant_bind_draws_nothing():
    ds = _dataset()
    mix = {**_open(), "binds": {**MIX["binds"],
                                "r": {"kind": "constant", "value": 0.5},
                                "ex": {"kind": "constant", "value": 3}}}
    a = traffic.generate(_open(), ds, 5, 11, 4.0)
    b = traffic.generate(mix, ds, 5, 11, 4.0)
    assert [_key(x) for x in a.binds] == [_key(x) for x in b.binds]
    np.testing.assert_array_equal(a.offsets, b.offsets)
    assert {x["r"].dtype for x in b.binds} == {np.dtype(np.float32)}
    assert {x["ex"].dtype for x in b.binds} == {np.dtype(np.int32)}
    assert {float(x["r"]) for x in b.binds} == {0.5}
    assert {int(x["ex"]) for x in b.binds} == {3}
    with pytest.raises(ValueError, match="a number"):
        traffic.generate({**mix, "binds": {"x": {"kind": "constant",
                                                 "value": "3"}}},
                         ds, 5, 11, 1.0)


def test_onoff_arrivals_come_only_while_on_at_the_mean_rate():
    ds = _dataset()
    rate, on_s, off_s, seconds = 200.0, 0.5, 1.5, 200.0
    mix = {**MIX, "loop": "onoff", "rate_per_s": rate, "on_s": on_s,
           "off_s": off_s}
    req = traffic.generate(mix, ds, 5, 9, seconds)
    assert len(req.binds) == pytest.approx(rate * seconds, rel=0.05)
    assert np.all(np.diff(req.offsets) >= 0)
    assert req.offsets.max() < seconds
    assert np.all(req.offsets % (on_s + off_s) < on_s)
    # inside an on period they come at the peak rate, four times the mean
    gaps = np.diff(req.offsets)
    within = gaps[gaps < off_s]
    assert 1 / within.mean() == pytest.approx(
        rate * (on_s + off_s) / on_s, rel=0.05)


# sha256 of the requests that each committed mix file makes at the CPU
# rehearsal's toy sizes (test_rehearsal.shrink, 1.5 s, its seed), as the
# generator drew them before the constant bind and the on/off loop came
MIX_DIGESTS = {
    "q1_closed128": (
        6016,
        "66e4bd41ef4a9fd5a0b1fb4c2d3c44660fca749276e8ca87882ac3ccf8cd48f7"),
    "q1_poisson": (
        227,
        "1403947af6c17148d0ba34fab74d6f56d5fa3697f62ca59fb11dbf6e8835d6c0"),
    "q1_serial": (
        6016,
        "66e4bd41ef4a9fd5a0b1fb4c2d3c44660fca749276e8ca87882ac3ccf8cd48f7"),
}


def _digest(req) -> str:
    h = hashlib.sha256()
    for b in req.binds:
        for name in sorted(b):
            h.update(name.encode())
            h.update(np.ascontiguousarray(b[name]).tobytes())
    if req.offsets is not None:
        h.update(np.ascontiguousarray(req.offsets).tobytes())
    h.update(repr(req.clients).encode())
    return h.hexdigest()


@pytest.mark.parametrize("cell", CELLS)
def test_committed_mixes_draw_what_they_drew(cell):
    from test_rehearsal import SEED, shrink
    c = harness.load_cell(cell)
    shrink(c)
    ds = harness.load_module("datasets", c.config["dataset"]).build(
        c.config, harness.seed_key(c.config["data_seed"]))
    req = traffic.generate(c.traffic, ds, c.config["data_seed"], SEED, 1.5)
    traffic_name = next(w["traffic"] for w in SPEC["workloads"]
                        if w["name"] == cell)
    assert (len(req.binds), _digest(req)) == MIX_DIGESTS[traffic_name]
