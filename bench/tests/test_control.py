"""The control at a size a test run holds: the reference put in the
program's place one precision below the configuration's (three bfloat16
passes for float32 at HIGHEST) fails one of each cell's limits, on the
same requests on which the program passes them all."""
import jax
import pytest

import harness
import readings as readings_mod
from test_rehearsal import CELLS, shrink

SEEDS = [7, 2**32 + 9, 123456789012]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell):
    out = readings_mod.readings(cell, 1.0, SEEDS, SEEDS,
                                device_check=lambda c: jax.devices()[:c],
                                adjust=shrink)
    loaded = harness.load_cell(cell)
    limits = {"failed": {"max": 0}, **loaded.config["limits"]}
    judge = harness.load_module("checks",
                                loaded.traffic["check"]["module"]).judge
    for numbers in out["program"]:
        assert all(ok for *_, ok in judge(numbers, limits)), numbers
    for numbers in out["control"]:
        numbers = {"failed": 0, **numbers}
        assert not all(ok for *_, ok in judge(numbers, limits)), numbers
