"""The control (the plain reference with 3-bit sketch counters, in the
program's place) fails the check, where the program passes it, on three
seeds at a size a test run holds. ``chipbench/control.py`` makes the same
readings on the chip at each cell's own size."""

import pytest

import control
import harness
from conftest import cells


@pytest.mark.parametrize("cell", cells())
def test_control_fails_where_the_program_passes(cell):
    for seed in (1, 2, 2**31 + 3):
        r = control.readings(harness, cell, seed, 1.0, rehearse=True, scale=0.02)
        assert all(v == 0 for v in r["program"].values()), r
        assert r["control"]["hit_mismatch"] > 0, r
