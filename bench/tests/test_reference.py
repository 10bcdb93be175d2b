"""The float32 reference against the program's own step, at small widths
on the CPU: loss, first gradient and parameter change of three steps."""
import jax
import pytest

import check
import run
import system
from reference import Reference

import small


def readings(cell, seed, devices):
    t = system.build(cell.config, cell.traffic, seed, devices)
    try:
        return run.check_steps(t, cell.traffic)
    finally:
        t.close()


@pytest.mark.parametrize("chips", [1, 2])
def test_float32_program_matches_reference(chips):
    cell = small.cell(config_over={"dtype": "float32"})
    prog = readings(cell, 2 ** 31 + 11, jax.devices()[:chips])
    ref = Reference(cell.config, cell.traffic).readings(2 ** 31 + 11)
    gaps = check.gaps(prog, ref)
    assert gaps["loss_gap"] < 1e-5, gaps
    assert gaps["grad_gap"] < 1e-4, gaps
    assert gaps["update_gap"] < 1e-4, gaps


def test_bfloat16_program_within_small_gaps():
    cell = small.cell()
    prog = readings(cell, 5, jax.devices()[:1])
    ref = Reference(cell.config, cell.traffic).readings(5)
    gaps = check.gaps(prog, ref)
    assert 0 < gaps["loss_gap"] < 2e-2, gaps
    assert 0 < gaps["grad_gap"] < 0.2, gaps
