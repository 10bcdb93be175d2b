"""The float32 reference against the program's own step, at small widths
on the CPU, for each model family: loss, first gradient and parameter
change of three steps."""
import json
from pathlib import Path

import jax
import pytest

import check
import run
import system
from reference import Reference

import small

DATA = Path(__file__).resolve().parent / "data"


def readings(cell, seed, devices):
    t = system.build(cell, seed, devices)
    try:
        return run.check_steps(t, cell.traffic)
    finally:
        t.close()


@pytest.mark.parametrize("family", small.families())
@pytest.mark.parametrize("chips", [1, 2])
def test_float32_program_matches_reference(family, chips):
    cell = small.cell(family, config_over={"dtype": "float32"})
    prog = readings(cell, 2 ** 31 + 11, jax.devices()[:chips])
    ref = Reference(cell).readings(2 ** 31 + 11)
    gaps = check.gaps(prog, ref)
    assert gaps["loss_gap"] < 1e-5, gaps
    assert gaps["grad_gap"] < 1e-4, gaps
    assert gaps["update_gap"] < 1e-4, gaps


@pytest.mark.parametrize("family", small.families())
def test_bfloat16_program_within_small_gaps(family):
    cell = small.cell(family)
    prog = readings(cell, 5, jax.devices()[:1])
    ref = Reference(cell).readings(5)
    gaps = check.gaps(prog, ref)
    assert 0 < gaps["loss_gap"] < 2e-2, gaps
    assert 0 < gaps["grad_gap"] < 0.2, gaps


def _close(got, want):
    if isinstance(want, dict):
        assert set(got) == set(want)
        for k in want:
            _close(got[k], want[k])
    elif isinstance(want, list):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _close(g, w)
    else:
        assert got == pytest.approx(want, rel=1e-6, abs=0)


def test_vit_readings_equal_the_recorded_ones():
    # recorded from the reference before the ViT model moved into
    # families/vit.py; the move keeps its key splits, op order and dtypes
    want = json.loads((DATA / "vit_small_readings.json").read_text())
    got = Reference(small.cell("vit")).readings(want["seed"])
    _close(got, want["readings"])
