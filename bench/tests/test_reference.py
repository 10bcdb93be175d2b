"""The float32 reference against the program's own step, at small widths
on the CPU, for each model family: loss, first gradient and parameter
change of three steps; and what the reference keeps on the device."""
import functools
import gc
import json
from pathlib import Path

import jax
import pytest

import check
import reference
import run
import system
from reference import Reference

import dense_lm
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
@pytest.mark.parametrize("chips", [1, 2])
def test_float32_program_with_accumulation_matches_reference(family, chips):
    # two micro-batches a step through the engine's accumulation loop
    cell = small.cell(family, config_over={"dtype": "float32"}, accum=2)
    prog = readings(cell, 2 ** 31 + 13, jax.devices()[:chips])
    ref = Reference(cell).readings(2 ** 31 + 13)
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


def _live_bytes():
    return sum(x.nbytes for x in jax.live_arrays())


CUTS = {"vit": lambda: small.cell("vit"),
        "dense_lm": lambda: dense_lm.cell(dense_lm.TINY)}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_reference_holds_three_copies_of_the_parameters(monkeypatch, cut):
    # after every jitted call of the reference, the device holds at most
    # the parameters, a gradient accumulator and a block's gradient (or a
    # leaf's two moments), one more leaf, and the batch
    cell = CUTS[cut]()
    seed = 2 ** 31 + 17
    ref = Reference(cell)
    shapes = jax.eval_shape(functools.partial(cell.family.init_params,
                                              cell.config),
                            jax.random.PRNGKey(seed))
    leaves = [x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)]
    batch = sum(a.nbytes for a in cell.family.batch(
        cell.config, cell.traffic, seed, 0))
    gc.collect()
    base, seen = _live_bytes(), []

    def watch(f):
        def call(*args, **kw):
            out = f(*args, **kw)
            seen.append(_live_bytes() - base)
            return out
        return call

    for owner in (reference, ref):
        for name, f in list(vars(owner).items()):
            if callable(f) and hasattr(f, "lower"):     # a jitted function
                monkeypatch.setattr(owner, name, watch(f))
    ref.readings(seed)
    copies = max(seen) / sum(leaves)
    assert len(seen) > 10
    assert max(seen) <= 3 * sum(leaves) + max(leaves) + batch, \
        f"{copies:.2f} copies of the parameters"


def test_dense_lm_runs_as_on_the_chip(capsys):
    # the chip run's script, at the tiny cut on the CPU
    assert dense_lm.main(["--tiny", "--seed", str(2 ** 31 + 19)]) == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["error"] is None and len(out["losses"]) == 3
    assert out["params"] == sum(
        x.size for x in jax.tree.leaves(dense_lm.init_params(
            dense_lm.TINY, jax.random.PRNGKey(0))))


def test_root_norms_equal_the_norms_of_a_rooted_copy():
    # the second moment's gradient norms, read without a sqrt(nu) tree
    nu = jax.tree.map(jax.numpy.square, dense_lm.init_params(
        dense_lm.TINY, jax.random.PRNGKey(3)))
    want = reference.leaf_norms(jax.tree.map(jax.numpy.sqrt, nu))
    got = reference.leaf_norms(nu, root=True)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6, abs=0)
