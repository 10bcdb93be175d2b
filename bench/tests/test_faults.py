"""A run with the timed path broken underneath comes out not correct, and
so does the control; a sound run comes out correct. CPU, small widths,
each model family at its ``SMALL`` cut and limits (set between that cut's
sound readings and its control's and faults'), the harness's look for a
chip skipped (``run.run`` is handed the devices)."""
import jax
import jax.numpy as jnp
import pytest

import calibrate
import check
import run
from reference import Reference

import small

PEAK = {"bf16_flops_per_s": 1e12}
FAMILIES = pytest.mark.parametrize("family", small.families())


def result(cell, seed=2 ** 31 + 3):
    return run.run(cell, seed, 0.5, False, jax.devices()[:cell.chips], PEAK)


@FAMILIES
def test_sound_run_is_correct(family):
    res = result(small.cell(family))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _patch_step(monkeypatch, wrap):
    from repro.core.engine import DistributedEngine
    orig = DistributedEngine._train_step
    monkeypatch.setattr(DistributedEngine, "_train_step",
                        lambda self, state, batch: wrap(orig, self, state,
                                                        batch))


def _rows(batch, part):
    """The first ``1/part`` of the batch's rows."""
    n = len(next(iter(batch.values()))) // part
    return {k: v[:n] for k, v in batch.items()}


@FAMILIES
def test_state_left_unchanged(monkeypatch, family):
    _patch_step(monkeypatch, lambda f, s, st, b: (st, f(s, st, b)[1]))
    res = result(small.cell(family))
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


@FAMILIES
def test_half_the_batch_left_out(monkeypatch, family):
    _patch_step(monkeypatch, lambda f, s, st, b: f(s, st, _rows(b, 2)))
    assert not result(small.cell(family))["correct"]


@pytest.mark.parametrize("family", small.families(chips=4))
def test_exchange_between_chips_left_out(monkeypatch, family):
    # each chip updating from its own shard is what chip 0 keeps
    _patch_step(monkeypatch, lambda f, s, st, b: f(s, st, _rows(b, 4)))
    cell = small.cell(family, chips=4, global_batch=16)
    assert not result(cell)["correct"]


def _alter_answer(monkeypatch):
    from repro.models import transformer
    head = transformer._head

    def altered(cfg, p, h):
        out = head(cfg, p, h)
        return out.at[(0,) * out.ndim].add(
            jnp.asarray(calibrate.LOGIT_SHIFT, h.dtype))
    monkeypatch.setattr(transformer, "_head", altered)


@FAMILIES
def test_answer_altered_where_produced(monkeypatch, family):
    _alter_answer(monkeypatch)
    assert not result(small.cell(family))["correct"]


@FAMILIES
def test_wrong_second_moment_decay(monkeypatch, family):
    from repro.core import engine
    make = engine.make_optimizer
    monkeypatch.setattr(engine, "make_optimizer",
                        lambda *a, **kw: make(*a, **dict(kw, b2=0.999)))
    res = result(small.cell(family))
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > 0.5


ACCUMULATED = {
    "sound": lambda mp: None,
    "state_unchanged": lambda mp: _patch_step(
        mp, lambda f, s, st, b: (st, f(s, st, b)[1])),
    "half_batch": lambda mp: _patch_step(
        mp, lambda f, s, st, b: f(s, st, _rows(b, 2))),
    "altered_answer": _alter_answer,
}


@FAMILIES
@pytest.mark.parametrize("fault", sorted(ACCUMULATED))
def test_faults_under_accumulation(monkeypatch, family, fault):
    # the same faults planted in a step that accumulates two micro-batches
    ACCUMULATED[fault](monkeypatch)
    res = result(small.cell(family, accum=2))
    assert res["correct"] == (fault == "sound"), res["checks"]


def test_only_numbers_with_a_limit_are_compared():
    checks, ok = check.judge(
        {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1e-4},
        {"update_gap": 1e-3})
    assert ok and list(checks) == ["update_gap"]
    with pytest.raises(KeyError):
        check.judge({}, {"step_gap": 1.0})


@FAMILIES
def test_control_is_not_correct(family):
    cell = small.cell(family)
    ref = Reference(cell).readings(7)
    fp8 = Reference(cell, precision="fp8").readings(7)
    _, ok = check.judge(check.gaps(fp8, ref), cell.traffic["limits"])
    assert not ok
