"""A run with the timed path broken underneath comes out not correct, and
so does the control; a sound run comes out correct. CPU, small widths,
the harness's look for a chip skipped (``run.run`` is handed the
devices)."""
import jax
import jax.numpy as jnp
import pytest

import calibrate
import check
import run
from reference import Reference

import small

# between the small cell's sound readings and its control's and faults'
# (calibrate.readings at these widths on the CPU: sound at most 1.5e-3,
# 6.2e-3, 4.9e-3; the control at least 1.1e-2, 2.9e-2, 2.6e-2)
LIMITS = {"loss_gap": 5e-3, "grad_gap": 2e-2, "update_gap": 2e-2}
PEAK = {"bf16_flops_per_s": 1e12}


def small_cell(workload="vit-b16.dp1", chips=1, **over):
    return small.cell(workload, chips=chips, limits=LIMITS, **over)


def result(cell, seed=2 ** 31 + 3):
    return run.run(cell, seed, 0.5, False, jax.devices()[:cell.chips], PEAK)


def test_sound_run_is_correct():
    res = result(small_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _patch_step(monkeypatch, wrap):
    from repro.core.engine import DistributedEngine
    orig = DistributedEngine._train_step
    monkeypatch.setattr(DistributedEngine, "_train_step",
                        lambda self, state, batch: wrap(orig, self, state,
                                                        batch))


def _rows(batch, n):
    return {k: v[:n] for k, v in batch.items()}


def test_state_left_unchanged(monkeypatch):
    _patch_step(monkeypatch, lambda f, s, st, b: (st, f(s, st, b)[1]))
    res = result(small_cell())
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(monkeypatch):
    _patch_step(monkeypatch, lambda f, s, st, b: f(
        s, st, _rows(b, b["labels"].shape[0] // 2)))
    assert not result(small_cell())["correct"]


def test_exchange_between_chips_left_out(monkeypatch):
    # each chip updating from its own shard is what chip 0 keeps
    _patch_step(monkeypatch, lambda f, s, st, b: f(
        s, st, _rows(b, b["labels"].shape[0] // 4)))
    cell = small_cell("vit-b16.dp4-zero0", chips=4, global_batch=16)
    assert not result(cell)["correct"]


def test_answer_altered_where_produced(monkeypatch):
    from repro.models import transformer
    head = transformer._head
    monkeypatch.setattr(transformer, "_head", lambda cfg, p, h: head(
        cfg, p, h).at[0, 0].add(jnp.asarray(calibrate.LOGIT_SHIFT, h.dtype)))
    assert not result(small_cell())["correct"]


def test_wrong_second_moment_decay(monkeypatch):
    from repro.core import engine
    make = engine.make_optimizer
    monkeypatch.setattr(engine, "make_optimizer",
                        lambda *a, **kw: make(*a, **dict(kw, b2=0.999)))
    res = result(small_cell())
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > 0.5


def test_only_numbers_with_a_limit_are_compared():
    checks, ok = check.judge(
        {"loss_gap": 1.0, "grad_gap": 1.0, "update_gap": 1e-4},
        {"update_gap": 1e-3})
    assert ok and list(checks) == ["update_gap"]
    with pytest.raises(KeyError):
        check.judge({}, {"step_gap": 1.0})


def test_control_is_not_correct():
    cell = small_cell()
    ref = Reference(cell.config, cell.traffic).readings(7)
    fp8 = Reference(cell.config, cell.traffic, precision="fp8").readings(7)
    _, ok = check.judge(check.gaps(fp8, ref), LIMITS)
    assert not ok
