"""flops.py against the dot and convolution FLOPs that
``launch/hlo_analysis.py`` counts in the compiled train step, at small
widths on the CPU."""
import jax
import pytest

import flops
import system
from repro.launch import hlo_analysis

import small


@pytest.mark.parametrize("over", [{}, {"image_size": 64, "d_ff": 96}])
def test_matmul_flops_match_compiled_step(over):
    cell = small.cell(config_over=over, global_batch=4)
    t = system.build(cell.config, cell.traffic, 0, jax.devices()[:1])
    try:
        batch = next(t.prefetcher)[1]
        with t.mesh:
            text = t.step_fn.lower(t.state, batch).compile().as_text()
    finally:
        t.close()
    counted = hlo_analysis.analyze(text).flops
    want = flops.train_flops_per_sample(cell.config) * 4
    assert counted == pytest.approx(want, rel=1e-6)


def test_vit_b16_per_sample():
    cfg = small.cells.load_json(small.cells.BENCH / "configs" /
                                "vit-b16.json")
    # 6 x 85.1M non-embedding matmul params x 197 tokens, plus attention,
    # the patch projection at 4/param/patch and the head
    f = flops.train_flops_per_sample(cfg)
    assert 1.05e11 < f < 1.1e11
