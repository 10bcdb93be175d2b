"""Each family's FLOP count against the dot and convolution FLOPs that
``launch/hlo_analysis.py`` counts in the compiled train step, at small
widths on the CPU."""
import jax
import pytest

import cells
import system
from repro.launch import hlo_analysis

import small

CASES = [(f, over) for f in small.families()
         for over in ({}, cells.family(f).SMALL["shapes_also"])]


@pytest.mark.parametrize("family,over", CASES)
def test_matmul_flops_match_compiled_step(family, over):
    cell = small.cell(family, config_over=over, global_batch=4)
    t = system.build(cell, 0, jax.devices()[:1])
    try:
        batch = next(t.prefetcher)[1]
        with t.mesh:
            text = t.step_fn.lower(t.state, batch).compile().as_text()
    finally:
        t.close()
    counted = hlo_analysis.analyze(text).flops
    want = cell.family.train_flops_per_sample(cell.config, cell.traffic) * 4
    assert counted == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("config,want", [("vit-b16", 105_147_196_416),
                                         ("vit-b16-384", 332_222_063_616)])
def test_vit_b16_per_sample(config, want):
    # 6 x 85.1M non-embedding matmul params x 197 (577) tokens, plus
    # attention, the patch projection at 4/param/patch and the head: the
    # count mfu has read since the benchmark began
    cell = cells.load_cell(f"{config}.dp1")
    assert cell.family.train_flops_per_sample(cell.config,
                                              cell.traffic) == want
