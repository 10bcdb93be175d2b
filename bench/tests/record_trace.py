"""Record the small trace that test_trace.py reads, on the chip.

    python bench/tests/record_trace.py [OUT_DIR]   # on a four-chip host

Two steps of the small cell (``small.py``) as dp4 ZeRO-0 through the
harness's loop, under the profiler and inside the ``traced_window`` span,
so that the trace holds host spans, device ops with their source files and
the gradient all-reduce. Writes ``small_dp4.xplane.pb.gz`` and the
compiled step's HLO text ``small_dp4.hlo.txt.gz``, its source paths made
relative to the checkout, to OUT_DIR (default ``data/``).
"""
import glob
import gzip
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

import jax  # noqa: E402

import run  # noqa: E402
import small  # noqa: E402
import system  # noqa: E402


def main():
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "data"
    out.mkdir(parents=True, exist_ok=True)
    devices = run.chip_devices(4, run.peaks())
    cell = small.cell("vit", chips=4, global_batch=16)
    t = system.build(cell, 1, devices)
    for _ in range(3):
        system.train_step(t)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(run.TRACED):
            for _ in range(2):
                system.train_step(t)
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        with open(path, "rb") as f, \
                gzip.open(out / "small_dp4.xplane.pb.gz", "wb") as g:
            g.write(f.read())
    batch = next(t.prefetcher)[1]
    text = t.step_fn.lower(t.state, batch).compile().as_text()
    text = text.replace(f'"{HERE.parents[1]}/', '"')
    with gzip.open(out / "small_dp4.hlo.txt.gz", "wt") as g:
        g.write(text)
    t.close()


if __name__ == "__main__":
    main()
