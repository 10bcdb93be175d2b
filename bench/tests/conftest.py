"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

Four host devices, so the dp cells' paths can be rehearsed. Set before any
backend starts."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)
