"""The entry points leave the accelerator to whoever runs on it: the
``--supervise`` parent never starts a JAX backend (its child owns the
devices), and chip_smoke.py refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys

from conftest import run_subprocess

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_supervise_parent_never_initialises_a_backend():
    out = run_subprocess(r"""
from jax._src import xla_bridge
from repro.launch import train
try:
    train.main(["--supervise", "--max-restarts", "0", "--arch", "vit-b16",
                "--smoke", "--steps", "1", "--batch", "4",
                "--dataset", "synthetic"])
except SystemExit as e:
    rc = e.code
print("RC", rc, "BACKENDS", xla_bridge.backends_are_initialized())
""", devices=1, timeout=300)
    assert "[train] done" in out          # the child trained
    assert "RC 0 BACKENDS False" in out, out


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU; JAX found cpu" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout
