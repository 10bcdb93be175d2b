"""A cell, a configuration and a per-layer metric added as new files are
found by name, with no existing file edited; run.py refuses a machine
without the chip the cell needs."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import cells
import run


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark's files with one more config, cell and
    metric, each added as a file plus its BENCHMARK.json entry."""
    shutil.copytree(cells.BENCH / "configs", tmp_path / "bench" / "configs")
    shutil.copytree(cells.BENCH / "workloads",
                    tmp_path / "bench" / "workloads")
    shutil.copytree(cells.BENCH / "metrics", tmp_path / "bench" / "metrics")
    bench = cells.load_json(cells.ROOT / "BENCHMARK.json")
    cfg = cells.load_json(cells.BENCH / "configs" / "vit-b16.json")
    (tmp_path / "bench" / "configs" / "vit-l16.json").write_text(json.dumps(
        dict(cfg, name="vit-l16", num_layers=24, d_model=1024)))
    traffic = cells.load_json(cells.BENCH / "workloads" / "vit-b16.dp1.json")
    (tmp_path / "bench" / "workloads" / "vit-l16.dp1-b32.json").write_text(
        json.dumps(dict(traffic, global_batch=32)))
    (tmp_path / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return float(run['steps'])\n")
    bench["configs"].append({"name": "vit-l16", "source": "x",
                             "file": "bench/configs/vit-l16.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "vit-l16.dp1", "config": "vit-l16",
                               "traffic": "vit-l16.dp1-b32", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "samples_per_s",
                               "workloads": ["vit-l16.dp1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_new_files_are_found_by_name(tree):
    cell = cells.load_cell("vit-l16.dp1", root=tree)
    assert cell.config["num_layers"] == 24
    assert cell.traffic["global_batch"] == 32
    assert [m["name"] for m in cell.per_layer][-1] == "steps_seen"
    assert cells.metric_reader("steps_seen", root=tree)({"steps": 3}) == 3.0
    old = cells.load_cell("vit-b16.dp1", root=tree)
    assert "steps_seen" not in [m["name"] for m in old.per_layer]
    assert "collective_exposed_ms" not in [m["name"] for m in old.per_layer]


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = cells.load_json(cells.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    for w in bench["workloads"]:
        assert cells.load_cell(w["name"]).chips == w["chips"]


def test_a_workload_key_the_harness_does_not_read_is_refused(tree):
    path = tree / "bench" / "workloads" / "vit-l16.dp1-b32.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    augment=True)))
    with pytest.raises(ValueError, match="augment"):
        cells.load_cell("vit-l16.dp1", root=tree)


def test_names_cannot_leave_the_directory():
    with pytest.raises(ValueError):
        cells.metric_reader("../run")


def fake(platform, kind, n=1):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


@pytest.mark.parametrize("devs", [fake("cpu", "cpu"),
                                  fake("tpu", "TPU v9 nonesuch"),
                                  fake("tpu", "TPU v5 lite", 1)])
def test_refuses_without_the_chip(monkeypatch, devs):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(run.Refused):
        run.chip_devices(4, run.peaks())


def test_command_on_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH / "run.py"), "--workload",
         "vit-b16.dp1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "measures a TPU" in p.stderr
