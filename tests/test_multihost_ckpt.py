"""Multi-host correctness of the v2 elastic checkpoint format
(repro-elastic-ckpt/v2): simulated multi-process saves (per-process
staging + manifests, process-0 merge barrier + single commit), the
merge-validation invariants, the shard-overlap LAZY restore byte
accounting, and the fd-leak / gc-truthfulness regressions.

Multi-process runs are simulated with ``simulate_processes`` — the seam
patches the process index/count and the device→process mapping that the
save/restore paths consult, so one controller can produce genuine
per-process artifacts and merge them (see the ``multihost-ckpt`` CI job).
"""
import json
import os

import numpy as np
import pytest

import repro.checkpoint as ck
import repro.checkpoint.checkpoint as ck_mod
from conftest import run_subprocess


def _tiny_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(4, 6)).astype(np.float32),
            "b": rng.normal(size=(6,)).astype(np.float32),
            "step": np.int64(3)}


# ---------------------------------------------------------------------------
# simulated 2-process save: layout, merge, restore equality
# ---------------------------------------------------------------------------

def test_simulated_two_process_save_merges_and_restores(tmp_path):
    """p1 stages its (empty-on-one-device) partition, p0 stages its own,
    merges at the barrier, and commits ONE directory holding both
    per-process manifests + shard files and the merged manifest; a plain
    restore reproduces every leaf exactly."""
    tree = _tiny_tree()
    d = str(tmp_path)
    # process 0 runs the commit, so the simulated p1 must save first
    with ck.simulate_processes(1, 2):
        ck.save_checkpoint(d, 3, tree, retry=None)
        assert ck.list_steps(d) == []          # nothing committed yet
    with ck.simulate_processes(0, 2):
        ck.save_checkpoint(d, 3, tree, retry=None)
    assert ck.list_steps(d) == [3]

    sd = os.path.join(d, "step_00000003")
    names = sorted(os.listdir(sd))
    assert names == ["manifest-p00.json", "manifest-p01.json",
                     "manifest.json", "shards-p00.npz", "shards-p01.npz"]
    assert not any(n.endswith(".tmp") or ".tmp-p" in n
                   for n in os.listdir(d))     # staging fully consumed

    man = json.load(open(os.path.join(sd, "manifest.json")))
    assert man["format"] == ck_mod.FORMAT
    assert man["processes"] == 2
    # host leaves are owned by process 0 ONLY — exactly one shard each
    for key in ("w", "b", "step"):
        entries = man["leaves"][key]["shards"]
        assert len(entries) == 1, (key, entries)
        assert entries[0]["process"] == 0

    ck.verify_checkpoint(d, 3)
    out = ck.restore_checkpoint(d, 3, tree)
    for key in tree:
        assert np.array_equal(np.asarray(out[key]), tree[key]), key

    rep = ck.checkpoint_size_report(d, 3)
    assert rep["saved_bytes"] == rep["logical_bytes"], rep
    assert set(rep["per_process_bytes"]) == {0}
    assert set(ck.per_process_restore_bytes(d, 3)) == {0, 1}


def test_snapshot_host_leaves_owned_by_process_zero_only():
    """The duplicate-host-shard fix: only process 0 claims host/scalar
    leaves, so a multi-process save cannot write them twice."""
    tree = _tiny_tree()
    with ck.simulate_processes(0, 2):
        snap0 = ck_mod._snapshot(tree)
    with ck.simulate_processes(1, 2):
        snap1 = ck_mod._snapshot(tree)
    assert snap0["process"] == 0 and snap1["process"] == 1
    for key in tree:
        assert len(snap0["leaves"][key]["shards"]) == 1
        assert snap1["leaves"][key]["shards"] == []
    # leaf METADATA still recorded by every process (merge alignment)
    assert set(snap1["leaves"]) == set(snap0["leaves"])


# ---------------------------------------------------------------------------
# merge_manifests validation invariants
# ---------------------------------------------------------------------------

def _manifest(process, processes, leaves):
    return {"format": ck_mod.FORMAT, "step": 5, "process": process,
            "processes": processes, "mesh": None, "leaves": leaves}


def _leaf(entries, shape=(4,)):
    return {"dtype": "float32", "shape": list(shape), "spec": None,
            "shards": entries}


def _entry(process, index):
    return {"file": f"shards-p{process:02d}.npz", "key": "a0",
            "shape": [b - a for a, b in index], "index": index,
            "device": 0, "process": process, "crc32": 0}


def test_merge_rejects_duplicate_host_leaf_ownership():
    """Over-coverage (the saved_bytes == logical_bytes invariant): a host
    leaf written by BOTH processes is caught at the barrier, not at some
    later restore."""
    m0 = _manifest(0, 2, {"s": _leaf([_entry(0, [[0, 4]])])})
    m1 = _manifest(1, 2, {"s": _leaf([_entry(1, [[0, 4]])])})
    with pytest.raises(ValueError, match="duplicate/overlapping"):
        ck.merge_manifests([m0, m1])


def test_merge_rejects_lost_shard_coverage():
    m0 = _manifest(0, 2, {"s": _leaf([_entry(0, [[0, 2]])])})
    m1 = _manifest(1, 2, {"s": _leaf([])})
    with pytest.raises(ValueError, match="incomplete"):
        ck.merge_manifests([m0, m1])


def test_merge_rejects_missing_process_and_key_mismatch():
    m0 = _manifest(0, 2, {"s": _leaf([_entry(0, [[0, 4]])])})
    with pytest.raises(ValueError, match="declared 2"):
        ck.merge_manifests([m0])
    m1 = _manifest(1, 2, {"t": _leaf([])})
    with pytest.raises(KeyError, match="leaf keys disagree"):
        ck.merge_manifests([m0, m1])


def test_merge_barrier_times_out_naming_stragglers(tmp_path, monkeypatch):
    """Process 0 alone at the barrier: the save fails with
    CheckpointBarrierTimeout (NOT an OSError — the IO retry must not
    re-run the wait) and nothing is committed."""
    monkeypatch.setattr(ck_mod, "MERGE_BARRIER_TIMEOUT", 0.2)
    d = str(tmp_path)
    with ck.simulate_processes(0, 2):
        with pytest.raises(ck.CheckpointBarrierTimeout, match=r"\[1\]"):
            ck.save_checkpoint(d, 1, _tiny_tree(), retry=None)
    assert ck.list_steps(d) == []
    assert not isinstance(ck.CheckpointBarrierTimeout("x"), OSError)


# ---------------------------------------------------------------------------
# regression: NpzFile handles are closed deterministically
# ---------------------------------------------------------------------------

def test_npz_handles_closed_after_fallback_scan(tmp_path, monkeypatch):
    """A restore_latest_valid fallback over several corrupt steps opens
    many npz files; every handle must be CLOSED afterwards (numpy marks a
    closed NpzFile by zip=None) — the fd-leak fix."""
    d = str(tmp_path)
    tree = _tiny_tree()
    for step in (1, 2, 3):
        ck.save_checkpoint(d, step, tree, retry=None)
    for step in (2, 3):                  # corrupt the two newest
        shard = os.path.join(d, f"step_{step:08d}", "shards-p00.npz")
        with open(shard, "r+b") as f:
            f.seek(os.path.getsize(shard) // 2)
            f.write(b"\xde\xad\xbe\xef" * 4)

    opened = []
    real_load = np.load

    def tracking_load(*a, **kw):
        f = real_load(*a, **kw)
        opened.append(f)
        return f

    monkeypatch.setattr(ck_mod.np, "load", tracking_load)
    out, step = ck.restore_latest_valid(d, tree)
    assert step == 1
    assert np.array_equal(np.asarray(out["w"]), tree["w"])
    with pytest.raises(ck.CheckpointCorruptError):
        ck.verify_checkpoint(d, 3)
    assert opened, "tracking hook never saw an np.load"
    still_open = [f for f in opened if f.zip is not None]
    assert not still_open, f"{len(still_open)} NpzFile(s) left open"


# ---------------------------------------------------------------------------
# regression: gc_checkpoints reports only deletions that actually happened
# ---------------------------------------------------------------------------

def test_gc_excludes_failed_deletions_and_warns(tmp_path, monkeypatch,
                                                capsys):
    d = str(tmp_path)
    tree = _tiny_tree()
    for step in (1, 2, 3, 4):
        ck.save_checkpoint(d, step, tree, retry=None)

    real_rmtree = ck_mod.shutil.rmtree

    def failing_rmtree(path, *a, **kw):
        if path.endswith("step_00000002"):
            raise OSError("device or resource busy")
        return real_rmtree(path, *a, **kw)

    monkeypatch.setattr(ck_mod.shutil, "rmtree", failing_rmtree)
    deleted = ck.gc_checkpoints(d, 1)
    assert deleted == [1, 3]             # 2 failed, truthfully excluded
    assert ck.list_steps(d) == [2, 4]    # the failed step is still there
    warn = capsys.readouterr().out
    assert "failed to delete step 2" in warn


# ---------------------------------------------------------------------------
# full engine round trip: simulated 2-process save -> merge -> elastic
# restore at a different layout, plus the lazy read-bytes contract
# ---------------------------------------------------------------------------

_MH = r"""
import json, os, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config, EngineConfig
from repro.core.engine import DistributedEngine
from repro.launch.mesh import make_local_mesh
import repro.checkpoint as ck
from repro.checkpoint.checkpoint import _flatten
from repro.launch.specs import concrete_batch

CFG = get_smoke_config("vit-b16").replace(dtype="float32")

def make_engine(zero=0, pipe=1):
    mesh = make_local_mesh(pipe=pipe, devices=jax.devices()[:8])
    ecfg = EngineConfig(train_batch_size=16, gradient_accumulation_steps=2,
                        zero_stage=zero, lr=1e-3, total_steps=10,
                        warmup_steps=1, pipeline_stages=pipe)
    return DistributedEngine(CFG, ecfg, mesh)

def run(eng, state, lo, hi):
    step = eng.jit_train_step(donate=False)
    losses = []
    with eng.mesh:
        for i in range(lo, hi):
            state, m = step(state, concrete_batch(CFG, 16, 16, seed=i))
            losses.append(float(m["loss"]))
    return state, losses

def assert_bitwise(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    for (pa, xa), (_, xb) in zip(fa, fb):
        assert np.array_equal(np.asarray(jax.device_get(xa)),
                              np.asarray(jax.device_get(xb))), pa
"""


def test_two_process_save_cross_layout_restore_and_lazy_reads():
    """ZeRO-3 dp=8 state saved as a SIMULATED 2-process run (4 devices per
    process): the commit holds two distinct shard files + per-process
    manifests + the merged manifest; restore into dp4 x pp2 is bitwise on
    params/opt and the resumed trajectory matches the uninterrupted one
    to 1e-5; and the per-process lazy restore reads strictly fewer shard
    entries/bytes than the logical whole — the O(local partition)
    contract, counter-asserted."""
    out = run_subprocess(_MH + r"""
src = make_engine(zero=3)
s2, _ = run(src, src.init_state(seed=0), 0, 2)
d = tempfile.mkdtemp()
# process 0 commits at the merge barrier, so the simulated p1 saves first
with ck.simulate_processes(1, 2):
    ck.save_checkpoint(d, 2, s2)
    assert ck.list_steps(d) == []
with ck.simulate_processes(0, 2):
    ck.save_checkpoint(d, 2, s2)
assert ck.list_steps(d) == [2]

sd = os.path.join(d, "step_00000002")
names = sorted(os.listdir(sd))
assert names == ["manifest-p00.json", "manifest-p01.json",
                 "manifest.json", "shards-p00.npz", "shards-p01.npz"], names
# both processes contributed real shard bytes (zero3 partitions over dp=8)
assert os.path.getsize(os.path.join(sd, "shards-p00.npz")) > 10000
assert os.path.getsize(os.path.join(sd, "shards-p01.npz")) > 10000
man = json.load(open(os.path.join(sd, "manifest.json")))
assert man["format"] == "repro-elastic-ckpt/v2" and man["processes"] == 2
files = {e["file"] for m in man["leaves"].values() for e in m["shards"]}
assert files == {"shards-p00.npz", "shards-p01.npz"}, files

rep = ck.checkpoint_size_report(d, 2)
assert rep["saved_bytes"] == rep["logical_bytes"], rep
assert set(rep["per_process_bytes"]) == {0, 1}, rep["per_process_bytes"]

_, ref = run(src, s2, 2, 5)                 # uninterrupted continuation

eng2 = make_engine(pipe=2)                  # different layout: dp4 x pp2
s2b = eng2.restore_state(d)
assert int(s2b.step) == 2
assert_bitwise(s2.params, s2b.params)
assert_bitwise(s2.opt_state, s2b.opt_state)
_, res = run(eng2, s2b, 2, 5)
for a, b in zip(ref, res):
    assert abs(a - b) < 1e-5, (ref, res)

# lazy-restore contract: per process, only intersecting shards are read
like = src.abstract_state()
shardings = src.state_shardings()
full = ck.restore_checkpoint(d, 2, like, shardings=None)
full_stats = ck.last_restore_stats()
assert full_stats.entries_read == full_stats.entries_total
full_items = dict(_flatten(full))
for p in (0, 1):
    with ck.simulate_processes(p, 2):
        plan, stats = ck.restore_local_shards(d, 2, like, shardings)
    assert stats.entries_read < stats.entries_total, stats
    assert stats.read_bytes < 0.8 * stats.logical_bytes, stats
    assert stats.partition_bytes < 0.8 * stats.logical_bytes, stats
    n_blocks = 0
    for key, items in plan.items():
        for dev_id, rkey, block in items:
            sl = tuple(slice(a, b) for a, b in rkey)
            want = np.asarray(full_items[key])[sl]
            assert np.array_equal(block, want), (key, dev_id, rkey)
            n_blocks += 1
    assert n_blocks > 0
print("OK", ref)
""", devices=8, timeout=900)
    assert "OK" in out
