"""trace.py: the reduction on hand-made intervals, and on a small trace
recorded on a four-chip v5e host (``record_trace.py``; two steps of the
small cell as dp4 ZeRO-0)."""
import gzip
from pathlib import Path

import pytest

import trace as T

DATA = Path(__file__).resolve().parent / "data"


def op(s, e, name, kind="fusion"):
    return T.Op(s, e, name, f"%{name} = f32[] {kind}(%x)")


def handmade():
    # device 0: a while [10, 60) enclosing two fusions, then an all-reduce
    # while nothing else runs; device 1 idle but for one op
    d0 = [op(10, 60, "while.1", "while"), op(12, 30, "fusion.1"),
          op(30, 55, "fusion.2"), op(70, 90, "all-reduce.1", "all-reduce")]
    d1 = [op(20, 40, "fusion.1")]
    d0.sort(key=lambda o: (o.start, -o.end))
    spans = [(0, 100, "traced_window"), (60, 70, "guard_read"),
             (90, 100, "dispatch"), (0, 10, "input_wait")]
    modules = {0: [(10, 90, "jit__train_step(1)")], 1: []}
    return T.Trace({0: d0, 1: d1}, modules, spans)


HLO = """HloModule m

FileNames
1 "/x/src/repro/models/attention.py"
2 "/x/src/repro/models/mlp.py"

FileLocations
1 {file_name_id=1 function_name_id=1 line=3}
2 {file_name_id=2 function_name_id=1 line=4}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}

ENTRY %main {
  %fusion.1 = f32[] fusion(%p), metadata={op_name="a" stack_frame_id=1}
  %fusion.2 = f32[] fusion(%p), metadata={op_name="b" stack_frame_id=2}
}
"""


def test_handmade_reduction():
    r = T.reduce(handmade(), "traced_window", HLO)
    assert r.window_s == pytest.approx(100e-9)
    # busy: d0 [10,60) + [70,90) = 70, d1 20; mean 45
    assert r.busy_s == pytest.approx(45e-9)
    assert r.idle_share == pytest.approx({0: 0.3, 1: 0.8})
    assert r.steps == 1
    # self times: while 50 - 18 - 25 = 7; fusion.1 18 + 20 (d1)
    assert r.op_s["while.1 [?]"] == pytest.approx(3.5e-9)
    assert r.op_s["fusion.1 [models/attention.py]"] == pytest.approx(19e-9)
    assert r.source_s["models/mlp.py"] == pytest.approx(12.5e-9)
    # the all-reduce [70,90) on device 0, uncovered: a mean of 10
    assert r.collective_s == pytest.approx(10e-9)
    assert r.exposed_collective_s == pytest.approx(10e-9)
    # d0 gaps [0,10) input_wait, [60,70) guard_read, [90,100) dispatch
    labels = {(g, round(s * 1e9)) for g, s in r.gaps}
    assert ("input_wait", 10) in labels and ("guard_read", 10) in labels \
        and ("dispatch", 10) in labels
    assert sum(s for _, s in r.gaps) == pytest.approx((30 + 80) * 1e-9)
    assert len(r.breakdown()["device_ops"]) <= 10


def test_interval_helpers():
    assert T.union([(5, 9), (0, 3), (2, 4)]) == [[0, 4], [5, 9]]
    assert T.union([(0, 10)], 2, 8) == [[2, 8]]
    assert T.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert T.label(0, 10, [(5, 20, "dispatch"), (0, 4, "guard_read")]) \
        == "dispatch"
    assert T.label(0, 10, []) == "other"


def test_recorded_dp4_trace():
    trace = T.load(str(DATA / "small_dp4.xplane.pb.gz"))
    assert sorted(trace.devices) == [0, 1, 2, 3]
    with gzip.open(DATA / "small_dp4.hlo.txt.gz", "rt") as f:
        r = T.reduce(trace, "traced_window", f.read())
    assert 0 < r.busy_s < r.window_s
    assert all(0 < v < 1 for v in r.idle_share.values())
    assert r.steps == 2
    assert r.source_s["models/attention.py"] > 0
    assert sum(r.op_s.values()) == pytest.approx(r.busy_s, rel=1e-6)
    assert 0 < r.exposed_collective_s <= r.collective_s
    assert {g for g, _ in r.gaps} <= {"input_wait", "dispatch",
                                      "guard_read", "other"}
    idle = sum(s for _, s in r.gaps) / len(trace.devices)
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
