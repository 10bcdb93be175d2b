"""scopes.py: the program's names read from hand-made HLO and intervals,
and from small traces recorded on a four-chip v5e (``record_trace.py``),
one of a program that predates the names and one of a program with them."""
import gzip
from pathlib import Path

import pytest

import scopes as S
import trace as T

DATA = Path(__file__).resolve().parent / "data"

HLO = """HloModule m, is_scheduled=true

FileNames
1 "/x/src/repro/models/attention.py"

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_computation.1 (param_0: f32[]) -> f32[] {
  %param_0 = f32[] parameter(0)
  ROOT %mul.1 = f32[] multiply(%param_0, %param_0), metadata={op_name="jit(_train_step)/transpose(jvp(forward))/mul" stack_frame_id=1}
}

%fused_computation.2 (param_0: f32[]) -> f32[] {
  %param_0.1 = f32[] parameter(0)
  ROOT %fusion.9 = f32[] fusion(%param_0.1), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  %fusion.1 = f32[] fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_train_step)/jvp(forward)/while/body/closed_call/attn_core/dot_general" stack_frame_id=1}
  %fusion.2 = f32[] fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.3 = f32[] fusion(%p), kind=kLoop, calls=%fused_computation.2
  %dot.4 = f32[] dot(%p, %p), metadata={op_name="jit(_train_step)/transpose(jvp(forward))/while/body/closed_call/attn_core/dot_general"}
  %fusion.5 = f32[] fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_train_step)/optimizer/jit(_where)/select_n"}
  %copy-start.6 = (f32[], f32[], u32[]) copy-start(%p)
  %all-reduce.7 = f32[] all-reduce(%p), metadata={op_name="jit(_train_step)/transpose(jvp(forward))/reduce_sum"}
  ROOT %fusion.8 = f32[] fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_train_step)/forward/vmap()/add"}
}
"""


def op(s, e, name, kind="fusion"):
    return T.Op(s, e, name, f"%{name} = f32[] {kind}(%x)")


def handmade():
    # one chip, two steps in [0, 200), self times: forward 20 + 10,
    # backward 20 + 10 + 10 (the dot nests in fusion.2; fusion.3 has no
    # metadata), optimizer 10, an unnamed copy 5 and an all-reduce 10
    d0 = [op(10, 30, "fusion.1"), op(30, 60, "fusion.2"),
          op(40, 50, "dot.4", "dot"), op(60, 70, "fusion.5"),
          op(70, 75, "copy-start.6", "copy-start"),
          op(75, 85, "all-reduce.7", "all-reduce"),
          op(110, 120, "fusion.8"), op(120, 130, "fusion.3")]
    d0.sort(key=lambda o: (o.start, -o.end))
    spans = [(0, 200, "traced_window"),
             (2, 8, "dispatch"), (102, 108, "dispatch"),
             (0, 6, "data/synth"), (5, 9, "data/transfer"),
             (150, 170, "data/synth"), (300, 310, "data/synth"),
             (180, 190, "data/wait"), (185, 195, "guard_read")]
    modules = {0: [(10, 90, "jit__train_step(1)"),
                   (109, 131, "jit__train_step(1)"),
                   (140, 150, "jit_roll(2)")]}
    return T.Trace({0: d0}, modules, spans)


def test_op_names_from_metadata_and_fusion_roots():
    names = S.op_names(HLO)
    assert names["fusion.1"].endswith("/attn_core/dot_general")
    # a fusion without metadata takes its called computation's root's,
    # through a nested fusion too
    assert names["fusion.2"] == "jit(_train_step)/transpose(jvp(forward))/mul"
    assert names["fusion.3"] == names["fusion.2"]
    assert names["dot.4"].startswith("jit(_train_step)/transpose(")
    assert "copy-start.6" not in names and "p" not in names


@pytest.mark.parametrize("name,want", [
    ("jit(_train_step)/jvp(forward)/while/body/dot_general", "forward"),
    ("jit(_train_step)/while/body/closed_call/jvp(forward)/add", "forward"),
    ("jit(_train_step)/forward/vmap()/add", "forward"),
    ("jit(_train_step)/transpose(jvp(forward))/while/body/checkpoint/"
     "rematted_computation/dot_general", "backward"),
    ("jit(_train_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(_train_step)/jvp()/dot_general", None),
    ("jit(_train_step)/reduce_sum", None),
    ("", None),
])
def test_phase(name, want):
    assert S.phase(name) == want


def test_handmade_readings():
    trace = handmade()
    names_s = S.op_name_seconds(trace, "traced_window", HLO)
    fwd, bwd = "jit(_train_step)/jvp(forward)", \
        "jit(_train_step)/transpose(jvp(forward))"
    assert names_s[fwd + "/while/body/closed_call/attn_core/dot_general"] \
        == pytest.approx(20e-9)
    # fusion.2 [30, 60) less the dot [40, 50), and fusion.3 via its root
    assert names_s[bwd + "/mul"] == pytest.approx(30e-9)
    assert names_s[S.COLLECTIVE] == pytest.approx(10e-9)
    assert names_s[""] == pytest.approx(5e-9)
    assert sum(names_s.values()) == pytest.approx(95e-9)

    host_s = S.host_seconds(trace, "traced_window")
    assert host_s["data/synth"] == pytest.approx(26e-9)   # clipped
    assert host_s["data/transfer"] == pytest.approx(4e-9)

    red = T.reduce(trace, "traced_window", HLO)
    assert red.steps == 2 and red.busy_s == pytest.approx(95e-9)
    got = S.phase_ms(names_s, host_s, red.busy_s, red.steps)
    # covered: 30 forward + 40 backward + 10 optimizer + 10 collective
    assert got["covered_share"] == pytest.approx(90 / 95)
    assert got["fwd_ms"] == pytest.approx(1e3 * 15e-9)
    assert got["bwd_ms"] == pytest.approx(1e3 * 20e-9)
    assert got["optimizer_ms"] == pytest.approx(1e3 * 5e-9)
    assert got["attn_core_ms"] == pytest.approx(1e3 * 15e-9)
    assert got["input_produce_ms"] == pytest.approx(1e3 * 15e-9)

    r = S.readings(trace, "traced_window", HLO)
    assert r["dispatch_lag_ms"]["n"] == 2
    assert r["dispatch_lag_ms"]["min"] == pytest.approx(1e3 * 7e-9)
    assert r["dispatch_lag_ms"]["median"] == pytest.approx(1e3 * 7.5e-9)
    assert r["host_ms"] == pytest.approx(
        {"data/synth": 1e3 * 13e-9, "data/transfer": 1e3 * 2e-9,
         "data/wait": 1e3 * 5e-9, "dispatch": 1e3 * 6e-9,
         "guard_read": 1e3 * 5e-9})
    assert r["data_overlap_ms"] == pytest.approx(
        {"dispatch": 1e3 * 6e-9 / 2, "guard_read": 0.0})


def test_missing_names_read_as_nothing():
    # under 80% of busy time in named phases: no phase is read
    low = S.phase_ms({"jit(_train_step)/jvp(forward)/add": 7.0,
                      "jit(_train_step)/reduce_sum": 3.0}, {}, 10.0, 2)
    assert low["covered_share"] == pytest.approx(0.7)
    assert low["fwd_ms"] is low["bwd_ms"] is low["optimizer_ms"] is None
    assert low["input_produce_ms"] is None
    # covered, but no op carries attn_core: None, never 0
    full = S.phase_ms({"jit(_train_step)/jvp(forward)/add": 9.0,
                       "jit(_train_step)/optimizer/add": 1.0}, {}, 10.0, 2)
    assert full["fwd_ms"] == pytest.approx(4500.0)
    assert full["bwd_ms"] == 0.0
    assert full["attn_core_ms"] is None


def test_negative_dispatch_lag():
    trace = T.Trace({0: []}, {0: [(95, 120, "jit__train_step(3)")]},
                    [(0, 200, "traced_window"), (100, 101, "dispatch")])
    assert S.dispatch_lags(trace, "traced_window") == \
        pytest.approx([-5e-9])


def test_strip_metadata():
    text = S.strip_metadata(HLO)
    assert "metadata" not in text and "StackFrames" not in text
    assert "/x/src" not in text and "1 {file_location_id" not in text
    assert "%dot.4 = f32[] dot(%p, %p)\n" in text
    assert "%fusion.5 = f32[] fusion(%p), kind=kLoop, " \
        "calls=%fused_computation.1\n" in text
    assert S.strip_metadata(text) == text


@pytest.mark.parametrize("fixture", ["small_dp4", "small_dp4_named"])
def test_recorded_dp4_trace(fixture):
    """Two fixtures of the small dp4 cell: ``small_dp4``'s program predates
    the names, so every phase reads None; ``small_dp4_named`` was recorded
    with them. Both read the dispatch lag from the harness's own span."""
    trace = T.load(str(DATA / f"{fixture}.xplane.pb.gz"))
    with gzip.open(DATA / f"{fixture}.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    named = fixture.endswith("_named")
    names = S.op_names(hlo)
    assert names and any("forward" in v for v in names.values()) == named
    r = S.readings(trace, "traced_window", hlo)
    assert r["steps"] == 2
    assert (r["covered_share"] >= S.MIN_COVERED) == named
    for k in ("fwd_ms", "bwd_ms", "optimizer_ms", "attn_core_ms",
              "input_produce_ms"):
        assert (r[k] is None) != named, k
        assert not named or r[k] > 0, k
    assert ("data/synth" in r["host_ms"]) == named
    lag = r["dispatch_lag_ms"]
    assert lag["n"] == 2 and 0 < lag["min"] <= lag["median"]
    red = T.reduce(trace, "traced_window", hlo)
    names_s = S.op_name_seconds(trace, "traced_window", hlo)
    assert sum(names_s.values()) == pytest.approx(red.busy_s, rel=1e-6)
    assert names_s[S.COLLECTIVE] >= red.collective_s
