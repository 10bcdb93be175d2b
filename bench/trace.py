"""Reduction of a profiler trace to the benchmark's per-layer numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes. On a TPU each chip
is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO instruction, named by its HLO text (``%fusion.12 = ...``),
with ``while`` events enclosing the ops of their body. The harness's host
spans (``jax.profiler.TraceAnnotation``) are events on the host plane, on
the same clock to within about a millisecond.

Inside the host span that marks the traced window:

- busy time of a chip: the union of its op intervals; idle is the rest;
- self time of an op: its duration less that of the ops it encloses,
  attributed to the source file of its innermost stack frame, which the
  compiled step's HLO text gives (``FileNames``, ``FileLocations``,
  ``StackFrames`` and each instruction's ``stack_frame_id``);
- exposed collective time: the part of the innermost collective ops
  (all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all)
  that no other innermost op covers;
- idle gaps, each labelled with the host span that overlaps it most, or
  ``other``.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_MODULE = "jit__train_step"
_INSTR = re.compile(r"^%?([\w.\-]+)")
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")


@dataclass
class Op:
    start: int          # ns
    end: int
    name: str           # HLO instruction name
    text: str           # the event's whole name (HLO text)

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.search(self.text)) or any(
            k in self.name for k in ("all-reduce", "all-gather",
                                     "reduce-scatter", "collective-permute",
                                     "all-to-all"))


@dataclass
class Trace:
    devices: dict       # device id -> [Op] sorted by start
    modules: dict       # device id -> [(start, end, name)]
    spans: list         # [(start, end, name)] host spans


def load(path: str) -> Trace:
    """A trace directory, or one ``.xplane.pb`` file (gzipped or not)."""
    import jax
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} xplane files under {path}")
        path = found[0]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    devices, modules, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = []
                for e in line.events:
                    start = int(e.start_ns)
                    ops.append(Op(start, start + int(e.duration_ns),
                                  _INSTR.match(e.name).group(1), e.name))
                ops.sort(key=lambda o: (o.start, -o.end))
                devices[int(m.group(1))] = ops
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                spans.extend((int(e.start_ns),
                              int(e.start_ns + e.duration_ns), e.name)
                             for e in line.events)
    return Trace(devices, modules, spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals, lo=None, hi=None) -> list:
    """Sorted disjoint [start, end) covering ``intervals``, clipped."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Disjoint sorted ``a`` less disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(ops) -> tuple:
    """(self ns per op index, indices of innermost ops) for ops sorted by
    (start, -end), where an op encloses those that start within it."""
    selft = [o.end - o.start for o in ops]
    leaf = [True] * len(ops)
    stack = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            p = stack[-1]
            selft[p] -= min(o.end, ops[p].end) - o.start
            leaf[p] = False
        stack.append(i)
    return selft, [i for i, x in enumerate(leaf) if x]


# ---------------------------------------------------------------------------
# source files from the compiled HLO text
# ---------------------------------------------------------------------------

_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")


def source_files(hlo_text: str) -> dict:
    """{instruction name: source file of its innermost stack frame}."""
    tables = defaultdict(dict)
    section = None
    names = {}
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = line
            continue
        row = _TABLE_ROW.match(line) if section else None
        if row:
            tables[section][int(row.group(1))] = row.group(2)
            continue
        section = None
        d = _DEF.match(line)
        f = _FRAME_ID.search(line) if d else None
        if f:
            names[d.group(1)] = int(f.group(1))
    files = {k: v.strip('"') for k, v in tables["FileNames"].items()}
    loc_file = {k: int(re.search(r"file_name_id=(\d+)", v).group(1))
                for k, v in tables["FileLocations"].items()}
    frame_loc = {k: int(re.search(r"file_location_id=(\d+)", v).group(1))
                 for k, v in tables["StackFrames"].items()}
    out = {}
    for name, frame in names.items():
        loc = frame_loc.get(frame)
        if loc in loc_file:
            out[name] = files.get(loc_file[loc], "")
    return out


def short_source(path: str) -> str:
    """``models/attention.py`` for ``.../src/repro/models/attention.py``."""
    parts = path.replace("\\", "/").split("/")
    return "/".join(parts[-2:]) if parts else path


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    idle_share: dict                    # device -> share of the window
    steps: int                          # train steps begun in the window
    op_s: Counter                       # "name [source]" -> s, mean/device
    source_s: Counter                   # source file -> self s, mean/device
    collective_s: float                 # mean over devices
    exposed_collective_s: float         # mean over devices
    gaps: list = field(default_factory=list)    # [(label, s)] longest first

    def breakdown(self, n: int = 10) -> dict:
        return {"device_ops": [[k, v] for k, v in self.op_s.most_common(n)],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def reduce(trace: Trace, window: str, hlo_text: str = "",
           span_names=("input_wait", "dispatch", "guard_read")) -> Reduction:
    marks = [(s, e) for s, e, name in trace.spans if name == window]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} host spans named {window!r}")
    lo, hi = marks[0]
    sources = source_files(hlo_text) if hlo_text else {}
    host = [(s, e, n) for s, e, n in trace.spans
            if n in span_names and e > lo and s < hi]
    ndev = len(trace.devices)
    if not ndev:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns, idle, coll_ns, exposed_ns = 0, {}, 0, 0
    op_ns, source_ns = Counter(), Counter()
    gaps = []
    for dev, ops in trace.devices.items():
        ops = [o for o in ops if o.end > lo and o.start < hi]
        busy = union(((o.start, o.end) for o in ops), lo, hi)
        busy_ns += measure(busy)
        idle[dev] = 1.0 - measure(busy) / (hi - lo)
        selft, leaves = self_times(ops)
        for i, o in enumerate(ops):
            src = short_source(sources.get(o.name, ""))
            op_ns[f"{o.name} [{src or '?'}]"] += selft[i]
            source_ns[src] += selft[i]
        coll = union((ops[i].start, ops[i].end) for i in leaves
                     if ops[i].collective)
        other = union((ops[i].start, ops[i].end) for i in leaves
                      if not ops[i].collective)
        coll_ns += measure(coll)
        exposed_ns += measure(subtract(coll, other))
        for s, e in subtract([[lo, hi]], busy):
            gaps.append((label(s, e, host), (e - s) / 1e9))
    steps = sum(1 for s, e, name in trace.modules.get(min(trace.devices), [])
                if name.startswith(STEP_MODULE) and e > lo and s < hi)
    gaps.sort(key=lambda g: -g[1])
    scale = 1e-9 / ndev
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns * scale, idle_share=idle,
        steps=steps,
        op_s=Counter({k: v * scale for k, v in op_ns.items()}),
        source_s=Counter({k: v * scale for k, v in source_ns.items()}),
        collective_s=coll_ns * scale, exposed_collective_s=exposed_ns * scale,
        gaps=gaps)


def label(s: int, e: int, host) -> str:
    """The host span that overlaps [s, e) most, or ``other``."""
    best, name = 0, "other"
    for hs, he, n in host:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name
