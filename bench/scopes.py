"""The program's own names in a profiler trace (``src/repro/obs.py``).

The compiled train step carries named scopes in each HLO instruction's
``op_name``: ``jvp(forward)`` for the loss's forward,
``transpose(jvp(forward))`` for its backward (a remat's recompute runs
there too), ``optimizer`` for the work after the gradients, and
``attn_core`` inside either direction for the attention core. The input
pipeline's threads write the host spans ``data/synth``, ``data/transfer``
and ``data/wait``. This module reads them from what ``trace.load`` returns:

- ``op_names``: each instruction's ``op_name`` in the compiled HLO text;
- ``op_name_seconds``: device self seconds per ``op_name`` in the window,
  the mean over the chips, over every op (collectives under
  ``COLLECTIVE``, ops without an ``op_name`` under ``""``);
- ``host_seconds``: host seconds per span name in the window, summed over
  threads;
- ``phase_ms``: forward, backward, optimizer and attention-core device ms
  per step, and the input threads' host ms per step;
- ``dispatch_lags``: for each step, the host ``dispatch`` span's start to
  the start of its train-step program on the first chip.

    python bench/scopes.py --workload vit-b16.dp1 --seed 7 [--hlo OUT]

runs the cell's set-up as ``run.py`` does and ``WARM_STEPS`` more, then
``trace_steps`` steps of the cell under the profiler, and prints these
readings as the last line of stdout (one JSON object). ``--hlo`` writes
the compiled step's HLO text with its metadata stripped, for comparing
two commits' programs. Give each commit its own
``JAX_COMPILATION_CACHE_DIR``: the cache's key leaves metadata out.
"""
from __future__ import annotations

import re
import statistics
from collections import Counter

import trace as T

# the names as src/repro/obs.py gives them, copied: a program that lacks
# or renames them must read as nothing here, not fail to import
FORWARD, BACKWARD, OPTIMIZER, ATTN_CORE = (
    "jvp(forward)", "transpose(jvp(forward))", "optimizer", "attn_core")
DATA_SPANS = ("data/synth", "data/transfer")
COLLECTIVE = "<collective>"
# the phases must cover this share of busy time before they are read
MIN_COVERED = 0.8
# steps after set-up and before the trace, as in run.py's window
WARM_STEPS = 10

_DEF = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_METADATA = re.compile(r',?\s*metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def op_names(hlo_text: str) -> dict:
    """{instruction name: its ``op_name``}. A fusion without metadata
    takes the ``op_name`` of its called computation's root."""
    names, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        d = _DEF.match(line)
        if not d:
            continue
        name = d.group(2)
        if d.group(1) and comp:
            roots[comp] = name
        m = _OP_NAME.search(line)
        if m:
            names[name] = m.group(1)
            continue
        called = _CALLS.search(line)
        if called and " fusion(" in line:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        seen = set()
        while name not in names and comp in roots and comp not in seen:
            seen.add(comp)
            root = roots[comp]
            if root in names:
                names[name] = names[root]
            comp = calls.get(root)
    return names


def strip_metadata(hlo_text: str) -> str:
    """The HLO text without instruction metadata and the stack-frame
    tables it points into: what a named scope may change."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in _TABLES:
            skip = True
            continue
        if skip:
            if re.match(r"^\d+ ", line):
                continue
            skip = False
        out.append(_METADATA.sub("", line))
    return "\n".join(out)


def _window(trace: T.Trace, window: str) -> tuple:
    marks = [(s, e) for s, e, name in trace.spans if name == window]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} host spans named {window!r}")
    return marks[0]


def op_name_seconds(trace: T.Trace, window: str, hlo_text: str) -> Counter:
    """Device self seconds per ``op_name`` inside the window, the mean over
    the chips: every op, collectives under ``COLLECTIVE``, ops without an
    ``op_name`` under ``""``."""
    lo, hi = _window(trace, window)
    names = op_names(hlo_text)
    ns = Counter()
    for ops in trace.devices.values():
        ops = [o for o in ops if o.end > lo and o.start < hi]
        selft, _ = T.self_times(ops)
        for o, s in zip(ops, selft):
            key = COLLECTIVE if o.collective else names.get(o.name, "")
            ns[key] += s
    scale = 1e-9 / len(trace.devices)
    return Counter({k: v * scale for k, v in ns.items()})


def host_seconds(trace: T.Trace, window: str) -> Counter:
    """Host seconds per span name inside the window, summed over threads
    (the window's own span included)."""
    lo, hi = _window(trace, window)
    out = Counter()
    for s, e, name in trace.spans:
        if e > lo and s < hi:
            out[name] += (min(e, hi) - max(s, lo)) * 1e-9
    return out


def phase(op_name: str):
    """``forward``, ``backward``, ``optimizer`` or None for an op_name."""
    path = f"/{op_name}/"
    if BACKWARD in op_name:
        return "backward"
    if FORWARD in op_name or "/forward/" in path:
        return "forward"
    if f"/{OPTIMIZER}/" in path:
        return "optimizer"
    return None


def phase_ms(op_name_s: Counter, host_s: Counter, busy_s: float,
             steps: int) -> dict:
    """Per-step ms of each phase (None where forward, backward, optimizer
    and collectives cover under ``MIN_COVERED`` of busy time), of the
    attention core (None where no op carries it) and of the input
    threads' ``data/synth`` + ``data/transfer`` (None without them)."""
    sums = Counter()
    for k, v in op_name_s.items():
        sums[phase(k)] += v
        if f"/{ATTN_CORE}/" in f"/{k}/":
            sums[ATTN_CORE] += v
    covered = sum(sums[p] for p in ("forward", "backward", "optimizer")) \
        + op_name_s.get(COLLECTIVE, 0.0)
    share = covered / busy_s if busy_s else 0.0
    ok = steps > 0 and share >= MIN_COVERED

    def ms(v, present=True):
        return 1e3 * v / steps if ok and present else None
    produce = [host_s[k] for k in DATA_SPANS if k in host_s]
    return {
        "covered_share": share,
        "fwd_ms": ms(sums["forward"]),
        "bwd_ms": ms(sums["backward"]),
        "optimizer_ms": ms(sums["optimizer"]),
        "attn_core_ms": ms(sums[ATTN_CORE], ATTN_CORE in sums),
        "input_produce_ms": 1e3 * sum(produce) / steps
        if produce and steps else None,
    }


def dispatch_lags(trace: T.Trace, window: str, span: str = "dispatch"
                  ) -> list:
    """Seconds from each host ``span`` in the window to the start of the
    train-step program it launched on the first chip, paired in order. A
    negative lag bounds how far the host and device clocks disagree."""
    lo, hi = _window(trace, window)
    starts = sorted(s for s, e, n in trace.spans
                    if n == span and lo <= s < hi)
    mods = sorted(s for s, e, n in trace.modules.get(min(trace.devices), [])
                  if n.startswith(T.STEP_MODULE) and e > lo and s < hi)
    return [(m - h) * 1e-9 for h, m in zip(starts, mods)]


def overlap_s(trace: T.Trace, window: str, a, b) -> float:
    """Seconds in the window in which a host span named in ``a`` and one
    named in ``b`` both run (each side's spans merged first)."""
    lo, hi = _window(trace, window)

    def merged(names):
        return T.union(((s, e) for s, e, n in trace.spans if n in names),
                       lo, hi)
    sa = merged(a)
    return T.measure(T.subtract(sa, T.subtract(sa, merged(b)))) * 1e-9


def readings(trace: T.Trace, window: str, hlo_text: str) -> dict:
    """Everything this module reads from one traced window."""
    red = T.reduce(trace, window, hlo_text)
    names_s = op_name_seconds(trace, window, hlo_text)
    host_s = host_seconds(trace, window)
    out = phase_ms(names_s, host_s, red.busy_s, red.steps)
    lags = dispatch_lags(trace, window)
    steps = red.steps or 1
    out.update({
        "steps": red.steps,
        "busy_ms_per_step": 1e3 * red.busy_s / steps,
        "host_ms": {k: 1e3 * host_s[k] / steps for k in DATA_SPANS + (
            "data/wait", "dispatch", "guard_read") if k in host_s},
        "dispatch_lag_ms": {
            "n": len(lags), "min": 1e3 * min(lags) if lags else None,
            "median": 1e3 * statistics.median(lags) if lags else None},
        "data_overlap_ms": {
            k: 1e3 * overlap_s(trace, window, (k,), DATA_SPANS) / steps
            for k in ("dispatch", "guard_read")},
        "op_names": [[k, 1e3 * v / steps]
                     for k, v in names_s.most_common(12)],
    })
    return out


def main(argv=None):
    import argparse
    import json
    import sys
    import tempfile
    import time

    import jax

    import cells
    import run
    import system

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--hlo", help="write the step's stripped HLO here")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    table = run.peaks()
    run.use_compile_cache()
    devices = run.chip_devices(cell.chips, table)
    tr = cell.traffic
    t = system.build(cell, args.seed, devices)
    run.check_steps(t, tr)
    for _ in range(WARM_STEPS):
        system.train_step(t)
    n = tr["trace_steps"]
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t.spans = {k: 0.0 for k in t.spans}
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(run.TRACED):
            for _ in range(n):
                system.train_step(t)
        elapsed = time.perf_counter() - start
        jax.profiler.stop_trace()
        host = run.host_ms(t.spans, n, elapsed)
        batch = next(t.prefetcher)[1]
        hlo = t.step_fn.lower(t.state, batch).compile().as_text()
        out = readings(T.load(d), run.TRACED, hlo)
    t.close()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(strip_metadata(hlo))
    d0 = devices[0]
    out.update({"workload": args.workload, "seed": args.seed,
                "traced_host_ms_per_step": host,
                "device": {"platform": d0.platform, "kind": d0.device_kind,
                           "count": len(devices)}})
    print(f"[scopes] {n} traced steps in {elapsed:.3f} s, host ms per step "
          f"{host}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
