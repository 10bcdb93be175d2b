"""Benchmark of the trainer on TPU: one cell, one run.

    python bench/run.py --workload vit-b16.dp1 --seed 7 --seconds 25 --trace 0

One process holds the cell's chips and starts no child. It builds the
trainer as ``launch/train.py`` does (``system.py``), drives its first
``check_steps`` steps from the seed through the loop's own calls (set-up:
they compile and warm up the one step shape), then runs the same loop for
``--seconds`` and reports the end-to-end metrics. ``--trace 1`` runs a
few more steps under the profiler after the window and reports the
per-layer metrics instead (``metrics/<name>.py`` read the run and the
trace reduction of ``trace.py``). Last, with the program's state freed,
the float32 reference (``reference.py``) trains the same steps and
``check.py`` compares; each number compared is printed beside its limit,
on stderr and as the last key of the result line, the last line of
stdout.

It refuses, with a non-zero exit and no result line, a platform other
than TPU, a device kind missing from ``peaks.json``, and fewer chips than
the cell asks for. The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import cells  # noqa: E402
import check  # noqa: E402

TRACED = "traced_window"
# a host-clock time spans this or more; shorter ones are mostly clock error
TAIL_SPAN_S = 0.25


class Refused(SystemExit):
    """The machine cannot run this cell: exit non-zero, print no result."""


def peaks(root: Path = ROOT) -> dict:
    return cells.load_json(root / "bench" / "peaks.json")


def chip_devices(chips: int, table: dict):
    """The first ``chips`` TPU devices, or Refused."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise Refused(f"bench/run.py measures a TPU; JAX found "
                      f"{devs[0].platform} ({kind})")
    if kind not in table["devices"]:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json "
                      f"({sorted(table['devices'])})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts JAX trace and compile events and persistent-cache hits and
    misses (``jax.monitoring``)."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, name, _secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.n += 1

    def _on_event(self, name, **_):
        key = name.rsplit("/", 1)[-1]
        if key in self.cache:
            self.cache[key] += 1


def slow_steps(ends: list, start: float, n: int = 5) -> str:
    """The median step and the ``n`` slowest, with their index."""
    import numpy as np
    d = np.diff([start] + ends) * 1e3
    top = np.argsort(d)[::-1][:n]
    return (f"step median {np.median(d):.2f} ms, slowest "
            + ", ".join(f"#{i} {d[i]:.1f} ms" for i in sorted(top)))


def tail_ms(ends: list, start: float, span_s: float = TAIL_SPAN_S) -> float:
    """95th percentile, over every step, of the mean step time of the
    shortest run of steps ending at it that spans ``span_s`` or more
    (a host-clock time over a shorter span is mostly clock error)."""
    import numpy as np
    marks = [start] + ends
    vals = []
    j = 0
    for i in range(1, len(marks)):
        while j + 1 < i and marks[i] - marks[j + 1] >= span_s:
            j += 1
        if marks[i] - marks[j] >= span_s:
            vals.append((marks[i] - marks[j]) / (i - j))
    if not vals:        # a window shorter than the span: its mean step
        vals = [(marks[-1] - marks[0]) / len(ends)]
    return float(np.percentile(vals, 95)) * 1e3


def snapshot_grad_norms(opt_state, opt: dict) -> tuple:
    """Per-leaf norms of the first gradient as the optimizer got it, from
    its first moment and from its second (which shows ``b2``)."""
    from reference import grad_norms_from_moments
    return grad_norms_from_moments(opt_state.mu, opt_state.nu, opt)


def change_norms(p3, p0) -> dict:
    """Per-leaf norms of ``p3 - p0`` (host arrays)."""
    import jax
    import numpy as np

    def flat(tree):
        return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                jax.tree_util.tree_flatten_with_path(tree)[0]}
    a, b = flat(p3), flat(p0)
    return {k: float(np.linalg.norm((a[k] - b[k]).astype(np.float64)))
            for k in a}


def check_steps(t, tr: dict) -> dict:
    """Set-up: the first ``check_steps`` steps through the loop's own
    calls, read for the check as far as the next step (which takes the
    state) keeps them."""
    import jax
    import system
    p0 = jax.device_get(t.state.params)
    prog = {"losses": []}
    for k in range(tr["check_steps"]):
        metrics = system.train_step(t)
        prog["losses"].append(float(metrics["loss"]))
        if k == 0:
            prog["grad"], prog["grad2"] = snapshot_grad_norms(
                t.state.opt_state, tr["optimizer"])
    prog["change"] = change_norms(jax.device_get(t.state.params), p0)
    return prog


def host_ms(spans: dict, steps: int, elapsed: float) -> str:
    """The host spans' milliseconds per step, and the rest of the loop's."""
    return ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in spans.items()) \
        + f", rest {1e3 * (elapsed - sum(spans.values())) / steps:.3f}"


def memory(devices) -> dict:
    """memory_stats() of each device and the peak on the fullest one.
    On the v5e the step's temporaries are counted in
    ``peak_bytes_reserved`` and the state in ``peak_bytes_in_use``."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    return {"stats": stats, "peak_bytes": peak}


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, devices,
        peak: dict, root: Path = ROOT) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    import system
    from reference import Reference

    tr = cell.traffic
    counter = CompileCounter()
    t_build = time.perf_counter()
    t = system.build(cell, seed, devices)
    t_steps = time.perf_counter()
    prog = check_steps(t, tr)
    setup_s = time.perf_counter() - T_START
    print(f"[bench] set-up {setup_s:.2f} s: imports and backend "
          f"{t_build - T_START:.2f} s, build {t_steps - t_build:.2f} s, "
          f"checked steps {T_START + setup_s - t_steps:.2f} s; "
          f"{counter.n} trace/compile events, persistent cache "
          f"{counter.cache}", file=sys.stderr, flush=True)

    # the window
    t.spans = {k: 0.0 for k in t.spans}
    t.skips = 0
    compiles0 = counter.n
    start = time.perf_counter()
    ends = []
    while time.perf_counter() - start < seconds:
        system.train_step(t)
        ends.append(time.perf_counter())
    window_s = ends[-1] - start
    spans = dict(t.spans)
    compiles = counter.n - compiles0
    steps = len(ends)
    samples_per_s = steps * tr["global_batch"] / window_s
    print(f"[bench] window: {steps} steps in {window_s:.3f} s, "
          f"{compiles} compilations inside it, guard skips {t.skips}; "
          f"{slow_steps(ends, start)}; host ms per step "
          f"{host_ms(spans, steps, window_s)}", file=sys.stderr, flush=True)

    reduction = compiled = step_bytes = None
    if trace:
        reduction, compiled = traced_steps(t, tr["trace_steps"])
    mem = memory(devices)
    print(f"[bench] memory_stats per device: {mem['stats']}",
          file=sys.stderr, flush=True)
    if compiled is not None:
        ma = compiled.memory_analysis()
        step_bytes = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                      + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        print(f"[bench] peak check: the step's memory_analysis {step_bytes} B "
              f"(temp {ma.temp_size_in_bytes}, args "
              f"{ma.argument_size_in_bytes}, out {ma.output_size_in_bytes}, "
              f"alias {ma.alias_size_in_bytes}) vs memory_stats "
              f"{mem['peak_bytes']} B (in_use + reserved)", file=sys.stderr,
              flush=True)
    failed = t.skips
    t.close()
    del t, compiled
    gc.collect()

    t_ref = time.perf_counter()
    ref = Reference(cell, device=devices[0]).readings(seed)
    checks, correct = check.judge(check.gaps(prog, ref), tr["limits"])
    print(f"[bench] reference {time.perf_counter() - t_ref:.2f} s; "
          f"grad_gap from the first moment "
          f"{check.leaf_gap(prog['grad'], ref['grad'])!r}, from the second "
          f"{check.leaf_gap(prog['grad2'], ref['grad'])!r}", file=sys.stderr)

    ctx = {
        "cell": cell, "peak": peak, "chips": len(devices), "steps": steps,
        "samples_per_s": samples_per_s, "spans": spans, "trace": reduction,
        "memory": mem, "step_bytes": step_bytes,
    }
    e2e = {
        "samples_per_s": samples_per_s,
        "step_ms_p95": tail_ms(ends, start),
        "setup_s": setup_s,
    }
    out = {}
    if trace:
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"], root)(ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            out[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem["peak_bytes"]}
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": out, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = checks
    return result


def traced_steps(t, n: int):
    """``n`` more steps under the profiler, reduced."""
    import jax
    import system
    import trace as trace_mod
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t.spans = {k: 0.0 for k in t.spans}
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(TRACED):
            for _ in range(n):
                system.train_step(t)
        elapsed = time.perf_counter() - start
        jax.profiler.stop_trace()
        print(f"[bench] traced: {n} steps in {elapsed:.3f} s, host ms per "
              f"step {host_ms(t.spans, n, elapsed)}", file=sys.stderr,
              flush=True)
        batch = next(t.prefetcher)[1]
        compiled = t.step_fn.lower(t.state, batch).compile()
        reduction = trace_mod.reduce(trace_mod.load(d), TRACED,
                                     hlo_text=compiled.as_text())
    return reduction, compiled


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    table = peaks()
    use_compile_cache()
    devices = chip_devices(cell.chips, table)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 table["devices"][devices[0].device_kind])
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
