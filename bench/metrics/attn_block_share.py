"""Share of the chip's busy time spent in ops whose innermost stack frame
is in the attention modules (``models/attention.py``,
``models/blockwise.py``, ``kernels/flash_attention.py``), in percent.
Nothing is read where the trace's ops carry no source file."""

FILES = ("models/attention.py", "models/blockwise.py",
         "kernels/flash_attention.py")


def read(run):
    red = run["trace"]
    if red is None or not red.busy_s:
        return None
    known = sum(v for k, v in red.source_s.items() if k)
    if known < 0.5 * red.busy_s:
        return None
    return 100.0 * sum(red.source_s.get(f, 0.0) for f in FILES) / red.busy_s
