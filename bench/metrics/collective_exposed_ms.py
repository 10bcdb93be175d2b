"""Time per step in collective ops (all-reduce, all-gather, ...) during
which no other op runs on the chip, in milliseconds, the mean over the
chips (``trace.py``). Nothing is read where the trace holds no
collective."""


def read(run):
    red = run["trace"]
    if red is None or not red.collective_s or not red.steps:
        return None
    return 1e3 * red.exposed_collective_s / red.steps
