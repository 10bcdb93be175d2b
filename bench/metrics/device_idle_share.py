"""Share of the traced window in which no op ran on the chip, in
percent, for the chip with the most idle time (``trace.py``)."""


def read(run):
    red = run["trace"]
    if red is None:
        return None
    return 100.0 * max(red.idle_share.values())
