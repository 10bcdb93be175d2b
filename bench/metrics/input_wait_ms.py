"""Host time the loop waited in ``next(prefetcher)`` (the data pipeline's
``Prefetcher``) per step of the window, in milliseconds: the harness's
own span around the call."""


def read(run):
    return 1e3 * run["spans"]["input_wait"] / run["steps"]
