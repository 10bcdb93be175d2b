"""Device memory the compiled train step needs on each chip, in GiB:
temporaries, arguments and outputs less the donated (aliased) buffers,
from the step's ``memory_analysis()``. Read in the traced run, where the
harness compiles the step once more (from the cache) to read its HLO.
``memory_stats()`` is printed beside it as a cross-check."""


def read(run):
    b = run["step_bytes"]
    return b / 2 ** 30 if b else None
