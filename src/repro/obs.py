"""Names the program puts on its own work, for the profiler to record.

Two kinds, one place:

* **Scopes** (``jax.named_scope``) enter the compiled step's HLO as each
  instruction's ``op_name`` metadata, so a device trace can say which part
  of the step an op belongs to. Autodiff wraps them: an op of the loss's
  forward reads ``.../jvp(forward)/...`` and its backward
  ``.../transpose(jvp(forward))/...``; a recomputed (remat) forward runs in
  the backward and reads the latter.

  - ``FORWARD``: the loss of one microbatch, inside the differentiated
    function (``core/engine.py``), and the pipeline route's chunk, embed
    and head functions (``core/pipeline.py``);
  - ``OPTIMIZER``: everything after the gradients exist: the lr schedule,
    the clip, the update and the anomaly guard's selects;
  - ``ATTN_CORE``: the attention core (scores, softmax, weighted sum)
    without the projections, whichever implementation runs it
    (``models/attention.py``); a Pallas kernel's backward inherits it
    through the kernel's custom VJP.

* **Spans** (``span(name)``, a ``jax.profiler.TraceAnnotation``) mark host
  work on the profiler's host plane, on the device planes' clock to
  within about a millisecond: the input pipeline's ``DATA_SYNTH`` (host
  batch synthesis), ``DATA_TRANSFER`` (its ``device_put``) and
  ``DATA_WAIT`` (the consumer's wait for a batch). With no trace running
  a span costs one check.
"""
from __future__ import annotations

import jax

FORWARD = "forward"
OPTIMIZER = "optimizer"
ATTN_CORE = "attn_core"

DATA_SYNTH = "data/synth"
DATA_TRANSFER = "data/transfer"
DATA_WAIT = "data/wait"


def span(name: str):
    """A host span over the code run inside it."""
    return jax.profiler.TraceAnnotation(name)
