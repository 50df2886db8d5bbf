"""Wall-clock spans that are correct under JAX's async dispatch.

``jax.jit`` returns before the device work finishes, so a naive
``time.perf_counter()`` pair around a jitted call measures dispatch, not
execution.  A :func:`span` yields a handle whose ``ready(x)`` calls
``jax.block_until_ready`` on the step outputs — call it on whatever the
span produced before the with-block closes and the recorded time covers
the actual device work::

    with span("run_total") as sp:
        for t in range(steps):
            state = step(state, key)
        sp.ready(state)                  # fence: drain the async queue

On exit the elapsed seconds accumulate into the ambient
:class:`~repro.obs.meters.Meters` (if any) as ``time/<name>_s`` plus an
occurrence counter ``time/<name>_n`` — counter semantics, so nested loops
of short spans sum.

:func:`annotate` marks a host region in the profiler's trace (a
``jax.profiler.TraceAnnotation``, or a ``StepTraceAnnotation`` when given
a ``step_num``); with no profiler session active it records nothing.
``TrainerRunner.step`` marks each call as :data:`STEP` with the runner's
count of dispatched steps, and its two parts as :data:`STEP_PLACE` and
:data:`STEP_CALL`.  The profiler writes these spans into the same trace as
the device ops, on the same clock.

The names of the train step's parts on the device live here too: the
step wraps each part in ``jax.named_scope(<scope>)``, so every op the
part makes carries the scope in its HLO ``op_name`` and a profile can
attribute the op.  Scopes are metadata: the compiled program is the same.
No scope of :data:`SCOPES` nests inside another of them, so an op lies
under at most one.  The model's own parts (:data:`MLA`, :data:`MOE_ROUTE`,
:data:`MOE_EXPERTS`: latent attention, the expert layer's routing and its
grouped matmuls) nest under ``fwd_bwd``, in the forward and in its
transpose alike; a reader of one takes the innermost scope an op lies
under.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

from repro.obs.meters import Meters, current_meters


class Span:
    """Handle yielded by :func:`span`; ``elapsed_s`` is set on exit."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.elapsed_s: float = 0.0

    def ready(self, x):
        """Block until every array in pytree ``x`` is computed; returns x."""
        return jax.block_until_ready(x)


@contextlib.contextmanager
def span(name: str, meters: Optional[Meters] = None):
    """Time a block (see module docstring).  ``meters`` overrides the
    ambient registry; with neither, the Span still carries ``elapsed_s``."""
    m = meters if meters is not None else current_meters()
    sp = Span(name)
    t0 = time.perf_counter()
    try:
        yield sp
    finally:
        sp.elapsed_s = time.perf_counter() - t0
        if m is not None:
            m.inc(f"time/{name}_s", sp.elapsed_s)
            m.inc(f"time/{name}_n", 1)


# Named scopes of the train step's parts (see module docstring).
FWD_BWD = "fwd_bwd"                    # loss_and_grad
NOISE = "prox_lead/noise"              # key folds and rounding-noise draws
WIRE_LAYOUT = "prox_lead/wire_layout"  # leaf <-> wire-buffer relayouts
EXCHANGE = "prox_lead/exchange"        # ppermutes; the identity path's mix
UPDATE = "prox_lead/update"            # Prox-LEAD lines 6-10, elementwise
CONSENSUS = "consensus"                # the consensus metric
SCOPES = (FWD_BWD, NOISE, WIRE_LAYOUT, EXCHANGE, UPDATE, CONSENSUS)

# Named scopes of the model's parts, under FWD_BWD (see module docstring).
MLA = "mla"                            # a latent-attention block
MOE_ROUTE = "moe/route"                # router, top-k, balance loss,
#                                        grouping and combine
MOE_EXPERTS = "moe/experts"            # the held experts' grouped matmuls

# Host spans of TrainerRunner.step.
STEP = "repro.step"
STEP_PLACE = "repro.step.place"        # the batch's device_put
STEP_CALL = "repro.step.call"          # the executable call


def annotate(name: str, step_num: Optional[int] = None):
    """Mark a host region ``name`` in the profiler's trace; with
    ``step_num``, as one step of a training loop."""
    if step_num is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)
