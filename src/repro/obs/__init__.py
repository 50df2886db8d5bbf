"""repro.obs — step-time telemetry, wire meters, and the step's names.

Three layers (ISSUE 6 / ROADMAP "Roofline-gated perf CI"):

* meters + spans (:mod:`repro.obs.meters`, :mod:`repro.obs.trace`) —
  a process-local name -> number registry fed by the exact accounting
  that already exists (``netsim.metrics`` bits, ``core.bucket`` payload
  bytes, trace counts, compile counts and seconds from
  ``jax.monitoring``) plus ``block_until_ready``-correct wall-clock
  spans.  Instrumented code (``WireExchange``, ``netsim.simulate``, the
  Runner adapters) records into the *ambient* registry installed with
  :func:`using_meters`; with no registry installed every hook is a no-op,
  so the telemetry costs nothing on the hot path and nothing at trace
  time.

* names in the profiler's trace (:mod:`repro.obs.trace`) — the named
  scopes of the train step's parts (:data:`SCOPES`) and of the model's
  own parts under ``fwd_bwd`` (:data:`MLA`, :data:`MOE_ROUTE`,
  :data:`MOE_EXPERTS`), which every device op carries in its HLO
  ``op_name``, and the host spans of
  ``TrainerRunner.step`` (:data:`STEP` and its children), written through
  :func:`annotate` on the profiler's clock.  ``bench/`` reads both.

* structured reports (:mod:`repro.obs.report`) — every ``Runner.run``
  emits a :class:`RunReport` (JSON-serializable) with the measured step
  time, the exact bits on the wire and the run's meters, stored on the
  runner as ``last_report``.
"""
from repro.obs.meters import Meters, current_meters, env_info, using_meters
from repro.obs.report import RunReport, build_report, wire_breakdown
from repro.obs.trace import (CONSENSUS, EXCHANGE, FWD_BWD, MLA,
                             MOE_EXPERTS, MOE_ROUTE, NOISE, SCOPES, STEP,
                             STEP_CALL, STEP_PLACE, UPDATE, WIRE_LAYOUT,
                             annotate, span)

__all__ = [
    "Meters", "current_meters", "using_meters", "env_info",
    "span", "annotate",
    "FWD_BWD", "NOISE", "WIRE_LAYOUT", "EXCHANGE", "UPDATE", "CONSENSUS",
    "SCOPES", "MLA", "MOE_ROUTE", "MOE_EXPERTS",
    "STEP", "STEP_PLACE", "STEP_CALL",
    "RunReport", "build_report", "wire_breakdown",
]
