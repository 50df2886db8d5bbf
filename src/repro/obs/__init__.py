"""repro.obs — step-time telemetry, wire meters, and kernel rooflines.

Three layers (ISSUE 6 / ROADMAP "Roofline-gated perf CI"):

* meters + spans (:mod:`repro.obs.meters`, :mod:`repro.obs.trace`) —
  a process-local name -> number registry fed by the exact accounting
  that already exists (``netsim.metrics`` bits, ``core.bucket`` payload
  bytes, trace counts) plus ``block_until_ready``-correct wall-clock
  spans.  Instrumented code (``WireExchange``, ``netsim.simulate``, the
  Runner adapters) records into the *ambient* registry installed with
  :func:`using_meters`; with no registry installed every hook is a no-op,
  so the telemetry costs nothing on the hot path and nothing at trace
  time.

* structured reports (:mod:`repro.obs.report`) — every ``Runner.run``
  emits a :class:`RunReport` (JSON-serializable) with the measured step
  time and the exact bits on the wire, stored on the runner as
  ``last_report``.

* roofline comparison (:mod:`repro.obs.roofline_gate`) — analytical
  HBM/link rooflines for the fused wire kernels, derived from the exact
  byte counts in :class:`repro.core.bucket.BucketLayout`, reported as
  measured-vs-predicted utilization.  The CI gate that closes the loop
  lives in ``tools/perf_gate.py``.
"""
from repro.obs.meters import Meters, current_meters, env_info, using_meters
from repro.obs.report import RunReport, build_report, wire_breakdown
from repro.obs.roofline_gate import (kernel_roofline, step_roofline,
                                     trainer_wire_layout)
from repro.obs.trace import annotate, span

__all__ = [
    "Meters", "current_meters", "using_meters", "env_info",
    "span", "annotate",
    "RunReport", "build_report", "wire_breakdown",
    "kernel_roofline", "step_roofline", "trainer_wire_layout",
]
