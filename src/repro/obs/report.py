"""RunReport: the structured record every ``Runner.run`` emits.

One JSON-serializable dataclass per run, stored on the runner as
``last_report`` (the ``(state, logs)`` return signatures are unchanged).
Sections:

=========  ================================================================
env        :func:`repro.obs.meters.env_info` stamp (jax version, backend,
           device kind/count, cpu count, x64) — history comparisons stay
           attributable across machines.
timing     measured total + per-step wall clock (block_until_ready-
           correct).  On a device in ``obs.roofline.PEAKS`` it also holds
           ``wire_model_s_per_step``: the exact bits on the wire priced at
           that chip's published interconnect bandwidth — a model, not a
           measurement.  On any other device the key is absent.
wire       the exact accounting: ``bits_per_step`` / ``bits_total`` from
           the same functions the tests pin against HLO-parsed collective
           bytes (``netsim.metrics``), plus the WireExchange gauges
           (bytes per hop, hops, collectives per step).  ``scope`` says
           what one "bits_per_step" covers: ``node`` (one sender, the
           sharded/dense convention) or ``system`` (all edges, the
           netsim trajectory convention).
meters     raw snapshot of the run's :class:`~repro.obs.meters.Meters`,
           the compile counters (``compile/...``) among them.
extra      engine-specific fields (algo name, final consensus, ...).
=========  ================================================================
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict, Optional

from repro.obs.meters import Meters, env_info
from repro.obs.roofline import Peaks, peaks_for


@dataclasses.dataclass
class RunReport:
    name: str
    engine: str
    steps: int
    env: Dict[str, Any]
    timing: Dict[str, float]
    wire: Dict[str, Any]
    meters: Dict[str, float]
    extra: Dict[str, Any]

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=1, default=str)

    def save(self, path) -> pathlib.Path:
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(self.to_json())
        return p

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RunReport":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @classmethod
    def from_json(cls, text_or_path) -> "RunReport":
        text = str(text_or_path)
        if not text.lstrip().startswith("{"):
            text = pathlib.Path(text).read_text()
        return cls.from_dict(json.loads(text))


def wire_breakdown(total_s: float, steps: int, bits_per_step: float,
                   peaks: Optional[Peaks] = None) -> Dict[str, float]:
    """Measured total and mean step time, plus the modelled wire time of
    the exact per-step bits when the chip's ``peaks`` are known."""
    out = {"total_s": float(total_s),
           "mean_step_s": total_s / steps if steps else 0.0}
    if peaks is not None:
        out["wire_model_s_per_step"] = (bits_per_step / 8.0) / peaks.ici_bw
    return out


def build_report(*, name: str, engine: str, steps: int, total_s: float,
                 bits_per_step: float = 0.0,
                 bits_total: Optional[float] = None,
                 scope: str = "node",
                 meters: Optional[Meters] = None,
                 extra: Optional[Dict] = None) -> RunReport:
    """Assemble a RunReport from a run's measured total seconds and exact
    bit accounting; derived timing fields and the env stamp are filled
    in here so every engine reports through one code path."""
    m = meters.as_dict() if isinstance(meters, Meters) else dict(meters or {})
    wire = {
        "scope": scope,
        "bits_per_step": float(bits_per_step),
        "bits_total": float(bits_total if bits_total is not None
                            else bits_per_step * steps),
        "bytes_per_hop": m.get("wire/bytes_per_hop", 0),
        "hops": m.get("wire/hops", 0),
        "collectives_per_step": m.get("wire/collectives_per_step", 0),
    }
    env = env_info()
    return RunReport(
        name=name, engine=engine, steps=int(steps), env=env,
        timing=wire_breakdown(total_s, steps, bits_per_step,
                              peaks_for(env["device_kind"])),
        wire=wire, meters=m,
        extra=dict(extra or {}))
