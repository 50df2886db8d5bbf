"""Analytical rooflines for the fused wire kernels, from exact byte counts.

``obs/roofline.py`` models the whole training step from HLO text and
napkin FLOP/HBM math.  This module models the *wire path* specifically —
the fused ``qinf_quantize_pack`` / ``qinf_unpack_dequant_mix`` kernels and
the collective-permutes between them — from the **exact** byte layout in
:class:`repro.core.bucket.BucketLayout`.  Nothing here is estimated: the
codes/scales byte counts are the same integers ``BucketLayout.wire_bits``
pins and the HLO-parsed ``collective_bytes`` reproduces (tested in
tests/test_dryrun_small.py), so predicted-vs-measured utilization is a
clean kernel-efficiency signal, not a modeling artifact.

Per-node, per-step traffic model (``elems`` = total quantization slots
= sum over groups of ``rows x block``; padding included — padded lanes
move through HBM even though they never ship):

* quantize_pack  — reads the f32 blocked input and the matching U(0,1)
  noise (``2 x 4 x elems`` bytes), writes the packed codes + byte-cast
  scales (exactly ``codes_bytes + scales_bytes``).
* unpack_dequant_mix — reads ``1 + hops`` received payload pairs, writes
  the f32 mix for each of ``receivers`` rows plus the f32 qself rows
  (``(receivers + 1) x 4 x elems``).
* wire — ``hops`` serial transfers of ``codes_bytes + scales_bytes``
  each (the exact bits :func:`repro.netsim.metrics.bucketed_payload_bits`
  counts, divided by the model-shard redundancy), priced at the chip's
  whole interconnect bandwidth: a lower bound on the time.

The peaks are a :class:`repro.obs.roofline.Peaks` entry of the chip the
model is for; every second here is modelled, none is measured.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from repro.core.bucket import BucketLayout
from repro.obs.roofline import Peaks


def _elems(layout: BucketLayout) -> int:
    return sum(g.rows * g.block for g in layout.groups)


def kernel_roofline(layout: BucketLayout, *, peaks: Peaks, hops: int = 1,
                    receivers: int = 1) -> Dict[str, Dict[str, float]]:
    """Predicted HBM bytes and roofline seconds per kernel (one node, one
    COMM exchange).  See the module docstring for the traffic model."""
    elems = _elems(layout)
    wire_bytes = layout.codes_bytes + layout.scales_bytes
    qp_bytes = 2 * 4 * elems + wire_bytes
    um_bytes = (1 + hops) * wire_bytes + (receivers + 1) * 4 * elems
    return {
        "quantize_pack": {"hbm_bytes": float(qp_bytes),
                          "t_s": qp_bytes / peaks.hbm_bw},
        "unpack_dequant_mix": {"hbm_bytes": float(um_bytes),
                               "t_s": um_bytes / peaks.hbm_bw},
        "wire": {"bytes_per_hop": float(wire_bytes), "hops": float(hops),
                 "t_s": hops * wire_bytes / peaks.ici_bw},
    }


def step_roofline(layout: BucketLayout, *, peaks: Peaks, hops: int,
                  receivers: int = 1,
                  measured_step_s: Optional[float] = None) -> Dict:
    """Whole-exchange roofline: kernel + wire seconds, plus
    ``utilization = predicted / measured`` when a measured step time is
    given (1.0 = running at the roofline)."""
    k = kernel_roofline(layout, peaks=peaks, hops=hops, receivers=receivers)
    wire_s = k["wire"]["t_s"]
    kernel_s = k["quantize_pack"]["t_s"] + k["unpack_dequant_mix"]["t_s"]
    out = {
        "predicted_step_s": kernel_s + wire_s,
        "predicted_kernel_s": kernel_s,
        "predicted_wire_s": wire_s,
        "wire_bytes_per_hop": k["wire"]["bytes_per_hop"],
        "kernels": k,
    }
    if measured_step_s:
        out["measured_step_s"] = float(measured_step_s)
        out["utilization"] = (kernel_s + wire_s) / measured_step_s
    return out


def trainer_wire_layout(trainer, leaves) -> Tuple[BucketLayout, int]:
    """(BucketLayout, model-shard redundancy) for a trainer's wire path —
    the same static construction ``bucketed_payload_bits`` prices, so
    ``model * layout.wire_bits`` equals that accounting (and the HLO's
    collective-permute bytes) exactly.  ``leaves`` are the stacked (N, ...)
    ``plead.X`` leaves (arrays or ShapeDtypeStructs)."""
    from repro.core import bucket
    from repro.netsim import metrics as netsim_metrics
    tcfg = trainer.tcfg
    model, locals_ = netsim_metrics._model_local_shapes(trainer, leaves)
    layout = bucket.compute_layout(
        [(1,) + tuple(s) for s in locals_], [l.dtype for l in leaves],
        bits=tcfg.bits, block_for=trainer._quant_block,
        scale_bytes=2 if tcfg.scales_bf16 else 4)
    return layout, model
