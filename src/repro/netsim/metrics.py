"""Trajectory containers and exact bits-on-wire accounting for netsim runs.

Bit accounting is *exact*, not expected-value: the engine re-derives the
per-round edge masks from the same fold_in(key, k) stream the mixer used, so
``Trajectory.bits[k]`` is payload bits per directed edge times the number of
directed edges that actually carried a payload at iteration k.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import Compressor, Identity

tmap = jax.tree_util.tree_map


def consensus_error(X) -> jax.Array:
    """sum_leaves || X - mean_node(X) ||_F^2 over the leading node dim."""
    return sum(jnp.sum((l - l.mean(0, keepdims=True)) ** 2)
               for l in jax.tree_util.tree_leaves(X))


def payload_bits_per_node(compressor: Optional[Compressor], X) -> int:
    """Exact wire bits ONE node sends to ONE neighbor per COMM round, summed
    over pytree leaves (leaves carry a leading node dim)."""
    bits = 0
    for leaf in jax.tree_util.tree_leaves(X):
        shape = leaf.shape[1:]
        if compressor is None or isinstance(compressor, Identity):
            bits += int(np.prod(shape, dtype=np.int64)) * 32
        else:
            bits += int(compressor.payload_bits(shape))
    return bits


def effective_bits_per_iter(compressor: Optional[Compressor], shape,
                            n_directed_edges: int,
                            faults: Sequence = ()) -> float:
    """Expected bits on the wire per iteration for a (faulty) gossip round:
    per-edge payload bits x directed edges x mean edge survival."""
    from repro.netsim.faults import mean_edge_survival
    if compressor is None or isinstance(compressor, Identity):
        per_edge = int(np.prod(shape, dtype=np.int64)) * 32
    else:
        per_edge = int(compressor.payload_bits(shape))
    return per_edge * n_directed_edges * mean_edge_survival(faults)


# ---------------------------------------------------------------------------
# Exchange-plan accounting (sharded neighbor backend)
# ---------------------------------------------------------------------------

def plan_bits_per_round(plan, payload_bits_per_edge: int) -> int:
    """Exact wire bits one gossip round of a compiled ExchangePlan moves:
    every union-support pair carries its payload every round (time-varying
    weights gate the *mixing*, not the send)."""
    return plan.pairs_per_round * payload_bits_per_edge


def plan_active_bits(plan, payload_bits_per_edge: int) -> np.ndarray:
    """(T,) wire bits per round counting only pairs with nonzero mixing
    weight — the dense netsim engine's accounting convention, for
    comparison against :func:`plan_bits_per_round`."""
    return plan.active_pairs() * payload_bits_per_edge


def qinf_wire_bits(shape, bits: int, block: int, scale_bits: int = 32) -> int:
    """u8 wire bits for one last-dim-quantized tensor: nibble/byte-packed
    codes — (b+1)-bit offset codes rounded to 4 or 8 bits, including block
    padding — plus byte-cast scales.  This is what the sharded backend's
    collective-permutes physically move (bigger than ``QInf.payload_bits``,
    which counts ideal b-bit packing)."""
    from repro.kernels.ops import wire_bits_per_element
    if not shape:
        shape = (1,)
    rows = int(np.prod(shape[:-1], dtype=np.int64)) if len(shape) > 1 else 1
    nb = -(-int(shape[-1]) // block)
    return rows * nb * (block * wire_bits_per_element(bits) + scale_bits)


def _model_local_shapes(trainer, leaves):
    """(model_size, per-device leaf shapes) as the full-manual shard_map
    sees them.

    Under the full-manual bucketed wire mode a node spans model_size
    devices and each device ppermutes its LOCAL arrays: leaves whose last dim is model-sharded
    quantize (and pad) per slice, every other leaf is ppermuted redundantly
    by all model_size devices — the physical edge payload is model_size x
    the per-device bytes (which is what the HLO's collective-permutes show,
    per device)."""
    # the trainer's own predicate: full-manual for the bucketed wire path
    # (identity is always per-leaf)
    full_manual = not trainer._partial_manual
    model = 1
    locals_ = [l.shape[1:] for l in leaves]      # per-node leaf shapes
    if full_manual and trainer.mesh is not None:
        from repro.models.sharding import model_axis_size
        model = model_axis_size(trainer.mesh)
        if model > 1:
            from jax.sharding import PartitionSpec as P
            from repro.models import transformer as TR
            from repro.models.sharding import model_local_shape, param_specs
            specs = jax.tree_util.tree_leaves(
                param_specs(TR.abstract_params(trainer.mcfg)),
                is_leaf=lambda s: isinstance(s, P))
            locals_ = [model_local_shape(shape, sp, model)
                       for shape, sp in zip(locals_, specs)]
    return model, locals_


def sharded_payload_bits(trainer, leaves) -> int:
    """Exact bits ONE directed edge carries per hop on the sharded neighbor
    backend: packed u8 codes (incl. block padding) plus byte-cast scales,
    summed over state leaves.

    ``leaves`` are stacked (N, ...) leaves (arrays or ShapeDtypeStructs) in
    ``plead.X`` order; the per-edge payload is the per-node slice.  Valid
    for BOTH wire modes: the bucketed buffers concatenate exactly the
    per-leaf payloads (see :func:`bucketed_payload_bits`)."""
    from repro.core.compression import Identity
    tcfg = trainer.tcfg
    identity = isinstance(trainer.compressor, Identity)
    scale_bits = 16 if tcfg.scales_bf16 else 32
    model, locals_ = _model_local_shapes(trainer, leaves)
    per_device = 0
    for l, local in zip(leaves, locals_):
        if identity:                 # raw floats, no blocking/padding
            per_device += (int(np.prod(local, dtype=np.int64))
                           * jnp.dtype(l.dtype).itemsize * 8)
        else:
            blk = trainer._quant_block((1,) + local)
            per_device += qinf_wire_bits(local, tcfg.bits, blk, scale_bits)
    return model * per_device


def bucketed_payload_bits(trainer, leaves) -> int:
    """Exact bits ONE directed edge carries per hop with
    ``wire_mode='bucketed'``, computed from the static BucketLayout: the
    flat packed-codes buffer plus the flat byte-cast-scales buffer, times
    the model-shard redundancy.  Byte-identical to
    :func:`sharded_payload_bits` — the bucket concatenates exactly the
    bytes the per-leaf path ships — and to the HLO's collective-permute
    bytes."""
    from repro.core import bucket
    from repro.core.compression import Identity
    tcfg = trainer.tcfg
    if isinstance(trainer.compressor, Identity):
        # identity falls back to the per-leaf wire path (raw floats)
        return sharded_payload_bits(trainer, leaves)
    model, locals_ = _model_local_shapes(trainer, leaves)
    layout = bucket.compute_layout(
        [(1,) + tuple(s) for s in locals_], [l.dtype for l in leaves],
        bits=tcfg.bits, block_for=trainer._quant_block,
        scale_bytes=2 if tcfg.scales_bf16 else 4)
    return model * layout.wire_bits


@dataclasses.dataclass
class Trajectory:
    """Per-iteration record of a netsim run (numpy, host-side)."""
    consensus: np.ndarray        # (steps,) consensus error after each step
    objective: np.ndarray        # (steps,) objective gap (0 if no objective)
    bits: np.ndarray             # (steps,) exact bits on wire that round
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def steps(self) -> int:
        return int(self.consensus.shape[0])

    @property
    def total_bits(self) -> float:
        return float(self.bits.sum())

    def summary(self) -> dict:
        out = {"steps": self.steps,
               "final_consensus": float(self.consensus[-1]),
               "final_objective_gap": float(self.objective[-1]),
               "total_bits_on_wire": self.total_bits,
               "mean_bits_per_iter": float(self.bits.mean())}
        out.update(self.meta)
        return out

    def to_json(self, path: Optional[Any] = None, *,
                full: bool = False) -> str:
        rec = self.summary()
        if full:
            rec["trajectory"] = {
                "consensus": self.consensus.tolist(),
                "objective": self.objective.tolist(),
                "bits": self.bits.tolist(),
            }
        text = json.dumps(rec, indent=1, default=str)
        if path is not None:
            p = pathlib.Path(path)
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(text)
        return text
