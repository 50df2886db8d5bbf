"""COMM wire path of the sharded gossip backend, factored out of the
trainer so ``DecentralizedTrainer._sharded_update`` and the wire
benchmarks (benchmarks/bench_wire.py) drive the exact same code.

Both modes turn per-leaf difference tensors into, per leaf,
(wq (T, *shape), qself (*shape)) where wq[t] = sum_s w[t, s] Q_s over
sender 0 = self plus one sender per hop:

  bucketed — ONE packed-codes buffer and ONE byte-cast-scales buffer per
             node, laid out by :mod:`repro.core.bucket`; each hop is 2
             collective-permutes regardless of leaf count, and quantize+
             pack / unpack+dequant+mix run as fused kernels.
  per_leaf — the original path: every leaf ppermutes its own packed codes
             and scales (2 x hops x leaves collectives).  Kept as the
             parity oracle; bit-for-bit equal to bucketed whenever both
             run under the same shard_map manualness (see
             repro.optim.decentralized's module docstring for the
             model-sharded exception).

Each part runs under its named scope (repro.obs): the ppermutes and the
identity and per-leaf mixes under ``prox_lead/exchange``; the bucketed
path's relayouts under ``prox_lead/wire_layout`` and its noise under
``prox_lead/noise`` (repro.core.bucket); the per-leaf quantizer, which
draws its noise inside, under ``prox_lead/wire_layout``.

All functions run INSIDE shard_map: leaves carry a leading local node dim
of 1, ``pp(x, pairs)`` is the axis-bound ppermute closure, and ``wmat`` is
the (1 + hops, T) receiver-indexed weight table (row 0 = self weight).

Bitwise caveat: both modes mix through the same fixed-order sum of
exact partial products (kernels.ref.mix_sum, which the Pallas mix kernel
runs too), so codes, scales, qself and the mixes agree bit for bit on
any backend.  Narrow (bf16) leaves agree only in a step compiled without
XLA's excess precision (repro.api.STEP_COMPILER_OPTIONS): the bucketed
path rounds diffs and payloads to the leaf dtype around its kernels,
while per_leaf fuses them and would otherwise keep them in f32.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import bucket
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs.meters import current_meters
from repro.obs.trace import EXCHANGE, WIRE_LAYOUT

WIRE_MODES = ("bucketed", "per_leaf")


class WireExchange:
    """One COMM exchange: diffs -> (wq leaves, qself leaves)."""

    def __init__(self, *, bits: int = 2, block: int = 256,
                 scales_bf16: bool = False, pack_mode: str = "lastdim",
                 block_for: Optional[Callable] = None, use_pallas=None):
        self.bits = bits
        self.scales_bf16 = scales_bf16
        self.pack_mode = pack_mode
        self.block_for = block_for or functools.partial(
            bucket.default_quant_block, block=block)
        self.use_pallas = use_pallas

    # ------------------------------------------------------------ bucketed
    def layout(self, shapes: Sequence[Tuple[int, ...]],
               dtypes: Sequence) -> bucket.BucketLayout:
        return bucket.compute_layout(
            shapes, dtypes, bits=self.bits, block_for=self.block_for,
            scale_bytes=2 if self.scales_bf16 else 4)

    # ------------------------------------------------------------ telemetry
    def _record(self, hop_pairs, *, bytes_per_hop: int,
                collectives_per_hop: int) -> None:
        """Gauge the static wire facts into the ambient Meters (no-op when
        none is installed).  Runs at jit TRACE time inside shard_map —
        values are host ints from the static layout, and ``set`` keeps
        retraces idempotent; only ``wire/traces`` counts re-executions."""
        m = current_meters()
        if m is None:
            return
        hops = len(hop_pairs)
        m.set("wire/bytes_per_hop", bytes_per_hop)
        m.set("wire/hops", hops)
        m.set("wire/collectives_per_step", collectives_per_hop * hops)
        m.inc("wire/traces")

    def bucketed(self, diffs, keys, wmat, hop_pairs, pp):
        layout = self.layout([d.shape for d in diffs],
                             [d.dtype for d in diffs])
        self._record(hop_pairs, bytes_per_hop=layout.wire_bits // 8,
                     collectives_per_hop=2)
        cw, sw = bucket.pack_to_wire(layout, diffs, keys,
                                     use_pallas=self.use_pallas)
        # the ONLY communication: 2 buffers x hops, leaf-count independent
        with jax.named_scope(EXCHANGE):
            wires = [(cw, sw)] + [(pp(cw, pr), pp(sw, pr))
                                  for pr in hop_pairs]
            w = jnp.asarray(wmat).T
        return bucket.mix_from_wire(layout, wires, w,
                                    use_pallas=self.use_pallas)

    # ------------------------------------------------------------ per-leaf
    def per_leaf(self, diffs, keys, wmat, hop_pairs, pp):
        # same bytes as bucketed (the bucket is a concatenation), but each
        # leaf ships its own (codes, scales) pair per hop
        self._record(hop_pairs,
                     bytes_per_hop=self.layout(
                         [d.shape for d in diffs],
                         [d.dtype for d in diffs]).wire_bits // 8,
                     collectives_per_hop=2 * len(diffs))
        wq: List = []
        qs: List = []
        bits = self.bits
        for d, kj in zip(diffs, keys):
            blk = self.block_for(d.shape)
            with jax.named_scope(WIRE_LAYOUT):
                codes, scales = kops.qinf_quantize_lastdim(
                    d, kj, bits=bits, block=blk)
                if self.scales_bf16:
                    scales = scales.astype(jnp.bfloat16)
                if self.pack_mode == "lastdim":
                    packed = kops.pack_codes_lastdim(codes, bits=bits)
                    unpack = lambda pk: kops.unpack_codes_lastdim(
                        pk, bits=bits)
                else:  # flat: reshape across sharded dims (baseline)
                    packed = kops.pack_codes(codes, bits=bits)
                    unpack = lambda pk: kops.unpack_codes(
                        pk, bits=bits, n=codes.size).reshape(codes.shape)
                # byte-cast scales: EVERY wire payload is u8
                s_wire = jax.lax.bitcast_convert_type(scales, jnp.uint8)
            dq = lambda pk, su8, b=blk: kops.qinf_dequantize_lastdim(
                unpack(pk),
                jax.lax.bitcast_convert_type(
                    su8, scales.dtype).astype(jnp.float32),
                d.shape, d.dtype, block=b)
            with jax.named_scope(EXCHANGE):
                got = [(pp(packed, pr), pp(s_wire, pr)) for pr in hop_pairs]
            with jax.named_scope(WIRE_LAYOUT):
                recvs = [dq(pk, su8) for pk, su8 in got]
                q_self = kops.qinf_dequantize_lastdim(
                    codes, scales.astype(jnp.float32), d.shape, d.dtype,
                    block=blk)
                # materialize the (1 + hops, ...) payloads in the leaf's
                # dtype and shape: left free, XLA moves the quantizer's
                # block-to-leaf relayout past the mix's multiplies and
                # copies every partial product out in f32 (4.6 GB more per
                # chip for four qwen3-1.7b nodes compiled for a v5e, past
                # its memory)
                qstack = jax.lax.optimization_barrier(
                    jnp.stack([q_self] + recvs))
            with jax.named_scope(EXCHANGE):
                wq.append(kref.weighted_mix_ref(
                    jnp.asarray(wmat).T, qstack, d.dtype).astype(d.dtype))
            qs.append(q_self)
        return wq, qs

    # ------------------------------------------------------------ identity
    def identity(self, diffs, wmat, hop_pairs, pp):
        """C = 0 wire path: raw leaves move, no quantization."""
        self._record(hop_pairs,
                     bytes_per_hop=sum(d.size * d.dtype.itemsize
                                       for d in diffs),
                     collectives_per_hop=len(diffs))
        wq: List = []
        with jax.named_scope(EXCHANGE):
            for d in diffs:
                recvs = [pp(d, pr) for pr in hop_pairs]
                wq.append(kref.weighted_mix_ref(
                    jnp.asarray(wmat).T, [d] + recvs,
                    d.dtype).astype(d.dtype))
        return wq, list(diffs)
