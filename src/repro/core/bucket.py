"""Bucketed wire layout for the sharded gossip backend (§Perf).

The per-leaf wire path ppermutes every pytree leaf's payload separately:
2 x hops x leaves collective-permutes per step — dozens of tiny collectives
for a transformer.  This module computes a STATIC layout table that maps
every leaf's quantization blocks into one contiguous row table per
(block-width, dtype) group, and concatenates the groups into exactly TWO
flat u8 wire buffers per node:

  codes buffer  — the nibble/byte-packed offset codes of every block of
                  every leaf, group by group, leaf by leaf;
  scales buffer — one byte-cast scale (f32 or bf16) per block, same order;
                  within a leaf's segment the scales' bytes are stored
                  as planes (all least significant bytes first).

A gossip hop then ppermutes those two buffers and nothing else: the COMM
step costs 2 x hops collectives regardless of leaf count.  The layout
reuses the trainer's ``_quant_block`` sizing (pass it as ``block_for``), so
a leaf whose (model-local) last dim is narrower than the configured block
quantizes at its own width and no padded block ever ships — the buffer
holds exactly the bytes the per-leaf path would have moved, concatenated.

The jnp work runs under the train step's named scopes (repro.obs):
``prox_lead/noise`` for the rounding-noise draws, ``prox_lead/wire_layout``
for everything else, the kernel calls included (they keep their own
``jit(qinf_*)`` names inside it).

Everything here is static Python over leaf shapes; the jnp work (blocking,
fused quantize+pack, fused unpack+dequant+mix) dispatches through
:mod:`repro.kernels.ops`, one kernel call per leaf: a call over a whole
group would hold f32 copies of every member leaf (and their noise) at
once, which at published widths is more than a chip's memory.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels.quantize import packed_width
from repro.obs.trace import NOISE, WIRE_LAYOUT


def default_quant_block(shape: Sequence[int], block: int = 256) -> int:
    """Quantization block width for a leaf of ``shape``: the configured
    ``block``, capped at the leaf's own last dim when that is even and
    smaller — a row narrower than the block would otherwise ship a full
    padded block per row on every hop (nibble packing needs even widths,
    so odd last dims keep the padded block)."""
    ld = shape[-1] if shape else 1
    if ld % 2 == 0 and ld < block:
        return ld
    return block


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf's quantization blocks live inside its group."""
    index: int                  # position in the flattened leaf list
    shape: Tuple[int, ...]      # leaf shape as the quantizer sees it
    dtype: Any
    block: int                  # quantization block width for this leaf
    nb: int                     # blocks per row: ceil(last_dim / block)
    rows: int                   # total blocks: prod(shape[:-1]) * nb
    group: int                  # index into BucketLayout.groups
    row_offset: int             # first row within the group's row table


@dataclasses.dataclass(frozen=True)
class GroupSlot:
    """One (block-width, dtype) row table and its wire-buffer segment."""
    block: int
    dtype: Any
    packed_width: int           # wire bytes per row (codes)
    rows: int                   # total rows over member leaves
    codes_offset: int           # byte offset into the codes wire buffer
    scales_offset: int          # byte offset into the scales wire buffer
    leaf_indices: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static map: pytree leaves <-> two flat u8 wire buffers."""
    slots: Tuple[LeafSlot, ...]
    groups: Tuple[GroupSlot, ...]
    codes_bytes: int
    scales_bytes: int
    scale_bytes: int            # bytes per block scale (4 f32 / 2 bf16)
    bits: int

    @property
    def wire_bits(self) -> int:
        """Exact bits one directed edge moves per hop (both buffers)."""
        return 8 * (self.codes_bytes + self.scales_bytes)


def compute_layout(shapes: Sequence[Tuple[int, ...]],
                   dtypes: Sequence[Any], *, bits: int,
                   block_for: Optional[Callable] = None,
                   scale_bytes: int = 4) -> BucketLayout:
    """Build the static layout for leaves of ``shapes``/``dtypes``.

    ``block_for(shape) -> int`` chooses each leaf's quantization block
    (default :func:`default_quant_block`); leaves sharing (block, dtype)
    land in one group, contiguous on the wire."""
    block_for = block_for or default_quant_block
    keys: List[Tuple[int, str]] = []        # group keys, first appearance
    members: List[List[int]] = []
    slots_raw = []
    for j, (shape, dtype) in enumerate(zip(shapes, dtypes)):
        shape = tuple(int(d) for d in shape) or (1,)
        blk = int(block_for(shape))
        nb = -(-shape[-1] // blk)
        rows = int(np.prod(shape[:-1], dtype=np.int64)) * nb
        key = (blk, np.dtype(dtype).name)
        if key not in keys:
            keys.append(key)
            members.append([])
        g = keys.index(key)
        members[g].append(j)
        slots_raw.append((j, shape, dtype, blk, nb, rows, g))

    group_rows = [sum(slots_raw[j][5] for j in m) for m in members]
    groups, codes_off, scales_off = [], 0, 0
    for g, (blk, _dname) in enumerate(keys):
        pw = packed_width(blk, bits)
        groups.append(GroupSlot(
            block=blk, dtype=np.dtype(dtypes[members[g][0]]),
            packed_width=pw, rows=group_rows[g], codes_offset=codes_off,
            scales_offset=scales_off, leaf_indices=tuple(members[g])))
        codes_off += group_rows[g] * pw
        scales_off += group_rows[g] * scale_bytes

    slots, row_off = [None] * len(shapes), [0] * len(groups)
    for (j, shape, dtype, blk, nb, rows, g) in slots_raw:
        slots[j] = LeafSlot(index=j, shape=shape, dtype=np.dtype(dtype),
                            block=blk, nb=nb, rows=rows, group=g,
                            row_offset=row_off[g])
        row_off[g] += rows
    return BucketLayout(slots=tuple(slots), groups=tuple(groups),
                        codes_bytes=codes_off, scales_bytes=scales_off,
                        scale_bytes=scale_bytes, bits=bits)


# ---------------------------------------------------------------------------
# jnp orchestration: leaves -> wire buffers -> mixed leaves.
# ---------------------------------------------------------------------------


def _scales_dtype(layout: BucketLayout):
    return jnp.bfloat16 if layout.scale_bytes == 2 else jnp.float32


def _uint(nbytes: int):
    return jnp.uint16 if nbytes == 2 else jnp.uint32


def _to_bytes(x: jax.Array) -> jax.Array:
    """(R, 1) f32/bf16 scales -> (itemsize * R,) u8 byte planes: the R
    least significant bytes, then the next R, and so on.  1-D planes made
    by shifts: on TPU a u8 array with a minor dim of 4 (what a bitcast to
    u8 gives) relays out through a program that takes minutes to
    compile at published widths."""
    nb = x.dtype.itemsize
    w = jax.lax.bitcast_convert_type(x[:, 0], _uint(nb))
    return jnp.concatenate([(w >> (8 * k)).astype(jnp.uint8)
                            for k in range(nb)])


def _from_bytes(b: jax.Array, rows: int, dtype) -> jax.Array:
    """Inverse of :func:`_to_bytes`: (itemsize * rows,) u8 -> (rows,)
    ``dtype``."""
    nb = b.shape[0] // rows
    ut = _uint(nb)
    w = b[:rows].astype(ut)
    for k in range(1, nb):
        w = w | (b[k * rows:(k + 1) * rows].astype(ut) << (8 * k))
    return jax.lax.bitcast_convert_type(w, dtype)


def pack_to_wire(layout: BucketLayout, diffs: Sequence[jax.Array],
                 keys: Sequence[jax.Array], *, use_pallas=None):
    """Quantize + pack every leaf into the two flat u8 wire buffers.

    Leaf j is blocked by :func:`kops.blockwise_lastdim` at its slot's
    block width and quantized with U[0,1) noise drawn from ``keys[j]`` at
    that blocked shape — the per-leaf quantizer's exact draw.  The fused
    kernel runs once per leaf, so its operands never exceed one leaf.
    Rows land group by group, leaf by leaf.  Returns (codes u8
    (codes_bytes,), scales u8 (scales_bytes,))."""
    codes_segs, scales_segs = [], []
    for g in layout.groups:
        for i in g.leaf_indices:
            d = diffs[i]
            # in the leaf's dtype: the kernel upcasts in VMEM, and an f32
            # copy of a large leaf is what a chip runs short of
            with jax.named_scope(WIRE_LAYOUT):
                xb = kops.blockwise_lastdim(d, block=g.block, dtype=d.dtype)
            with jax.named_scope(NOISE):
                u = jax.random.uniform(keys[i], xb.shape, jnp.float32)
            with jax.named_scope(WIRE_LAYOUT):
                packed, scales = kops.qinf_quantize_pack(
                    xb.reshape(-1, g.block), u.reshape(-1, g.block),
                    bits=layout.bits, block=g.block, use_pallas=use_pallas)
                codes_segs.append(packed.reshape(-1))
                scales_segs.append(_to_bytes(
                    scales.astype(_scales_dtype(layout))))
    with jax.named_scope(WIRE_LAYOUT):
        return jnp.concatenate(codes_segs), jnp.concatenate(scales_segs)


def rows_to_leaf(slot: LeafSlot, rows: jax.Array,
                 lead: Tuple[int, ...] = ()) -> jax.Array:
    """Inverse of the row mapping: ``rows`` (*lead, slot.rows, block) ->
    (*lead, *slot.shape), dropping last-axis block padding."""
    shape = slot.shape
    flat = rows.reshape(lead + shape[:-1] + (slot.nb * rows.shape[-1],))
    return flat[..., :shape[-1]].reshape(lead + shape)


def mix_from_wire(layout: BucketLayout, wires: Sequence[Tuple[jax.Array,
                                                              jax.Array]],
                  w: jax.Array, *, use_pallas=None):
    """Unpack + dequantize + mix the received wire buffers back to leaves.

    ``wires`` — [(codes u8 flat, scales u8 flat)]: entry 0 is this node's
    own payload, then one entry per hop.  ``w`` — (T, S) receiver weights,
    S == len(wires), column order matching ``wires``.  Returns
    (wq leaves [(T, *shape) dtype], qself leaves [shape dtype]) in leaf
    order, where wq[t] = sum_s w[t, s] Q_s."""
    T = w.shape[0]
    sdtype = _scales_dtype(layout)
    sb = layout.scale_bytes
    wq: list = [None] * len(layout.slots)
    qs: list = [None] * len(layout.slots)
    with jax.named_scope(WIRE_LAYOUT):
        for g in layout.groups:
            pw = g.packed_width
            for i in g.leaf_indices:            # one kernel call per leaf
                sl = layout.slots[i]
                c0 = g.codes_offset + sl.row_offset * pw
                s0 = g.scales_offset + sl.row_offset * sb
                pstack = jnp.stack([
                    c[c0: c0 + sl.rows * pw].reshape(sl.rows, pw)
                    for c, _ in wires])
                sstack = jnp.stack([
                    _from_bytes(s[s0: s0 + sl.rows * sb], sl.rows,
                                sdtype).astype(jnp.float32)[:, None]
                    for _, s in wires])
                mix, qself = kops.qinf_unpack_dequant_mix(
                    pstack, sstack, w, bits=layout.bits, block=g.block,
                    out_dtype=g.dtype, use_pallas=use_pallas)
                wq[i] = rows_to_leaf(sl, mix, lead=(T,))
                qs[i] = rows_to_leaf(sl, qself)
    return wq, qs
