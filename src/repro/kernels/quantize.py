"""Pallas TPU kernel for blockwise inf-norm b-bit quantization (paper eq. 21).

TPU adaptation (vs. the GPU warp-shuffle reduction the paper's codebase uses):
the quantization block size (256) is laid out along the *lane* dimension so a
row-max is a single VPU cross-lane reduction, and each grid step streams a
tile of whole rows HBM->VMEM via BlockSpec.  The quantize-only and mix
kernels tile rows 8 at a time (``ROWS_TILE``); the fused quantize+pack
kernel of the wire path takes a tile sized to its operands
(:func:`pack_rows_tile`).  Stochastic-rounding noise is
a second streamed operand (precomputed with jax.random outside) so the kernel
stays a pure function of its inputs — bit-for-bit testable against
``repro.kernels.ref``.

On this CPU container the kernels execute with ``interpret=True``; the
BlockSpecs below are the TPU-target tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (mix_sum, payload_parts, split_hi_lo,
                               wire_bits_per_element)

# One quantization block per row; 256 matches the paper's block size and is a
# multiple of the 128-lane VPU width.
ROWS_TILE = 8  # sublane tile: f32 min tile is (8, 128)


# BlockSpec index maps.  Their indices are int32 even under
# jax_enable_x64: Mosaic refuses an index map that returns an i64.
def _row_tile(i):
    return i, jnp.int32(0)


def _sender_row_tile(i):
    return jnp.int32(0), i, jnp.int32(0)


def _whole(i):
    return jnp.int32(0), jnp.int32(0)


def _lane_tile(i):
    return jnp.int32(0), i


# x and noise bytes one grid step of the quantize+pack kernel streams in.
# A grid step has a fixed cost of a fraction of a microsecond, which a tile
# of a few rows does not cover; on a v5e the kernel's time is flat from 256
# rows of 256 up.  This budget gives 1024 bf16 rows, whose double-buffered
# operands and f32 temporaries take 4.7 MiB of the 16 MiB default scoped
# VMEM.
PACK_TILE_BYTES = 3 * 2 ** 19


def pack_rows_tile(rows: int, block: int, dtype) -> int:
    """Rows of one quantize+pack grid step, from the operands' shape.

    Every row is its own quantization block, so the tile sets only how
    much a grid step moves.  Up to the cap the tile is ``rows`` itself: a
    block equal to the whole dimension is always legal, so short leaves
    need no padding.  Above it the tile is the cap, a multiple of 128 (the
    lanes of the scales row, and a multiple of the u8 sublane tile), and
    the last block may be ragged: its rows past ``rows`` reach no stored
    output.  A row narrower than 128 lanes still fills 128 in VMEM."""
    per_row = max(block, 128) * (jnp.dtype(dtype).itemsize + 4)
    cap = max(128, PACK_TILE_BYTES // per_row // 128 * 128)
    return rows if rows <= cap else cap


def packed_width(block: int, bits: int) -> int:
    """Wire bytes per quantization block: nibble-packed for bits <= 3."""
    return block // 2 if wire_bits_per_element(bits) == 4 else block


def _quantize_kernel(x_ref, u_ref, codes_ref, scales_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)           # (ROWS_TILE, BLOCK)
    u = u_ref[...].astype(jnp.float32)
    levels = jnp.float32(2 ** (bits - 1))
    maxabs = jnp.max(jnp.abs(x), axis=-1, keepdims=True)   # (ROWS_TILE, 1)
    safe = jnp.where(maxabs > 0, maxabs, jnp.float32(1.0))
    mag = jnp.floor(levels * jnp.abs(x) / safe + u)
    mag = jnp.minimum(mag, levels)
    codes_ref[...] = (jnp.sign(x) * mag).astype(jnp.int8)
    scales_ref[...] = (maxabs / levels).astype(jnp.float32)


def _dequantize_kernel(codes_ref, scales_ref, out_ref, *, out_dtype):
    c = codes_ref[...].astype(jnp.float32)
    s = scales_ref[...].astype(jnp.float32)      # (ROWS_TILE, 1)
    out_ref[...] = (c * s).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def qinf_quantize_blocks(xb: jax.Array, ub: jax.Array, *, bits: int,
                         block: int = 256, interpret: bool = True):
    """Quantize (R, block) rows -> (codes int8 (R, block), scales f32 (R, 1)).

    R must be a multiple of ROWS_TILE (callers pad; see kernels.ops).
    """
    R, B = xb.shape
    assert B == block, (xb.shape, block)
    assert R % ROWS_TILE == 0, f"R={R} must be a multiple of {ROWS_TILE}"
    grid = (R // ROWS_TILE,)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_TILE, block), _row_tile),
            pl.BlockSpec((ROWS_TILE, block), _row_tile),
        ],
        out_specs=[
            pl.BlockSpec((ROWS_TILE, block), _row_tile),
            pl.BlockSpec((ROWS_TILE, 1), _row_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, block), jnp.int8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb, ub)


# ---------------------------------------------------------------------------
# Fused wire-path kernels (bucketed gossip backend).
#
# ``_quantize_pack_kernel`` emits the uint8 wire payload directly — the int8
# code tile lives only in VMEM, never round-tripping through HBM between a
# quantize pass and a separate pack pass.  Packing uses HALVES order (byte k
# = code k | code k+B/2 << 4): both halves are contiguous lane slices, so no
# strided access or lane reshape is needed (see kernels.ref).
#
# ``_unpack_dequant_mix_kernel`` consumes the (1 + hops) received payloads
# of one bucket group and produces the weight-mixed sum_s w[t,s] Q_s for
# every schedule round t plus the dequantized self payload — per-sender
# dequantized tensors exist only as VMEM tiles.
# ---------------------------------------------------------------------------


def _quantize_pack_kernel(x_ref, u_ref, packed_ref, scales_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)           # (ROWS_TILE, BLOCK)
    u = u_ref[...].astype(jnp.float32)
    levels = jnp.float32(2 ** (bits - 1))
    maxabs = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.where(maxabs > 0, maxabs, jnp.float32(1.0))
    mag = jnp.minimum(jnp.floor(levels * jnp.abs(x) / safe + u), levels)
    enc = (jnp.sign(x) * mag).astype(jnp.int32) + 2 ** (bits - 1)
    if wire_bits_per_element(bits) == 4:
        half = enc.shape[-1] // 2
        enc = enc[:, :half] | (enc[:, half:] << 4)
    packed_ref[...] = enc.astype(jnp.uint8)
    # lane-dense: the tile's (rows, 1) scales column as a (1, rows) row
    scales_ref[...] = (maxabs / levels).astype(jnp.float32).T


def _unpack_dequant_mix_kernel(p_ref, s_ref, w_ref, mix_ref, qself_ref, *,
                               bits: int, out_dtype):
    S, T = p_ref.shape[0], mix_ref.shape[0]
    offset = jnp.int32(2 ** (bits - 1))
    qs = []
    for s in range(S):                           # 2-D (ROWS_TILE, W) tiles
        p = p_ref[s].astype(jnp.int32)
        if wire_bits_per_element(bits) == 4:
            lo = (p & 0x0F) - offset
            hi = ((p >> 4) & 0x0F) - offset
            codes = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
        else:
            codes = (p - offset).astype(jnp.float32)
        q = codes * s_ref[s].astype(jnp.float32)     # (ROWS_TILE, BLOCK)
        # round each sender's dequantized payload through the leaf dtype
        # before the f32 accumulation — bit-for-bit what the per-leaf path
        # computes when it dequantizes whole leaves
        qs.append(q.astype(out_dtype).astype(jnp.float32))
    # kernels.ref.mix_sum, the per-leaf path's sum of exact partial
    # products, on 2-D tiles; the weights' (hi | lo) halves are SMEM scalars
    parts = [payload_parts(q, out_dtype) for q in qs]
    for t in range(T):
        mix_ref[t] = mix_sum([w_ref[t, s] for s in range(S)],
                             [w_ref[t, S + s] for s in range(S)],
                             parts).astype(out_dtype)
    qself_ref[...] = qs[0].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def qinf_quantize_pack_blocks(xb: jax.Array, ub: jax.Array, *, bits: int,
                              block: int = 256, interpret: bool = True):
    """Fused quantize+pack: (R, block) float rows (upcast to f32 in VMEM)
    -> (packed u8 (R, W), scales f32 (R, 1)), W = packed_width(block,
    bits), for any R.  The grid steps over :func:`pack_rows_tile` rows;
    the kernel writes the scales as one lane-dense (1, R) row, which a
    (R, 1) column would pad to 128 lanes, and the reshape back is free.
    """
    R, B = xb.shape
    assert B == block, (xb.shape, block)
    W = packed_width(block, bits)
    tile = pack_rows_tile(R, block, xb.dtype)
    packed, scales = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, bits=bits),
        grid=(pl.cdiv(R, tile),),
        in_specs=[
            pl.BlockSpec((tile, block), _row_tile),
            pl.BlockSpec((tile, block), _row_tile),
        ],
        out_specs=[
            pl.BlockSpec((tile, W), _row_tile),
            pl.BlockSpec((1, tile), _lane_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, W), jnp.uint8),
            jax.ShapeDtypeStruct((1, R), jnp.float32),
        ],
        interpret=interpret,
    )(xb, ub)
    return packed, scales.reshape(R, 1)


@functools.partial(jax.jit, static_argnames=("bits", "block", "out_dtype",
                                             "interpret"))
def qinf_unpack_dequant_mix_blocks(packed: jax.Array, scales: jax.Array,
                                   w: jax.Array, *, bits: int,
                                   block: int = 256, out_dtype=jnp.float32,
                                   interpret: bool = True):
    """Fused unpack+dequant+mix: packed (S, R, W) u8 + scales (S, R, 1) f32
    + weights (T, S) -> (mix (T, R, block) out_dtype, qself (R, block)
    out_dtype) with mix[t] = sum_s w[t, s] Q_s.  Sender 0 is self."""
    S, R, W = packed.shape
    T = w.shape[0]
    assert W == packed_width(block, bits), (packed.shape, block, bits)
    assert scales.shape == (S, R, 1) and w.shape == (T, S)
    assert R % ROWS_TILE == 0, f"R={R} must be a multiple of {ROWS_TILE}"
    grid = (R // ROWS_TILE,)
    return pl.pallas_call(
        functools.partial(_unpack_dequant_mix_kernel, bits=bits,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((S, ROWS_TILE, W), _sender_row_tile),
            pl.BlockSpec((S, ROWS_TILE, 1), _sender_row_tile),
            pl.BlockSpec((T, 2 * S), _whole, memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((T, ROWS_TILE, block), _sender_row_tile),
            pl.BlockSpec((ROWS_TILE, block), _row_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, R, block), out_dtype),
            jax.ShapeDtypeStruct((R, block), out_dtype),
        ],
        interpret=interpret,
    )(packed, scales, jnp.concatenate(split_hi_lo(w.astype(jnp.float32)),
                                      axis=1))


@functools.partial(jax.jit, static_argnames=("block", "interpret", "out_dtype"))
def qinf_dequantize_blocks(codes: jax.Array, scales: jax.Array, *,
                           block: int = 256, out_dtype=jnp.float32,
                           interpret: bool = True):
    """Dequantize (R, block) int8 codes with (R, 1) scales -> (R, block)."""
    R, B = codes.shape
    assert B == block and R % ROWS_TILE == 0
    grid = (R // ROWS_TILE,)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_TILE, block), _row_tile),
            pl.BlockSpec((ROWS_TILE, 1), _row_tile),
        ],
        out_specs=pl.BlockSpec((ROWS_TILE, block), _row_tile),
        out_shape=jax.ShapeDtypeStruct((R, block), out_dtype),
        interpret=interpret,
    )(codes, scales)
