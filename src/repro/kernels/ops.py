"""jit'd public wrappers around the kernels: padding, reshaping, packing.

``qinf_quantize`` / ``qinf_dequantize`` operate on arbitrary-shaped tensors by
flattening into (R, block) rows (zero-padded), dispatching to either the
Pallas kernel (interpret=True on CPU, compiled on TPU) or the pure-jnp oracle.

``pack_codes`` / ``unpack_codes`` turn int8 sign-magnitude codes into the
dense uint8 wire format actually communicated by the ring-gossip backend:
offset-encode c + 2^{b-1} in (b+1) bits, nibble-packed for b <= 3 and
byte-packed otherwise.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quantize as qk
from repro.kernels import ref as kref
from repro.kernels.ref import wire_bits_per_element  # noqa: F401  (re-export)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas_default() -> bool:
    # interpret-mode Pallas is a parity/debug tool, not a fast path: off
    # TPU the fused wire ops run their pure-jnp oracles (kernels.ref)
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Last-dim blockwise quantization (rank-generic, sharding-preserving).
# This is the math the Pallas kernel implements for (R, block) tiles; the
# distributed code paths use this form because it never flattens a sharded
# tensor (leading dims — node, layer — pass through untouched).
# ---------------------------------------------------------------------------

def blockwise_lastdim(x: jax.Array, *, block: int,
                      dtype=jnp.float32) -> jax.Array:
    """(..., D) -> (..., nb, block) ``dtype``, zero-padded along the last
    axis.

    The exact reshape ``qinf_quantize_lastdim`` quantizes — factored out so
    the bucketed wire path blocks its leaves (and draws stochastic-rounding
    noise of the same shape) bit-for-bit like the per-leaf path.  The
    quantizers compute in f32; a narrower ``dtype`` (the leaf's own) only
    defers that exact upcast to the kernel."""
    if x.ndim == 0:
        x = x[None]
    D = x.shape[-1]
    nb = -(-D // block)
    pad = nb * block - D
    xf = x.astype(dtype)
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return xf.reshape(*x.shape[:-1], nb, block)


@functools.partial(jax.jit, static_argnames=("bits", "block"))
def qinf_quantize_lastdim(x: jax.Array, key: jax.Array, *, bits: int = 2,
                          block: int = 256):
    """Blockwise quantize along the last axis.  Returns (codes int8
    (..., nb, block), scales f32 (..., nb, 1))."""
    xb = blockwise_lastdim(x, block=block)
    u = jax.random.uniform(key, xb.shape, jnp.float32)
    levels = jnp.float32(2 ** (bits - 1))
    maxabs = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    safe = jnp.where(maxabs > 0, maxabs, jnp.float32(1.0))
    mag = jnp.minimum(jnp.floor(levels * jnp.abs(xb) / safe + u), levels)
    codes = (jnp.sign(xb) * mag).astype(jnp.int8)
    scales = (maxabs / levels).astype(jnp.float32)
    return codes, scales


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "block"))
def qinf_dequantize_lastdim(codes: jax.Array, scales: jax.Array, shape,
                            dtype, *, block: int = 256):
    xb = codes.astype(jnp.float32) * scales.astype(jnp.float32)
    D = shape[-1] if shape else 1
    flatlast = xb.reshape(*xb.shape[:-2], xb.shape[-2] * block)
    return flatlast[..., :D].reshape(shape).astype(dtype)


def _rows_for(n: int, block: int) -> int:
    rows = -(-n // block)
    # round rows up to the sublane tile so the pallas grid is exact
    return -(-rows // qk.ROWS_TILE) * qk.ROWS_TILE


@functools.partial(jax.jit, static_argnames=("bits", "block", "use_pallas"))
def qinf_quantize(x: jax.Array, key: jax.Array, *, bits: int = 2,
                  block: int = 256, use_pallas: bool = True):
    """Quantize an arbitrary tensor.  Returns (codes, scales, meta)."""
    n = x.size
    rows = _rows_for(n, block)
    flat = jnp.zeros((rows * block,), jnp.float32).at[:n].set(
        x.reshape(-1).astype(jnp.float32))
    xb = flat.reshape(rows, block)
    ub = jax.random.uniform(key, (rows, block), jnp.float32)
    if use_pallas:
        codes, scales = qk.qinf_quantize_blocks(
            xb, ub, bits=bits, block=block, interpret=_interpret_default())
    else:
        codes, scales = kref.qinf_quantize_blocks_ref(xb, ub, bits)
    meta = {"n": n}
    return codes, scales, meta


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "bits", "block",
                                             "use_pallas"))
def qinf_dequantize(codes: jax.Array, scales: jax.Array, meta, shape, dtype,
                    *, bits: int = 2, block: int = 256, use_pallas: bool = True):
    n = int(np.prod(shape)) if shape else 1
    if use_pallas:
        xb = qk.qinf_dequantize_blocks(
            codes, scales, block=block, out_dtype=jnp.float32,
            interpret=_interpret_default())
    else:
        xb = kref.qinf_dequantize_blocks_ref(codes, scales)
    return xb.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Wire packing: int8 sign-magnitude codes -> dense uint8 payload.
#
# ``pack_codes`` flattens (simple, but a reshape across sharded dims forces
# an all-gather under GSPMD — measured in EXPERIMENTS.md §Perf).
# ``pack_codes_lastdim`` packs PAIRS WITHIN the last (block) axis only:
# (..., nb, block) int8 -> (..., nb, block/2) uint8 — every other dim is
# untouched, so model-axis sharding survives and the ring backend ppermutes
# a genuinely local payload.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bits",))
def pack_codes_lastdim(codes: jax.Array, *, bits: int) -> jax.Array:
    """(..., B) int8 -> (..., B/2) uint8 for bits <= 3; identity-offset
    bytes for larger bits.  B must be even (quantizer blocks are)."""
    offset = jnp.uint8(2 ** (bits - 1))
    u = (codes.astype(jnp.int16) + offset).astype(jnp.uint8)
    if wire_bits_per_element(bits) == 4:
        # pair-reshape on the last axis only (strided slices trip an XLA
        # SPMD partitioner CHECK under partial-manual shard_map)
        pairs = u.reshape(*u.shape[:-1], u.shape[-1] // 2, 2)
        return (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.uint8)
    return u


@functools.partial(jax.jit, static_argnames=("bits",))
def unpack_codes_lastdim(packed: jax.Array, *, bits: int) -> jax.Array:
    offset = jnp.int16(2 ** (bits - 1))
    if wire_bits_per_element(bits) == 4:
        lo = (packed & jnp.uint8(0x0F)).astype(jnp.int16)
        hi = ((packed >> 4) & jnp.uint8(0x0F)).astype(jnp.int16)
        inter = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    else:
        inter = packed.astype(jnp.int16)
    return (inter - offset).astype(jnp.int8)

@functools.partial(jax.jit, static_argnames=("bits",))
def pack_codes(codes: jax.Array, *, bits: int) -> jax.Array:
    """Pack int8 codes in [-2^{b-1}, 2^{b-1}] into uint8 wire format."""
    offset = jnp.uint8(2 ** (bits - 1))
    u = (codes.astype(jnp.int16) + offset).astype(jnp.uint8)  # [0, 2^b]
    flat = u.reshape(-1)
    if wire_bits_per_element(bits) == 4:
        # two codes per byte
        if flat.size % 2:
            flat = jnp.concatenate([flat, jnp.zeros((1,), jnp.uint8)])
        pairs = flat.reshape(-1, 2)
        return (pairs[:, 0] | (pairs[:, 1] << 4)).astype(jnp.uint8)
    return flat


# ---------------------------------------------------------------------------
# Fused wire-path ops (bucketed gossip backend): quantize+pack and
# unpack+dequant+mix as single passes.  On TPU these are the Pallas kernels
# in repro.kernels.quantize; elsewhere the pure-jnp oracles (kernels.ref)
# run directly — interpret-mode Pallas is parity-test-only.
# ---------------------------------------------------------------------------


def _pad_rows(a: jax.Array, rows: int) -> jax.Array:
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))


@functools.partial(jax.jit, static_argnames=("bits", "block", "use_pallas"))
def qinf_quantize_pack(xrows: jax.Array, urows: jax.Array, *, bits: int,
                       block: int, use_pallas=None):
    """Fused quantize + wire-pack of (R, block) rows for any R.

    Returns (packed u8 (R, W), scales f32 (R, 1)) with
    W = packed_width(block, bits).  The Pallas path pads R up to ROWS_TILE
    and slices back — padded rows exist only inside the kernel launch,
    never on the wire."""
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    if not use_pallas:
        return kref.qinf_quantize_pack_blocks_ref(xrows, urows, bits)
    R = xrows.shape[0]
    Rp = -(-R // qk.ROWS_TILE) * qk.ROWS_TILE
    packed, scales = qk.qinf_quantize_pack_blocks(
        _pad_rows(xrows, Rp), _pad_rows(urows, Rp),
        bits=bits, block=block, interpret=_interpret_default())
    return packed[:R], scales[:R]


@functools.partial(jax.jit, static_argnames=("bits", "block", "out_dtype",
                                             "use_pallas"))
def qinf_unpack_dequant_mix(packed: jax.Array, scales: jax.Array,
                            w: jax.Array, *, bits: int, block: int,
                            out_dtype=jnp.float32, use_pallas=None):
    """Fused unpack + dequantize + weighted mix across the (1 + hops)
    received payloads of one bucket group.

    ``packed`` (S, R, W) u8, ``scales`` (S, R, 1) f32, ``w`` (T, S) — sender
    0 is self.  Returns (mix (T, R, block) out_dtype, qself (R, block)
    out_dtype); per-sender dequantized tensors are never materialized in
    HBM on the Pallas path."""
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    if not use_pallas:
        return kref.qinf_unpack_dequant_mix_blocks_ref(
            packed, scales, w, bits, out_dtype)
    R = packed.shape[1]
    Rp = -(-R // qk.ROWS_TILE) * qk.ROWS_TILE
    pad2 = lambda a: jnp.moveaxis(_pad_rows(jnp.moveaxis(a, 1, 0), Rp), 0, 1)
    mix, qself = qk.qinf_unpack_dequant_mix_blocks(
        pad2(packed), pad2(scales), w, bits=bits, block=block,
        out_dtype=out_dtype, interpret=_interpret_default())
    return mix[:, :R], qself[:R]


@functools.partial(jax.jit, static_argnames=("bits", "n"))
def unpack_codes(packed: jax.Array, *, bits: int, n: int) -> jax.Array:
    """Inverse of pack_codes: uint8 wire payload -> int8 codes of length n."""
    offset = jnp.int16(2 ** (bits - 1))
    if wire_bits_per_element(bits) == 4:
        lo = (packed & jnp.uint8(0x0F)).astype(jnp.int16)
        hi = ((packed >> 4) & jnp.uint8(0x0F)).astype(jnp.int16)
        interleaved = jnp.stack([lo, hi], axis=-1).reshape(-1)[:n]
    else:
        interleaved = packed.astype(jnp.int16)[:n]
    return (interleaved - offset).astype(jnp.int8)
