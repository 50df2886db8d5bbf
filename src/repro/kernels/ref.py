"""Pure-jnp oracles for the kernels in this package.

These are the ground truth the Pallas kernels are validated against
(tests sweep shapes/dtypes/bits and assert_allclose).  On CPU (no TPU
backend) the fused wire-path ops in :mod:`repro.kernels.ops` dispatch to
these oracles directly — interpret-mode Pallas is for parity tests only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wire_bits_per_element(bits: int) -> int:
    """(b+1)-bit offset codes, rounded up to nibble/byte packing."""
    raw = bits + 1
    if raw <= 4:
        return 4
    return 8


def qinf_quantize_blocks_ref(xb: jnp.ndarray, ub: jnp.ndarray, bits: int):
    """Quantize rows of ``xb`` (R, B): one quantization block per row.

    Paper eq. (21) with inf-norm scaling:
        code  = sign(x) * floor(2^{b-1} |x| / ||x||_inf + u)
        scale = ||x||_inf / 2^{b-1}
        Q(x)  = code * scale

    Returns (codes int8 (R, B), scales f32 (R, 1)).  All-zero blocks give
    scale 0 and codes 0.  ``ub`` is U[0,1) noise of the same shape.
    """
    xf = xb.astype(jnp.float32)
    levels = jnp.float32(2 ** (bits - 1))
    maxabs = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    safe = jnp.where(maxabs > 0, maxabs, jnp.float32(1.0))
    mag = jnp.floor(levels * jnp.abs(xf) / safe + ub.astype(jnp.float32))
    mag = jnp.minimum(mag, levels)  # guard u==1.0-eps edge
    codes = (jnp.sign(xf) * mag).astype(jnp.int8)
    scales = (maxabs / levels).astype(jnp.float32)
    return codes, scales


def qinf_dequantize_blocks_ref(codes: jnp.ndarray, scales: jnp.ndarray,
                               out_dtype=jnp.float32):
    """Inverse of :func:`qinf_quantize_blocks_ref`: codes (R,B) * scales (R,1)."""
    return (codes.astype(jnp.float32) * scales.astype(jnp.float32)).astype(out_dtype)


# ---------------------------------------------------------------------------
# Fused wire-path oracles (bucketed gossip backend).
#
# Wire format: offset-encode c + 2^{b-1} into (b+1) bits; for b <= 3 two
# codes share a byte in HALVES order — byte k of a block packs code k (low
# nibble) with code k + B/2 (high nibble).  Halves packing only ever slices
# contiguous runs of the lane axis, so the TPU kernel needs neither strided
# access nor an in-kernel reshape (pairs-adjacent packing, as
# ``ops.pack_codes_lastdim`` uses, would).  The two layouts differ on the
# wire but pack/unpack round-trips are exact either way, and only the
# round-trip enters the update math.
# ---------------------------------------------------------------------------


def pack_codes_halves_ref(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(..., B) int codes -> (..., B/2) uint8 for bits <= 3 (halves order);
    plain offset bytes otherwise."""
    enc = codes.astype(jnp.int32) + 2 ** (bits - 1)
    if wire_bits_per_element(bits) == 4:
        half = enc.shape[-1] // 2
        return (enc[..., :half] | (enc[..., half:] << 4)).astype(jnp.uint8)
    return enc.astype(jnp.uint8)


def unpack_codes_halves_ref(packed: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of :func:`pack_codes_halves_ref` -> int8 codes (..., B)."""
    offset = jnp.int32(2 ** (bits - 1))
    p = packed.astype(jnp.int32)
    if wire_bits_per_element(bits) == 4:
        lo = (p & 0x0F) - offset
        hi = ((p >> 4) & 0x0F) - offset
        codes = jnp.concatenate([lo, hi], axis=-1)
    else:
        codes = p - offset
    return codes.astype(jnp.int8)


def qinf_quantize_pack_blocks_ref(xb: jnp.ndarray, ub: jnp.ndarray,
                                  bits: int):
    """Fused quantize + wire-pack: (R, B) rows -> (packed uint8 (R, W),
    scales f32 (R, 1)) with W = B/2 for bits <= 3 else B.  No int8 code
    intermediate ever reaches HBM in the Pallas twin."""
    codes, scales = qinf_quantize_blocks_ref(xb, ub, bits)
    return pack_codes_halves_ref(codes, bits), scales


def split_hi_lo(x: jnp.ndarray):
    """f32 ``x`` -> (hi, lo), hi + lo == x exactly, each part of at most
    12 significant bits (hi clears x's 12 low mantissa bits), so the
    product of two parts is exact in f32.  Made from x's bits, never from
    x itself: lo is (sign, exponent, low bits) minus (sign, exponent),
    an exact difference of two bit patterns that no compiler can fuse
    with the multiply that produced x."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    f = lambda b: jax.lax.bitcast_convert_type(b, jnp.float32)
    e = u & jnp.uint32(0xFF800000)
    return (f(u & jnp.uint32(0xFFFFF000)),
            f(e | (u & jnp.uint32(0x00000FFF))) - f(e))


def payload_parts(q: jnp.ndarray, dtype):
    """A sender's dequantized payload (f32 values of leaf ``dtype``) as
    the parts :func:`mix_sum` multiplies: whole when ``dtype`` carries at
    most 12 significant bits (bf16: 8), else :func:`split_hi_lo`."""
    if jnp.finfo(dtype).nmant + 1 <= 12:
        return (q,)
    return split_hi_lo(q)


def mix_sum(w_hi, w_lo, q_parts):
    """sum_s (w_hi[s] + w_lo[s]) * sum(q_parts[s]), added up in a fixed
    order of EXACT partial products (every part has <= 12 significant
    bits).  Each add rounds once, and a multiply contracted into the next
    add (an FMA) rounds no differently — so any compiler, and the Pallas
    mix kernel, gives the same bits.  ``w_hi``/``w_lo`` index to scalars
    (or broadcastable arrays).

    The order runs over senders innermost, so the first add joins two
    senders' products, which share no operand: XLA:TPU rewrites
    ``a*c + b*c`` into ``(a + b)*c`` (rounded, not exact), and a sender's
    hi and lo products would be such a pair."""
    terms = [wk[s] * parts[k]
             for wk in (w_hi, w_lo)
             for k in range(len(q_parts[0]))
             for s, parts in enumerate(q_parts)]
    acc = terms[0]
    for term in terms[1:]:
        acc = acc + term
    return acc


def weighted_mix_ref(w: jnp.ndarray, q, dtype) -> jnp.ndarray:
    """out[t] = sum_s w[t, s] * q[s] in f32 via :func:`mix_sum`.

    Not a dot: a dot leaves its accumulation to the compiler, and on a
    TPU v5e XLA turns a one-round (1, S) dot into a multiply and a reduce
    while Mosaic runs it through the MXU in bf16 passes — they differ in
    the last ulp for weights such as 1/3.  Nor a plain multiply-add
    chain: XLA's CPU backend contracts a product into the following add
    (FMA) and picks which of two products to fuse by the loop's shape.
    ``w`` (T, S); ``q`` (S, ...) or a sequence of S arrays holding values
    of leaf ``dtype`` -> (T, ...) f32.
    """
    w_hi, w_lo = split_hi_lo(w.astype(jnp.float32))
    parts = [payload_parts(q[s].astype(jnp.float32), dtype)
             for s in range(w.shape[1])]
    return jnp.stack([mix_sum(w_hi[t], w_lo[t], parts)
                      for t in range(w.shape[0])])


def qinf_unpack_dequant_mix_blocks_ref(packed: jnp.ndarray,
                                       scales: jnp.ndarray,
                                       w: jnp.ndarray, bits: int,
                                       out_dtype=jnp.float32):
    """Fused unpack + dequantize + weighted mix across senders.

    ``packed``: (S, R, W) uint8 — sender 0 is self, then one per hop.
    ``scales``: (S, R, 1) f32.  ``w``: (T, S) receiver weights per schedule
    round.  Returns (mix (T, R, B) out_dtype, qself (R, B) out_dtype) where
    mix[t] = sum_s w[t, s] * Q_s.  Each Q_s rounds through ``out_dtype``
    before the f32 accumulation — exactly what the per-leaf path does when
    it dequantizes whole leaves — so the two wire modes agree bit for bit.
    """
    codes = unpack_codes_halves_ref(packed, bits).astype(jnp.float32)
    q = codes * scales.astype(jnp.float32)            # (S, R, B)
    q = q.astype(out_dtype).astype(jnp.float32)
    mix = weighted_mix_ref(w, q, out_dtype)
    return mix.astype(out_dtype), q[0].astype(out_dtype)
