"""Pallas quantization kernel vs pure-jnp oracle: shape/dtype/bit sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # optional dep: fall back to
    from tests._hypothesis_compat import (  # deterministic shim
        given, settings, strategies as st)

from repro.kernels import ops as kops
from repro.kernels import quantize as qk
from repro.kernels import ref as kref


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 7])
@pytest.mark.parametrize("rows", [8, 16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float64])
def test_kernel_matches_ref_blocks(bits, rows, dtype):
    x = (jax.random.normal(jax.random.key(0), (rows, 256)) * 3).astype(dtype)
    u = jax.random.uniform(jax.random.key(1), (rows, 256), jnp.float32)
    ck, sk = qk.qinf_quantize_blocks(x, u, bits=bits, block=256, interpret=True)
    cr, sr = kref.qinf_quantize_blocks_ref(x, u, bits)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    # dequant kernel vs ref
    dk = qk.qinf_dequantize_blocks(ck, sk, block=256, interpret=True)
    dr = kref.qinf_dequantize_blocks_ref(cr, sr)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), rtol=1e-6)


@pytest.mark.parametrize("shape", [(5,), (1000,), (3, 7, 11), (256,), (2, 256),
                                   (8, 256), (129,)])
@pytest.mark.parametrize("bits", [2, 4])
def test_ops_wrapper_pallas_vs_ref(shape, bits):
    x = jax.random.normal(jax.random.key(0), shape) * 2
    key = jax.random.key(1)
    cp, sp, mp = kops.qinf_quantize(x, key, bits=bits, use_pallas=True)
    cr, sr, mr = kops.qinf_quantize(x, key, bits=bits, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(cr))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), rtol=1e-6)
    outp = kops.qinf_dequantize(cp, sp, mp, shape, jnp.float32, bits=bits)
    outr = kops.qinf_dequantize(cr, sr, mr, shape, jnp.float32, bits=bits,
                                use_pallas=False)
    np.testing.assert_allclose(np.asarray(outp), np.asarray(outr), rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 2000), st.integers(1, 7), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_roundtrip(n, bits, seed):
    lim = 2 ** (bits - 1)
    codes = jax.random.randint(jax.random.key(seed), (n,), -lim, lim + 1,
                               dtype=jnp.int32).astype(jnp.int8)
    packed = kops.pack_codes(codes, bits=bits)
    un = kops.unpack_codes(packed, bits=bits, n=n)
    np.testing.assert_array_equal(np.asarray(un), np.asarray(codes))
    # wire size: nibble for <=3 bits, byte otherwise
    per = kops.wire_bits_per_element(bits)
    assert packed.size == -(-n * per // 8)


def test_code_range_and_scale_semantics():
    bits = 3
    x = jnp.linspace(-4, 4, 256).reshape(1, 256).repeat(8, 0)
    u = jnp.zeros((8, 256))
    c, s = qk.qinf_quantize_blocks(x, u, bits=bits, block=256, interpret=True)
    lim = 2 ** (bits - 1)
    assert int(jnp.abs(c.astype(jnp.int32)).max()) <= lim
    # scale * lim == maxabs
    np.testing.assert_allclose(float(s[0, 0] * lim), 4.0, rtol=1e-6)


def test_padding_blocks_are_zero():
    # 300 elements -> 2 blocks of 256 with padding; padded tail must decode to 0
    x = jnp.ones((300,))
    c, s, m = kops.qinf_quantize(x, jax.random.key(0), bits=2)
    out = kops.qinf_dequantize(c, s, m, (300,), jnp.float32, bits=2)
    np.testing.assert_allclose(np.asarray(out), np.ones(300), atol=1e-6)


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 7])
@pytest.mark.parametrize("shape", [(4, 6, 8), (2, 256), (16,)])
def test_pack_lastdim_roundtrip(bits, shape):
    lim = 2 ** (bits - 1)
    codes = jax.random.randint(jax.random.key(0), shape, -lim, lim + 1,
                               dtype=jnp.int32).astype(jnp.int8)
    packed = kops.pack_codes_lastdim(codes, bits=bits)
    un = kops.unpack_codes_lastdim(packed, bits=bits)
    np.testing.assert_array_equal(np.asarray(un), np.asarray(codes))
    if kops.wire_bits_per_element(bits) == 4:
        assert packed.shape == shape[:-1] + (shape[-1] // 2,)


@pytest.mark.parametrize("block", [2, 48, 88, 128, 256])
def test_lastdim_quantize_any_block(block):
    """Shard-aligned block sizes (§Perf it4) are still valid quantizers."""
    x = jax.random.normal(jax.random.key(0), (3, 1408)) * 2
    codes, scales = kops.qinf_quantize_lastdim(x, jax.random.key(1), bits=2,
                                               block=block)
    out = kops.qinf_dequantize_lastdim(codes, scales, x.shape, x.dtype,
                                       block=block)
    nb = -(-1408 // block)
    assert codes.shape == (3, nb, block)
    # elementwise error bounded by the per-block scale
    pad = jnp.zeros((3, nb * block)).at[:, :1408].set(x).reshape(3, nb, block)
    bound = jnp.max(jnp.abs(pad), axis=-1, keepdims=True) / 2.0
    outp = jnp.zeros((3, nb * block)).at[:, :1408].set(out).reshape(3, nb, block)
    assert (jnp.abs(outp - pad) <= bound + 1e-5).all()


def test_split_hi_lo_exact_parts():
    """hi + lo == x bit for bit, each part has <= 12 significant bits, and
    the product of two parts is exact (checked in f64)."""
    x = jnp.concatenate([
        jax.random.normal(jax.random.key(0), (4096,), jnp.float32) * 3,
        jnp.asarray([0.0, -0.0, 1 / 3, -2 / 3, 1.0, 1e-30, -7.5e20],
                    jnp.float32)])
    hi, lo = kref.split_hi_lo(x)
    np.testing.assert_array_equal(np.asarray(hi + lo), np.asarray(x))
    for part in (hi, lo):
        m, _ = np.frexp(np.asarray(part, np.float64))
        assert np.all(m * 2 ** 12 == np.round(m * 2 ** 12))
    y = np.asarray(jax.random.normal(jax.random.key(1), x.shape, jnp.float32))
    yh, yl = (np.asarray(p) for p in kref.split_hi_lo(jnp.asarray(y)))
    for a in (hi, lo):
        for b in (yh, yl):
            a64 = np.asarray(a, np.float64)
            np.testing.assert_array_equal(
                (np.asarray(a) * b).astype(np.float64), a64 * b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_mix_same_bits_in_any_view(dtype):
    """The mix of a (S, R, B) stack equals, bit for bit, the mix of its
    (S, R * B) view and of its per-sender list under jit — the bucketed
    and per-leaf wire paths mix differently shaped views of one payload —
    and stays within f32 rounding of the f64 sum."""
    S, T = 3, 2
    q = (jax.random.normal(jax.random.key(0), (S, 24, 104),
                           jnp.float32) * 2).astype(
        dtype).astype(jnp.float32)
    w = jnp.asarray([[1 / 3, 1 / 3, 1 / 3], [0.4, 0.1, 0.5]], jnp.float32)
    mix = jax.jit(lambda w, q: kref.weighted_mix_ref(w, q, dtype))
    a = mix(w, q)
    b = mix(w, q.reshape(S, -1)).reshape(a.shape)
    c = jax.jit(lambda w, *qs: kref.weighted_mix_ref(w, qs, dtype))(w, *q)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    want = np.tensordot(np.asarray(w, np.float64), np.asarray(q, np.float64),
                        axes=(1, 0))
    np.testing.assert_allclose(np.asarray(a), want, rtol=1e-6, atol=1e-6)


# row counts of the quantize+pack kernel, as functions of its row tile's cap:
# under the cap (a single block the size of R), at it, and past it with a
# ragged last block
PACK_ROWS = {"8": lambda cap: 8, "16": lambda cap: 16, "40": lambda cap: 40,
             "cap": lambda cap: cap, "cap+8": lambda cap: cap + 8,
             "2cap+24": lambda cap: 2 * cap + 24}


@pytest.mark.parametrize("rows", list(PACK_ROWS))
@pytest.mark.parametrize("block", [32, 64, 128, 256])
@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_quantize_pack_tiles_match_ref(rows, block, bits, dtype):
    """The quantize+pack kernel at its row tiles gives the oracle's packed
    codes and scales bit for bit, for R below, at and past the cap."""
    cap = qk.pack_rows_tile(2 ** 30, block, dtype)
    assert cap % 128 == 0
    R = PACK_ROWS[rows](cap)
    assert qk.pack_rows_tile(R, block, dtype) == min(R, cap)
    x = (jax.random.normal(jax.random.key(R), (R, block)) * 3).astype(dtype)
    u = jax.random.uniform(jax.random.key(1), (R, block), jnp.float32)
    pk, sk = qk.qinf_quantize_pack_blocks(x, u, bits=bits, block=block,
                                          interpret=True)
    pr, sr = kref.qinf_quantize_pack_blocks_ref(x, u, bits)
    assert pk.shape == (R, qk.packed_width(block, bits)) and sk.shape == (R, 1)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
