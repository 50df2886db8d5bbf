"""Shared model primitives: norms, RoPE (plain and YaRN-scaled), attention
(GQA / cross / sliding window / KV-cache), MLPs.  Pure functions over
explicit param dicts."""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    return (x.astype(F32) * jax.lax.rsqrt(var + eps) * scale.astype(F32)).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(F32)
            + bias.astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, positions, inv=None,
               scale: float = 1.0):
    """positions (...,) -> (cos, sin) of shape (..., head_dim//2), from the
    inverse frequencies ``inv`` (plain RoPE's by default), times ``scale``."""
    if inv is None:
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32)
                               / head_dim))
    ang = positions.astype(F32)[..., None] * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor as DeepSeek-V2 writes it: 0.1 mscale ln(s) + 1
    for a context scale s > 1, else 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, *, factor: float,
                  original_max_position_embeddings: int, beta_fast: float,
                  beta_slow: float, **_) -> np.ndarray:
    """(dim//2,) float32 inverse frequencies of YaRN rope
    (DeepseekV2YarnRotaryEmbedding): plain RoPE's ``theta^(-2i/dim)`` for the
    fast dims, the same over ``factor`` for the slow ones, blended by a
    linear ramp between the correction dims at which ``beta_fast`` and
    ``beta_slow`` rotations fit in the original context."""
    orig = original_max_position_embeddings

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def deinterleave(x):
    """Rope pairs (2i, 2i+1) of the last dim -> rotate-half pairs
    (i, i + hd/2), so that :func:`apply_rope` rotates DeepSeek's interleaved
    pairs (q and k take the same permutation: their dot product holds)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def apply_rope(x, cos, sin):
    """x (B, T, H, hd); cos/sin (T, hd//2) or (B, T, hd//2)."""
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    if cos.ndim == 2:  # (T, hd//2) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:              # (B, T, hd//2)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq_len: int, dim: int, offset: int = 0):
    pos = np.arange(offset, offset + seq_len)[:, None]
    div = np.exp(-np.log(10000.0) * np.arange(0, dim, 2) / dim)
    pe = np.zeros((seq_len, dim), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(pe)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q (B,T,H,hd), k (B,S,KV,hd) -> scores (B,KV,G,T,S) with H = KV*G."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd)
    return jnp.einsum("btkgh,bskh->bkgts", qg, k)


def _gqa_out(probs, v):
    """probs (B,KV,G,T,S), v (B,S,KV,hd) -> (B,T,H,hd)."""
    B, KV, G, T, S = probs.shape
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, T, KV * G, -1)


def _mask_bias(T, S, *, causal, window, q_offset, dtype=F32):
    """(T, S) additive bias: 0 allowed, -inf masked.  Query t has absolute
    position q_offset + t; keys have positions 0..S-1."""
    qpos = jnp.arange(T) + q_offset
    kpos = jnp.arange(S)
    ok = jnp.ones((T, S), bool)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        ok &= kpos[None, :] > qpos[:, None] - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(dtype)


def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              kv_len: Optional[jax.Array] = None, q_chunk: int = 1024,
              softmax_scale: Optional[float] = None):
    """GQA dot-product attention with optional causal/sliding-window masking
    and query chunking (keeps the score tensor at chunk x S — the
    memory-sane formulation for 32k prefill).

    q (B,T,H,hd); k (B,S,KV,hd); v (B,S,KV,hd_v).  ``kv_len``: dynamic
    number of valid KV entries (decode with pre-allocated cache).
    ``softmax_scale`` defaults to hd^-1/2.  Returns (B,T,H,hd_v).
    """
    B, T, H, hd = q.shape
    S = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(hd)

    def block(q_blk, off):
        scores = _gqa_scores(q_blk, k).astype(F32) * scale   # (B,KV,G,t,S)
        bias = _mask_bias(q_blk.shape[1], S, causal=causal, window=window,
                          q_offset=off)
        if kv_len is not None:
            valid = (jnp.arange(S) < kv_len)
            bias = bias + jnp.where(valid, 0.0, -jnp.inf)[None, :]
        scores = scores + bias[None, None, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return _gqa_out(probs, v)

    if T <= q_chunk:
        return block(q, q_offset)
    if T % q_chunk:  # largest divisor of T that fits (e.g. whisper's 3000)
        q_chunk = max(d for d in range(1, q_chunk + 1) if T % d == 0)
        if q_chunk == 1:
            return block(q, q_offset)
    nblk = T // q_chunk
    qs = q.reshape(B, nblk, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    offs = q_offset + jnp.arange(nblk) * q_chunk

    # scan over query chunks: one (chunk x S) score tensor live at a time
    _, outs = jax.lax.scan(lambda c, xs: ((), block(xs[0], xs[1])),
                           (), (qs, offs))
    # v's head dim, which latent attention sets apart from q's
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, T, H, -1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("btd,df->btf", x, w_gate.astype(x.dtype))
    u = jnp.einsum("btd,df->btf", x, w_up.astype(x.dtype))
    return jnp.einsum("btf,fd->btd", jax.nn.silu(g) * u, w_down.astype(x.dtype))


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = jnp.einsum("btd,df->btf", x, w_in.astype(x.dtype)) + b_in.astype(x.dtype)
    h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("btf,fd->btd", h, w_out.astype(x.dtype)) + b_out.astype(x.dtype)


# ---------------------------------------------------------------------------
# KV cache helpers
# ---------------------------------------------------------------------------

def cache_update(cache_k, cache_v, k_new, v_new, pos):
    """Write k/v (B, t, KV, hd) at position ``pos`` into (B, S, KV, hd)."""
    ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                      (0, pos, 0, 0))
    return ck, cv
