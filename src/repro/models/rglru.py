"""RecurrentGemma / Griffin (arXiv:2402.19427): RG-LRU + local attention, 1:2.

Block pattern (rec, rec, attn) repeats; each temporal block is followed by a
gated MLP.  The recurrent block is:

    x -> RMSNorm -> [ branch_x: Linear -> causal depthwise conv(4) -> RG-LRU ]
                    [ branch_g: Linear -> GeLU                              ]
    out = (branch_x * branch_g) @ W_out

RG-LRU (gates block-diagonal, G blocks; c = 8):
    i_t = sigmoid(Wx y_t + bx)         input gate
    r_t = sigmoid(Wa y_t + ba)         recurrence gate
    log a_t = -c * softplus(Lambda) * r_t
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * y_t)

Training/prefill uses jax.lax.associative_scan (parallel, log-depth);
decode is the O(1)-state single step — with the local-attention window cache
this is why the arch runs long_500k.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import layers as L

F32 = jnp.float32
C_RGLRU = 8.0
GATE_BLOCKS = 16


def template(cfg) -> Dict[str, Any]:
    from repro.models.transformer import (ParamT, _attn_template,
                                          _mlp_template)
    D, W = cfg.d_model, cfg.lru_width or cfg.d_model
    Vp = cfg.padded_vocab
    n_super = cfg.n_layers // len(cfg.block_pattern)
    n_attn = n_super
    n_rec = cfg.n_layers - n_attn
    G = GATE_BLOCKS if W % GATE_BLOCKS == 0 else 1
    rec = {
        "ln1": ParamT((n_rec, D), "ones"),
        "rg_w_x": ParamT((n_rec, D, W)),
        "rg_w_gate": ParamT((n_rec, D, W)),
        "conv_w": ParamT((n_rec, cfg.conv_width, W), fan=cfg.conv_width),
        "conv_b": ParamT((n_rec, W), "zeros"),
        "gate_x_w": ParamT((n_rec, G, W // G, W // G), fan=W // G),
        "gate_x_b": ParamT((n_rec, W), "zeros"),
        "gate_a_w": ParamT((n_rec, G, W // G, W // G), fan=W // G),
        "gate_a_b": ParamT((n_rec, W), "zeros"),
        "lam": ParamT((n_rec, W), "ones"),
        "rg_w_out": ParamT((n_rec, W, D), fan=W),
    }
    rec.update(_mlp_template(cfg, n_rec, gelu=False))
    att = _attn_template(cfg, n_attn, biases=False)
    att.update(_mlp_template(cfg, n_attn, gelu=False))
    return {
        "embed": ParamT((Vp, D), fan=D),
        "final_norm": ParamT((D,), "ones"),
        "lm_head": ParamT((D, Vp)),
        "rec_blocks": rec,
        "attn_blocks": att,
    }


def _block_diag(y, w):
    """y (B,T,W), w (G, W/G, W/G) -> (B,T,W)."""
    B, T, Wd = y.shape
    G = w.shape[0]
    yg = y.reshape(B, T, G, Wd // G)
    return jnp.einsum("btgk,gkl->btgl", yg, w).reshape(B, T, Wd)


def _causal_conv(y, w, b, conv_state=None):
    """Depthwise causal conv width K.  y (B,T,W), w (K,W).
    conv_state (B, K-1, W) holds the previous inputs (decode/prefill carry).
    Returns (out, new_conv_state)."""
    B, T, Wd = y.shape
    K = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((B, K - 1, Wd), y.dtype)
    ext = jnp.concatenate([conv_state.astype(y.dtype), y], axis=1)  # (B,T+K-1,W)
    out = sum(ext[:, i:i + T] * w[i].astype(y.dtype) for i in range(K))
    out = out + b.astype(y.dtype)
    new_state = ext[:, -(K - 1):] if K > 1 else conv_state
    return out, new_state


def rglru(y, p, h_prev):
    """y (B,T,W) f32.  Returns (h (B,T,W), h_last (B,W))."""
    i_g = jax.nn.sigmoid(_block_diag(y, p["gate_x_w"].astype(F32))
                         + p["gate_x_b"].astype(F32))
    r_g = jax.nn.sigmoid(_block_diag(y, p["gate_a_w"].astype(F32))
                         + p["gate_a_b"].astype(F32))
    log_a = -C_RGLRU * jax.nn.softplus(p["lam"].astype(F32)) * r_g
    a = jnp.exp(log_a)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))          # sqrt(1 - a^2)
    b = beta * (i_g * y)

    T = y.shape[1]
    if T == 1:
        h = a[:, 0] * h_prev + b[:, 0]
        return h[:, None], h
    # parallel linear recurrence; fold h_prev in as the first element
    a0 = jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1)
    b0 = jnp.concatenate([h_prev[:, None], b], axis=1)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, hs = jax.lax.associative_scan(combine, (a0, b0), axis=1)
    return hs[:, 1:], hs[:, -1]


def rec_block(cfg, p, x, cache, mode):
    """Returns (out, new_cache {h, conv})."""
    B, T, D = x.shape
    xn = L.rmsnorm(x, p["ln1"])
    yx = jnp.einsum("btd,dw->btw", xn, p["rg_w_x"].astype(xn.dtype))
    gate = jax.nn.gelu(jnp.einsum("btd,dw->btw", xn,
                                  p["rg_w_gate"].astype(xn.dtype)))
    conv_state = None if cache is None else cache["conv"]
    h_prev = (jnp.zeros((B, yx.shape[-1]), F32) if cache is None
              else cache["h"].astype(F32))
    yc, new_conv = _causal_conv(yx, p["conv_w"], p["conv_b"], conv_state)
    h, h_last = rglru(yc.astype(F32), p, h_prev)
    out = jnp.einsum("btw,wd->btd", (h.astype(x.dtype) * gate),
                     p["rg_w_out"].astype(x.dtype))
    new_cache = None
    if cache is not None:
        new_cache = {"h": h_last.astype(cfg.dtype),
                     "conv": new_conv.astype(cfg.dtype)}
    return out, new_cache


def forward(cfg, params, batch, *, mode="train", cache=None, pos=None):
    from repro.models.transformer import (attn_block, lm_logits, mlp_block)
    tokens = batch["tokens"]
    B, T = tokens.shape
    D = cfg.d_model
    x = params["embed"].astype(cfg.dtype)[tokens] * jnp.asarray(
        D ** 0.5, cfg.dtype)

    plen = len(cfg.block_pattern)
    n_super = cfg.n_layers // plen
    n_rec_per = plen - 1
    n_rec_super = n_super * n_rec_per
    trailing = cfg.n_layers - n_super * plen  # trailing rec blocks

    rec = params["rec_blocks"]
    rec_super = jax.tree_util.tree_map(
        lambda a: a[:n_rec_super].reshape(n_super, n_rec_per, *a.shape[1:]), rec)
    rec_tail = jax.tree_util.tree_map(lambda a: a[n_rec_super:], rec)

    def one_rec(h, p_l, c_l):
        a, nc = rec_block(cfg, p_l, h, c_l, mode)
        h = h + a
        m, _, _ = mlp_block(cfg, p_l, h)
        return h + m, nc

    def super_body(carry, xs):
        h = carry
        if cache is None:
            pr, pa = xs
            cr = ca = None
        else:
            (pr, pa), (cr, ca) = xs

        def rec_scan_body(hh, rxs):
            if cache is None:
                p_l, c_l = rxs, None
            else:
                p_l, c_l = rxs
            hh, nc = one_rec(hh, p_l, c_l)
            return hh, nc

        h, ncr = jax.lax.scan(rec_scan_body, h,
                              pr if cache is None else (pr, cr))
        a, nca = attn_block(cfg, pa, h, mode=mode, causal=True, rope=True,
                            window=cfg.local_window, cache=ca, pos=pos)
        h = h + a
        m, _, _ = mlp_block(cfg, pa, h)
        return h + m, (ncr, nca)

    xs = ((rec_super, params["attn_blocks"]) if cache is None
          else ((rec_super, params["attn_blocks"]),
                (jax.tree_util.tree_map(
                    lambda a: a[:n_rec_super].reshape(
                        n_super, n_rec_per, *a.shape[1:]), cache["rec"]),
                 cache["attn"])))
    if n_super > 0:
        x, caches = jax.lax.scan(super_body, x, xs)
    else:
        caches = (None, None)

    # trailing recurrent blocks
    new_tail = None
    if trailing > 0:
        tail_xs = (rec_tail if cache is None
                   else (rec_tail, jax.tree_util.tree_map(
                       lambda a: a[n_rec_super:], cache["rec"])))

        def tail_body(h, rxs):
            if cache is None:
                p_l, c_l = rxs, None
            else:
                p_l, c_l = rxs
            return one_rec(h, p_l, c_l)

        x, new_tail = jax.lax.scan(tail_body, x, tail_xs)

    logits = lm_logits(cfg, params, x)
    new_cache = None
    if cache is not None:
        ncr, nca = caches
        if ncr is not None:
            ncr_flat = jax.tree_util.tree_map(
                lambda a: a.reshape(n_rec_super, *a.shape[2:]), ncr)
        if trailing > 0 and ncr is not None:
            ncr_all = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], 0), ncr_flat, new_tail)
        elif trailing > 0:
            ncr_all = new_tail
        else:
            ncr_all = ncr_flat
        new_cache = {"rec": ncr_all, "attn": nca}
    return logits, new_cache, jnp.float32(0.0)


def init_cache(cfg, B, S, mk):
    D, W = cfg.d_model, cfg.lru_width or cfg.d_model
    plen = len(cfg.block_pattern)
    n_attn = cfg.n_layers // plen
    n_rec = cfg.n_layers - n_attn
    KV, hd = cfg.n_kv_heads, cfg.hd
    Sw = min(S, cfg.local_window)
    return {
        "rec": {"h": mk((n_rec, B, W)),
                "conv": mk((n_rec, B, cfg.conv_width - 1, W))},
        "attn": {"k": mk((n_attn, B, Sw, KV, hd)),
                 "v": mk((n_attn, B, Sw, KV, hd))},
    }
