"""The dense decoder kind (bench/kinds.py): a llama-style decoder's
parameter shapes, model FLOPs, the program's fields for it, and its plain
reference loss and gradient.

Pre-norm RMSNorm blocks; grouped-query attention with RoPE (rotate-half
form, positions 0..S-1) and optional RMSNorm on each q and k head (Qwen3);
SwiGLU MLP; final RMSNorm and an untied ``lm_head``; mean next-token cross
entropy over the padded vocabulary.  Everything runs in float32 at
``Precision.HIGHEST`` from the parameters as stored; the gradient is
accumulated over the batch one row at a time and returned in the
parameters' dtype."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.weights import padded_vocab

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def param_shapes(cfg: dict) -> Dict[str, object]:
    """``embed`` (Vp, D), ``final_norm`` (D,), ``lm_head`` (D, Vp) and the
    layer-stacked ``blocks``, each leaf (shape, fan-in or None)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    L, Vp = cfg["num_hidden_layers"], padded_vocab(cfg)
    blocks = {
        "ln1": ((L, D), None),
        "wq": ((L, D, H * hd), D),
        "wk": ((L, D, KV * hd), D),
        "wv": ((L, D, KV * hd), D),
        "wo": ((L, H * hd, D), H * hd),
        "ln2": ((L, D), None),
        "w_gate": ((L, D, F), D),
        "w_up": ((L, D, F), D),
        "w_down": ((L, F, D), F),
    }
    if cfg.get("qk_norm"):
        blocks["q_norm"] = ((L, hd), None)
        blocks["k_norm"] = ((L, hd), None)
    return {"embed": ((Vp, D), D), "final_norm": ((D,), None),
            "lm_head": ((D, Vp), D), "blocks": blocks}


def matmul_weights(cfg: dict) -> int:
    """Every weight matrix but the embedding table (a gather does no
    matmul); ``lm_head`` counts, over the published vocabulary."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return cfg["num_hidden_layers"] * per_layer + D * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """The PaLM paper's appendix B: 6 x the matmul weights, plus 12 x
    layers x seq x heads x head_dim for attention."""
    attn = (12 * cfg["num_hidden_layers"] * seq_len
            * cfg["num_attention_heads"] * cfg["head_dim"])
    return 6.0 * matmul_weights(cfg) + attn


def program_params(cfg: dict) -> dict:
    """The program's ModelConfig fields for a configuration file."""
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("the program's dense decoder is SwiGLU with an "
                         "untied lm_head")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["padded_vocab_multiple"] != 256:
        raise ValueError("the program's RMSNorm eps is 1e-6 and it pads the "
                         "vocabulary to a multiple of 256")
    return {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "dtype": cfg["torch_dtype"]}


def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(F32))


def _mm(x, w):
    """x (..., k) float32 times w (k, n) as stored: the weight is widened
    inside the product, never copied out in float32."""
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               precision=HI, preferred_element_type=F32)


def _rope(x, theta):
    """x (S, H, hd) -> rotated, rotate-half pairs (i, i + hd/2)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, x, p):
    """One block over one row: x (S, D) float32."""
    S = x.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, p["wq"]).reshape(S, H, hd)
    k = _mm(h, p["wk"]).reshape(S, KV, hd)
    v = _mm(h, p["wv"]).reshape(S, KV, hd)
    if "q_norm" in p:
        q = _rms(q, p["q_norm"], eps)
        k = _rms(k, p["k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    G = H // KV
    qg = q.reshape(S, KV, G, hd)
    s = jnp.einsum("skgh,tkh->kgst", qg, k, precision=HI) / jnp.sqrt(
        jnp.asarray(hd, F32))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("kgst,tkh->skgh", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(S, H * hd)
    x = x + _mm(a, p["wo"])
    h = _rms(x, p["ln2"], eps)
    return x + _mm(jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"]),
                   p["w_down"])


def row_loss_sum(cfg, params, tokens, labels):
    """Summed cross entropy of one row: tokens, labels (S,)."""
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda c, p: (_layer(cfg, c, p), None), x,
                        params["blocks"])
    logits = _mm(_rms(x, params["final_norm"], cfg["rms_norm_eps"]),
                 params["lm_head"])
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - tgt)


def loss_and_grad(cfg, params, tokens, labels):
    """Mean cross entropy over tokens, labels (B, S), and its gradient in
    the parameters' dtype."""
    n = tokens.size
    g0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, F32), params)
    vg = jax.value_and_grad(lambda p, t, l: row_loss_sum(cfg, p, t, l))

    def body(carry, row):
        tot, g = carry
        val, gr = vg(params, *row)
        return (tot + val, jax.tree_util.tree_map(
            lambda a, b: a + b.astype(F32), g, gr)), None

    (tot, g), _ = jax.lax.scan(body, (jnp.zeros((), F32), g0),
                               (tokens, labels))
    grads = jax.tree_util.tree_map(lambda a, p: (a / n).astype(p.dtype),
                                   g, params)
    return tot / n, grads
