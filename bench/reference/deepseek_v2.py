"""The DeepSeek-V2 kind (bench/kinds.py): a decoder with latent attention
(MLA), YaRN rope, ``first_k_dense_replace`` leading dense layers and then
layers of routed and shared experts, of which this chip holds a share.

Its plain reference follows the published modeling code
(``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite) layer by layer:

  attention  q = x Wq, per head [q_nope (qk_nope_head_dim), q_pe
             (qk_rope_head_dim)]; [c_kv, k_pe] = x Wkv_a (kv_lora_rank +
             qk_rope_head_dim, k_pe one for all heads); [k_nope, v] =
             RMSNorm(c_kv) Wkv_b per head; q_pe and k_pe de-interleaved
             (pairs (2i, 2i+1)) and rotated half by YaRN's cos and sin;
             causal softmax of q k^T times (qk head dim)^-1/2 mscale^2;
             o = softmax v Wo.
  YaRN       inverse frequencies blend theta^(-2i/d) and the same over
             ``factor`` by a linear ramp between the correction dims of
             ``beta_fast`` and ``beta_slow`` at the original length; cos and
             sin times mscale(factor, mscale) / mscale(factor,
             mscale_all_dim).
  experts    router softmax over all the published routed experts, greedy
             top-k, gates not renormalised, times routed_scaling_factor;
             SwiGLU experts of moe_intermediate_size; shared experts one
             SwiGLU of n_shared_experts times that width.
  balance    sequence-level (seq_aux): alpha sum_e (count_e E / (S k))
             mean_s p_se per row, mean over rows, summed over layers.

Departures, each the cut's or the harness's:

- The chip's share of experts: of the router's ``published`` routed
  experts this chip holds ``held_experts`` (first, stop), whose count is
  the configuration's ``n_routed_experts``; a token's slots routed to
  experts held elsewhere add nothing to its output (as in the program).
  Every held expert is evaluated on every token and weighted by the
  token's gate for it, zero where the router did not choose it: the same
  sum as routing, computed densely.
- The vocabulary is the chip's slice, padded to ``padded_vocab_multiple``
  (the padded logits take part in the softmax, as in the program).
- ``loss_and_grad`` returns the mean cross entropy, which the program
  reports as its loss, and the gradient of the cross entropy plus
  ``aux_loss_alpha`` times the balance loss, which the program trains on.

Everything runs in float32 at ``Precision.HIGHEST`` from the parameters as
stored; the gradient is accumulated over the batch one row at a time and
returned in the parameters' dtype."""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.weights import padded_vocab

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "R": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "Fd": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "E": cfg["published"].get("n_routed_experts",
                                      cfg["n_routed_experts"]),
            "Eh": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "Ld": cfg["first_k_dense_replace"],
            "Lm": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}


def _attn_shapes(d: dict, L: int) -> dict:
    D, H = d["D"], d["H"]
    return {"ln1": ((L, D), None),
            "wq": ((L, D, H * (d["nope"] + d["dr"])), D),
            "wkv_a": ((L, D, d["R"] + d["dr"]), D),
            "kv_norm": ((L, d["R"]), None),
            "wkv_b": ((L, d["R"], H * (d["nope"] + d["dv"])), d["R"]),
            "wo": ((L, H * d["dv"], D), H * d["dv"]),
            "ln2": ((L, D), None)}


def param_shapes(cfg: dict) -> Dict[str, object]:
    """``embed`` (Vp, D), ``final_norm`` (D,), ``lm_head`` (D, Vp), the
    leading dense stack ``dense_blocks`` and the expert stack ``blocks``,
    each leaf (shape, fan-in or None)."""
    d = _dims(cfg)
    D, Vp, Ld, Lm = d["D"], padded_vocab(cfg), d["Ld"], d["Lm"]
    dense = _attn_shapes(d, Ld)
    dense.update({"w_gate": ((Ld, D, d["Fd"]), D),
                  "w_up": ((Ld, D, d["Fd"]), D),
                  "w_down": ((Ld, d["Fd"], D), d["Fd"])})
    moe = _attn_shapes(d, Lm)
    moe.update({"router": ((Lm, D, d["E"]), D),
                "experts_gate": ((Lm, d["Eh"], D, d["Fe"]), D),
                "experts_up": ((Lm, d["Eh"], D, d["Fe"]), D),
                "experts_down": ((Lm, d["Eh"], d["Fe"], D), d["Fe"]),
                "shared_gate": ((Lm, D, d["Fs"]), D),
                "shared_up": ((Lm, D, d["Fs"]), D),
                "shared_down": ((Lm, d["Fs"], D), d["Fs"])})
    return {"embed": ((Vp, D), D), "final_norm": ((D,), None),
            "lm_head": ((D, Vp), D), "dense_blocks": dense, "blocks": moe}


def matmul_weights(cfg: dict) -> int:
    """Weights one token multiplies: every layer's attention projections,
    the dense FFN, the router, the shared experts and the held experts'
    share of the k a token is routed to (held / published of them), and
    ``lm_head`` over the vocabulary slice; not the embedding table (a
    gather does no matmul)."""
    d = _dims(cfg)
    D, H = d["D"], d["H"]
    attn = (D * H * (d["nope"] + d["dr"]) + D * (d["R"] + d["dr"])
            + d["R"] * H * (d["nope"] + d["dv"]) + H * d["dv"] * D)
    experts = d["Eh"] * 3 * D * d["Fe"] * d["k"] / d["E"]
    moe = D * d["E"] + 3 * D * d["Fs"] + experts
    total = ((d["Ld"] + d["Lm"]) * attn + d["Ld"] * 3 * D * d["Fd"]
             + d["Lm"] * moe + D * cfg["vocab_size"])
    return int(round(total))


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 x the matmul weights, plus the PaLM paper's attention term with
    q.k over the qk head dim and the value product over v's:
    6 x layers x seq x heads x (qk head dim + v head dim)."""
    d = _dims(cfg)
    attn = (6 * cfg["num_hidden_layers"] * seq_len * d["H"]
            * (d["nope"] + d["dr"] + d["dv"]))
    return 6.0 * matmul_weights(cfg) + attn


def held_expert_flops_per_token(cfg: dict) -> float:
    """The held experts' grouped-matmul FLOPs of one token, forward and
    backward, at the balanced load: k x held / published pairs, 3
    matmuls of 2 D F each, x 3, over the expert layers."""
    d = _dims(cfg)
    pairs = d["k"] * d["Eh"] / d["E"]
    return 3.0 * pairs * 3 * 2 * d["D"] * d["Fe"] * d["Lm"]


def held_range(cfg: dict):
    first, stop = cfg["held_experts"]
    if stop - first != cfg["n_routed_experts"]:
        raise ValueError("held_experts must span n_routed_experts experts")
    return first, stop


def program_params(cfg: dict) -> dict:
    """The program's ModelConfig fields for a configuration file."""
    fixed = {"hidden_act": "silu", "tie_word_embeddings": False,
             "q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "norm_topk_prob": False,
             "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
             "attention_bias": False, "seq_aux": True,
             "routed_scaling_factor": 1,
             "rms_norm_eps": 1e-6, "padded_vocab_multiple": 256}
    off = [k for k, v in fixed.items() if cfg.get(k) != v]
    if off or cfg["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"the program's DeepSeek-V2 decoder runs "
                         f"{fixed} with yarn rope; the configuration "
                         f"differs in {off or ['rope_scaling.type']}")
    d = _dims(cfg)
    rope = {k: v for k, v in cfg["rope_scaling"].items() if k != "type"}
    return {"n_layers": cfg["num_hidden_layers"], "d_model": d["D"],
            "n_heads": d["H"], "n_kv_heads": d["H"],
            "head_dim": d["nope"] + d["dr"], "d_ff": d["Fd"],
            "vocab": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
            "kv_lora_rank": d["R"], "qk_rope_head_dim": d["dr"],
            "v_head_dim": d["dv"], "rope_scaling": rope,
            "first_k_dense": d["Ld"], "n_experts": d["E"],
            "top_k": d["k"], "n_shared_experts": cfg["n_shared_experts"],
            "moe_d_ff": d["Fe"], "held_experts": held_range(cfg),
            "aux_loss_alpha": float(cfg["aux_loss_alpha"]),
            "dtype": cfg["torch_dtype"]}


# ------------------------------------------------------------ the reference
def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(F32))


def _mm(x, w):
    """x (..., k) float32 times w (k, n) as stored: the weight is widened
    inside the product, never copied out in float32."""
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               precision=HI, preferred_element_type=F32)


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg: dict, S: int):
    """(cos, sin) (S, qk_rope_head_dim) of YaRN rope, and the softmax
    scale, from the configuration's ``rope_scaling``."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    orig, factor = rs["original_max_position_embeddings"], rs["factor"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=F32)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    inv = (extra / factor) * ramp + extra * (1.0 - ramp)
    freqs = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    m = (_mscale(factor, rs["mscale"])
         / _mscale(factor, rs["mscale_all_dim"]))
    qk = cfg["qk_nope_head_dim"] + dim
    softmax_scale = qk ** -0.5 * _mscale(factor, rs["mscale_all_dim"]) ** 2
    return jnp.cos(emb) * m, jnp.sin(emb) * m, softmax_scale


def _rope(x, cos, sin):
    """x (S, h, d): de-interleave the pairs (2i, 2i+1), then rotate half."""
    S, h, d = x.shape
    x = x.reshape(S, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(S, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def mla(cfg, x, p, rope):
    """Latent attention over one row: x (S, D) float32, normed."""
    S = x.shape[0]
    d = _dims(cfg)
    H, nope, dr, dv, R = d["H"], d["nope"], d["dr"], d["dv"], d["R"]
    cos, sin, scale = rope
    q = _mm(x, p["wq"]).reshape(S, H, nope + dr)
    c = _mm(x, p["wkv_a"])
    c_kv, k_pe = c[:, :R], c[:, R:].reshape(S, 1, dr)
    kv = _mm(_rms(c_kv, p["kv_norm"], cfg["rms_norm_eps"]),
             p["wkv_b"]).reshape(S, H, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k_pe = jnp.broadcast_to(_rope(k_pe, cos, sin), (S, H, dr))
    k = jnp.concatenate([k_nope, k_pe], -1)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HI) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(S, H * dv)
    return _mm(a, p["wo"])


def _swiglu(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


def route(cfg, x, router):
    """Router over one row: (probs (S, E), gates (S, k), experts (S, k))."""
    probs = jax.nn.softmax(_mm(x, router), -1)
    gate, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    return probs, gate * cfg["routed_scaling_factor"], idx


def experts(cfg, x, p):
    """The expert layer over one row: (routed + shared output (S, D), the
    row's balance loss, unweighted)."""
    d = _dims(cfg)
    S, E, k = x.shape[0], d["E"], d["k"]
    first, _ = held_range(cfg)
    probs, gate, idx = route(cfg, x, p["router"])
    held = first + jnp.arange(d["Eh"])
    weight = jnp.sum(gate[:, :, None] * (idx[:, :, None] == held), 1)
    outs = jax.vmap(lambda wg, wu, wd: _swiglu(x, wg, wu, wd))(
        p["experts_gate"], p["experts_up"], p["experts_down"])  # (Eh, S, D)
    routed = jnp.einsum("esd,se->sd", outs, weight, precision=HI)
    shared = _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    count = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), (0, 1))
    balance = jnp.sum(count * E / (S * k) * jnp.mean(probs, 0))
    return routed + shared, balance


def _layer(cfg, x, p, rope, ffn):
    eps = cfg["rms_norm_eps"]
    x = x + mla(cfg, _rms(x, p["ln1"], eps), p, rope)
    out, balance = ffn(_rms(x, p["ln2"], eps), p)
    return x + out, balance


def _dense_ffn(cfg):
    return lambda h, p: (_swiglu(h, p["w_gate"], p["w_up"], p["w_down"]),
                         jnp.zeros((), F32))


def row_losses(cfg, params, tokens, labels):
    """One row's (summed cross entropy, balance loss summed over the
    expert layers): tokens, labels (S,)."""
    rope = yarn(cfg, tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    total = jnp.zeros((), F32)
    for name, ffn in (("dense_blocks", _dense_ffn(cfg)),
                      ("blocks", lambda h, p: experts(cfg, h, p))):
        x, bal = jax.lax.scan(lambda c, p: _layer(cfg, c, p, rope, ffn), x,
                              params[name])
        total = total + jnp.sum(bal)
    logits = _mm(_rms(x, params["final_norm"], cfg["rms_norm_eps"]),
                 params["lm_head"])
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - tgt), total


def loss_and_grad(cfg, params, tokens, labels):
    """Mean cross entropy over tokens, labels (B, S), and the gradient of
    it plus ``aux_loss_alpha`` times the balance loss (the mean over rows),
    in the parameters' dtype."""
    n, B = tokens.size, tokens.shape[0]
    alpha = cfg["aux_loss_alpha"]

    def objective(p, t, l):
        ce, bal = row_losses(cfg, p, t, l)
        return ce / n + alpha * bal / B, ce

    g0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, F32), params)
    vg = jax.value_and_grad(objective, has_aux=True)

    def body(carry, row):
        tot, g = carry
        (_, ce), gr = vg(params, *row)
        return (tot + ce, jax.tree_util.tree_map(
            lambda a, b: a + b.astype(F32), g, gr)), None

    (tot, g), _ = jax.lax.scan(body, (jnp.zeros((), F32), g0),
                               (tokens, labels))
    grads = jax.tree_util.tree_map(lambda a, p: a.astype(p.dtype), g,
                                   params)
    return tot / n, grads
