"""Model zoo: config, parameters, and forwards for the 6 assigned families.

Families
  dense  — llama-style decoder (GQA, RoPE, SwiGLU; qk_norm / qkv-bias / SWA
           variants cover qwen3, qwen2, phi4, yi)
  moe    — dense skeleton with MoE FF layers: capacity dispatch over every
           expert (mixtral, deepseek-moe), or the dropless layer of a node
           that holds a range of the routed experts, after a leading dense
           stack and with latent attention (deepseek-v2-lite)
  vlm    — dense skeleton with gated cross-attention layers every k-th layer
           (llama-3.2-vision); vision embeddings arrive pre-projected (stub)
  encdec — whisper: encoder (full attn) + decoder (causal self + cross);
           conv/mel frontend is stubbed, frames arrive as embeddings
  ssm    — rwkv6 (repro.models.rwkv6)
  hybrid — recurrentgemma (repro.models.rglru)

Layers are stacked on a leading L dim and executed with lax.scan so the HLO
stays compact for 100-layer configs; a decoder with ``first_k_dense``
leading dense layers keeps them in a stack of their own (``dense_blocks``),
scanned before ``blocks``.  Everything is a pure function over an explicit
param dict; init/abstract params share one template.

Latent attention (MLA, ``kv_lora_rank > 0``; DeepSeek-V2): q = x Wq per
head, split into a ``hd - qk_rope_head_dim`` no-position part and a rope
part; c = x Wkv_a, split into the latent ``c_kv`` and one rope key ``k_pe``
shared by the heads; [k_nope, v] = RMSNorm(c_kv) Wkv_b per head; rope
(YaRN-scaled where ``rope_scaling`` says so, on DeepSeek's interleaved
pairs) on q_pe and k_pe; softmax scale hd^-1/2 times YaRN's mscale squared.
The block runs under the named scope ``mla``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.obs.trace import MLA

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | vlm | encdec | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0
    # attention variants
    rope_theta: float = 1e4
    qk_norm: bool = False
    qkv_bias: bool = False
    sliding_window: Optional[int] = None
    # latent attention (MLA) when kv_lora_rank > 0: head_dim is q/k's
    # (no-position + rope parts), v_head_dim v's
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope: {factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim}, as the published config gives it
    rope_scaling: Optional[Dict[str, float]] = None
    first_k_dense: int = 0       # leading layers with a dense FF (d_ff)
    # moe
    n_experts: int = 0           # routed experts the router scores
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_d_ff: int = 0            # expert width where d_ff is the dense one
    # [first, stop) of the routed experts this node holds: the dropless
    # held-expert layer; None: capacity dispatch over all of them
    held_experts: Optional[Tuple[int, int]] = None
    # weight of the model's own balance loss; None: the trainer's
    # aux_weight
    aux_loss_alpha: Optional[float] = None
    # vlm
    cross_attn_every: int = 0    # every k-th layer is cross-attention
    n_vision_tokens: int = 1601
    # encdec
    n_enc_layers: int = 0
    max_source_positions: int = 1500
    max_target_positions: int = 448
    # serving: cap the decode self-cache at this many positions (ring
    # buffer).  For whisper the decoder grammar never exceeds
    # max_target_positions, so a 32k cache is pure waste (§Perf pair C).
    decode_cache_cap: Optional[int] = None
    # hybrid (recurrentgemma)
    block_pattern: Tuple[str, ...] = ()
    lru_width: int = 0
    conv_width: int = 4
    local_window: int = 2048
    # rwkv
    rwkv_head_size: int = 64
    # misc
    norm: str = "rmsnorm"
    act: str = "swiglu"
    dtype: Any = jnp.float32
    citation: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def v_hd(self) -> int:
        return self.v_head_dim or self.hd

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    @property
    def sub_quadratic(self) -> bool:
        return (self.family in ("ssm", "hybrid")
                or self.sliding_window is not None)

    def reduced(self, n_layers=2, d_model=256, n_experts=4) -> "ModelConfig":
        """Smoke-test variant: same family/wiring, tiny dims."""
        hd = 32
        heads = max(2, d_model // 64)
        kv = max(1, min(self.n_kv_heads, heads) * heads // self.n_heads) \
            if self.n_heads else 1
        kw: Dict[str, Any] = dict(
            name=self.name + "-smoke", n_layers=n_layers, d_model=d_model,
            n_heads=heads, n_kv_heads=max(1, kv), head_dim=hd,
            d_ff=d_model * 2, vocab=512,
        )
        if self.family == "moe":
            kw.update(n_experts=min(n_experts, self.n_experts),
                      top_k=min(self.top_k, 2),
                      n_shared_experts=min(self.n_shared_experts, 1))
            if self.moe_d_ff:
                kw.update(moe_d_ff=d_model)
            if self.held_experts is not None:
                kw.update(held_experts=(0, kw["n_experts"]))
        if self.kv_lora_rank:
            kw.update(kv_lora_rank=64, qk_rope_head_dim=16, v_head_dim=16,
                      n_kv_heads=heads)
        if self.first_k_dense:
            kw.update(first_k_dense=1, n_layers=max(n_layers, 2))
        if self.family == "vlm":
            kw.update(cross_attn_every=2, n_vision_tokens=8)
        if self.family == "encdec":
            kw.update(n_enc_layers=n_layers, max_source_positions=64,
                      max_target_positions=64)
        if self.family == "hybrid":
            kw.update(n_layers=max(n_layers, 3),  # >= one (rec,rec,attn) unit
                      block_pattern=("rec", "rec", "attn"),
                      lru_width=d_model, local_window=16)
        if self.family == "ssm":
            kw.update(rwkv_head_size=32)
        if self.sliding_window is not None:
            kw.update(sliding_window=16)
        return dataclasses.replace(self, **{k: v for k, v in kw.items()
                                            if hasattr(self, k)})

    # ---- parameter counting (for roofline MODEL_FLOPS) --------------------
    def param_count(self, active_only: bool = False) -> int:
        tpl = param_template(self)
        total = 0
        for path, t in _iter_template(tpl):
            n = int(np.prod(t.shape))
            if active_only and "experts_" in path and self.n_experts:
                n = int(n * (self.top_k / self.n_experts))
            total += n
        return total


# ---------------------------------------------------------------------------
# Parameter templates (shared by abstract/init)
# ---------------------------------------------------------------------------

class ParamT:
    __slots__ = ("shape", "kind", "fan")

    def __init__(self, shape, kind="normal", fan=None):
        self.shape = tuple(int(s) for s in shape)
        self.kind = kind
        self.fan = fan or (self.shape[-2] if len(self.shape) >= 2 else self.shape[-1])


def _iter_template(tpl, prefix=""):
    if isinstance(tpl, dict):
        for k, v in tpl.items():
            yield from _iter_template(v, prefix + "/" + k)
    else:
        yield prefix, tpl


def _mla_template(cfg: ModelConfig, Ls: int) -> Dict[str, ParamT]:
    D, H, hd, R = cfg.d_model, cfg.n_heads, cfg.hd, cfg.kv_lora_rank
    nope, vd = hd - cfg.qk_rope_head_dim, cfg.v_hd
    return {"ln1": ParamT((Ls, D), "ones"),
            "wq": ParamT((Ls, D, H * hd)),
            "wkv_a": ParamT((Ls, D, R + cfg.qk_rope_head_dim)),
            "kv_norm": ParamT((Ls, R), "ones"),
            "wkv_b": ParamT((Ls, R, H * (nope + vd))),
            "wo": ParamT((Ls, H * vd, D), fan=H * vd)}


def _attn_template(cfg: ModelConfig, Ls: int, biases: bool) -> Dict[str, ParamT]:
    if cfg.kv_lora_rank:
        return _mla_template(cfg, Ls)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    t: Dict[str, ParamT] = {
        "ln1": ParamT((Ls, D), "ones"),
        "wq": ParamT((Ls, D, H * hd)),
        "wk": ParamT((Ls, D, KV * hd)),
        "wv": ParamT((Ls, D, KV * hd)),
        "wo": ParamT((Ls, H * hd, D), fan=H * hd),
    }
    if biases or cfg.qkv_bias:
        t.update({"wq_b": ParamT((Ls, H * hd), "zeros"),
                  "wk_b": ParamT((Ls, KV * hd), "zeros"),
                  "wv_b": ParamT((Ls, KV * hd), "zeros"),
                  "wo_b": ParamT((Ls, D), "zeros")})
    if cfg.qk_norm:
        t.update({"q_norm": ParamT((Ls, hd), "ones"),
                  "k_norm": ParamT((Ls, hd), "ones")})
    return t


def _mlp_template(cfg: ModelConfig, Ls: int, gelu: bool) -> Dict[str, ParamT]:
    D, F = cfg.d_model, cfg.d_ff
    t = {"ln2": ParamT((Ls, D), "ones")}
    if gelu:
        t.update({"w_in": ParamT((Ls, D, F)), "w_in_b": ParamT((Ls, F), "zeros"),
                  "w_out": ParamT((Ls, F, D), fan=F),
                  "w_out_b": ParamT((Ls, D), "zeros")})
    else:
        t.update({"w_gate": ParamT((Ls, D, F)), "w_up": ParamT((Ls, D, F)),
                  "w_down": ParamT((Ls, F, D), fan=F)})
    return t


def _moe_template(cfg: ModelConfig, Ls: int) -> Dict[str, ParamT]:
    """Router over all ``n_experts``; expert weights of the held ones."""
    D, F, E = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    Eh = (E if cfg.held_experts is None
          else cfg.held_experts[1] - cfg.held_experts[0])
    t = {"ln2": ParamT((Ls, D), "ones"),
         "router": ParamT((Ls, D, E)),
         "experts_gate": ParamT((Ls, Eh, D, F)),
         "experts_up": ParamT((Ls, Eh, D, F)),
         "experts_down": ParamT((Ls, Eh, F, D), fan=F)}
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        t.update({"shared_gate": ParamT((Ls, D, Fs)),
                  "shared_up": ParamT((Ls, D, Fs)),
                  "shared_down": ParamT((Ls, Fs, D), fan=Fs)})
    return t


def param_template(cfg: ModelConfig):
    if cfg.family == "ssm":
        from repro.models import rwkv6
        return rwkv6.template(cfg)
    if cfg.family == "hybrid":
        from repro.models import rglru
        return rglru.template(cfg)

    Vp, D = cfg.padded_vocab, cfg.d_model
    tpl: Dict[str, Any] = {
        "embed": ParamT((Vp, D), fan=D),
        "final_norm": ParamT((D,), "ones"),
        "lm_head": ParamT((D, Vp)),
    }
    if cfg.norm == "layernorm":
        tpl["final_norm_b"] = ParamT((D,), "zeros")

    if cfg.family in ("dense",):
        blk = _attn_template(cfg, cfg.n_layers, biases=False)
        blk.update(_mlp_template(cfg, cfg.n_layers, gelu=cfg.act == "gelu"))
        tpl["blocks"] = blk
    elif cfg.family == "moe":
        n_dense = cfg.first_k_dense
        if n_dense:
            blk = _attn_template(cfg, n_dense, biases=False)
            blk.update(_mlp_template(cfg, n_dense, gelu=False))
            tpl["dense_blocks"] = blk
        blk = _attn_template(cfg, cfg.n_layers - n_dense, biases=False)
        blk.update(_moe_template(cfg, cfg.n_layers - n_dense))
        tpl["blocks"] = blk
    elif cfg.family == "vlm":
        k = cfg.cross_attn_every
        assert cfg.n_layers % k == 0
        n_cross = cfg.n_layers // k
        n_self = cfg.n_layers - n_cross
        blk = _attn_template(cfg, n_self, biases=False)
        blk.update(_mlp_template(cfg, n_self, gelu=False))
        tpl["blocks"] = blk
        xb = _attn_template(cfg, n_cross, biases=False)
        xb.update(_mlp_template(cfg, n_cross, gelu=False))
        xb.update({"q_norm": ParamT((n_cross, cfg.hd), "ones"),
                   "k_norm": ParamT((n_cross, cfg.hd), "ones"),
                   "gate_attn": ParamT((n_cross,), "zeros"),
                   "gate_mlp": ParamT((n_cross,), "zeros")})
        tpl["xblocks"] = xb
    elif cfg.family == "encdec":
        enc = _attn_template(cfg, cfg.n_enc_layers, biases=True)
        enc.update(_mlp_template(cfg, cfg.n_enc_layers, gelu=True))
        tpl["enc_blocks"] = enc
        tpl["enc_final_norm"] = ParamT((D,), "ones")
        tpl["enc_final_norm_b"] = ParamT((D,), "zeros")
        dec = _attn_template(cfg, cfg.n_layers, biases=True)
        dec.update({f"x_{k}": v for k, v in
                    _attn_template(cfg, cfg.n_layers, biases=True).items()})
        dec.update(_mlp_template(cfg, cfg.n_layers, gelu=True))
        tpl["dec_blocks"] = dec
        tpl["pos_embed"] = ParamT((cfg.max_target_positions, D), fan=D)
    else:
        raise ValueError(cfg.family)
    return tpl


def abstract_params(cfg: ModelConfig):
    tpl = param_template(cfg)
    return jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, cfg.dtype), tpl,
        is_leaf=lambda x: isinstance(x, ParamT))


def init_params(cfg: ModelConfig, key):
    tpl = param_template(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        tpl, is_leaf=lambda x: isinstance(x, ParamT))
    keys = jax.random.split(key, len(leaves))

    def one(t: ParamT, k):
        if t.kind == "ones":
            return jnp.ones(t.shape, cfg.dtype)
        if t.kind == "zeros":
            return jnp.zeros(t.shape, cfg.dtype)
        std = 1.0 / math.sqrt(t.fan)
        return (jax.random.normal(k, t.shape, F32) * std).astype(cfg.dtype)

    return jax.tree_util.tree_unflatten(treedef, [one(t, k) for t, k
                                                  in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _norm(cfg, x, scale, bias=None):
    if cfg.norm == "layernorm":
        return L.layernorm(x, scale, bias if bias is not None else
                           jnp.zeros_like(scale))
    return L.rmsnorm(x, scale)


def _proj(x, w, b=None):
    out = jnp.einsum("btd,dk->btk", x, w.astype(x.dtype))
    if b is not None:
        out = out + b.astype(x.dtype)
    return out


def _positions(mode, B, T, pos):
    """Rope positions: (B, 1) at ``pos`` in decode, else 0..T-1."""
    return jnp.full((B, 1), pos) if mode == "decode" else jnp.arange(T)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """hd^-1/2, times YaRN's mscale squared where rope is YaRN-scaled."""
    scale = cfg.hd ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        scale *= L.yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def mla_rope(cfg: ModelConfig, positions):
    """(cos, sin) of the rope part of a latent-attention head: YaRN
    frequencies and cos/sin scale where ``rope_scaling`` says so."""
    d, rs = cfg.qk_rope_head_dim, cfg.rope_scaling
    if not rs:
        return L.rope_freqs(d, cfg.rope_theta, positions)
    inv = jnp.asarray(L.yarn_inv_freq(d, cfg.rope_theta, **rs))
    scale = (L.yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
             / L.yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))
    return L.rope_freqs(d, cfg.rope_theta, positions, inv=inv, scale=scale)


def mla_qkv(cfg: ModelConfig, g, xn, positions):
    """Latent attention's q, k (B, T, H, hd) and v (B, T, H, v_hd) from the
    normed input (module docstring); ``g(name)`` reads the block's params."""
    B, T, _ = xn.shape
    H, hd, R, dr = cfg.n_heads, cfg.hd, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope = hd - dr
    q = _proj(xn, g("wq")).reshape(B, T, H, hd)
    c = _proj(xn, g("wkv_a"))
    c_kv, k_pe = c[..., :R], c[..., R:].reshape(B, T, 1, dr)
    kv = _proj(L.rmsnorm(c_kv, g("kv_norm")), g("wkv_b")).reshape(
        B, T, H, nope + cfg.v_hd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cos, sin = mla_rope(cfg, positions)
    q_pe = L.apply_rope(L.deinterleave(q[..., nope:]), cos, sin)
    k_pe = L.apply_rope(L.deinterleave(k_pe), cos, sin)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, T, H, dr))],
                        axis=-1)
    return q, k, v


def attn_block(cfg: ModelConfig, p, x, *, mode: str, causal=True, rope=True,
               window=None, cache=None, pos=None, kv_src=None, cross=False,
               prefix=""):
    """One attention sub-block (pre-norm, residual applied by the caller).

    cross=True: k/v come from ``kv_src`` (prefill/train) or from the cache of
    precomputed cross k/v (decode).  Self-attention decode writes k/v into a
    ring-buffer cache at ``pos % cache_len`` (sliding-window archs have
    cache_len == window) and masks with kv_len — causality follows because
    the query's absolute position dominates every cached entry.
    Latent attention (``cfg.kv_lora_rank``) runs the same cache path with
    its own q, k, v (:func:`mla_qkv`) under the named scope ``mla``.
    Returns (attn_out, new_cache)."""
    if cfg.kv_lora_rank and cross:
        raise ValueError("latent attention is self-attention only")
    with (jax.named_scope(MLA) if cfg.kv_lora_rank
          else contextlib.nullcontext()):
        return _attn_block(cfg, p, x, mode=mode, causal=causal, rope=rope,
                           window=window, cache=cache, pos=pos,
                           kv_src=kv_src, cross=cross, prefix=prefix)


def _attn_block(cfg: ModelConfig, p, x, *, mode: str, causal=True, rope=True,
                window=None, cache=None, pos=None, kv_src=None, cross=False,
                prefix=""):
    g = lambda name: p.get(prefix + name)
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    mla = bool(cfg.kv_lora_rank)

    xn = _norm(cfg, x, g("ln1"), g("ln1_b"))
    if mla:
        q, k, v = mla_qkv(cfg, g, xn, _positions(mode, B, T, pos))
    else:
        q = _proj(xn, g("wq"), g("wq_b")).reshape(B, T, H, hd)
        if g("q_norm") is not None:
            q = L.rmsnorm(q, g("q_norm"))

    new_cache = cache
    kv_len = None
    q_off = 0
    causal_eff = causal

    if cross:
        causal_eff = False
        if mode == "decode":
            k, v = cache["k"], cache["v"]          # precomputed at prefill
        else:
            S = kv_src.shape[1]
            k = _proj(kv_src, g("wk"), g("wk_b")).reshape(B, S, KV, hd)
            v = _proj(kv_src, g("wv"), g("wv_b")).reshape(B, S, KV, hd)
            if g("k_norm") is not None:
                k = L.rmsnorm(k, g("k_norm"))
            if mode == "prefill":
                new_cache = {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}
    else:
        if not mla:
            k = _proj(xn, g("wk"), g("wk_b")).reshape(B, T, KV, hd)
            v = _proj(xn, g("wv"), g("wv_b")).reshape(B, T, KV, hd)
            if g("k_norm") is not None:
                k = L.rmsnorm(k, g("k_norm"))
        if rope and not mla:
            cos, sin = L.rope_freqs(hd, cfg.rope_theta,
                                    _positions(mode, B, T, pos))
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        if mode == "decode":
            S_c = cache["k"].shape[1]
            write_idx = pos % S_c
            ck, cv = L.cache_update(cache["k"], cache["v"], k, v, write_idx)
            new_cache = {"k": ck, "v": cv}
            k, v = ck, cv
            kv_len = jnp.minimum(pos + 1, S_c)
            causal_eff = False  # kv_len masking subsumes causality
            window = None       # ring buffer only ever holds the window
        elif mode == "prefill":
            S_c = cache["k"].shape[1]
            if S_c >= T:
                ck, cv = L.cache_update(cache["k"], cache["v"], k, v, 0)
            else:  # sliding window: keep the last S_c entries
                ck, cv = L.cache_update(cache["k"], cache["v"],
                                        k[:, T - S_c:], v[:, T - S_c:], 0)
            new_cache = {"k": ck, "v": cv}

    out = L.attention(q, k, v, causal=causal_eff, window=window,
                      q_offset=q_off, kv_len=kv_len,
                      softmax_scale=mla_softmax_scale(cfg) if mla else None)
    out = out.reshape(B, T, H * cfg.v_hd)
    out = jnp.einsum("btk,kd->btd", out, g("wo").astype(x.dtype))
    if g("wo_b") is not None:
        out = out + g("wo_b").astype(x.dtype)
    return out, new_cache


def mlp_block(cfg: ModelConfig, p, x, prefix=""):
    """The FF sub-block (pre-norm, residual applied by the caller).
    Returns (out, aux loss, load): ``load`` is the held-expert layer's
    (held pairs, max load) (moe.held_moe_mlp), else None."""
    g = lambda name: p.get(prefix + name)
    xn = _norm(cfg, x, g("ln2"), g("ln2_b"))
    if cfg.family == "moe" and g("router") is not None:
        shared = None
        if cfg.n_shared_experts:
            shared = (g("shared_gate"), g("shared_up"), g("shared_down"))
        experts = (g("router"), g("experts_gate"), g("experts_up"),
                   g("experts_down"))
        if cfg.held_experts is not None:
            return moe_mod.held_moe_mlp(
                xn, *experts, top_k=cfg.top_k, held=cfg.held_experts,
                shared=shared)
        out, aux = moe_mod.moe_mlp(
            xn, *experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, shared=shared)
        return out, aux, None
    if g("w_in") is not None:
        return L.gelu_mlp(xn, g("w_in"), g("w_in_b"), g("w_out"),
                          g("w_out_b")), 0.0, None
    return L.swiglu(xn, g("w_gate"), g("w_up"), g("w_down")), 0.0, None


# ---------------------------------------------------------------------------
# Stacks (scan over layers)
# ---------------------------------------------------------------------------

def run_stack(cfg, stack, x, *, mode, causal=True, window=None, cache=None,
              pos=None):
    """lax.scan over the layer-stacked self-attention params (and cache).
    Returns (x, new cache, summed aux loss, per-layer load or None)."""
    use_rope = cfg.norm != "layernorm"  # whisper (layernorm) has no RoPE

    def body(carry, xs):
        h, aux_sum = carry
        if cache is None:
            p_l, c_l = xs, None
        else:
            p_l, c_l = xs
        a, nc = attn_block(cfg, p_l, h, mode=mode, causal=causal,
                           rope=use_rope, window=window, cache=c_l, pos=pos)
        h = h + a
        m, aux, load = mlp_block(cfg, p_l, h)
        return (h + m, aux_sum + aux), (nc, load)

    xs = stack if cache is None else (stack, cache)
    (x, aux), (new_cache, load) = jax.lax.scan(
        body, (x, jnp.float32(0.0)), xs)
    return x, new_cache, aux, load


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------

def embed_tokens(cfg, params, tokens):
    return params["embed"].astype(cfg.dtype)[tokens]


def lm_logits(cfg, params, x):
    xn = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    return jnp.einsum("btd,dv->btv", xn, params["lm_head"].astype(x.dtype))


def forward(cfg: ModelConfig, params, batch, *, mode="train", cache=None,
            pos=None):
    """Family dispatch.  Returns (logits, new_cache, aux_loss)."""
    return forward_with_load(cfg, params, batch, mode=mode, cache=cache,
                             pos=pos)[:3]


def forward_with_load(cfg: ModelConfig, params, batch, *, mode="train",
                      cache=None, pos=None):
    """:func:`forward` and the expert load of a held-expert stack:
    (logits, new_cache, aux_loss, load), ``load`` a pair of (layers,)
    arrays (held pairs, max load) or None."""
    if cfg.family not in ("dense", "moe"):
        return _forward_other(cfg, params, batch, mode=mode, cache=cache,
                              pos=pos) + (None,)
    return _forward_decoder(cfg, params, batch, mode=mode, cache=cache,
                            pos=pos)


def _forward_other(cfg, params, batch, *, mode, cache, pos):
    if cfg.family == "ssm":
        from repro.models import rwkv6
        return rwkv6.forward(cfg, params, batch, mode=mode, cache=cache,
                             pos=pos)
    if cfg.family == "hybrid":
        from repro.models import rglru
        return rglru.forward(cfg, params, batch, mode=mode, cache=cache,
                             pos=pos)
    if cfg.family == "encdec":
        return _forward_encdec(cfg, params, batch, mode=mode, cache=cache,
                               pos=pos)
    return _forward_vlm(cfg, params, batch, mode=mode, cache=cache, pos=pos)


def _forward_decoder(cfg, params, batch, *, mode, cache, pos):
    """The decoder stacks in order: the leading dense one, where the model
    has it, then ``blocks``."""
    x = embed_tokens(cfg, params, batch["tokens"])
    aux, load, new_cache = jnp.float32(0.0), None, {}
    for name in ("dense_blocks", "blocks"):
        if name not in params:
            continue
        x, new_cache[name], a, ld = run_stack(
            cfg, params[name], x, mode=mode, causal=True,
            window=cfg.sliding_window, cache=None if cache is None
            else cache[name], pos=pos)
        aux = aux + a
        if ld is not None:
            load = ld
    logits = lm_logits(cfg, params, x)
    return logits, (None if cache is None else new_cache), aux, load


def _forward_vlm(cfg, params, batch, *, mode, cache, pos):
    k = cfg.cross_attn_every
    n_super = cfg.n_layers // k
    x = embed_tokens(cfg, params, batch["tokens"])
    vision = batch.get("vision")  # (B, n_vis, D); None in decode (cached)

    # reshape self blocks (n_self, ...) -> (n_super, k-1, ...)
    selfb = jax.tree_util.tree_map(
        lambda a: a.reshape(n_super, k - 1, *a.shape[1:]), params["blocks"])

    def super_body(carry, xs):
        h, aux_sum = carry
        if cache is None:
            ps, px = xs
            cs, cx = None, None
        else:
            (ps, px), (cs, cx) = xs
        # (k-1) self layers
        h, cs_new, aux, _ = run_stack(cfg, ps, h, mode=mode, causal=True,
                                      cache=cs, pos=pos)
        # 1 gated cross layer
        a, cx_new = attn_block(cfg, px, h, mode=mode, rope=False, cache=cx,
                               pos=pos, kv_src=vision, cross=True)
        h = h + jnp.tanh(px["gate_attn"]).astype(h.dtype) * a
        m, aux2, _ = mlp_block(cfg, px, h)
        h = h + jnp.tanh(px["gate_mlp"]).astype(h.dtype) * m
        return (h, aux_sum + aux + aux2), (cs_new, cx_new)

    xs = ((selfb, params["xblocks"]) if cache is None
          else ((selfb, params["xblocks"]),
                (jax.tree_util.tree_map(
                    lambda a: a.reshape(n_super, k - 1, *a.shape[1:]),
                    cache["self"]), cache["cross"])))
    (x, aux), caches = jax.lax.scan(super_body, (x, jnp.float32(0.0)), xs)
    new_cache = None
    if cache is not None:
        cs, cx = caches
        new_cache = {"self": jax.tree_util.tree_map(
            lambda a: a.reshape(n_super * (k - 1), *a.shape[2:]), cs),
            "cross": cx}
    logits = lm_logits(cfg, params, x)
    return logits, new_cache, aux


def _forward_encdec(cfg, params, batch, *, mode, cache, pos):
    B = batch["tokens"].shape[0]
    if mode == "decode" and cache is not None:
        enc_out = None  # cross k/v cached
    else:
        frames = batch["frames"].astype(cfg.dtype)  # (B, S_enc, D) stub
        pe = L.sinusoidal_pos(frames.shape[1], cfg.d_model).astype(cfg.dtype)
        h = frames + pe[None]
        h, _, _, _ = run_stack(cfg, params["enc_blocks"], h, mode="train",
                               causal=False)
        enc_out = _norm(cfg, h, params["enc_final_norm"],
                        params.get("enc_final_norm_b"))

    tokens = batch["tokens"]
    T = tokens.shape[1]
    x = embed_tokens(cfg, params, tokens)
    if mode == "decode":
        idx = jnp.minimum(pos, cfg.max_target_positions - 1)
        x = x + params["pos_embed"].astype(x.dtype)[idx][None, None]
    else:
        idx = jnp.minimum(jnp.arange(T), cfg.max_target_positions - 1)
        x = x + params["pos_embed"].astype(x.dtype)[idx][None]

    def body(carry, xs):
        h, aux_sum = carry
        if cache is None:
            p_l, c_self, c_cross = xs, None, None
        else:
            p_l, (c_self, c_cross) = xs
        a, nc_self = attn_block(cfg, p_l, h, mode=mode, causal=True,
                                rope=False, cache=c_self, pos=pos)
        h = h + a
        xa, nc_cross = attn_block(cfg, p_l, h, mode=mode, rope=False,
                                  cache=c_cross, pos=pos, kv_src=enc_out,
                                  cross=True, prefix="x_")
        h = h + xa
        m, aux, _ = mlp_block(cfg, p_l, h)
        return (h + m, aux_sum + aux), (nc_self, nc_cross)

    xs = (params["dec_blocks"] if cache is None
          else (params["dec_blocks"], (cache["self"], cache["cross"])))
    (x, aux), caches = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
    new_cache = None
    if cache is not None:
        new_cache = {"self": caches[0], "cross": caches[1]}
    logits = lm_logits(cfg, params, x)
    return logits, new_cache, aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, B: int, S: int, abstract=False):
    """Pre-allocated decode cache for seq_len S."""
    mk = (lambda shape: jax.ShapeDtypeStruct(shape, cfg.dtype)) if abstract \
        else (lambda shape: jnp.zeros(shape, cfg.dtype))
    KV, hd, vd = cfg.n_kv_heads, cfg.hd, cfg.v_hd
    if cfg.family == "ssm":
        from repro.models import rwkv6
        return rwkv6.init_cache(cfg, B, mk)
    if cfg.family == "hybrid":
        from repro.models import rglru
        return rglru.init_cache(cfg, B, S, mk)
    Seff = S if cfg.sliding_window is None else min(S, cfg.sliding_window)
    if cfg.decode_cache_cap is not None:
        Seff = min(Seff, cfg.decode_cache_cap)
    kv = lambda n, s: {"k": mk((n, B, s, KV, hd)), "v": mk((n, B, s, KV, vd))}
    if cfg.family == "dense" or cfg.family == "moe":
        n_dense = cfg.first_k_dense
        cache = {"blocks": kv(cfg.n_layers - n_dense, Seff)}
        if n_dense:
            cache["dense_blocks"] = kv(n_dense, Seff)
        return cache
    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        return {"self": kv(cfg.n_layers - n_cross, Seff),
                "cross": kv(n_cross, cfg.n_vision_tokens)}
    if cfg.family == "encdec":
        return {"self": kv(cfg.n_layers, Seff),
                "cross": kv(cfg.n_layers, min(S, cfg.max_source_positions))}
    raise ValueError(cfg.family)


def decode_step(cfg, params, cache, tokens, pos):
    """serve_step: ONE new token (B, 1) against a pre-allocated cache.

    ``pos`` is the absolute position; attn_block handles ring-buffer
    indexing (pos % cache_len) for sliding-window caches internally."""
    logits, new_cache, _ = forward(cfg, params, {"tokens": tokens},
                                   mode="decode", cache=cache, pos=pos)
    return logits[:, -1], new_cache


def loss_fn(cfg, logits, labels):
    """Mean next-token CE (labels already shifted by the data pipeline).

    Formulated as logsumexp - one_hot einsum (no gather over the vocab dim),
    so a vocab-sharded logits tensor never gets all-gathered under GSPMD."""
    lf = logits.astype(F32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(labels, lf.shape[-1], dtype=F32)
    correct = jnp.einsum("btv,btv->bt", lf, onehot)
    return jnp.mean(lse - correct)
