"""Per-architecture smoke tests: REDUCED variants (<=3 layers, d_model<=512,
<=4 experts), one forward + one SGD train step + one decode step on CPU,
asserting output shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as TR

B, T = 2, 16


def _batch(cfg, key):
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "vlm":
        batch["vision"] = jax.random.normal(
            key, (B, cfg.n_vision_tokens, cfg.d_model), cfg.dtype)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (B, 8, cfg.d_model), cfg.dtype)
    return batch


@pytest.fixture(scope="module", params=configs.ARCH_IDS)
def arch_setup(request):
    cfg = configs.get(request.param).reduced()
    params = TR.init_params(cfg, jax.random.key(0))
    return request.param, cfg, params


def test_full_config_exact(arch_setup):
    """The full (non-reduced) config matches the assignment table."""
    arch, _, _ = arch_setup
    full = configs.get(arch)
    table = {
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
    }
    L_, D, H, KV, F, V = table[arch]
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.d_ff, full.vocab) == (L_, D, H, KV, F, V)
    assert full.citation


def test_reduced_limits(arch_setup):
    _, cfg, _ = arch_setup
    assert cfg.n_layers <= 3 and cfg.d_model <= 512
    if cfg.family == "moe":
        assert cfg.n_experts <= 4


def test_forward_shapes_finite(arch_setup):
    _, cfg, params = arch_setup
    batch = _batch(cfg, jax.random.key(1))
    logits, _, aux = TR.forward(cfg, params, batch)
    Tl = batch["tokens"].shape[1]
    assert logits.shape == (B, Tl, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


def test_train_step_decreases_loss(arch_setup):
    """One SGD step on the reduced model: grads finite, loss drops."""
    _, cfg, params = arch_setup
    batch = _batch(cfg, jax.random.key(2))

    def loss(p):
        logits, _, aux = TR.forward(cfg, p, batch)
        return TR.loss_fn(cfg, logits, batch["labels"]) + 0.01 * aux

    l0, g = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l0))
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    p1 = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg, params, g)
    l1 = loss(p1)
    assert float(l1) < float(l0)


def test_decode_step_shapes(arch_setup):
    _, cfg, params = arch_setup
    batch = _batch(cfg, jax.random.key(3))
    cache = TR.init_cache(cfg, B, 32)
    if cfg.family in ("vlm", "encdec"):
        _, cache, _ = TR.forward(cfg, params,
                                 {**batch, "tokens": batch["tokens"][:, :1]},
                                 mode="prefill", cache=cache)
    logits, new_cache = TR.decode_step(cfg, params, cache,
                                       batch["tokens"][:, :1], 1)
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all()
    assert jax.tree_util.tree_structure(new_cache) == \
        jax.tree_util.tree_structure(cache)


def test_decode_matches_forward(arch_setup):
    """Step-by-step decode reproduces the full forward logits (MoE archs use
    no-drop capacity so routing is identical across T)."""
    arch, cfg, params = arch_setup
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    batch = _batch(cfg, jax.random.key(4))
    toks = batch["tokens"][:, :8]
    full_batch = {**batch, "tokens": toks}
    logits_full, _, _ = TR.forward(cfg, params, full_batch)
    cache = TR.init_cache(cfg, B, 16)
    start = 0
    outs = []
    if cfg.family in ("vlm", "encdec"):
        _, cache, _ = TR.forward(cfg, params, {**batch, "tokens": toks[:, :1]},
                                 mode="prefill", cache=cache)
        outs.append(logits_full[:, 0])
        start = 1
    for t in range(start, 8):
        lg, cache = TR.decode_step(cfg, params, cache, toks[:, t:t + 1], t)
        outs.append(lg)
    err = float(jnp.max(jnp.abs(jnp.stack(outs, 1) - logits_full)))
    assert err < 1e-4, err


def test_param_count_positive(arch_setup):
    arch, _, _ = arch_setup
    full = configs.get(arch)
    n = full.param_count()
    assert n > 1e9, (arch, n)  # all assigned archs are >1B params
    if full.n_experts:
        assert full.param_count(active_only=True) < n
