"""DeepSeek-V2's parts against the benchmark's plain reference
(bench/reference/deepseek_v2.py, imported as it is, no second copy), on
seeded weights at tiny widths on the CPU: YaRN rope, latent attention, the
dropless held-expert layer, the share-sum of a layer cut over 8 chips, the
whole 1 + 4 layer model through api.build and TrainerRunner.step, and the
4-D expert leaves through both wire modes.

Tolerances: the f32 comparisons take 2e-5 relative (program and reference
sum the same float32 products in other orders: XLA's fused einsums
against the reference's row-by-row HIGHEST-precision dots); the trainer's
gradient takes 2e-3, since it is read back from one step as (X0 - X1) /
eta, where X1 = X0 - eta G rounds in float32 at the scale of X, not of
eta G."""
import dataclasses
import importlib.util
import json
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import weights  # noqa: E402
from bench.reference import deepseek_v2 as ref  # noqa: E402
from repro import api, configs  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models import transformer as TR  # noqa: E402

TINY = ROOT / "tests" / "bench_harness" / "data" / "tiny-dsv2.json"
RTOL = 2e-5


def _cfg(**changes):
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(torch_dtype="float32", **changes)
    return cfg


def _program(cfg):
    """The program's ModelConfig for a reference configuration."""
    params = ref.program_params(cfg)
    params["dtype"] = jnp.dtype(params["dtype"]).type
    return dataclasses.replace(configs.get("deepseek-v2-lite"), **params)


def _layer(tree, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rtol * scale, (np.abs(a - b).max(), scale)


def test_published_sizes():
    cfg = configs.get("deepseek-v2-lite")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == \
        (27, 2048, 16, 10944, 102400)
    assert (cfg.kv_lora_rank, cfg.hd, cfg.qk_rope_head_dim, cfg.v_hd) == \
        (512, 192, 64, 128)
    assert (cfg.first_k_dense, cfg.n_experts, cfg.top_k, cfg.moe_d_ff,
            cfg.n_shared_experts, cfg.held_experts) == (1, 64, 6, 1408, 2,
                                                        (0, 64))
    # scale 192^-1/2 x mscale(40, 0.707)^2 = 1.5896 / sqrt(192)
    assert abs(TR.mla_softmax_scale(cfg) * 192 ** 0.5 - 1.58961) < 1e-4


@pytest.mark.parametrize("dim,theta", [(64, 1e4), (16, 1e4)])
def test_yarn_frequencies_match_the_reference(dim, theta):
    """Program and reference agree on cos and sin over the cell's 1024
    positions to S x 2^-22: numpy and XLA may round an inverse frequency
    an ulp (2^-23 of it, at most 2^-23) apart, which a position S
    multiplies."""
    cfg = _cfg(qk_rope_head_dim=dim, rope_theta=theta)
    mcfg = _program(cfg)
    S = 1024
    cos, sin = TR.mla_rope(mcfg, jnp.arange(S))
    rcos, rsin, scale = ref.yarn(cfg, S)
    # the reference repeats the half-dim table (rotate-half layout)
    _close(cos, rcos[:, :dim // 2], S * 2.0 ** -22)
    _close(sin, rsin[:, :dim // 2], S * 2.0 ** -22)
    assert abs(scale - TR.mla_softmax_scale(mcfg)) < 1e-7
    inv = L.yarn_inv_freq(dim, theta, **cfg["rope_scaling"])
    plain = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    # the fast dims keep plain RoPE, the slow ones are interpolated 40x
    np.testing.assert_allclose(inv[:2], plain[:2], rtol=1e-6)
    np.testing.assert_allclose(inv[-1], plain[-1] / 40, rtol=1e-6)


def test_mla_block_matches_the_reference():
    cfg = _cfg()
    mcfg = _program(cfg)
    params = weights.init_params(cfg, jax.random.key(3))
    p = _layer(params["blocks"], 1)
    x = jax.random.normal(jax.random.key(4), (2, 32, cfg["hidden_size"]))
    got, _ = TR.attn_block(mcfg, p, x, mode="train")
    rope = ref.yarn(cfg, 32)
    want = jnp.stack([ref.mla(cfg, ref._rms(r, p["ln1"], 1e-6), p, rope)
                      for r in x])
    _close(got, want)


def _moe_args(cfg, params, layer=0):
    p = _layer(params["blocks"], layer)
    experts = (p["router"], p["experts_gate"], p["experts_up"],
               p["experts_down"])
    shared = (p["shared_gate"], p["shared_up"], p["shared_down"])
    return p, experts, shared


def _held_layer(cfg, x, experts, shared):
    return moe.held_moe_mlp(
        x, *experts, top_k=cfg["num_experts_per_tok"],
        held=tuple(cfg["held_experts"]), shared=shared)


def _ref_layer(cfg, x, p):
    outs, bals = zip(*[ref.experts(cfg, r, p) for r in x])
    return jnp.stack(outs), jnp.mean(jnp.stack(bals))


@pytest.mark.parametrize("held", [(0, 8), (24, 32)])
def test_held_expert_layer_matches_the_reference(held):
    cfg = _cfg(held_experts=list(held))
    params = weights.init_params(cfg, jax.random.key(5))
    p, experts, shared = _moe_args(cfg, params)
    x = jax.random.normal(jax.random.key(6), (2, 32, cfg["hidden_size"]))
    out, balance, (pairs, max_load) = _held_layer(cfg, x, experts, shared)
    want, want_balance = _ref_layer(cfg, x, p)
    _close(out, want)
    _close(balance, want_balance)
    _, _, idx = jax.vmap(lambda r: ref.route(cfg, r, p["router"]))(x)
    n = int(np.sum((np.asarray(idx) >= held[0]) & (np.asarray(idx) < held[1])))
    assert int(pairs) == n and float(max_load) >= 1.0


def test_a_skewed_router_drops_no_token():
    """Every token routes to held expert 3: its group is every token, a
    capacity dispatch would drop all but 1.25 x its share, and the layer
    still computes every pair."""
    cfg = _cfg()
    params = weights.init_params(cfg, jax.random.key(7))
    p, experts, shared = _moe_args(cfg, params)
    D = cfg["hidden_size"]
    u = jax.random.normal(jax.random.key(8), (D,))
    u = u / jnp.linalg.norm(u)
    x = (jax.random.normal(jax.random.key(9), (2, 32, D)) * 0.1
         + 3.0 * u)
    router = experts[0].at[:, 3].set(10.0 * u)
    p = dict(p, router=router)
    out, _, (pairs, max_load) = _held_layer(
        cfg, x, (router,) + experts[1:], shared)
    _, _, idx = jax.vmap(lambda r: ref.route(cfg, r, router))(x)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    want, _ = _ref_layer(cfg, x, p)
    _close(out, want)
    n_held = int(np.sum(np.asarray(idx) < 8))
    assert int(pairs) == n_held >= 2 * 32
    assert float(max_load) == pytest.approx(64 / (n_held / 8))


def test_eight_shares_sum_to_the_uncut_layer():
    """The guide's share-sum test: a layer of 64 routed experts cut over 8
    chips; the 8 shares' routed outputs, with the shared experts and the
    balance loss (which every chip computes alike) counted once, equal the
    uncut layer, in the program and in the reference."""
    full = _cfg(n_routed_experts=64, held_experts=[0, 64], published={})
    params = weights.init_params(full, jax.random.key(10))
    p, experts, shared = _moe_args(full, params)
    x = jax.random.normal(jax.random.key(11), (2, 32, full["hidden_size"]))
    whole, whole_balance, _ = _held_layer(full, x, experts, shared)
    ref_whole, _ = _ref_layer(full, x, p)
    shared_out = L.swiglu(x, *shared)
    parts, ref_parts = [], []
    for i in range(8):
        cut = _cfg(held_experts=[8 * i, 8 * i + 8])
        sl = slice(8 * i, 8 * i + 8)
        mine = (experts[0],) + tuple(e[sl] for e in experts[1:])
        out, balance, _ = _held_layer(cut, x, mine, None)
        _close(balance, whole_balance)
        parts.append(out)
        p_cut = dict(p, experts_gate=p["experts_gate"][sl],
                     experts_up=p["experts_up"][sl],
                     experts_down=p["experts_down"][sl])
        r_out, _ = _ref_layer(cut, x, p_cut)
        ref_parts.append(r_out - shared_out)
    _close(sum(parts) + shared_out, whole)
    _close(sum(ref_parts) + shared_out, ref_whole)
    _close(whole, ref_whole)


def _runner(cfg, wire_mode="bucketed", batch=2, seq=16):
    spec = api.ExperimentSpec(
        name="dsv2-test", n_nodes=1, steps=0, seed=0,
        algorithm=api.AlgorithmSpec(eta=0.05, alpha=0.5, gamma=1.0),
        compressor=api.CompressorSpec("qinf", {"bits": 2, "block": 256}),
        topology=api.TopologySpec(graph="ring"),
        model=api.ModelSpec(arch="deepseek-v2-lite", full=True,
                            local_batch=batch, seq_len=seq,
                            params=ref.program_params(cfg)),
        execution=api.ExecutionSpec(engine="sharded", backend="neighbor",
                                    wire_mode=wire_mode, mesh=(1, 1)))
    return api.build(spec)


def test_model_loss_and_gradient_through_the_trainer():
    """The 1 dense + 4 expert layer model, f32, one TrainerRunner.step on
    the sharded engine with the bucketed 2-bit wire: its loss is the
    reference's mean cross entropy, and the gradient it applied,
    (X0 - X1) / eta (one node: D1 = 0), is the reference's gradient of the
    cross entropy plus aux_loss_alpha x the balance loss."""
    cfg = _cfg()
    runner = _runner(cfg)
    key = jax.random.key(12)
    x0 = weights.init_params(cfg, key)
    state = runner.trainer.state_from_stacked(
        weights.stacked_params(cfg, key, 1))
    toks = jax.random.randint(jax.random.key(13), (1, 2, 17), 0,
                              cfg["vocab_size"])
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    state, m = runner.step(state, batch)
    loss, grad = ref.loss_and_grad(cfg, x0, batch["tokens"][0],
                                   batch["labels"][0])
    assert abs(float(m["loss"]) - float(loss)) <= RTOL * abs(float(loss))
    got = jax.tree_util.tree_map(lambda a, b: (a - b[0]) / 0.05, x0,
                                 state.plead.X)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(grad)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-12)
        assert err <= 2e-3, (jax.tree_util.keystr(path), err)
    assert 0 < int(m["moe_held_pairs"]) <= 4 * 2 * 16 * 6
    assert float(m["moe_max_load"]) >= 1.0


def test_expert_leaves_bit_equal_between_wire_modes():
    """The 4-D expert leaves (L, E, D, F), F = 352 (1.375 blocks of 256),
    a held share of 8 of 64, bf16, three steps: the bucketed and per-leaf
    wire modes leave the same state, bit for bit."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    model = dict(arch="deepseek-v2-lite", n_layers=2, d_model=64,
                 local_batch=2, seq_len=16,
                 params={"dtype": "bfloat16", "n_experts": 64, "top_k": 6,
                         "held_experts": (8, 16), "moe_d_ff": 352})
    res = cs.phase(1, model, audit_wire=False)
    cs.verify(res, 1, on_chip=False)
    assert any("experts_down" in k for k in res["diffs"])
    assert all(v is None for v in res["diffs"].values())
    assert res["bucketed"]["losses"] == res["per_leaf"]["losses"]


def test_sharding_rules_of_the_new_leaves():
    """Latent attention's down-projection stays whole (its columns split
    into c_kv and the shared rope key); its up-projection and the query
    shard by heads over 'model'; a 4-D expert leaf shards its width."""
    from jax.sharding import PartitionSpec as P
    from repro.models.sharding import param_specs
    specs = param_specs(TR.abstract_params(configs.get("deepseek-v2-lite")))
    for stack in ("dense_blocks", "blocks"):
        assert specs[stack]["wkv_a"] == P(None, None, None)
        assert specs[stack]["wkv_b"] == P(None, None, "model")
        assert specs[stack]["wq"] == P(None, None, "model")
        assert specs[stack]["wo"] == P(None, "model", None)
        assert specs[stack]["kv_norm"] == P(None, None)
    assert specs["blocks"]["experts_gate"] == P(None, None, None, "model")
    assert specs["blocks"]["experts_down"] == P(None, None, "model", None)
    assert specs["dense_blocks"]["w_gate"] == P(None, None, "model")


@pytest.mark.parametrize("nodes", [1, 3])
def test_grouped_matmul_under_vmap_and_grad(nodes):
    """The trainer vmaps the model over its nodes: the grouped matmul folds
    the batch into its groups (rows past each node's last group stay 0)
    and its custom VJP gives ragged_dot's own gradient."""
    k = jax.random.split(jax.random.key(14), 2)
    lhs = jax.random.normal(k[0], (nodes, 12, 4))
    rhs = jax.random.normal(k[1], (nodes, 3, 4, 5))
    gs = jnp.array([[2, 3, 1], [4, 0, 5], [1, 1, 1]], jnp.int32)[:nodes]

    def loss(f):
        return lambda l, r: jnp.sum(jnp.sin(jax.vmap(f)(l, r, gs)))

    got = jax.jit(jax.value_and_grad(loss(moe.grouped_matmul),
                                     argnums=(0, 1)))(lhs, rhs)
    want = jax.value_and_grad(loss(jax.lax.ragged_dot),
                              argnums=(0, 1))(lhs, rhs)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b)
    out = jax.vmap(moe.grouped_matmul)(lhs, rhs, gs)
    for n in range(nodes):
        assert not np.asarray(out[n, int(gs[n].sum()):]).any()


@pytest.mark.parametrize("nodes", [1, 3])
def test_grouped_matmul_drops_what_rows_past_the_groups_hold(
        nodes, monkeypatch):
    """The TPU ragged dot leaves the rows past the last group unwritten, in
    its output and in its lhs gradient.  A ragged dot that fills them with
    NaN stands in for it here: the grouped matmul's value and gradients
    stay those of the clean one, and its rows past the groups stay 0.
    The shapes are this test's own, so no trace made before the patch is
    reused."""
    real = jax.lax.ragged_dot

    def fill(y, gs):
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(gs), y, jnp.nan)

    @jax.custom_vjp
    def junk(lhs, rhs, gs):
        return fill(real(lhs, rhs, gs), gs)

    def junk_fwd(lhs, rhs, gs):
        return junk(lhs, rhs, gs), (lhs, rhs, gs)

    def junk_bwd(res, dout):
        lhs, rhs, gs = res
        dl, dr = jax.vjp(lambda a, b: real(a, b, gs), lhs, rhs)[1](dout)
        return fill(dl, gs), dr, None

    junk.defvjp(junk_fwd, junk_bwd)
    k = jax.random.split(jax.random.key(15), 2)
    lhs = jax.random.normal(k[0], (nodes, 13, 6))
    rhs = jax.random.normal(k[1], (nodes, 3, 6, 7))
    gs = jnp.array([[2, 3, 1], [4, 0, 5], [1, 1, 1]], jnp.int32)[:nodes]

    def value_and_grad(f):
        def loss(l, r):
            out = jax.vmap(f)(l, r, gs)
            return jnp.sum(jnp.sin(out)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(lhs, rhs)

    want = value_and_grad(real)
    monkeypatch.setattr(jax.lax, "ragged_dot", junk)
    assert np.isnan(np.asarray(jax.lax.ragged_dot(lhs[0], rhs[0], gs[0]))).any()
    (value, out), grads = value_and_grad(moe.grouped_matmul)
    (want_value, want_out), want_grads = want
    _close(value, want_value)
    for a, b in zip(grads, want_grads):
        _close(a, b)
    for n in range(nodes):
        assert not np.asarray(out[n, int(gs[n].sum()):]).any()
        assert not np.asarray(grads[0][n, int(gs[n].sum()):]).any()
