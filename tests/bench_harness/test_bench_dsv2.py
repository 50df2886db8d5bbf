"""The DeepSeek-V2 kind in the harness: the configuration's cut against the
published config and the manifest's rules, its counts, a tiny run of the
whole harness that the comparison judges correct, a planted fault it
judges not correct, and the three readers of the model's named scopes."""
import json
import os

import pytest

import tiny
from _bench_path import ROOT
from bench import counts, kinds, run, trace
from bench.reference import deepseek_v2
from test_bench_manifest import CHIPS_PER_LAYER, reduction_faults

CONFIG = os.path.join(ROOT, "bench", "configs", "deepseek-v2-lite-5L.json")

# the catalog's DeepSeek-V2-Lite (its config.json), every key
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_cut_is_a_chips_share(cfg):
    assert reduction_faults(cfg) == []
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 12800)
    assert cfg[CHIPS_PER_LAYER] == 8 and cfg["held_experts"] == [0, 8]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert set(cfg["assumed"]) == {"aux_loss_alpha", "padded_vocab_multiple",
                                   "torch_dtype"}
    assert kinds.of(cfg) is deepseek_v2


def test_counts(cfg):
    """About 258M weights a token multiplies and 1.705 GFLOP a token; the
    held experts' balanced grouped matmuls 1.276 TFLOP a step of 8 x
    1024 tokens."""
    assert counts.matmul_weights(cfg) == 257_949_696
    assert counts.flops_per_token(cfg, 1024) == 6 * 257_949_696 + \
        6 * 5 * 1024 * 16 * (192 + 128)
    flops = deepseek_v2.held_expert_flops_per_token(cfg) * 8 * 1024
    assert flops == 8192 * 6 * 8 / 64 * 3 * 2 * 2048 * 1408 * 3 * 4
    params = run.model_params(cfg)
    assert params["held_experts"] == (0, 8) and params["n_experts"] == 64
    assert params["vocab"] == 12800 and params["n_layers"] == 5


def _tiny_cell():
    c = tiny.cell("node1.q2")
    with open(os.path.join(tiny.DATA, "tiny-dsv2.json")) as f:
        c["cfg"] = json.load(f)
    c["name"] = "tiny-dsv2.node1.q2"
    return c


def test_a_tiny_run_is_correct(tmp_path):
    """The whole harness at tiny widths: 1 dense + 4 expert layers, 8 of
    64 routed experts held, bf16, through api.build and TrainerRunner.step
    with the bucketed 2-bit wire, judged against the plain reference by
    the tiny limits the dense kind's tests use."""
    res = tiny.run(_tiny_cell(), 3_000_000_019, str(tmp_path))
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_renormalised_gates_are_not_correct(tmp_path, monkeypatch):
    """A program whose routed experts' part comes out 10x, as renormalising
    the top 6 of 64 softmax gates (about 0.1 together at initialisation)
    would make it, is judged not correct, at the expert leaves.  A fault
    confined to the routed experts shows only when it is this large: a
    leaf's gap is over the median leaf's norm, and the expert leaves'
    gradients are a few hundredths of it (PERF.md, Open questions)."""
    from repro.models import moe
    real = moe._routed
    monkeypatch.setattr(moe, "_routed", lambda *a: 10 * real(*a))
    res = tiny.run(_tiny_cell(), 3_000_000_019, str(tmp_path))
    assert not res["correct"]
    assert "experts_" in res["compared"]["grad_gap"]["worst"]


HLO = """
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/while/body/closed_call/mla/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(vmap()))/while/body/closed_call/mla/dot_general"}
  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/while/body/closed_call/moe/route/sort"}
  %ragged-dot.4 = bf16[8]{0} ragged-dot(%p), metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/while/body/closed_call/moe/experts/ragged_dot_general"}
  %fusion.5 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/dot_general"}
  %fusion.6 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/prox_lead/update/sub"}
  %ragged-dot-none.7 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}
"""


def _ctx(cfg, hlo, steps=2):
    ops = [trace.Op(0, name, 0, dur) for name, dur in (
        ("fusion.1", 3_000_000), ("fusion.2", 5_000_000),
        ("fusion.3", 2_000_000), ("ragged-dot.4", 10_000_000),
        ("fusion.5", 7_000_000), ("fusion.6", 1_000_000),
        ("ragged-dot-none.7", 4_000_000))]
    table = trace.table_from(ops, [], (0,))
    cell = {"cfg": cfg, "traffic_params": {"seq_len": 1024}}
    return trace.Context(cell=cell, table=table, steps=steps,
                         tokens=steps * 8192, window_s=1.0, chips=1,
                         device_kind="TPU v5 lite", hlo_text=lambda: hlo)


def test_the_model_scopes_readers(cfg):
    """The TPU compiler names its ragged-dot kernels ``ragged-dot-*`` in
    place of the name stack (ragged-dot-none.7 here): the experts' share counts
    them with the ops under ``moe/experts``."""
    ctx = _ctx(cfg, HLO)
    assert run.reader("mla_ms")(ctx) == pytest.approx(4.0)
    assert run.reader("moe_route_ms")(ctx) == pytest.approx(1.0)
    need = deepseek_v2.held_expert_flops_per_token(cfg) * 8192
    assert run.reader("experts_roofline")(ctx) == pytest.approx(
        100 * need / 197e12 / 7e-3)
    # fwd_bwd_ms still counts every forward+backward op, scoped or not
    assert run.reader("fwd_bwd_ms")(ctx) == pytest.approx(13.5)


def test_the_readers_find_nothing_without_the_scopes(cfg):
    """A build without the model's scopes (the parent's, or a dense
    model's) reads nothing, and raises nothing."""
    plain = HLO.replace("/mla/", "/").replace("/moe/route/", "/").replace(
        "/moe/experts/", "/")
    for name in ("mla_ms", "moe_route_ms", "experts_roofline"):
        assert run.reader(name)(_ctx(cfg, plain)) is None
    with open(os.path.join(tiny.DATA, "tiny-2L.json")) as f:
        dense = json.load(f)
    assert run.reader("experts_roofline")(_ctx(dense, HLO)) is None


@pytest.mark.parametrize("changes", [
    {"norm_topk_prob": True}, {"q_lora_rank": 1536},
    {"topk_method": "group_limited_greedy"}, {"routed_scaling_factor": 16},
    {"rope_scaling": {"type": "linear", "factor": 4}},
    {"held_experts": [0, 4]},
], ids=["renormalised", "q_lora", "grouped_topk", "routed_scaling", "rope",
        "held_span"])
def test_what_the_program_cannot_run_is_refused(cfg, changes):
    with pytest.raises(run.BenchError):
        run.model_params(dict(cfg, **changes))
