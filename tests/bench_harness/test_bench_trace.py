"""The reduction from a trace's ops to busy, idle and per-layer time."""
import pytest

import _bench_path  # noqa: F401
from bench import layers, trace


def _op(dev, name, start, dur):
    return trace.Op(dev, name, start, dur)


def test_busy_union_merges_overlaps_per_device():
    ops = [_op(0, "a", 0, 10), _op(0, "b", 5, 10), _op(0, "c", 30, 5),
           _op(1, "a", 0, 40)]
    t = trace.table_from(ops, [("bench.dispatch", 0, 2),
                               ("bench.fence", 35, 5)], (0, 1))
    assert t.busy_ns(0) == 15 + 5
    assert t.busy_ns(1) == 40
    assert t.window_s == pytest.approx(40e-9)
    assert t.busy_s() == pytest.approx((20 + 40) / 2 * 1e-9)
    assert t.gaps(0) == [(15, 30)]
    bd = t.breakdown()
    assert bd["device_ops"][0][0] == "a"
    assert bd["idle_gaps"] == [["no host phase", 15e-9]]


def test_idle_gap_named_by_overlapping_host_phase():
    ops = [_op(0, "x", 0, 10), _op(0, "y", 100, 10)]
    host = [("bench.dispatch", 0, 20), ("bench.wait", 20, 85)]
    t = trace.table_from(ops, host, (0,))
    assert t.breakdown()["idle_gaps"] == [["bench.wait", 90e-9]]


HLO = """
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_type="dot_general" op_name="jit(train_step)/jvp(vmap())/while/body/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_type="dot_general" op_name="jit(train_step)/transpose(jvp(vmap()))/dot_general"}
  %custom-call.3 = u8[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jit(qinf_quantize_pack)/pallas_call"}
  %custom-call.4 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jit(qinf_unpack_dequant_mix)/pallas_call"}
  %collective-permute-start.5 = (u8[8]{0}, u8[8]{0}) collective-permute-start(%p), source_target_pairs={{0,1}}
  %fusion.6 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f6, metadata={op_type="sub" op_name="jit(train_step)/sub"}
}
"""


def test_layer_attribution_from_hlo_metadata():
    meta = layers.hlo_meta(HLO)
    got = {n: layers.classify(n, meta) for n in
           ("fusion.1", "fusion.2", "custom-call.3", "custom-call.4",
            "collective-permute-start.5", "fusion.6", "unknown.7",
            "collective-permute-done.5")}
    assert got == {"fusion.1": "fwd_bwd", "fusion.2": "fwd_bwd",
                   "custom-call.3": "quant_pack", "custom-call.4": "mix",
                   "collective-permute-start.5": "permute",
                   "fusion.6": "other", "unknown.7": "other",
                   "collective-permute-done.5": "permute"}


def test_kernel_time_per_step():
    ops = [_op(0, "custom-call.3", 0, 3_000_000),
           _op(0, "custom-call.3", 10_000_000, 1_000_000),
           _op(0, "fusion.1", 4_000_000, 5_000_000)]
    ctx = trace.Context(cell={}, table=trace.table_from(ops, [], (0,)),
                        steps=2, tokens=0, window_s=0.0, chips=1,
                        device_kind="TPU v5 lite", hlo_text=lambda: HLO)
    assert ctx.layer_s_per_step("quant_pack") == pytest.approx(2e-3)
    assert ctx.layer_s_per_step("fwd_bwd") == pytest.approx(2.5e-3)
    assert ctx.layer_s_per_step("permute") is None


NAMED_HLO = HLO.replace("}\n", "", 1).rstrip().rstrip("}") + """
  %custom-call.7 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/jit(moe_gmm)/pallas_call"}
  %custom-call.8 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(vmap()))/jit(moe_gmm)/pallas_call"}
  %while.9 = bf16[8]{0} while(%p), condition=%c, body=%b, metadata={op_name="jit(train_step)/jit(moe_gmm)/while"}
}
"""


@pytest.mark.parametrize("name,want", [
    ("jit(moe_gmm)", 2e-3 + 3e-3),           # forward and backward, no loop
    ("jit(qinf_quantize_pack)", 4e-3),
    ("jit(qinf_unpack_dequant_mix)", 5e-3),
    ("jit(no_such_kernel)", None),
])
def test_named_time_per_step_sums_a_kernels_ops(name, want):
    durs = {"custom-call.7": 2, "custom-call.8": 3, "while.9": 100,
            "custom-call.3": 4, "custom-call.4": 5, "fusion.1": 6}
    ops = [_op(0, n, 200 * i * 1_000_000, d * 1_000_000)
           for i, (n, d) in enumerate(durs.items())]
    ops += [_op(0, n, o.start_ns + 100_000_000_000, o.dur_ns)
            for o in ops for n in [o.name]]        # a second step
    ctx = trace.Context(cell={}, table=trace.table_from(ops, [], (0,)),
                        steps=2, tokens=0, window_s=0.0, chips=1,
                        device_kind="TPU v5 lite",
                        hlo_text=lambda: NAMED_HLO)
    got = ctx.named_s_per_step(name)
    assert got == (None if want is None else pytest.approx(want))
    assert ctx.layer_s_per_step("fwd_bwd") == pytest.approx(6e-3 + 5e-3)


# ---- a trace recorded on a TPU v5e: two steps of a tiny qwen3-layout node
# (d_model 128, 2 layers, 2 x 64 tokens, QInf 2-bit bucketed), with the
# compiled step's HLO text
import gzip  # noqa: E402
import os  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("xplane")
    path = d / "tiny.xplane.pb"
    with gzip.open(os.path.join(DATA, "tiny_chip.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    ops, host = trace.read_xplane(str(path), (0,))
    with gzip.open(os.path.join(DATA, "tiny_chip_hlo.txt.gz"), "rt") as f:
        meta = layers.hlo_meta(f.read())
    return trace.table_from(ops, host, (0,)), meta


def _sweep_busy(ops):
    """Busy time by a sweep over interval end points."""
    events = sorted([(o.start_ns, 1) for o in ops]
                    + [(o.start_ns + o.dur_ns, -1) for o in ops])
    busy, depth, last = 0, 0, None
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_trace_busy_and_idle(chip_trace):
    t, _ = chip_trace
    assert len(t.ops) == 1394 and all(o.device == 0 for o in t.ops)
    assert t.busy_ns(0) == _sweep_busy(t.ops) == 331284
    assert t.window_s == pytest.approx(1.236889e-3)
    idle = 1 - t.busy_s() / t.window_s
    assert idle == pytest.approx(1 - 331284 / 1236889)


def test_recorded_trace_layers(chip_trace):
    t, meta = chip_trace
    assert all(o.name in meta for o in t.ops)
    by = {}
    for o in t.ops:
        layer = layers.classify(o.name, meta)
        by[layer] = by.get(layer, 0) + o.dur_ns
        if o.name.startswith("qinf_quantize_pack_blocks"):
            assert layer == "quant_pack"
        if o.name.startswith("qinf_unpack_dequant_mix_blocks"):
            assert layer == "mix"
        if o.name.startswith("while"):
            assert layer == "container"
    assert by == {"other": 85207, "fwd_bwd": 48474, "container": 39210,
                  "quant_pack": 114610, "mix": 82549}
    ctx = trace.Context(cell={}, table=t, steps=2, tokens=0, window_s=0.0,
                        chips=1, device_kind="TPU v5 lite",
                        hlo_text=lambda: "", _meta=meta)
    assert ctx.layer_s_per_step("quant_pack") == pytest.approx(114610e-9 / 2)
    assert ctx.layer_s_per_step("mix") == pytest.approx(82549e-9 / 2)
    assert ctx.layer_s_per_step("permute") is None
    assert ctx.named_s_per_step("jit(qinf_quantize_pack)") == pytest.approx(
        114610e-9 / 2)
    assert ctx.named_s_per_step("jit(qinf_unpack_dequant_mix)") == \
        pytest.approx(82549e-9 / 2)
    ops = dict(ctx.breakdown()["device_ops"])
    assert not any("container" in k for k in ops)
    assert any(k.startswith("qinf_quantize_pack_blocks") for k in ops)
