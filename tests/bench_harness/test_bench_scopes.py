"""The program's names in a trace: the named scopes that split update_ms,
the host spans of TrainerRunner.step, and the idle gaps they name."""
import gzip
import json
import os
import re

import pytest

import _bench_path  # noqa: F401
from _bench_path import ROOT
from bench import layers, scopes, spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PARTS = ("noise_ms", "wire_layout_ms", "prox_update_ms", "consensus_ms")


def _op(dev, name, start, dur):
    return trace.Op(dev, name, start, dur)


def _ctx(table, hlo, steps=1, cell=None):
    return trace.Context(cell=cell or {}, table=table, steps=steps, tokens=0,
                         window_s=0.0, chips=1, device_kind="TPU v5 lite",
                         hlo_text=lambda: hlo)


def _read(metric, ctx):
    from bench import run
    return run.reader(metric)(ctx)


def test_scope_strings_are_the_programs():
    from repro import obs
    assert scopes.SCOPES == obs.SCOPES
    assert (scopes.FWD_BWD, scopes.NOISE, scopes.WIRE_LAYOUT,
            scopes.EXCHANGE, scopes.UPDATE, scopes.CONSENSUS) == (
        obs.FWD_BWD, obs.NOISE, obs.WIRE_LAYOUT, obs.EXCHANGE, obs.UPDATE,
        obs.CONSENSUS)
    assert spans.STEP == obs.STEP
    assert {obs.STEP_PLACE, obs.STEP_CALL} <= {
        obs.STEP + ".place", obs.STEP + ".call"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/prox_lead/update/mul", scopes.UPDATE),
    ("jit(train_step)/fwd_bwd/transpose(jvp(vmap()))/dot_general",
     scopes.FWD_BWD),
    ("jit(train_step)/prox_lead/noise/jit(_uniform)/while/body/add",
     scopes.NOISE),
    ("jit(train_step)/consensus;jit(train_step)/prox_lead/update/sub",
     scopes.CONSENSUS),
    ("jit(train_step)/prox_lead/updates/mul", None),
    ("jit(train_step)/mul", None),
    ("", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/dot_general"}
  %custom-call.2 = u8[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/prox_lead/wire_layout/jit(qinf_quantize_pack)/pallas_call"}
  %custom-call.3 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/prox_lead/wire_layout/jit(qinf_unpack_dequant_mix)/pallas_call"}
  %collective-permute-start.4 = (u8[8]{0}, u8[8]{0}) collective-permute-start(%p), source_target_pairs={{0,1}}, metadata={op_name="jit(train_step)/prox_lead/exchange/ppermute"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/prox_lead/noise/jit(_uniform)/add"}
  %fusion.6 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/prox_lead/wire_layout/concatenate"}
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/prox_lead/update/sub"}
  %fusion.8 = f32[]{0} fusion(%p), kind=kLoop, calls=%f8, metadata={op_name="jit(train_step)/consensus/reduce_sum"}
  %fusion.9 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f9, metadata={op_name="jit(train_step)/prox_lead/exchange/mul"}
  %copy.10 = bf16[8]{0} copy(%p)
}
"""


def test_update_parts_partition_update_ms():
    durs = {"fusion.1": 50, "custom-call.2": 40, "custom-call.3": 30,
            "collective-permute-start.4": 20, "fusion.5": 7, "fusion.6": 5,
            "fusion.7": 11, "fusion.8": 3, "fusion.9": 2, "copy.10": 1}
    ops, t = [], 0
    for _ in range(2):                         # two steps
        for name, d in durs.items():
            ops.append(_op(0, name, t, d * 1_000_000))
            t += d * 1_000_000
    ctx = _ctx(trace.table_from(ops, [], (0,)), HLO, steps=2)
    got = {m: _read(m, ctx) for m in PARTS + ("update_ms",)}
    assert got == pytest.approx({"noise_ms": 7.0, "wire_layout_ms": 5.0,
                                 "prox_update_ms": 11.0, "consensus_ms": 3.0,
                                 "update_ms": 7 + 5 + 11 + 3 + 2 + 1})
    # the wire kernels under wire_layout stay the kernels' time alone
    assert ctx.layer_s_per_step("quant_pack") == pytest.approx(40e-3)
    remainder = got["update_ms"] - sum(got[m] for m in PARTS)
    assert remainder == pytest.approx(2 + 1)   # the exchange's mix, a copy


@pytest.fixture(scope="module")
def chip_trace():
    """A window recorded on a TPU v5e from a program without scopes or
    spans (tests/bench_harness/test_bench_trace.py reads it too)."""
    import tempfile
    d = tempfile.mkdtemp()
    path = os.path.join(d, "tiny.xplane.pb")
    with gzip.open(os.path.join(DATA, "tiny_chip.xplane.pb.gz")) as f:
        with open(path, "wb") as out:
            out.write(f.read())
    with gzip.open(os.path.join(DATA, "tiny_chip_hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    ops, host = trace.read_xplane(path, (0,))
    return path, trace.table_from(ops, host, (0,)), hlo


def test_readers_find_nothing_in_a_program_without_names(chip_trace,
                                                         monkeypatch,
                                                         tmp_path):
    path, table, hlo = chip_trace
    assert spans.read_spans(path) == []
    ctx = _ctx(table, hlo, steps=2, cell={"name": "tiny"})
    assert ctx.layer_s_per_step("other") is not None
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    for m in PARTS + ("host_step_ms",):
        assert _read(m, ctx) is None, m
    assert spans.idle_gaps(table, []) == table.breakdown()["idle_gaps"]


def test_idle_gap_named_by_innermost_program_span():
    ops = [_op(0, "x", 0, 10), _op(0, "y", 100, 10), _op(0, "z", 200, 10),
           _op(0, "w", 400, 10)]
    host = [("bench.dispatch", 5, 100), ("bench.wait", 150, 60),
            ("bench.fence", 300, 120)]
    program = [("repro.step", 8, 95), ("repro.step.place", 9, 5),
               ("repro.step.call", 15, 85), ("repro.step", 305, 40),
               ("repro.step.call", 310, 30)]
    t = trace.table_from(ops, host, (0,))
    assert spans.idle_gaps(t, program) == [
        ["bench.fence/repro.step.call", 190e-9],
        ["bench.wait", 90e-9],
        ["bench.dispatch/repro.step.call", 90e-9]]
    # the harness's own naming is unchanged where no span overlaps
    assert spans.idle_gaps(t, []) == t.breakdown()["idle_gaps"]


# ---- the compiled step of the tiny cells, on the CPU
def _tiny(traffic_name):
    with open(os.path.join(DATA, "tiny-2L.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           traffic_name + ".json")) as f:
        tr = json.load(f)
    tr.update(local_batch=2, seq_len=32, pool_batches=4)
    return cfg, tr


@pytest.fixture(scope="module")
def programs():
    """{traffic: (program, state, pool)}, each step compiled once."""
    from bench import run
    out = {}

    def get(name):
        if name not in out:
            cfg, tr = _tiny(name)
            prog = run.Program(cfg, tr, "tiny." + name, 1)
            state, pool, _ = prog.first_steps(3)
            out[name] = (prog, state, pool)
        return out[name]
    return get


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLED = re.compile(r"\b(calls|to_apply)=%?([\w.\-]+)")
_OPERANDS = re.compile(r"%([\w.\-]+)")


def executed(hlo_text):
    """{instruction: (opcode, op_name, operands)} of the instructions a
    device runs as ops of their own: those of the entry, loop and branch
    computations, not of the computations a fusion calls or a reduction
    applies."""
    by_comp, inlined, comp = {}, set(), None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:                  # a header starts its line; instructions don't
            comp = m.group(1)
            by_comp[comp] = []
            continue
        if comp is not None:
            by_comp[comp].append(line)
            for kind, name in _CALLED.findall(line):
                if kind == "to_apply" or " fusion(" in line:
                    inlined.add(name)
    out = {}
    for name, lines in by_comp.items():
        if name in inlined:
            continue
        for line in lines:
            m = layers._INSTR.match(line)
            if m:
                on = layers._OP_NAME.search(line)
                out[m.group("name")] = (
                    m.group("opcode"), on.group(1) if on else "",
                    _OPERANDS.findall(m.group("rest").split(")")[0]))
    return out


@pytest.mark.parametrize("traffic_name", ["node1.q2", "node1.identity"])
def test_every_update_op_lies_under_one_scope(programs, traffic_name):
    """Every op of the compiled step that carries an op_name and that
    bench/layers.py puts in 'other' lies under exactly one scope, and every
    jvp( op under fwd_bwd.  Left out: parameters and constants, which run
    nothing, and ops that read constants alone: the rope and mask tables,
    which JAX's scan hoists out of the layer loop with its name stack
    reset (so no scope outside the loop body reaches them)."""
    prog, _, _ = programs(traffic_name)
    ex = executed(prog.compiled.as_text())
    meta = {n: v[:2] for n, v in ex.items()}
    consts = {n for n, v in ex.items() if v[0] == "constant"}
    by_scope, stray = {}, []
    for name, (opcode, op_name, operands) in ex.items():
        if not op_name or opcode in ("parameter", "constant"):
            continue
        found = scopes.scopes_in(op_name)
        layer = layers.classify(name, meta)
        if layer == "fwd_bwd":
            assert found == [scopes.FWD_BWD], (name, op_name)
        if layer != "other" or set(operands) <= consts:
            continue
        if len(found) != 1:
            stray.append((name, op_name))
        else:
            by_scope[found[0]] = by_scope.get(found[0], 0) + 1
    assert stray == []
    want = {scopes.UPDATE, scopes.CONSENSUS}
    if traffic_name == "node1.q2":
        want.add(scopes.NOISE)
    assert want <= set(by_scope)


def test_live_trace_of_runner_steps_reads_host_step_ms(programs, tmp_path,
                                                       monkeypatch):
    import jax
    prog, state, pool = programs("node1.q2")
    cell = {"name": "tiny.node1.q2"}
    trace_dir = tmp_path / cell["name"]
    jax.profiler.start_trace(str(trace_dir))
    for batch in pool[3:]:
        state, _ = prog.runner.step(state, batch)
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    got = spans.of_cell(cell["name"])
    steps = [s for s in got if s[0] == spans.STEP]
    assert len(steps) == len(pool) - 3
    calls = [s for s in got if s[0] == "repro.step.call"]
    assert len(calls) == len(steps)
    # each call lies inside its step, on one clock
    for (_, s0, d0), (_, s1, d1) in zip(sorted(steps, key=lambda s: s[1]),
                                        sorted(calls, key=lambda s: s[1])):
        assert s0 <= s1 and s1 + d1 <= s0 + d0
    table = trace.load(str(trace_dir), jax.devices()[:1])
    ms = _read("host_step_ms", _ctx(table, "", steps=len(steps), cell=cell))
    assert ms == pytest.approx(spans.step_ms(got)) and ms > 0


# ---- a scope a reader asks for by name, nested in the program's own
NESTED_HLO = """
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/moe/dispatch/dot_general"}
  %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/fwd_bwd/transpose(jvp(vmap()))/while/body/moe/dispatch/dot_general"}
  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/attention/dot_general"}
  %fusion.4 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f4, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/moe/dispatched/add"}
  %fusion.5 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/prox_lead/update/moe/dispatch/mul"}
  %fusion.6 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f6, metadata={op_name="jit(train_step)/consensus/mul;jit(train_step)/fwd_bwd/jvp(vmap())/moe/dispatch/mul"}
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/fwd_bwd/jvp(vmap())/mul"}
}
"""


@pytest.mark.parametrize("scope,layer,ms", [
    ("moe/dispatch", "fwd_bwd", 1 + 2),     # under fwd_bwd, loop body too
    ("moe", "fwd_bwd", 1 + 2 + 4),          # the whole segment 'moe' only
    ("attention", "fwd_bwd", 3),
    ("moe/dispatch", "other", 5),           # innermost of update's
    (scopes.UPDATE, "other", 5),            # update's own reader keeps it
    (scopes.CONSENSUS, "fwd_bwd", 6),       # the first merged name decides
    (scopes.FWD_BWD, "fwd_bwd", 1 + 2 + 3 + 4 + 7),  # nested ones too
    ("moe/dispatch", "mix", None),
    ("router", "fwd_bwd", None),
])
def test_part_ms_finds_a_scope_by_name(scope, layer, ms):
    ops = [_op(0, f"fusion.{i}", 10 * i * 1_000_000, i * 1_000_000)
           for i in range(1, 8)]
    ctx = _ctx(trace.table_from(ops, [], (0,)), NESTED_HLO)
    got = scopes.part_ms(ctx, scope, layer)
    assert got == (None if ms is None else pytest.approx(ms))
