"""Layer 2: lower (never execute) a built Runner, audit its wire contracts.

The paper's bits-on-wire claims are only as good as what XLA actually puts
on the wire.  ``tests/test_dryrun_small.py`` pins that at a handful of
hand-picked configurations; this module generalizes those assertions into
``audit_*`` functions that run over EVERY golden spec in
``tests/golden_specs/`` — each audit lowers a step through
``jax.jit(...).lower(...).compile()`` on abstract operands, so nothing is
executed, and asserts against the optimized HLO text:

* ``audit_wire_hlo`` — every gossip collective-permute payload is u8;
  exactly ``2 x hops`` of them (one codes + one scales buffer per hop,
  leaf-count independent); their byte volume equals
  ``hops x per_edge_bits / 8 / model_shards`` exactly.  On a model-sharded
  mesh GSPMD adds small non-u8 resharding permutes of its own, which are
  tolerated but must stay byte-dominated by the u8 payloads.
* ``audit_no_f64`` — no f64 op leaks into the sharded path (the trainer is
  bf16/f32 end to end; an f64 usually means a stray python float crossed
  a jit boundary as x64).
* ``audit_no_host_callbacks`` — no host callback / infeed / outfeed inside
  the lowered step: a callback in the scanned trajectory would serialize
  every iteration through python.

``audit_spec`` dispatches on the spec kind (sharded trainers additionally
re-audited on both (8, 1) and (4, 2) meshes); ``audit_spec_dir`` drives a
whole golden-spec directory.  The pure ``audit_*`` functions take HLO text
+ expected numbers so tests can feed synthetic HLO for injected
violations.  Device counts: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in a fresh process
(the ``python -m repro.check`` driver spawns one) — importing this module
does not require it, only the trainer audits do.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re
from typing import List, Optional, Sequence, Tuple

GateFinding = Tuple[str, bool, str]          # (claim, ok, detail)

# the shape an HLO op assigns to a collective-permute(-start) result
CP_RE = re.compile(r'=\s*((?:\([^)]*\))|(?:[\w\[\],.{}]+))\s+'
                   r'collective-permute(?:-start)?\(')
F64_RE = re.compile(r'\bf64\[')
HOST_RE = re.compile(r'custom-call[^\n]*callback|\binfeed\(|\boutfeed\(')


LAYOUT_RE = re.compile(r'\{[^{}]*\}')


def collective_permute_shapes(hlo: str) -> List[str]:
    """Payload shape strings of every collective-permute in ``hlo``.

    Layouts are dropped first (a TPU layout such as ``{0:T(1024)(4,1)}``
    holds parentheses).  An asynchronous ``collective-permute-start``
    yields a tuple (operand, result, contexts...): its payload is the
    operand."""
    out = []
    for m in CP_RE.finditer(LAYOUT_RE.sub("", hlo)):
        shape = m.group(1)
        if m.group(0).rstrip("(").endswith("-start") and \
                shape.startswith("("):
            shape = shape[1:].split(",", 1)[0].strip()
        out.append(shape)
    return out


def _u8_bytes(shapes: Sequence[str]) -> float:
    from repro.obs import roofline
    return sum(roofline._shape_bytes(c) for c in shapes
               if c.startswith("u8["))


def audit_wire_hlo(hlo: str, *, hops: int, per_edge_bits: float,
                   model_shards: int = 1,
                   name: str = "wire") -> List[GateFinding]:
    """The three gossip-wire contracts against one compiled-HLO text."""
    cps = collective_permute_shapes(hlo)
    u8 = [c for c in cps if c.startswith("u8[")]
    other = [c for c in cps if not c.startswith("u8[")]
    out: List[GateFinding] = []
    out.append((f"{name}: collective count == 2 x hops",
                len(u8) == 2 * hops,
                f"{len(u8)} u8 collective-permutes vs 2 x {hops} hops"))
    if model_shards == 1:
        out.append((f"{name}: every collective-permute payload is u8",
                    not other, f"non-u8: {other[:5]}"))
    else:
        from repro.obs import roofline
        other_b = sum(roofline._shape_bytes(c) for c in other)
        out.append((f"{name}: u8 payloads dominate GSPMD reshard bytes",
                    _u8_bytes(u8) > 4 * other_b,
                    f"u8 {_u8_bytes(u8):.0f}B vs other {other_b:.0f}B"))
    predicted = hops * per_edge_bits / 8 / model_shards
    got = _u8_bytes(u8)
    out.append((f"{name}: ppermute bytes == bucketed payload accounting",
                got == predicted,
                f"HLO {got:.0f}B vs plan {predicted:.0f}B "
                f"(hops={hops}, per_edge={per_edge_bits}b, "
                f"shards={model_shards})"))
    return out


def audit_no_f64(hlo: str, *, name: str = "step") -> List[GateFinding]:
    m = F64_RE.search(hlo)
    return [(f"{name}: no f64 in the lowered step", m is None,
             "" if m is None else hlo[m.start():m.start() + 60])]


def audit_no_host_callbacks(hlo: str, *,
                            name: str = "step") -> List[GateFinding]:
    m = HOST_RE.search(hlo)
    return [(f"{name}: no host callbacks in the lowered step", m is None,
             "" if m is None else hlo[m.start():m.start() + 80])]


# --- lowering drivers (one per engine) -------------------------------------

def _named_shardings(mesh, tree):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_trainer(spec) -> Tuple[str, dict]:
    """Compiled-HLO text + wire facts for a sharded-engine spec.

    Mirrors the ``tests/test_dryrun_small.py`` recipe: abstract state from
    the trainer, spec-shaped abstract batch, explicit in_shardings, then
    ``lower(...).compile()`` — no training step runs."""
    import jax
    from repro import api, compat
    from repro.configs import shapes as shp
    from repro.models.sharding import model_axis_size
    from repro.netsim import metrics as netsim_metrics

    runner = api.build(spec)
    tr = runner.trainer
    mesh = runner.mesh
    if mesh is None:
        raise ValueError(f"{spec.name}: trainer built meshless "
                         f"(need >= prod(mesh) devices)")
    state = tr.abstract_state()
    ms = spec.model
    shape = shp.InputShape("audit", ms.seq_len,
                           spec.n_nodes * ms.local_batch, "train")
    batch = shp.train_input_specs(tr.mcfg, shape, spec.n_nodes)
    with compat.set_mesh(mesh):
        hlo = jax.jit(
            tr.train_step,
            in_shardings=(
                _named_shardings(mesh, tr.state_specs(("data",))),
                _named_shardings(mesh, tr.batch_specs(batch, ("data",)))),
        ).lower(state, batch).compile().as_text()
    facts = {"model_shards": model_axis_size(mesh)}
    if tr.plan is not None:
        leaves = jax.tree_util.tree_leaves(state.plead.X)
        facts["hops"] = len(tr.plan.hops)
        facts["per_edge_bits"] = (
            netsim_metrics.bucketed_payload_bits(tr, leaves)
            if tr.tcfg.wire_mode == "bucketed"
            else netsim_metrics.sharded_payload_bits(tr, leaves))
    return hlo, facts


def _lower_scalar_runner(runner) -> str:
    """Compiled HLO of one dense/netsim step on abstract operands."""
    import jax
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(runner.init_state, key)
    step = getattr(runner, "_jit_step", None)
    if step is None:
        step = jax.jit(runner.step)
    return step.lower(state, key).compile().as_text()


def _lower_sweep_runner(runner) -> str:
    import jax
    state = jax.eval_shape(runner.init_state)
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), runner.n_points))
    args = runner.step_args(state, keys)
    return runner.point_step_fn().lower(*args).compile().as_text()


def _mesh_variants(spec) -> List:
    """The sharded spec on both canonical mesh shapes (its own shape kept
    as-is, a meshless spec realized on both)."""
    variants = []
    for shape in ((8, 1), (4, 2)):
        if spec.execution.mesh == shape and spec.n_nodes == shape[0]:
            variants.append(spec)
            continue
        variants.append(dataclasses.replace(
            spec, name=f"{spec.name}@{shape[0]}x{shape[1]}",
            n_nodes=shape[0],
            execution=dataclasses.replace(spec.execution, mesh=shape)))
    return variants


def audit_spec(spec) -> List[GateFinding]:
    """All contract findings for one spec (Experiment or Sweep)."""
    import jax
    from repro import api

    out: List[GateFinding] = []
    if isinstance(spec, api.SweepSpec):
        runner = api.build(spec)
        hlo = _lower_sweep_runner(runner)
        out.extend(audit_no_host_callbacks(hlo, name=spec.name))
        return out

    engine = spec.execution.engine
    if engine == "sharded":
        for variant in _mesh_variants(spec):
            hlo, facts = lower_trainer(variant)
            nm = variant.name
            if "hops" in facts:
                out.extend(audit_wire_hlo(
                    hlo, hops=facts["hops"],
                    per_edge_bits=facts["per_edge_bits"],
                    model_shards=facts["model_shards"], name=nm))
            out.extend(audit_no_f64(hlo, name=nm))
            out.extend(audit_no_host_callbacks(hlo, name=nm))
        return out

    runner = api.build(spec)
    hlo = _lower_scalar_runner(runner)
    out.extend(audit_no_host_callbacks(hlo, name=spec.name))
    return out


def load_spec(path: pathlib.Path):
    from repro import api
    text = pathlib.Path(path).read_text()
    cls = api.SweepSpec if "base" in json.loads(text) else api.ExperimentSpec
    return cls.from_json(text)


def audit_spec_dir(spec_dir: pathlib.Path,
                   only: Optional[Sequence[str]] = None) -> List[GateFinding]:
    """Contract-audit every ``*.json`` golden spec under ``spec_dir``."""
    spec_dir = pathlib.Path(spec_dir)
    files = sorted(spec_dir.glob("*.json"))
    out: List[GateFinding] = []
    if not files:
        return [(f"contracts: no golden specs under {spec_dir}", False, "")]
    for f in files:
        if only and f.stem not in only:
            continue
        try:
            out.extend(audit_spec(load_spec(f)))
        except Exception as e:                    # noqa: BLE001
            out.append((f"{f.stem}: contract audit raised", False,
                        f"{type(e).__name__}: {e}"))
    return out
