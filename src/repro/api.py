"""One declarative experiment API across dense, sharded, and netsim execution.

The paper's whole point is isolating *algorithm x compressor x topology*
trade-offs; this module is the single composable layer every entry point
builds that grid through:

* :class:`ExperimentSpec` — a frozen, JSON-round-trippable description of an
  experiment: nested :class:`AlgorithmSpec` (with per-iteration schedules for
  eta/alpha/gamma), :class:`CompressorSpec`, :class:`TopologySpec` (static
  graph or netsim schedule), :class:`FaultSpec`, :class:`ProxSpec`,
  :class:`OracleSpec` / :class:`ModelSpec` (the objective: a finite-sum
  problem or an NN), and :class:`ExecutionSpec` (engine + wire knobs).
  ``spec == ExperimentSpec.from_json(spec.to_json())`` always holds.

* ``build(spec) -> Runner`` — one protocol (``init_state(key)``,
  ``step(state, batch_or_key)``, ``run(...)``, ``metrics_fns``,
  ``state_specs``) implemented by three adapters:

  - :class:`DenseRunner`   — ProxLEAD / LEAD / NIDS and every
    ``repro.core.baselines`` algorithm over a DenseMixer.  Its ``run`` is THE
    shared driver loop (the per-class ``Baseline.run`` / ``ProxLEAD.run``
    loops are gone).
  - :class:`NetsimRunner`  — ``repro.netsim.engine.simulate``: time-varying
    schedules + fault injection with exact bits-on-wire accounting.
  - :class:`TrainerRunner` — ``repro.optim.DecentralizedTrainer``: the
    GSPMD/shard_map NN path (dense or neighbor gossip backend, bucketed
    wire).  Checkpoints written through the runner embed the originating
    spec, so ``load_checkpoint`` rebuilds the exact experiment.

* :class:`SweepSpec` — a declarative experiment *grid*: one base
  ExperimentSpec plus :class:`AxisSpec` axes (``seed``, the constant/
  harmonic schedule fields, ``compressor.bits``, ...).  ``build(sweep)``
  resolves it to a ``repro.sweep.SweepRunner`` that executes the whole
  grid as ONE jitted computation, every point bit-for-bit equal to its
  serial ``build(point).run`` (see ``docs/ARCHITECTURE.md``).

Every component is resolved through ``repro.registry`` name->factory tables,
so a new compressor/topology/algorithm registered with
``@register_compressor`` etc. is immediately reachable from specs, CLIs, and
golden files without touching any call site.

Construction is bit-for-bit faithful: a spec-built runner produces states
identical to the hand-built ``DecentralizedTrainer`` / dense ``ProxLEAD``
paths (tested in tests/test_api.py and tests/test_api_mesh.py).

CLI sanity gate::

    PYTHONPATH=src python -m repro.api --check tests/golden_specs
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, registry
# imported for their registration side effects (compressors, proxes,
# oracles, topologies, schedules, faults, algorithms, problems)
from repro.core import baselines as _baselines            # noqa: F401
from repro.core import compression as _compression        # noqa: F401
from repro.core import oracles as _oracles                # noqa: F401
from repro.core import prox as _prox                      # noqa: F401
from repro.core import prox_lead as _prox_lead            # noqa: F401
from repro.core import topology as topo_mod
from repro.core.comm import DenseMixer
from repro.data import synthetic as _synthetic            # noqa: F401
from repro.netsim import engine as netsim_engine
from repro.netsim import metrics as netsim_metrics
from repro.netsim import schedule as sched_mod

tmap = jax.tree_util.tree_map


# ===========================================================================
# Spec tree
# ===========================================================================

def _norm_params(params) -> dict:
    """Normalize a params mapping so construction-time and JSON-loaded specs
    compare equal: lists become tuples (JSON has no tuple type)."""
    def norm(v):
        if isinstance(v, (list, tuple)):
            return tuple(norm(x) for x in v)
        if isinstance(v, Mapping):
            return {k: norm(x) for k, x in v.items()}
        return v

    return {k: norm(v) for k, v in dict(params or {}).items()}


def _to_jsonable(obj):
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _to_jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(v) for v in obj]
    if isinstance(obj, Mapping):
        return {k: _to_jsonable(v) for k, v in obj.items()}
    return obj


@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """A scalar hyperparameter as a function of the iteration k.

    ``constant`` — ``value`` every step (what the sharded trainer requires).
    ``harmonic`` — ``value * t0 / (k + t0)``: the diminishing-stepsize shape
    of Theorem 7 (pick t0 = B to recover the paper's eta^k envelope).
    """
    kind: str = "constant"
    value: float = 0.0
    t0: float = 1.0

    @classmethod
    def coerce(cls, v) -> "ScheduleSpec":
        if isinstance(v, cls):
            return v
        if isinstance(v, Mapping):
            return cls(**v)
        return cls("constant", float(v))

    def resolve(self):
        """A float (constant) or a callable k -> float, as ProxLEAD takes."""
        if self.kind == "constant":
            return float(self.value)
        if self.kind == "harmonic":
            v, t0 = float(self.value), float(self.t0)
            return lambda k: v * t0 / (k + t0)
        raise ValueError(f"unknown schedule kind {self.kind!r}; "
                         f"have ['constant', 'harmonic']")

    def constant(self) -> float:
        if self.kind != "constant":
            raise ValueError(
                f"a {self.kind!r} schedule cannot run here: the sharded "
                f"trainer takes constant eta/alpha/gamma only")
        return float(self.value)


def constant(v: float) -> ScheduleSpec:
    return ScheduleSpec("constant", float(v))


@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    """prox_lead | lead | nids | dgd | pg_extra | nids_independent | choco |
    lessbit | centralized (see ``registry.names('algorithm')``)."""
    name: str = "prox_lead"
    eta: ScheduleSpec = dataclasses.field(default_factory=lambda: constant(0.05))
    alpha: ScheduleSpec = dataclasses.field(default_factory=lambda: constant(0.5))
    gamma: ScheduleSpec = dataclasses.field(default_factory=lambda: constant(1.0))
    params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for f in ("eta", "alpha", "gamma"):
            object.__setattr__(self, f, ScheduleSpec.coerce(getattr(self, f)))
        object.__setattr__(self, "params", _norm_params(self.params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "AlgorithmSpec":
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class CompressorSpec:
    """identity | qinf | randk | topk | any ``@register_compressor`` name."""
    name: str = "qinf"
    params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "params", _norm_params(self.params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "CompressorSpec":
        return cls(**d)

    def build(self):
        return registry.make("compressor", self.name, **self.params)


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """A static graph (``schedule='static'``) or a netsim schedule cycling
    over ``graph`` as its base topology.

    ``params`` feeds the graph builder (e.g. ``self_weight`` for ring,
    ``rows`` for torus2d); ``schedule_params`` feeds the schedule factory
    (e.g. ``drop``/``sticky`` for markov_drop, ``with_`` for alternating).
    """
    graph: str = "ring"
    schedule: str = "static"
    rounds: int = 32
    params: dict = dataclasses.field(default_factory=dict)
    schedule_params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "params", _norm_params(self.params))
        object.__setattr__(self, "schedule_params",
                           _norm_params(self.schedule_params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "TopologySpec":
        return cls(**d)

    def build_graph(self, n: int) -> topo_mod.Topology:
        return topo_mod.make_topology(self.graph, n, **self.params)

    def build_schedule(self, n: int, seed: int = 0):
        return sched_mod.make_schedule(
            self.schedule, n, base=self.graph, rounds=self.rounds, seed=seed,
            **self.schedule_params)


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """linkdrop | straggler | noise (repro.netsim.faults)."""
    name: str = "linkdrop"
    params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "params", _norm_params(self.params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "FaultSpec":
        return cls(**d)

    def build(self):
        return registry.make("fault", self.name, **self.params)


@dataclasses.dataclass(frozen=True)
class ProxSpec:
    """none | l1 | l2sq | elastic_net | group_lasso | nonneg."""
    name: str = "none"
    params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "params", _norm_params(self.params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "ProxSpec":
        return cls(**d)

    def build(self):
        return registry.make("prox", self.name, **self.params)


@dataclasses.dataclass(frozen=True)
class OracleSpec:
    """The finite-sum objective for the dense/netsim engines: a registered
    ``problem`` factory plus the SGO sampling scheme over it."""
    name: str = "full"               # full | sgd | lsvrg | saga
    problem: str = "logreg"          # registry.names('problem')
    params: dict = dataclasses.field(default_factory=dict)
    problem_params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        object.__setattr__(self, "params", _norm_params(self.params))
        object.__setattr__(self, "problem_params",
                           _norm_params(self.problem_params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "OracleSpec":
        return cls(**d)

    def build_problem(self, n_nodes: int):
        """-> (FiniteSumProblem, X0 stacked zeros)."""
        return registry.make("problem", self.problem, n_nodes=n_nodes,
                             **self.problem_params)

    def build(self, problem):
        return registry.make("oracle", self.name, problem=problem,
                             **self.params)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The NN objective for the sharded engine (repro.configs arch ids)."""
    arch: str = "qwen3-1.7b"
    full: bool = False               # True -> non-reduced production config
    n_layers: int = 2
    d_model: int = 256
    local_batch: int = 4
    seq_len: int = 64
    params: dict = dataclasses.field(default_factory=dict)  # cfg overrides

    def __post_init__(self):
        object.__setattr__(self, "params", _norm_params(self.params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "ModelSpec":
        return cls(**d)

    def build(self):
        from repro import configs
        cfg = configs.get(self.arch)
        if not self.full:
            cfg = cfg.reduced(n_layers=self.n_layers, d_model=self.d_model)
        overrides = dict(self.params)
        if isinstance(overrides.get("dtype"), str):
            overrides["dtype"] = jnp.dtype(overrides["dtype"]).type
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        return cfg


@dataclasses.dataclass(frozen=True)
class ExecutionSpec:
    """How the experiment executes.

    ``engine``   — dense | netsim | sharded (see module docstring).
    ``backend``  — sharded-engine gossip backend: dense | neighbor | ring.
    ``mesh``     — optional (data, model) mesh shape, e.g. (8, 1); built via
                   repro.compat when ``build`` is not handed a mesh.
    ``params``   — extra TrainerConfig knobs for the sharded engine
                   (scales_bf16, shard_aligned_blocks, tp_ways, aux_weight,
                   precondition, adam_*) — validated against TrainerConfig's
                   fields, unknown keys raise.
    """
    engine: str = "dense"
    backend: str = "dense"
    wire_mode: str = "bucketed"
    pack_mode: str = "lastdim"
    mesh: Optional[Tuple[int, int]] = None
    params: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.mesh is not None:
            object.__setattr__(self, "mesh", tuple(int(x) for x in self.mesh))
        object.__setattr__(self, "params", _norm_params(self.params))

    @classmethod
    def from_dict(cls, d: Mapping) -> "ExecutionSpec":
        return cls(**d)


_NESTED = {"algorithm": AlgorithmSpec, "compressor": CompressorSpec,
           "topology": TopologySpec, "prox": ProxSpec, "oracle": OracleSpec,
           "model": ModelSpec, "execution": ExecutionSpec}


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """The full declarative experiment: algorithm x compressor x topology x
    faults x objective x execution.  Frozen and JSON-round-trippable
    (``spec == ExperimentSpec.from_json(spec.to_json())`` always holds).

    Fields: ``name`` (label only), ``n_nodes``, ``steps``, ``seed`` (run
    PRNG chain), ``fault_seed`` (netsim fault draws), nested
    :class:`AlgorithmSpec` / :class:`CompressorSpec` / :class:`TopologySpec`
    / ``faults`` (tuple of :class:`FaultSpec`) / :class:`ProxSpec` /
    :class:`OracleSpec` (dense+netsim objective) or :class:`ModelSpec`
    (sharded NN objective) / :class:`ExecutionSpec` (engine + wire knobs).
    Resolve with :func:`build`; compare with :meth:`diff`; persist with
    :meth:`save` / :meth:`load`."""
    name: str = "experiment"
    n_nodes: int = 8
    steps: int = 200
    seed: int = 0
    fault_seed: int = 0
    algorithm: AlgorithmSpec = dataclasses.field(default_factory=AlgorithmSpec)
    compressor: CompressorSpec = dataclasses.field(
        default_factory=CompressorSpec)
    topology: TopologySpec = dataclasses.field(default_factory=TopologySpec)
    faults: Tuple[FaultSpec, ...] = ()
    prox: ProxSpec = dataclasses.field(default_factory=ProxSpec)
    oracle: Optional[OracleSpec] = None
    model: Optional[ModelSpec] = None
    execution: ExecutionSpec = dataclasses.field(default_factory=ExecutionSpec)

    def __post_init__(self):
        for f, cls in _NESTED.items():
            v = getattr(self, f)
            if isinstance(v, Mapping):
                object.__setattr__(self, f, cls.from_dict(v))
        faults = tuple(FaultSpec.from_dict(f) if isinstance(f, Mapping) else f
                       for f in self.faults)
        object.__setattr__(self, "faults", faults)

    # --- serialization ----------------------------------------------------
    def to_dict(self) -> dict:
        return _to_jsonable(self)

    def to_json(self, indent: int = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Mapping) -> "ExperimentSpec":
        return cls(**dict(d))

    @classmethod
    def from_json(cls, text: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(text))

    def save(self, path) -> pathlib.Path:
        p = pathlib.Path(path)
        p.write_text(self.to_json() + "\n")
        return p

    @classmethod
    def load(cls, path) -> "ExperimentSpec":
        return cls.from_json(pathlib.Path(path).read_text())

    # --- comparison -------------------------------------------------------
    def diff(self, other: "ExperimentSpec") -> Dict[str, Tuple[Any, Any]]:
        """Dotted-path map of every field that differs: path -> (self,
        other).  Empty dict == equal specs."""
        def flat(prefix, v, out):
            if isinstance(v, Mapping):
                keys = set(v)
                for k in sorted(keys):
                    flat(f"{prefix}.{k}" if prefix else str(k), v[k], out)
            elif isinstance(v, list):
                out[prefix] = tuple(json.dumps(x, sort_keys=True) for x in v)
            else:
                out[prefix] = v

        a, b = {}, {}
        flat("", self.to_dict(), a)
        flat("", other.to_dict(), b)
        out = {}
        for k in sorted(set(a) | set(b)):
            if a.get(k, _MISSING) != b.get(k, _MISSING):
                out[k] = (a.get(k), b.get(k))
        return out

    # --- legacy-flag adapter ----------------------------------------------
    @classmethod
    def from_flags(cls, args, *, engine: Optional[str] = None,
                   **overrides) -> "ExperimentSpec":
        """Build a spec from an argparse.Namespace carrying the historical
        launch flags (train.py / simulate.py / dryrun.py names are all
        understood; missing attributes fall back to spec defaults).  The old
        flags are thereby aliases for spec fields — one flag->spec layer for
        every entry point."""
        return _spec_from_flags(cls, args, engine=engine, **overrides)


_MISSING = object()


# ===========================================================================
# SweepSpec: a grid of ExperimentSpecs as one declarative object
# ===========================================================================

#: axis paths a SweepSpec understands (the same table repro.sweep enforces)
SWEEP_AXIS_PATHS = (
    "seed", "fault_seed",
    "algorithm.eta[.value|.t0]", "algorithm.alpha[.value|.t0]",
    "algorithm.gamma[.value|.t0]",
    "algorithm.params.<field>", "compressor.bits",
)

_AXIS_SCHED = {"algorithm.eta": ("eta", "value"),
               "algorithm.eta.value": ("eta", "value"),
               "algorithm.eta.t0": ("eta", "t0"),
               "algorithm.alpha": ("alpha", "value"),
               "algorithm.alpha.value": ("alpha", "value"),
               "algorithm.alpha.t0": ("alpha", "t0"),
               "algorithm.gamma": ("gamma", "value"),
               "algorithm.gamma.value": ("gamma", "value"),
               "algorithm.gamma.t0": ("gamma", "t0")}


def set_axis_value(spec: "ExperimentSpec", path: str,
                   value) -> "ExperimentSpec":
    """``spec`` with the sweep-axis ``path`` set to ``value`` — the single
    place axis paths are interpreted, shared by ``SweepSpec.points()`` and
    the ``--axis`` CLI.  Unknown paths raise listing the supported axes."""
    if path == "seed":
        return dataclasses.replace(spec, seed=int(value))
    if path == "fault_seed":
        return dataclasses.replace(spec, fault_seed=int(value))
    if path in _AXIS_SCHED:
        field, attr = _AXIS_SCHED[path]
        sched = dataclasses.replace(getattr(spec.algorithm, field),
                                    **{attr: float(value)})
        algorithm = dataclasses.replace(spec.algorithm, **{field: sched})
        return dataclasses.replace(spec, algorithm=algorithm)
    if path.startswith("algorithm.params."):
        name = path[len("algorithm.params."):]
        params = dict(spec.algorithm.params)
        params[name] = value
        algorithm = dataclasses.replace(spec.algorithm, params=params)
        return dataclasses.replace(spec, algorithm=algorithm)
    if path in ("compressor.bits", "compressor.params.bits"):
        params = dict(spec.compressor.params)
        params["bits"] = int(value)
        return dataclasses.replace(
            spec, compressor=dataclasses.replace(spec.compressor,
                                                 params=params))
    raise ValueError(f"unknown sweep axis {path!r}; supported axes: "
                     f"{SWEEP_AXIS_PATHS}")


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """One sweep axis: a supported ``path`` (see :data:`SWEEP_AXIS_PATHS`)
    and the numeric values it takes."""
    path: str
    values: Tuple = ()

    def __post_init__(self):
        object.__setattr__(self, "values", tuple(self.values))
        if not self.values:
            raise ValueError(f"axis {self.path!r} needs at least one value")

    @classmethod
    def from_dict(cls, d: Mapping) -> "AxisSpec":
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A declarative experiment grid: one ``base`` :class:`ExperimentSpec`
    plus :class:`AxisSpec` axes whose cartesian product (later axes fastest)
    expands to the per-point specs.  Frozen and JSON-round-trippable like
    ExperimentSpec; ``build(sweep_spec)`` resolves it to a
    ``repro.sweep.SweepRunner`` that executes the whole grid as ONE jitted,
    vmapped computation — every point bit-for-bit equal to its serial
    ``build(point).run`` (tests/test_sweep.py)."""
    name: str = "sweep"
    base: "ExperimentSpec" = dataclasses.field(
        default_factory=lambda: ExperimentSpec())
    axes: Tuple[AxisSpec, ...] = ()

    def __post_init__(self):
        if isinstance(self.base, Mapping):
            object.__setattr__(self, "base",
                               ExperimentSpec.from_dict(self.base))
        axes = tuple(AxisSpec.from_dict(a) if isinstance(a, Mapping) else a
                     for a in self.axes)
        object.__setattr__(self, "axes", axes)

    @property
    def n_points(self) -> int:
        n = 1
        for a in self.axes:
            n *= len(a.values)
        return n

    def points(self) -> Tuple["ExperimentSpec", ...]:
        """Expand the grid: cartesian product of the axes over ``base``,
        later axes varying fastest; each point is named
        ``<base.name>@path=value,...``."""
        import itertools
        out = []
        for combo in itertools.product(*(a.values for a in self.axes)):
            p = self.base
            tags = []
            for a, v in zip(self.axes, combo):
                p = set_axis_value(p, a.path, v)
                tags.append(f"{a.path}={v:g}" if isinstance(v, float)
                            else f"{a.path}={v}")
            if tags:
                p = dataclasses.replace(p, name=f"{self.base.name}@"
                                        + ",".join(tags))
            out.append(p)
        return tuple(out)

    # --- serialization (same conventions as ExperimentSpec) ---------------
    def to_dict(self) -> dict:
        return _to_jsonable(self)

    def to_json(self, indent: int = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Mapping) -> "SweepSpec":
        return cls(**dict(d))

    @classmethod
    def from_json(cls, text: str) -> "SweepSpec":
        return cls.from_dict(json.loads(text))

    def save(self, path) -> pathlib.Path:
        p = pathlib.Path(path)
        p.write_text(self.to_json() + "\n")
        return p

    @classmethod
    def load(cls, path) -> "SweepSpec":
        return cls.from_json(pathlib.Path(path).read_text())


def parse_axis(arg: str) -> AxisSpec:
    """CLI axis shorthand ``path=v1,v2,...`` or ``path=lo:hi[:step]``
    (integer range, half-open like Python's) -> AxisSpec.  Examples:
    ``seed=0:16``, ``compressor.bits=2,4,8``, ``algorithm.eta=0.05,0.1``."""
    path, sep, rhs = arg.partition("=")
    if not sep or not rhs:
        raise ValueError(f"--axis wants path=values, got {arg!r}")
    if ":" in rhs:
        parts = [int(x) for x in rhs.split(":")]
        if len(parts) not in (2, 3):
            raise ValueError(f"range axis wants lo:hi[:step], got {rhs!r}")
        return AxisSpec(path, tuple(range(*parts)))
    return AxisSpec(path, tuple(_cast_scalar(v) for v in rhs.split(",")))


# ===========================================================================
# Flag -> spec layer
# ===========================================================================

def _cast_scalar(arg: str):
    try:
        return int(arg)
    except ValueError:
        try:
            return float(arg)
        except ValueError:
            return arg


# factory params that carry shared construction context rather than a
# component's own tunable (skipped by the name:arg CLI shorthand)
_CONTEXT_PARAMS = frozenset({"n", "n_nodes", "base", "rounds", "seed",
                             "problem", "name"})


def parse_component(kind: str, spec_str: str) -> Tuple[str, dict]:
    """Parse the CLI shorthand ``name[:arg]`` (e.g. ``qinf:2``,
    ``linkdrop:0.1``, ``markov_drop:0.2``) into (name, params): the
    positional arg binds to the factory's first declared *tunable* field
    (bits / frac / rate / sigma / drop / ...)."""
    name, _, arg = spec_str.partition(":")
    name = name.replace("-", "_")
    if not arg:
        return name, {}
    acc = [a for a in registry.accepts(kind, name) if a not in _CONTEXT_PARAMS]
    if not acc:
        raise ValueError(f"{kind} {name!r} takes no parameters "
                         f"(got {spec_str!r})")
    return name, {acc[0]: _cast_scalar(arg)}


def parse_faults(spec_str: str) -> Tuple[FaultSpec, ...]:
    """``'linkdrop:0.1,noise:0.01'`` -> FaultSpec tuple ('' -> ())."""
    out = []
    for part in (spec_str or "").split(","):
        part = part.strip()
        if part:
            name, params = parse_component("fault", part)
            out.append(FaultSpec(name, params))
    return tuple(out)


def _spec_from_flags(cls, args, *, engine=None, **overrides):
    def g(name, default=None):
        return getattr(args, name, default)

    engine = engine or g("engine") or ("sharded" if g("arch") else "dense")

    algo_name = (g("algo") or "prox_lead").replace("-", "_")
    aparams = {}
    if g("allow_biased"):
        aparams["allow_biased"] = True
    algorithm = AlgorithmSpec(
        algo_name, eta=constant(g("eta", 0.05)),
        alpha=constant(g("alpha", 0.5)), gamma=constant(g("gamma", 1.0)),
        params=aparams)

    cname, cparams = parse_component("compressor", g("compressor", "qinf"))
    for flag, field in (("bits", "bits"), ("block", "block"),
                        ("frac", "frac")):
        v = g(flag)
        if v is not None and field not in cparams \
                and field in registry.accepts("compressor", cname):
            cparams[field] = v
    compressor = CompressorSpec(cname, cparams)

    sname, sparams = parse_component("schedule", g("schedule", "static"))
    topology = TopologySpec(
        graph=g("topology", "ring"), schedule=sname,
        rounds=g("rounds", g("schedule_rounds", 32)),
        schedule_params=sparams)

    faults = parse_faults(g("fault", ""))
    drop_rate = g("drop_rate", 0.0)
    if drop_rate:
        faults = faults + (FaultSpec("linkdrop", {"rate": drop_rate}),)

    pname = g("prox")
    if pname in (None, "none"):
        l1 = g("l1", 0.0)
        prox = ProxSpec("l1", {"lam": l1}) if l1 else ProxSpec("none")
    else:
        pp = ({"lam": g("lam", 1e-5)} if pname in ("l1", "l2sq") else {})
        prox = ProxSpec(pname, pp)

    oracle = model = None
    if engine == "sharded":
        model = ModelSpec(arch=g("arch", "qwen3-1.7b"), full=g("full", False),
                          n_layers=g("layers", 2), d_model=g("d_model", 256),
                          local_batch=g("local_batch", 4),
                          seq_len=g("seq_len", 64))
    else:
        pparams = {}
        for flag, field in (("features", "n_features"),
                            ("classes", "n_classes"), ("lam2", "lam2"),
                            ("n_per_node", "n_per_node"),
                            ("n_batches", "n_batches")):
            v = g(flag)
            if v is not None:
                pparams[field] = v
        if g("seed") is not None:
            pparams["seed"] = g("seed")
        oracle = OracleSpec(
            name=g("oracle", "full"),
            problem=g("problem", "logreg2d" if engine == "netsim"
                      else "logreg"),
            problem_params=pparams)

    execution = ExecutionSpec(
        engine=engine, backend=g("backend", "dense"),
        wire_mode=g("wire_mode", "bucketed"),
        pack_mode=g("pack_mode", "lastdim"))

    spec = cls(name=g("name", "experiment"), n_nodes=g("nodes", 8),
               steps=g("steps", 200), seed=g("seed", 0),
               fault_seed=g("fault_seed", g("seed", 0)),
               algorithm=algorithm, compressor=compressor, topology=topology,
               faults=faults, prox=prox, oracle=oracle, model=model,
               execution=execution)
    return dataclasses.replace(spec, **overrides) if overrides else spec


# ===========================================================================
# Runner protocol + adapters
# ===========================================================================

class Runner:
    """The single execution protocol every engine adapter implements.

    ``init_state(key)``            — build the initial state pytree.
    ``step(state, batch_or_key)``  — one jitted update (a PRNG key for the
                                     oracle-driven engines, a data batch for
                                     the trainer).
    ``run(...)``                   — the shared driver loop; returns
                                     (final_state, logs).
    ``metrics_fns``                — name -> fn(state) diagnostics.
    ``state_specs(node_axes)``     — PartitionSpec pytree for the state (the
                                     sharded engine delegates to the
                                     trainer; host-resident engines return
                                     a replicated tree).
    ``last_report``                — :class:`repro.obs.RunReport` from the
                                     most recent ``run()``: env stamp,
                                     measured step time, and exact
                                     bits-on-wire (None until a run
                                     completes).
    """
    spec: Optional[ExperimentSpec] = None
    last_report: Optional[obs.RunReport] = None

    def init_state(self, key):
        raise NotImplementedError

    def step(self, state, batch_or_key):
        raise NotImplementedError

    def run(self, **kw):
        raise NotImplementedError

    # shared default implementations (the host-resident, key-driven engines;
    # TrainerRunner overrides all three against its trainer) -----------------
    @property
    def metrics_fns(self) -> Dict[str, Callable]:
        return {"consensus": _consensus_of_X,
                "iteration": lambda st: st.k}

    def state_specs(self, node_axes: Tuple[str, ...] = ()):
        from jax.sharding import PartitionSpec as P
        state = jax.eval_shape(self.init_state, jax.random.key(0))
        return tmap(lambda _: P(), state)

    # checkpoints always embed the originating spec --------------------------
    def save(self, path, state, step: int = 0, extra: Optional[dict] = None):
        from repro.checkpoint.ckpt import save_state
        meta = dict(extra or {})
        if self.spec is not None:
            meta["spec"] = self.spec.to_dict()
        return save_state(path, state, step=step, extra=meta)


def _consensus_of_X(state):
    return netsim_metrics.consensus_error(state.X)


class DenseRunner(Runner):
    """Adapter over ProxLEAD and every baselines algorithm (stacked leaves,
    DenseMixer).  ``run`` is THE shared driver loop — bit-for-bit the old
    ``Baseline.run`` / ``ProxLEAD.run`` semantics (init on one split, one
    fresh subkey per step)."""

    def __init__(self, algo, X0, *, spec: Optional[ExperimentSpec] = None,
                 problem=None):
        self.algo = algo
        self.X0 = X0
        self.spec = spec
        self.problem = problem
        self._jit_step = jax.jit(algo.step)

    def init_state(self, key):
        return self.algo.init(self.X0, key)

    def step(self, state, key):
        return self._jit_step(state, key)

    def run(self, *, num_steps: Optional[int] = None, key=None, X0=None,
            callback=None, log_every: int = 0):
        if num_steps is None:
            num_steps = self.spec.steps if self.spec else 0
        if key is None:
            key = self.spec.seed if self.spec else 0
        key = jax.random.key(key) if isinstance(key, int) else key
        meters = obs.Meters()
        with obs.using_meters(meters), obs.span("run_total", meters) as sp:
            k0, key = jax.random.split(key)
            state = self.algo.init(X0 if X0 is not None else self.X0, k0)
            logs = []
            for t in range(num_steps):
                key, sub = jax.random.split(key)
                state = self._jit_step(state, sub)
                if callback is not None and log_every and t % log_every == 0:
                    logs.append(callback(state, t))
            sp.ready(state)
        self.last_report = obs.build_report(
            name=self.spec.name if self.spec else "dense",
            engine="dense", steps=num_steps, total_s=sp.elapsed_s,
            bits_per_step=self.bits_per_step(
                X0 if X0 is not None else self.X0),
            scope="node", meters=meters,
            extra={"algo": getattr(self.algo, "name",
                                   type(self.algo).__name__)})
        return state, logs

    def bits_per_step(self, X=None) -> float:
        """Exact bits ONE node sends per step: per-edge payload bits
        (``netsim.metrics.payload_bits_per_node`` — the same accounting
        the netsim engine charges) times the node's out-degree under the
        mixer's W support.  0.0 when the mixer has no explicit W
        (nothing to price)."""
        X = X if X is not None else self.X0
        per_edge = netsim_metrics.payload_bits_per_node(
            getattr(self.algo, "compressor", None), X)
        W = getattr(getattr(self.algo, "mixer", None), "W", None)
        if W is None:
            return 0.0
        Wn = np.abs(np.asarray(W))
        n = Wn.shape[0]
        directed = int((Wn > 1e-12).sum() - (np.diag(Wn) > 1e-12).sum())
        return per_edge * directed / n



class NetsimRunner(Runner):
    """Adapter over ``repro.netsim.engine.simulate``: the algorithm's mixer
    is swapped for a SimMixer (schedule + faults) and the whole trajectory
    runs as one jitted scan with exact bits-on-wire accounting."""

    def __init__(self, algo, X0, schedule, faults=(), *,
                 spec: Optional[ExperimentSpec] = None, problem=None):
        self.algo = algo
        self.X0 = X0
        self.schedule = schedule
        self.faults = tuple(faults)
        self.spec = spec
        self.problem = problem
        fault_seed = spec.fault_seed if spec else 0
        mixer = netsim_engine.SimMixer(schedule, self.faults,
                                       jax.random.key(fault_seed))
        self._sim_algo = dataclasses.replace(algo, mixer=mixer)
        self._jit_step = jax.jit(self._sim_algo.step)

    def init_state(self, key):
        return self._sim_algo.init(self.X0, key)

    def step(self, state, key):
        return self._jit_step(state, key)

    def run(self, *, steps: Optional[int] = None, seed: Optional[int] = None,
            fault_seed: Optional[int] = None, objective_fn=None, X0=None):
        """-> (final_state, netsim.metrics.Trajectory)."""
        sp = self.spec
        meters = obs.Meters()
        with obs.using_meters(meters), obs.span("run_total", meters) as tsp:
            final, traj = netsim_engine.simulate(
                self.algo, self.schedule, self.faults,
                X0=X0 if X0 is not None else self.X0,
                steps=steps if steps is not None else (sp.steps if sp else 0),
                seed=seed if seed is not None else (sp.seed if sp else 0),
                fault_seed=fault_seed if fault_seed is not None
                else (sp.fault_seed if sp else 0),
                objective_fn=objective_fn)
            tsp.ready(final)
        # trajectory bits are the fault-exact SYSTEM total per round (every
        # directed edge that actually carried a payload), not one node's
        self.last_report = obs.build_report(
            name=sp.name if sp else "netsim", engine="netsim",
            steps=traj.steps, total_s=tsp.elapsed_s,
            bits_per_step=(traj.total_bits / traj.steps if traj.steps
                           else 0.0),
            bits_total=traj.total_bits, scope="system", meters=meters,
            extra={"algo": traj.meta.get("algo"),
                   "schedule": traj.meta.get("schedule"),
                   "final_consensus": (float(traj.consensus[-1])
                                       if traj.steps else None)})
        return final, traj

# The train step rounds every value to its declared dtype.  By default XLA
# may keep a bf16 intermediate in f32 inside a fusion ("excess
# precision"), so whether a bf16 leaf diff or dequantized payload is
# rounded depends on where fusions end.  The bucketed wire path hands
# those values to Pallas kernels through HBM, rounded; the per-leaf path
# fuses them and would not round, and the two modes' bf16 states drift
# apart (by up to 0.04 in H and Hw after 4 steps of a bf16 qwen3 node).
STEP_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


class TrainerRunner(Runner):
    """Adapter over ``repro.optim.DecentralizedTrainer`` (the GSPMD /
    shard_map NN path).  Construction goes through the same registries as
    every other engine; the update math is the trainer's own — bit-for-bit
    identical to a hand-built ``DecentralizedTrainer``."""

    def __init__(self, trainer, *, spec: Optional[ExperimentSpec] = None):
        self.trainer = trainer
        self.spec = spec
        self._jit_step = None
        self._jit_init = None
        self._compiled_step = None
        self._batch_sh = None
        self._dispatched = 0            # steps dispatched, for the trace

    # trainer passthroughs ---------------------------------------------------
    @property
    def mesh(self):
        return self.trainer.mesh

    def abstract_state(self):
        return self.trainer.abstract_state()

    def batch_specs(self, batch_tree, node_axes: Tuple[str, ...]):
        return self.trainer.batch_specs(batch_tree, node_axes)

    # placement ---------------------------------------------------------------
    def _require_mesh(self):
        want = self.spec.execution.mesh if self.spec is not None else None
        if want is not None and self.mesh is None:
            raise RuntimeError(
                f"spec {self.spec.name!r} asks for mesh {want} but the "
                f"runner was built without one ({len(jax.devices())} "
                f"device(s) visible); refusing to run it on one device")

    def _on_mesh(self):
        from repro import compat
        return (compat.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _shardings(self, tree_specs):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return tmap(lambda sp: NamedSharding(self.mesh, sp), tree_specs,
                    is_leaf=lambda x: isinstance(x, P))

    def _node_axes(self):
        from repro.models.sharding import node_axes
        return node_axes(self.mesh)

    def _step_fn(self, batch):
        """The jitted train step.  On a mesh its operands and state output
        are pinned to ``state_specs`` / ``batch_specs``, and the input
        state is donated: at published widths a node's state is a third of
        a chip, and the step's output reuses its buffers."""
        if self._jit_step is None:
            if self.mesh is None:
                self._jit_step = jax.jit(self.trainer.train_step,
                                         compiler_options=STEP_COMPILER_OPTIONS)
            else:
                naxes = self._node_axes()
                state_sh = self._shardings(self.trainer.state_specs(naxes))
                self._batch_sh = self._shardings(
                    self.trainer.batch_specs(batch, naxes))
                self._jit_step = jax.jit(
                    self.trainer.train_step,
                    in_shardings=(state_sh, self._batch_sh),
                    out_shardings=(state_sh, None), donate_argnums=0,
                    compiler_options=STEP_COMPILER_OPTIONS)
        return self._jit_step

    # Runner protocol --------------------------------------------------------
    def init_state(self, key):
        """Fresh state.  On a mesh every leaf is created under its
        ``state_specs`` sharding: each node's replica on its own devices,
        never gathered on one first."""
        self._require_mesh()
        if self.mesh is None:
            return self.trainer.init_state(key)
        if self._jit_init is None:
            self._jit_init = jax.jit(
                self.trainer.init_state, out_shardings=self._shardings(
                    self.trainer.state_specs(self._node_axes())))
        with self._on_mesh():
            return self._jit_init(key)

    def lower_step(self, state, batch):
        """Trace and lower the train step for operands shaped like
        ``state`` and ``batch`` (arrays or ShapeDtypeStructs)."""
        self._require_mesh()
        with self._on_mesh():
            return self._step_fn(batch).lower(state, batch)

    def compile_step(self, lowered):
        """Compile a :meth:`lower_step` result; ``step`` runs this
        executable from then on (returned so a caller can time the compile
        and read ``as_text()``)."""
        self._compiled_step = lowered.compile()
        return self._compiled_step

    def step(self, state, batch):
        """One train step.  On a mesh ``state`` is donated: use the
        returned state, not the one passed in.  In a profile the call is
        the span ``obs.STEP`` numbered by this runner's count of
        dispatched steps, with the batch's placement and the executable
        call as its children."""
        self._require_mesh()
        n = self._dispatched
        self._dispatched += 1
        with obs.annotate(obs.STEP, step_num=n):
            fn = self._compiled_step or self._step_fn(batch)
            if self.mesh is not None:
                # each node's batch to its own devices, wherever it was made
                with obs.annotate(obs.STEP_PLACE):
                    batch = jax.device_put(batch, self._batch_sh)
            with self._on_mesh(), obs.annotate(obs.STEP_CALL):
                return fn(state, batch)

    def run(self, *, num_steps: Optional[int] = None, data=None, state=None,
            key=None, callback=None, log_every: int = 0):
        """Drive ``num_steps`` train steps over ``data`` (an object with
        ``batch_at(t)``; defaults to the spec's synthetic token stream).
        Step indices continue from ``state.step`` when resuming."""
        sp = self.spec
        if num_steps is None:
            num_steps = sp.steps if sp else 0
        if data is None:
            data = self.default_data()
        meters = obs.Meters()
        with obs.using_meters(meters), obs.span("run_total", meters) as tsp:
            if state is None:
                state = self.init_state(
                    key if key is not None else jax.random.key(0))
            logs = []
            t0 = int(state.step)
            for t in range(t0, t0 + num_steps):
                state, metrics = self.step(state, data.batch_at(t))
                if callback is not None and log_every and t % log_every == 0:
                    logs.append(callback(state, metrics, t))
            tsp.ready(state)
        bits = self.bits_per_step(state)
        self.last_report = obs.build_report(
            name=sp.name if sp else "trainer", engine="sharded",
            steps=num_steps, total_s=tsp.elapsed_s, bits_per_step=bits,
            scope="node", meters=meters,
            extra={"backend": self.trainer.tcfg.backend,
                   "wire_mode": self.trainer.tcfg.wire_mode})
        return state, logs

    def bits_per_step(self, state) -> float:
        """Exact bits ONE node ships per train step.  Neighbor/ring
        backends: gossip hops x the per-edge u8 wire payload — the
        ``netsim.metrics.{bucketed,sharded}_payload_bits`` accounting the
        tests pin byte-for-byte against HLO-parsed collective-permute
        bytes.  Dense backend: ideal per-edge payload x W out-degree
        (no collectives to parse)."""
        tr = self.trainer
        leaves = jax.tree_util.tree_leaves(state.plead.X)
        if tr.plan is not None:
            hops = len(tr.plan.hops)
            if tr.tcfg.wire_mode == "bucketed":
                per_edge = netsim_metrics.bucketed_payload_bits(tr, leaves)
            else:
                per_edge = netsim_metrics.sharded_payload_bits(tr, leaves)
            return float(hops * per_edge)
        per_edge = netsim_metrics.payload_bits_per_node(
            tr.compressor, state.plead.X)
        W = getattr(tr.mixer, "W", None)
        if W is None:
            return 0.0
        Wn = np.abs(np.asarray(W))
        directed = int((Wn > 1e-12).sum() - (np.diag(Wn) > 1e-12).sum())
        return per_edge * directed / Wn.shape[0]

    def default_data(self):
        if self.spec is None or self.spec.model is None:
            raise ValueError("no spec/model to derive a data stream from; "
                             "pass data= explicitly")
        ms = self.spec.model
        cfg = self.trainer.mcfg
        from repro.data.pipeline import DecentralizedBatches
        return DecentralizedBatches(
            self.spec.n_nodes, ms.local_batch, ms.seq_len, cfg.vocab,
            family=cfg.family, n_vision_tokens=cfg.n_vision_tokens,
            d_model=cfg.d_model, dtype=cfg.dtype)

    @property
    def metrics_fns(self):
        return {"consensus": lambda st: _consensus_of_X(st.plead),
                "iteration": lambda st: st.step}

    def state_specs(self, node_axes: Tuple[str, ...] = ()):
        return self.trainer.state_specs(node_axes)


# ===========================================================================
# build(spec) -> Runner
# ===========================================================================

def build_algorithm(spec: ExperimentSpec, mixer, oracle):
    """Resolve AlgorithmSpec through the registry.  Factories receive the
    subset of the shared context (eta/alpha/gamma/compressor/prox/mixer/
    oracle) their signature declares; AlgorithmSpec.params are strict."""
    a = spec.algorithm
    ctx = {"eta": a.eta.resolve(), "alpha": a.alpha.resolve(),
           "gamma": a.gamma.resolve(), "compressor": spec.compressor.build(),
           "prox": spec.prox.build(), "mixer": mixer, "oracle": oracle}
    ctx = registry.kwargs_subset("algorithm", a.name, ctx)
    return registry.make("algorithm", a.name, **ctx, **a.params)


def default_oracle_spec(spec: ExperimentSpec) -> OracleSpec:
    """The OracleSpec an engine falls back on when ``spec.oracle`` is None —
    same convention as the flag layer: the netsim engine defaults to the
    small natural-shape 'logreg2d' instance, dense to the paper-scale flat
    'logreg'."""
    if spec.oracle is not None:
        return spec.oracle
    return OracleSpec(problem="logreg2d"
                      if spec.execution.engine == "netsim" else "logreg")


# (problem name, factory identity, params json, n_nodes) -> (problem, X0).
# Problem factories are deterministic in their params and FiniteSumProblem
# is frozen, so sharing one instance across runners is safe — and a grouped
# figure sweep (benchmarks/common.run_cells) builds dozens of runners over
# ONE dataset; without the cache each re-generated it.  The factory object
# sits in the key so re-registering a name (tests shadow components) misses.
_PROBLEM_CACHE: Dict[Any, Any] = {}
_PROBLEM_CACHE_MAX = 8


def build_problem(osp: "OracleSpec", n_nodes: int):
    """(FiniteSumProblem, X0) for an OracleSpec, built once per distinct
    (problem, params, n_nodes) and shared thereafter."""
    key = (osp.problem, registry.get("problem", osp.problem),
           json.dumps(_to_jsonable(osp.problem_params), sort_keys=True),
           n_nodes)
    if key not in _PROBLEM_CACHE:
        if len(_PROBLEM_CACHE) >= _PROBLEM_CACHE_MAX:
            _PROBLEM_CACHE.pop(next(iter(_PROBLEM_CACHE)))
        _PROBLEM_CACHE[key] = osp.build_problem(n_nodes)
    return _PROBLEM_CACHE[key]


def _oracle_and_problem(spec: ExperimentSpec):
    osp = default_oracle_spec(spec)
    problem, X0 = build_problem(osp, spec.n_nodes)
    return osp.build(problem), problem, X0


@registry.register_engine("dense")
def _build_dense(spec: ExperimentSpec, mesh=None) -> DenseRunner:
    if spec.topology.schedule != "static" or spec.faults:
        raise ValueError(
            "engine='dense' is the static, fault-free path; time-varying "
            "schedules and faults run on engine='netsim'")
    oracle, problem, X0 = _oracle_and_problem(spec)
    mixer = DenseMixer(spec.topology.build_graph(spec.n_nodes).W)
    algo = build_algorithm(spec, mixer, oracle)
    return DenseRunner(algo, X0, spec=spec, problem=problem)


@registry.register_engine("netsim")
def _build_netsim(spec: ExperimentSpec, mesh=None) -> NetsimRunner:
    oracle, problem, X0 = _oracle_and_problem(spec)
    schedule = spec.topology.build_schedule(spec.n_nodes, seed=spec.seed)
    faults = tuple(f.build() for f in spec.faults)
    # placeholder mixer: simulate() swaps in the SimMixer before init
    mixer = DenseMixer(spec.topology.build_graph(spec.n_nodes).W)
    algo = build_algorithm(spec, mixer, oracle)
    return NetsimRunner(algo, X0, schedule, faults, spec=spec,
                        problem=problem)


def trainer_config_from_spec(spec: ExperimentSpec):
    """Map an ExperimentSpec onto TrainerConfig — the one place the flat
    trainer knob bag is produced.  Strict: spec entries that do not map onto
    a TrainerConfig field raise instead of vanishing."""
    from repro.optim.decentralized import TrainerConfig
    tc_fields = {f.name for f in dataclasses.fields(TrainerConfig)}
    if spec.algorithm.name != "prox_lead":
        raise ValueError(
            f"engine='sharded' runs Prox-LEAD (the trainer's outer "
            f"optimizer); algorithm {spec.algorithm.name!r} runs on the "
            f"dense/netsim engines")
    kw = dict(
        n_nodes=spec.n_nodes,
        eta=spec.algorithm.eta.constant(),
        alpha=spec.algorithm.alpha.constant(),
        gamma=spec.algorithm.gamma.constant(),
        compressor=spec.compressor.name,
        allow_biased=bool(spec.algorithm.params.get("allow_biased", False)),
        prox=spec.prox.build(),
        topology=spec.topology.graph,
        backend=spec.execution.backend,
        schedule=spec.topology.schedule,
        schedule_rounds=spec.topology.rounds,
        wire_mode=spec.execution.wire_mode,
        pack_mode=spec.execution.pack_mode,
        seed=spec.seed,
        fault_seed=spec.fault_seed,
    )
    extra = set(spec.algorithm.params) - {"allow_biased"}
    if extra:
        raise ValueError(f"sharded engine: unsupported algorithm params "
                         f"{sorted(extra)}")
    for k, v in spec.compressor.params.items():
        if k not in tc_fields:
            raise ValueError(
                f"compressor param {k!r} has no TrainerConfig field; the "
                f"trainer understands {sorted(tc_fields)}")
        kw[k] = v
    sp = dict(spec.topology.schedule_params)
    if "drop" in sp:
        kw["schedule_drop"] = sp.pop("drop")
    if sp:
        raise ValueError(f"sharded engine: unsupported schedule params "
                         f"{sorted(sp)}")
    for f in spec.faults:
        if f.name != "linkdrop" or "drop_rate" in kw:
            raise ValueError(
                f"sharded engine supports a single linkdrop fault only "
                f"(got {[x.name for x in spec.faults]}); richer fault "
                f"models run on engine='netsim'")
        kw["drop_rate"] = f.params.get("rate", 0.1)
    for k, v in spec.execution.params.items():
        if k not in tc_fields:
            raise ValueError(
                f"execution param {k!r} has no TrainerConfig field; the "
                f"trainer understands {sorted(tc_fields)}")
        kw[k] = v
    return TrainerConfig(**kw)


def build_trainer_runner(spec: ExperimentSpec, *, model_cfg=None,
                         mesh=None) -> TrainerRunner:
    """The sharded engine with an optional prebuilt ModelConfig (dryrun
    hands in arch variants with ad-hoc overrides; everything else resolves
    spec.model through the config registry)."""
    from repro.optim.decentralized import DecentralizedTrainer
    if model_cfg is None:
        if spec.model is None:
            raise ValueError(
                "engine='sharded' needs a ModelSpec (spec.model)")
        model_cfg = spec.model.build()
    tcfg = trainer_config_from_spec(spec)
    if mesh is None and spec.execution.mesh is not None:
        import math
        shape = spec.execution.mesh
        if len(jax.devices()) >= math.prod(shape):
            from repro import compat
            mesh = compat.make_mesh(shape, ("data", "model"))
        else:
            # not enough devices to realize the spec'd mesh (e.g. the
            # golden-spec build gate on a 1-device host): construct
            # meshless — init/abstract paths work, the neighbor update
            # itself asserts on a concrete mesh at trace time
            import warnings
            warnings.warn(
                f"spec {spec.name!r} wants mesh {shape} but only "
                f"{len(jax.devices())} device(s) are visible; building "
                f"without a mesh", stacklevel=2)
    trainer = DecentralizedTrainer(model_cfg, tcfg, mesh=mesh)
    return TrainerRunner(trainer, spec=spec)


@registry.register_engine("sharded")
def _build_sharded(spec: ExperimentSpec, mesh=None) -> TrainerRunner:
    return build_trainer_runner(spec, mesh=mesh)


def build(spec, *, mesh=None) -> Runner:
    """Resolve a spec into a Runner via the engine registry.

    ``ExperimentSpec`` -> its ``execution.engine`` (dense | netsim |
    sharded); ``SweepSpec`` -> the one-jit vmapped grid engine
    (``repro.sweep.SweepRunner``)."""
    if isinstance(spec, SweepSpec):
        from repro import sweep as _sweep           # noqa: F401 (registers)
        return registry.make("engine", "sweep", spec=spec, mesh=mesh)
    return registry.make("engine", spec.execution.engine, spec=spec,
                         mesh=mesh)


def runner_for(algo, X0, *, spec: Optional[ExperimentSpec] = None,
               problem=None) -> DenseRunner:
    """Wrap an already-constructed dense algorithm (ProxLEAD or any
    baseline) in the shared Runner protocol — the upgrade path for code
    holding algorithm objects rather than specs."""
    return DenseRunner(algo, X0, spec=spec, problem=problem)


# ===========================================================================
# Checkpoints round-trip the spec
# ===========================================================================

def load_checkpoint(path, step: Optional[int] = None, *, mesh=None):
    """Rebuild the runner from the spec a checkpoint embeds and restore its
    state: -> (runner, state, step).  Training continues bit-for-bit (the
    state pytree is restored exactly; step indices resume from it)."""
    from repro.checkpoint.ckpt import latest_step, load_manifest, load_state
    if step is None:
        step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no checkpoint manifests under {path}")
    manifest = load_manifest(path, step)
    spec_dict = (manifest.get("extra") or {}).get("spec")
    if spec_dict is None:
        raise ValueError(
            f"checkpoint {path} (step {step}) embeds no ExperimentSpec; "
            f"re-save through Runner.save or pass the spec explicitly")
    spec = ExperimentSpec.from_dict(spec_dict)
    runner = build(spec, mesh=mesh)
    template = runner.init_state(jax.random.key(0))
    state = load_state(path, template, step=step)
    return runner, state, step


# ===========================================================================
# Golden-spec gate (make ci)
# ===========================================================================

def check_spec_file(path):
    """Round-trip + build one golden spec file; raises on any failure.

    Handles both spec kinds: a JSON object with a ``base`` key is a
    :class:`SweepSpec` (its build also validates the axis plan), anything
    else an :class:`ExperimentSpec`."""
    text = pathlib.Path(path).read_text()
    cls = SweepSpec if "base" in json.loads(text) else ExperimentSpec
    spec = cls.from_json(text)
    again = cls.from_json(spec.to_json())
    if spec != again:
        detail = spec.diff(again) if cls is ExperimentSpec else ""
        raise ValueError(f"{path}: spec does not round-trip through JSON; "
                         f"diff: {detail}")
    build(spec)
    return spec


def _main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.api",
        description="ExperimentSpec utilities: golden-spec round-trip + "
                    "build gate, spec diffing")
    ap.add_argument("--check", default=None, metavar="DIR_OR_JSON",
                    help="round-trip and build every *.json under the path")
    ap.add_argument("--diff", nargs=2, default=None, metavar=("A", "B"),
                    help="print the field-level diff of two spec files")
    args = ap.parse_args(argv)
    if args.diff:
        a = ExperimentSpec.load(args.diff[0])
        b = ExperimentSpec.load(args.diff[1])
        for k, (va, vb) in a.diff(b).items():
            print(f"{k}: {va!r} -> {vb!r}")
        return 0
    if args.check:
        root = pathlib.Path(args.check)
        files = sorted(root.glob("*.json")) if root.is_dir() else [root]
        if not files:
            print(f"[spec-check] FAIL: no spec files under {root}")
            return 1
        for f in files:
            spec = check_spec_file(f)
            if isinstance(spec, SweepSpec):
                print(f"[spec-check] OK {f.name}: {spec.name} "
                      f"(sweep of {spec.n_points} points over "
                      f"{[a.path for a in spec.axes]}, "
                      f"engine={spec.base.execution.engine})")
            else:
                print(f"[spec-check] OK {f.name}: {spec.name} "
                      f"(engine={spec.execution.engine}, "
                      f"algo={spec.algorithm.name}, "
                      f"compressor={spec.compressor.name})")
        print(f"[spec-check] {len(files)} golden specs round-trip and build")
        return 0
    ap.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(_main())
