#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the TPU this process is started on.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

One process: it builds the trainer through the program's normal path
(ExperimentSpec -> api.build -> TrainerRunner), makes the weights and a
pool of token batches on the device from the seed, compiles and warms the
cell's one step shape, drives the first ``check_steps`` steps that the
comparison follows, then times ``TrainerRunner.step`` for ``--seconds``,
cycling the pool, with a few steps in flight and one fence at the end.
After the window it frees the program's state, runs the plain reference
over the same first steps and compares (bench/check.py).  The last line of
standard output is the result as one JSON object; the numbers compared,
each beside its limit, are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from bench import check, graph, kinds, traffic, weights  # noqa: E402
from bench import trace as traces  # noqa: E402
from bench.kinds import BenchError  # noqa: E402
from bench.reference import prox_lead  # noqa: E402


# --------------------------------------------------------------- manifest
def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, manifest: dict) -> dict:
    """The cell's manifest entry with its configuration, traffic and
    limits loaded from their files."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in manifest["configs"]}
    cell["cfg"] = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    kinds.of(cell["cfg"])          # an unknown model kind measures nothing
    cell["traffic_params"] = load_json(
        os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    tr = cell["traffic_params"]
    try:
        graph.weights(tr["topology"], tr["nodes"])
    except ValueError as e:
        raise BenchError(str(e)) from None
    cell["limits"] = load_json(os.path.join(BENCH, "limits", name + ".json"))
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if name in m.get("workloads", [name])]
    cell["end_to_end"] = [m for m in manifest["end_to_end"]
                          if name in m.get("workloads", [name])]
    return cell


def reader(metric: str):
    """``read(ctx)`` of bench/metrics/<metric>.py."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- devices
def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, or BenchError naming what is
    missing.  No fallback to another platform."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees platform {devs[0].platform!r} "
                         f"({len(devs)} device(s)); this benchmark runs on "
                         f"a chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


# --------------------------------------------------------------- program
def model_params(cfg: dict) -> dict:
    """The program's ModelConfig fields for a configuration file, by its
    model kind."""
    try:
        return kinds.of(cfg).program_params(cfg)
    except ValueError as e:
        raise BenchError(str(e)) from None


# The program's own stochastic-rounding seed.  The trainer compiles it into
# the step as a constant, so a seed that followed --seed would compile a
# new step in every run; the run's weights and tokens follow --seed.
PROGRAM_SEED = 0


def build_runner(cfg: dict, tr: dict, name: str):
    from repro import api
    comp = (api.CompressorSpec("qinf", {"bits": tr["bits"],
                                        "block": tr["block"]})
            if tr["compressor"] == "qinf" else api.CompressorSpec("identity"))
    spec = api.ExperimentSpec(
        name=name, n_nodes=tr["nodes"], steps=0, seed=PROGRAM_SEED,
        algorithm=api.AlgorithmSpec(eta=tr["eta"], alpha=tr["alpha"],
                                    gamma=tr["gamma"]),
        compressor=comp, topology=api.TopologySpec(graph=tr["topology"]),
        model=api.ModelSpec(arch=cfg["program_arch"], full=True,
                            local_batch=tr["local_batch"],
                            seq_len=tr["seq_len"], params=model_params(cfg)),
        execution=api.ExecutionSpec(engine="sharded", backend=tr["backend"],
                                    wire_mode=tr["wire_mode"],
                                    mesh=tuple(tr["mesh"])))
    return api.build(spec)


def _check_layout(runner, cfg: dict, n_nodes: int) -> None:
    want = [((n_nodes,) + tuple(s), str(weights.dtype_of(cfg)))
            for _, s in weights.leaf_list(cfg)]
    have = [(tuple(a.shape), str(a.dtype)) for a in
            jax.tree_util.tree_leaves(runner.abstract_state().plead.X)]
    if want != have:
        raise BenchError(f"the program's state layout {have} is not the "
                         f"benchmark's {want}")


def shardings(runner):
    """(state shardings, batch sharding) of the runner's mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.sharding import node_axes
    naxes = node_axes(runner.mesh)
    state_sh = jax.tree_util.tree_map(
        lambda sp: NamedSharding(runner.mesh, sp),
        runner.state_specs(naxes), is_leaf=lambda x: isinstance(x, P))
    return state_sh, NamedSharding(runner.mesh, P(naxes, None, None))


# --------------------------------------------------------------- the run
def annotator(on: bool):
    """Marks the harness's host phases in the profiler's trace when one is
    on."""
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


# Steps dispatched ahead of the oldest whose loss the window waits for: at
# least a second and a half of queued work in every cell, so that the host
# falling behind by that long leaves the chip busy.
IN_FLIGHT = 10


def timed_window(runner, state, pool, seconds: float, mark):
    """Steps for ``seconds``, cycling ``pool``, with up to ``IN_FLIGHT``
    steps dispatched ahead of the oldest one not yet waited for, and one
    fence at the end.  Dispatch stops once the steps in flight would run
    past ``seconds`` at the pace of those done.  Returns (state, losses,
    elapsed s)."""
    losses = []
    i = 0
    t0 = time.perf_counter()
    while True:
        with mark("bench.pick"):
            batch = pool[i % len(pool)]
        with mark("bench.dispatch"):
            state, m = runner.step(state, batch)
        losses.append(m["loss"])
        i += 1
        done = i - IN_FLIGHT
        if done > 0:
            with mark("bench.wait"):
                losses[done - 1].block_until_ready()
        elapsed = time.perf_counter() - t0
        pace = elapsed / done if done > 0 else 0.0
        if elapsed + (i - max(done, 0)) * pace >= seconds:
            break
    with mark("bench.fence"):
        jax.block_until_ready(state)
    return state, losses, time.perf_counter() - t0


def memory_peak(devices) -> int:
    """The peak on the fullest chip: buffers in use, and the scratch the
    TPU runtime reserves for a compiled program apart from them."""
    def peak(d):
        st = d.memory_stats() or {}
        return (st.get("peak_bytes_in_use", 0)
                + st.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devices)


class Program:
    """The system under test for one cell: the runner built through the
    program's normal path, its compiled step, and the state and token pool
    it starts from, made on the device from a seed."""

    def __init__(self, cfg: dict, tr: dict, name: str, n_devices: int):
        N = tr["nodes"]
        if N != n_devices or tuple(tr["mesh"]) != (N, 1):
            raise BenchError(f"one node per chip on an (N, 1) mesh: nodes "
                             f"{N}, mesh {tr['mesh']}, chips {n_devices}")
        self.cfg, self.tr = cfg, tr
        self.runner = runner = build_runner(cfg, tr, name)
        _check_layout(runner, cfg, N)
        state_sh, batch_sh = shardings(runner)
        n_pool = traffic.n_batches(tr)
        self._make_state = jax.jit(
            lambda k: runner.trainer.state_from_stacked(
                weights.stacked_params(cfg, k, N)), out_shardings=state_sh)
        self._make_pool = jax.jit(
            lambda k: traffic.batches(tr, cfg["vocab_size"], k, n_pool),
            out_shardings=[{"tokens": batch_sh, "labels": batch_sh}] * n_pool)
        eta = tr["eta"]
        self._grad = jax.jit(lambda k, X, D: check.grad_sq(
            weights.stacked_params(cfg, k, N), X, D, eta))
        self._change = jax.jit(lambda k, s: check.change_sq(
            weights.stacked_params(cfg, k, N), s.plead.X, s.plead.D,
            s.plead.comm.H, s.plead.comm.Hw))
        self.compiled = None

    def first_steps(self, seed: int):
        """State and pool from ``seed``, the step compiled on first use,
        and the first ``check_steps`` steps through the window's own call
        and feed.  Returns (state, pool, reading)."""
        state = self._make_state(weights.key(seed))
        pool = self._make_pool(traffic.key(seed))
        if self.compiled is None:
            self.compiled = self.runner.compile_step(
                self.runner.lower_step(state, pool[0]))
        wkey = weights.key(seed)
        losses, grad_sq = [], None
        for k in range(self.tr["check_steps"]):
            state, m = self.runner.step(state, pool[k])
            losses.append(m["loss"])
            if k == 0:
                grad_sq = jax.block_until_ready(
                    self._grad(wkey, state.plead.X, state.plead.D))
        reading = {"losses": [float(x) for x in losses],
                   "grad": check.to_norms(grad_sq),
                   "change": check.to_norms(self._change(wkey, state))}
        return state, pool, reading


def free(*trees) -> None:
    for a in jax.tree_util.tree_leaves(trees):
        a.delete()


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, t_start: float, trace_dir: str) -> dict:
    """Everything after the look for a chip: set-up, window, reference,
    comparison.  Returns the result object (without printing it)."""
    cfg, tr = cell["cfg"], cell["traffic_params"]
    N, K = tr["nodes"], tr["check_steps"]
    program = Program(cfg, tr, cell["name"], len(devices))
    state, pool, prog = program.first_steps(seed)
    runner, compiled = program.runner, program.compiled
    setup_s = time.time() - t_start

    mark = annotator(trace)
    # the harness's own garbage is collected before the window, not in it
    gc.collect()
    gc.disable()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    state, losses, window_s = timed_window(runner, state, pool[K:],
                                           seconds, mark)
    if trace:
        jax.profiler.stop_trace()
    gc.enable()
    loss_vals = [float(x) for x in losses]
    steps = len(loss_vals)
    failed = sum(not math.isfinite(x) for x in loss_vals)
    tokens = steps * N * tr["local_batch"] * tr["seq_len"]
    peak = memory_peak(devices)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if trace:
        table = traces.load(trace_dir, devices)
        device["busy_s"] = table.busy_s()
        device["window_s"] = table.window_s
        ctx = traces.Context(cell=cell, table=table, steps=steps,
                             tokens=tokens, window_s=window_s,
                             chips=len(devices), device_kind=d0.device_kind,
                             hlo_text=compiled.as_text)
        for m in cell["per_layer"]:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = ctx.breakdown()
    else:
        e2e = {"tokens_per_s": tokens / window_s / len(devices),
               "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the program's state is freed before the reference runs
    free(state, pool)
    del state, pool, compiled
    t_ref = time.perf_counter()
    ref = reference_reading(cfg, tr, seed, devices)
    reference_s = time.perf_counter() - t_ref
    numbers = check.compare(prog, ref, [p for p, _ in weights.leaf_list(cfg)])
    correct, rows = check.judge(numbers, cell["limits"])
    correct = correct and steps > 0 and failed == 0
    result = {"correct": bool(correct), "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["reference_s"] = reference_s
    result["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
    result["compared"] = {name: {"value": v, "limit": lim, "worst": where}
                          for name, v, lim, where in rows}
    return result


def reference_reading(cfg: dict, tr: dict, seed: int, devices, *,
                      dtype=None, faults=()) -> dict:
    """The plain reference over the cell's first steps: node i on
    ``devices[i]``, in the configuration's dtype or ``dtype``."""
    N, K, eta = tr["nodes"], tr["check_steps"], tr["eta"]
    n_pool = traffic.n_batches(tr)
    host = jax.device_get(jax.jit(lambda k: traffic.batches(
        tr, cfg["vocab_size"], k, n_pool)[:K])(traffic.key(seed)))
    wkey = weights.key(seed)
    x0 = jax.jit(lambda k: weights.init_params(cfg, k))(wkey)
    if dtype is not None:
        x0 = jax.tree_util.tree_map(lambda a: a.astype(dtype), x0)
    x0s = [jax.device_put(x0, d) for d in devices[:N]]
    del x0
    ref = prox_lead.Reference(cfg, tr, seed, x0s, devices[:N], faults=faults)
    del x0s
    key = kinds.frozen(cfg)
    grad_fn, change_fn = _ref_grad_fn(key, eta), _ref_change_fn(key)
    losses, grad = [], None
    for k in range(K):
        losses.append(ref.step(host[k]))
        if k == 0:
            grad = check.to_norms(_node_sum(
                [grad_fn(wkey, ref.X[i], ref.D[i]) for i in range(N)]))
    change = check.to_norms(_node_sum(
        [change_fn(wkey, ref.X[i], ref.D[i], ref.H[i], ref.Hw[i])
         for i in range(N)]))
    return {"losses": losses, "grad": grad, "change": change}


def _node_sum(parts):
    """Sum per-node sums of squares that live on different devices."""
    parts = jax.device_get(parts)
    total = parts[0]
    for p in parts[1:]:
        total = check.add_sq(total, p)
    return total


# keyed on the whole configuration (kinds.frozen), nested entries too
@functools.lru_cache(maxsize=None)
def _ref_grad_fn(cfg_json: str, eta):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda k, X, D: check.grad_sq(
        weights.init_params(cfg, k), X, D, eta))


@functools.lru_cache(maxsize=None)
def _ref_change_fn(cfg_json: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda k, X, D, H, Hw: check.change_sq(
        weights.init_params(cfg, k), X, D, H, Hw))


def report(result: dict) -> None:
    """Compared numbers as the last lines of stderr; the result as the
    last line of stdout, with ``compared`` as its last key."""
    for name, c in result["compared"].items():
        lim = "not compared" if c["limit"] is None else repr(c["limit"])
        print(f"{name} {c['value']!r} limit {lim} (worst: {c['worst']})",
              file=sys.stderr)
    out = dict(result)
    compared = out.pop("compared")
    out["compared"] = compared
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # the TPU runtime's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench_out",
                                                      "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    try:
        manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = load_cell(args.workload, manifest)
        try:
            from repro.launch.cache import use_compile_cache
        except ImportError as e:
            raise BenchError(f"the program under test is not in this "
                             f"checkout ({e})") from None
        devices = tpu_devices(cell["chips"])
        use_compile_cache()
        # every program of a run, small ones too, is served from the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        trace_dir = os.path.join(ROOT, "bench_out", "trace", cell["name"])
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, t_start=T_START, trace_dir=trace_dir)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
