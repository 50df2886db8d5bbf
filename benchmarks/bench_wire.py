"""Wire-path benchmark: bucketed vs per-leaf sharded gossip (§Perf).

Measures one COMM exchange (quantize -> pack -> ppermute x hops -> unpack
-> dequant -> mix) over synthetic L-leaf pytrees on a fake 8-device CPU
mesh, for both wire modes of ``repro.optim.wire.WireExchange`` — the same
code the trainer's ``_sharded_update`` runs.  Reports per-step walltime
and the HLO collective-permute count: bucketed must stay at 2 x hops
whatever L, per-leaf scales as 2 x hops x L.

The measurement child re-executes this module with
``--xla_force_host_platform_device_count=8`` (the parent process — pytest
or benchmarks.run — must keep its own device count), so ``run()`` works
from any host process.

  PYTHONPATH=src:. python -m benchmarks.bench_wire --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent.parent

# (topology, leaf count) grid: the leaf sweep shows collectives/walltime
# scaling with L on a fixed graph; exponential adds a 5-hop graph.
CONFIGS = [("ring", 4), ("ring", 16), ("ring", 32), ("exponential", 16)]
LEAF_ROWS, LEAF_WIDTH = 4, 256
N_NODES = 8


def _measure_child(steps: int) -> list:
    """Runs with 8 fake devices (set via XLA_FLAGS by the parent)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core import topology as topo_mod
    from repro.optim.wire import WireExchange

    mesh = compat.make_mesh((N_NODES, 1), ("data", "model"))

    def build(topo_name, L, mode):
        topo = topo_mod.make_topology(topo_name, N_NODES)
        plan = topo_mod.compile_plan(topo.W, name=topo.name)
        wmat_np = np.concatenate(
            [plan.self_weights(np.float32)[None]]
            + [h.weights[None] for h in plan.hops], 0).astype(np.float32)
        hop_pairs = [list(h.pairs) for h in plan.hops]
        wx = WireExchange(bits=2)

        def gossip(Xs, k_arr, node_id):
            idx = node_id[0]
            wmat = jnp.asarray(wmat_np)[:, :, idx]      # (1 + hops, T)
            key = jax.random.fold_in(jax.random.wrap_key_data(k_arr), idx)
            keys = [jax.random.fold_in(key, j) for j in range(L)]
            pp = lambda x, pairs: jax.lax.ppermute(x, "data", pairs)
            fn = wx.bucketed if mode == "bucketed" else wx.per_leaf
            wq, qs = fn(list(Xs), keys, wmat, hop_pairs, pp)
            acc = sum(jnp.sum(w) for w in wq) + sum(jnp.sum(q) for q in qs)
            return acc[None]

        lspec = P("data", None, None)
        shmapped = compat.shard_map(
            gossip, mesh=mesh,
            in_specs=((lspec,) * L, P(), P("data")),
            out_specs=P("data"),
            axis_names=set(mesh.axis_names), check=False)
        return plan, jax.jit(shmapped)

    import re
    rows = []
    for topo_name, L in CONFIGS:
        Xs = tuple(
            (jax.random.normal(jax.random.key(j), (N_NODES, LEAF_ROWS,
                                                   LEAF_WIDTH)))
            for j in range(L))
        key_data = jax.random.key_data(jax.random.key(7))
        node_ids = jnp.arange(N_NODES, dtype=jnp.int32)
        rec = {"name": f"wire[{topo_name},L={L}]", "topology": topo_name,
               "leaves": L, "timing_steps": steps}
        fns, times = {}, {}
        for mode in ("per_leaf", "bucketed"):
            plan, fn = build(topo_name, L, mode)
            rec["hops"] = len(plan.hops)
            txt = fn.lower(Xs, key_data, node_ids).compile().as_text()
            rec[f"cp_{mode}"] = len(re.findall(
                r"collective-permute(?:-start)?\(", txt))
            fn(Xs, key_data, node_ids).block_until_ready()   # warm
            fns[mode], times[mode] = fn, []
        # interleave the two modes and keep each mode's BEST time: machine
        # load on a shared box drifts on the timescale of a measurement
        # run, and alternating A/B cancels it out of the ratio
        for _ in range(steps):
            for mode, fn in fns.items():
                t0 = time.perf_counter()
                fn(Xs, key_data, node_ids).block_until_ready()
                times[mode].append(time.perf_counter() - t0)
        for mode in fns:
            rec[f"{mode}_ms"] = round(float(np.min(times[mode])) * 1e3, 3)
        rec["speedup"] = round(rec["per_leaf_ms"] / rec["bucketed_ms"], 2)
        rows.append(rec)
    return rows


def run(steps: int = 10, verbose: bool = False) -> list:
    """Spawn the 8-device measurement child and collect its rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the parent holds the accelerator, if there is one: the child measures
    # on 8 virtual CPU devices and must not try to open it
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), str(REPO)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_wire", "--child",
         "--steps", str(steps)],
        capture_output=True, text=True, env=env, cwd=str(REPO),
        timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"bench_wire child failed:\n{r.stderr[-3000:]}")
    rows = json.loads(r.stdout.splitlines()[-1])
    if verbose:
        for rec in rows:
            print(f"  {rec['name']:24s} hops={rec['hops']} "
                  f"per_leaf {rec['per_leaf_ms']:8.2f} ms "
                  f"({rec['cp_per_leaf']:3d} cps)  "
                  f"bucketed {rec['bucketed_ms']:8.2f} ms "
                  f"({rec['cp_bucketed']:2d} cps)  "
                  f"{rec['speedup']:.2f}x")
    return rows


def validate(rows) -> list:
    big = [r for r in rows if r["leaves"] >= 16]
    checks = [
        ("bucketed path ppermutes exactly 2 x hops, leaf-count independent",
         all(r["cp_bucketed"] == 2 * r["hops"] for r in rows),
         {r["name"]: r["cp_bucketed"] for r in rows}),
        ("per-leaf collectives scale as 2 x hops x leaves",
         all(r["cp_per_leaf"] == 2 * r["hops"] * r["leaves"] for r in rows),
         {r["name"]: r["cp_per_leaf"] for r in rows}),
        # the 2x-class headroom seen on some boxes is machine-dependent
        # (absolute step times vary ~6x across smoke hosts and the per-leaf
        # path parallelizes differently); the portable claim is a clear
        # geomean win, and run-over-run walltime REGRESSION tracking lives
        # in tools/perf_gate.py's speedup-ratio history gate (PERF_TOL)
        ("bucketed >= 1.1x faster per step at >= 16 leaves (geomean)",
         bool(big)
         and float(np.prod([r["speedup"] for r in big])) ** (1 / len(big))
         >= 1.1,
         {r["name"]: r["speedup"] for r in big}),
        # NOT a monotonicity check: per-row walltime ratios jitter on a
        # loaded 1-core box; what must always hold is that fewer
        # collectives never lose
        ("bucketed is faster at every measured leaf count",
         all(r["speedup"] > 1.0 for r in rows),
         {r["name"]: r["speedup"] for r in rows}),
    ]
    return checks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--child", action="store_true",
                    help="internal: run the measurement in-process "
                         "(requires the 8-device XLA flag)")
    ap.add_argument("--smoke", action="store_true",
                    help="write BENCH_wire.json at the repo root")
    args = ap.parse_args(argv)
    if args.child:
        print(json.dumps(_measure_child(args.steps)))
        return 0
    rows = run(steps=args.steps, verbose=True)
    checks = validate(rows)
    n_fail = 0
    for claim, ok, detail in checks:
        n_fail += not ok
        print(f"[{'PASS' if ok else 'FAIL'}] {claim}   [{detail}]")
    if args.smoke:
        out = REPO / "BENCH_wire.json"
        out.write_text(json.dumps(
            {"suite": "wire", "steps": args.steps, "rows": rows,
             "checks": [{"claim": c, "ok": bool(o), "detail": str(d)}
                        for c, o, d in checks]}, indent=1, default=str))
        print("smoke trajectory written to", out)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
