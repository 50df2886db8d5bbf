#!/usr/bin/env python3
"""Bring-up check of the decentralized trainer on a TPU.

Drives the main path through the normal entry points — ExperimentSpec ->
api.build -> TrainerRunner -> DecentralizedTrainer.train_step -> bucketed
neighbor gossip through the compiled Pallas wire kernels — for qwen3-1.7b
at its published widths (d_model 2048, 16 q / 8 kv heads, head_dim 128,
d_ff 6144, vocab 151936) with random weights.

  python chip_smoke.py              # one chip: one node on a (1, 1) mesh
  python chip_smoke.py --four-chip  # 2x2 host: four nodes, ring, (4, 1)

Each phase trains with ``wire_mode="bucketed"`` (fused kernels) and again
with ``"per_leaf"`` (no fused kernels) from the same seed and data, and
requires bit-equal final states.  Only one node's state fits on a chip at
a time, so the first run's final state moves to the host before the
second run starts.  Any failed check, or a JAX without a TPU, exits
non-zero with the reason; the last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WARMUP_STEPS = 1
TIMED_STEPS = 3

# one node of the published config, cut only in depth: 2 x (28 -> 2)
# layers of untied embedding + lm_head state per replica is what a 16 GB
# chip holds in bf16 (f32 does not fit even at one layer)
FULL_MODEL = dict(arch="qwen3-1.7b", full=True,
                  params={"n_layers": 2, "dtype": "bfloat16"},
                  local_batch=2, seq_len=1024)
CUTS = ("depth: 28 -> 2 layers",
        "nodes per chip: 1 (one node replica fills a 16 GB chip)",
        "dtype: bfloat16 (Qwen3's published dtype; f32 does not fit)")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"PASS {what}")


def make_spec(n_nodes: int, wire_mode: str, model: dict):
    from repro import api
    return api.ExperimentSpec(
        name=f"chip-smoke-{n_nodes}node-{wire_mode}", n_nodes=n_nodes,
        steps=TIMED_STEPS,
        compressor=api.CompressorSpec("qinf", {"bits": 2, "block": 256}),
        topology=api.TopologySpec(graph="ring"),
        model=api.ModelSpec(**model),
        execution=api.ExecutionSpec(engine="sharded", backend="neighbor",
                                    wire_mode=wire_mode,
                                    mesh=(n_nodes, 1)))


def lowered_permutes(text: str):
    """Operand types of every collective_permute in lowered StableHLO."""
    import re
    return re.findall(r"stablehlo\.collective_permute\"?\(?[^\n]*?"
                      r"\(tensor<([^>]*)>\)", text)


def train(spec, *, audit_wire: bool):
    """Build, compile, warm up and time one wire mode; returns its facts
    and the final (on-device) state."""
    import jax
    from repro import api
    from repro.check.contracts import audit_wire_hlo
    from repro.netsim import metrics as nmetrics

    runner = api.build(spec)
    data = runner.default_data()
    state = runner.init_state(jax.random.key(spec.seed))
    t0 = time.perf_counter()
    lowered = runner.lower_step(state, data.batch_at(0))
    t1 = time.perf_counter()
    compiled = runner.compile_step(lowered)
    t2 = time.perf_counter()
    hlo = compiled.as_text()
    facts = {"lower_s": t1 - t0, "compile_s": t2 - t1,
             "tpu_custom_call": hlo.count("tpu_custom_call")}
    tr = runner.trainer
    if audit_wire:
        hops = len(tr.plan.hops)
        lp = lowered_permutes(lowered.as_text())
        facts["hops"] = hops
        facts["lowered_permutes"] = len(lp)
        facts["lowered_permutes_u8"] = sum(t.endswith("ui8") for t in lp)
        facts["compiled_audit"] = audit_wire_hlo(
            hlo, hops=hops, per_edge_bits=nmetrics.bucketed_payload_bits(
                tr, jax.tree_util.tree_leaves(state.plead.X)),
            name=spec.execution.wire_mode)
    state, _ = runner.run(num_steps=WARMUP_STEPS, data=data, state=state)
    state, losses = runner.run(
        num_steps=TIMED_STEPS, data=data, state=state, log_every=1,
        callback=lambda st, m, t: m["loss"])
    facts["warm_step_s"] = runner.last_report.timing["mean_step_s"]
    facts["losses"] = [float(l) for l in losses]
    return facts, state


def placement(state):
    """{node: device ids} of every sharded state leaf, as a set of the
    distinct layouts seen (one layout when every leaf is placed alike)."""
    import jax
    layouts = set()
    for leaf in jax.tree_util.tree_leaves(state.plead):
        if leaf.ndim == 0:
            continue
        nodes = {}
        for sh in leaf.addressable_shards:
            rows = range(leaf.shape[0])[sh.index[0]]
            nodes.setdefault(tuple(rows), set()).add(sh.device.id)
        layouts.add(tuple(sorted((k, tuple(sorted(v)))
                                 for k, v in nodes.items())))
    return layouts


def to_host_and_free(state):
    import jax
    host = jax.device_get(state)
    for leaf in {id(x): x for x in jax.tree_util.tree_leaves(state)}.values():
        leaf.delete()
    return host


def _leaf_diff(a, b):
    """(bit-for-bit equal, max |a - b| in f32) of two device arrays."""
    import jax
    import jax.numpy as jnp
    if jnp.issubdtype(a.dtype, jnp.floating):
        ut = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[a.dtype.itemsize]
        same = jnp.all(jax.lax.bitcast_convert_type(a, ut)
                       == jax.lax.bitcast_convert_type(b, ut))
    else:
        same = jnp.all(a == b)
    err = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)),
                  initial=0.0)
    return same, err


def compare(host_a, state_b):
    """Per-leaf max |a - b| of two final states (a on the host, b on
    device); None for a leaf that is bit-for-bit equal.  Each host leaf
    goes back to b's devices alone and is compared there: on the host a
    four-node state is 23 GB."""
    import jax
    import numpy as np
    diff = jax.jit(_leaf_diff)
    la = jax.tree_util.tree_leaves_with_path(host_a)
    lb = jax.tree_util.tree_leaves(state_b)
    diffs = {}
    for (path, a), b in zip(la, lb):
        a = np.asarray(a)
        assert a.shape == b.shape and a.dtype == b.dtype, (path, a.shape,
                                                          b.shape)
        a = jax.device_put(a, b.sharding)
        same, err = diff(a, b)
        diffs[jax.tree_util.keystr(path)] = None if bool(same) else float(err)
        a.delete()
    return diffs


def phase(n_nodes: int, model: dict, *, audit_wire: bool) -> dict:
    """Bucketed vs per-leaf training of ``n_nodes`` nodes, one per device."""
    import jax
    spec = make_spec(n_nodes, "bucketed", model)
    print("spec:", spec.to_json(indent=None))
    facts_b, state_b = train(spec, audit_wire=audit_wire)
    print("bucketed:", json.dumps({k: v for k, v in facts_b.items()
                                   if k != "compiled_audit"}))
    where = placement(state_b)
    print("placement (node -> device ids, per leaf layout):", sorted(where))
    host_b = to_host_and_free(state_b)
    facts_p, state_p = train(make_spec(n_nodes, "per_leaf", model),
                             audit_wire=False)
    print("per_leaf:", json.dumps(facts_p))
    diffs = compare(host_b, state_p)
    differ = {k: v for k, v in diffs.items() if v is not None}
    print(f"bucketed vs per_leaf: {len(diffs) - len(differ)} of "
          f"{len(diffs)} state leaves bit-for-bit equal")
    for k, v in differ.items():
        print(f"  differs {k}: max |diff| {v!r}")
    peak = [d.memory_stats().get("peak_bytes_in_use")
            for d in jax.local_devices() if d.memory_stats()]
    print("peak_bytes_in_use per device:", peak)
    return {"bucketed": facts_b, "per_leaf": facts_p, "diffs": diffs,
            "placement": where, "peak_bytes": peak}


def verify(res: dict, n_nodes: int, *, on_chip: bool) -> None:
    import math
    fb = res["bucketed"]
    if on_chip:
        check(fb["tpu_custom_call"] > 0,
              "compiled bucketed step holds the Pallas kernels "
              f"(tpu_custom_call x{fb['tpu_custom_call']})")
    for mode in ("bucketed", "per_leaf"):
        check(all(math.isfinite(l) for l in res[mode]["losses"]),
              f"{mode} losses finite: {res[mode]['losses']}")
    check(res["bucketed"]["losses"] == res["per_leaf"]["losses"],
          "bucketed and per_leaf losses equal")
    check(all(v is None for v in res["diffs"].values()),
          "bucketed and per_leaf final states bit-for-bit equal")
    # one layout for all leaves: node i alone on its own device
    layouts = res["placement"]
    check(len(layouts) == 1 and all(
        len(nodes) == 1 and len(devs) == 1
        for nodes, devs in next(iter(layouts)))
        and len({devs for _, devs in next(iter(layouts))}) == n_nodes,
        f"each node's state on its own device: {sorted(layouts)}")
    if "hops" in fb:
        hops = fb["hops"]
        check(fb["lowered_permutes"] == 2 * hops,
              f"lowered step: {fb['lowered_permutes']} collective-permutes "
              f"== 2 x {hops} hops")
        check(fb["lowered_permutes_u8"] == fb["lowered_permutes"],
              "every lowered collective-permute moves u8")
        for claim, ok, detail in fb["compiled_audit"]:
            print(f"compiled step: {claim}: {'holds' if ok else 'differs'}"
                  f" [{detail}]")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="four nodes on a 2x2 host, one per chip (ring)")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.cache import use_compile_cache
    except ImportError as e:
        print(f"FAIL: the repro package is not next to chip_smoke.py ({e})",
              file=sys.stderr)
        return 2
    print("compile cache:", use_compile_cache())
    import jax
    devs = jax.devices()
    print("devices:", [f"{d.platform}:{d.id}:{d.device_kind}" for d in devs])
    if devs[0].platform != "tpu":
        print(f"FAIL: no TPU — JAX sees platform {devs[0].platform!r}; "
              f"this check runs on a chip only", file=sys.stderr)
        return 1
    n_nodes = 4 if args.four_chip else 1
    if len(devs) < n_nodes:
        print(f"FAIL: {n_nodes} chips needed, {len(devs)} visible",
              file=sys.stderr)
        return 1
    print("model: qwen3-1.7b at published widths; cuts:")
    for c in CUTS:
        print("  cut:", c)
    try:
        res = phase(n_nodes, FULL_MODEL, audit_wire=args.four_chip)
        verify(res, n_nodes, on_chip=True)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
