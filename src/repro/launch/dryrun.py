import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) combo.

The two lines above MUST run before any jax import (jax locks the device
count on first init); 512 placeholder host devices back both the 256-chip
single-pod mesh and the 512-chip two-pod mesh.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
  python -m repro.launch.dryrun ... --backend ring      # compressed-ring gossip
  python -m repro.launch.dryrun ... --out experiments/dryrun

Per combo it records compiled memory_analysis() + cost_analysis() + parsed
collective bytes into a JSON file consumed by the §Roofline report.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import api  # noqa: E402
from repro import compat  # noqa: E402
from repro import configs  # noqa: E402
from repro.configs import shapes as shp  # noqa: E402
from repro.launch import mesh as mesh_mod  # noqa: E402
from repro.obs import roofline  # noqa: E402
from repro.models import transformer as TR  # noqa: E402
from repro.models.sharding import node_axes, param_specs  # noqa: E402


tmap = jax.tree_util.tree_map


def _ns(mesh, spec_tree):
    return tmap(lambda s: NamedSharding(mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Train step lowering
# ---------------------------------------------------------------------------

def lower_train(cfg, shape, mesh, backend="dense", bits=2,
                pack_mode="lastdim", scales_bf16=False,
                shard_aligned_blocks=False, topology="ring"):
    N = mesh_mod.n_nodes(mesh)
    naxes = node_axes(mesh)
    # one flag->spec layer shared with train.py/simulate.py/benchmarks: the
    # dryrun sweep is an ExperimentSpec too (cfg arrives prebuilt because
    # the sweep applies ad-hoc arch overrides)
    exec_params = {}
    if scales_bf16:
        exec_params["scales_bf16"] = True
    if shard_aligned_blocks:
        exec_params["shard_aligned_blocks"] = True
    spec = api.ExperimentSpec(
        name=f"dryrun-{backend}-{topology}", n_nodes=N,
        # eta/alpha/gamma pinned to TrainerConfig's defaults so the lowered
        # program's scalar constants match the pre-spec dryrun exactly
        algorithm=api.AlgorithmSpec("prox_lead", eta=api.constant(1e-2),
                                    alpha=api.constant(0.5),
                                    gamma=api.constant(1.0)),
        compressor=api.CompressorSpec("qinf", {"bits": bits}),
        topology=api.TopologySpec(graph=topology),
        execution=api.ExecutionSpec(engine="sharded", backend=backend,
                                    pack_mode=pack_mode,
                                    params=exec_params))
    tr = api.build_trainer_runner(spec, model_cfg=cfg, mesh=mesh).trainer
    state = tr.abstract_state()
    batch = shp.train_input_specs(cfg, shape, N)
    state_specs = tr.state_specs(naxes)
    batch_specs = tr.batch_specs(batch, naxes)
    with compat.set_mesh(mesh):
        lowered = jax.jit(
            tr.train_step,
            in_shardings=(_ns(mesh, state_specs), _ns(mesh, batch_specs)),
        ).lower(state, batch)
    return lowered, tr


# ---------------------------------------------------------------------------
# Serve lowering (prefill / decode)
# ---------------------------------------------------------------------------

def _serve_param_shardings(cfg, mesh):
    ap = TR.abstract_params(cfg)
    return ap, _ns(mesh, param_specs(ap))


def _cache_specs(cfg, cache, baxes):
    def one(path, leaf):
        names = [None] * leaf.ndim
        # shard batch dim (dim 1 for layer-stacked caches)
        if leaf.ndim >= 2 and leaf.shape[1] % 2 == 0:
            names[1] = baxes
        # shard the last dim over model when divisible (head_dim / width / D)
        if leaf.shape[-1] % 16 == 0:
            names[-1] = "model"
        return P(*names)

    return jax.tree_util.tree_map_with_path(one, cache)


def lower_serve(cfg, shape, mesh):
    baxes = node_axes(mesh)
    nb = mesh_mod.n_nodes(mesh)
    params, p_shard = _serve_param_shardings(cfg, mesh)
    if shape.kind == "prefill":
        batch = shp.serve_input_specs(cfg, shape)
        bspec = tmap(lambda l: P(baxes if l.shape[0] % nb == 0 else None,
                                 *((None,) * (l.ndim - 1))), batch)

        def prefill(p, b):
            logits, _, _ = TR.forward(cfg, p, b, mode="train")
            return logits[:, -1]

        with compat.set_mesh(mesh):
            return jax.jit(prefill, in_shardings=(p_shard, _ns(mesh, bspec))
                           ).lower(params, batch)

    assert shape.kind == "decode"
    specs = shp.serve_input_specs(cfg, shape)
    cache = specs["cache"]
    B = shape.global_batch
    bax = baxes if B % nb == 0 else None
    cache_specs = _cache_specs(cfg, cache, bax)
    tok_spec = P(bax, None)

    def serve_step(p, c, toks, pos):
        return TR.decode_step(cfg, p, c, toks, pos)

    with compat.set_mesh(mesh):
        return jax.jit(
            serve_step,
            in_shardings=(p_shard, _ns(mesh, cache_specs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, P())),
        ).lower(params, specs["cache"], specs["tokens"], specs["pos"])


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_one(arch: str, shape_name: str, *, multi_pod=False, backend="dense",
            out_dir="experiments/dryrun", verbose=True, bits=2,
            pack_mode="lastdim", scales_bf16=False, tag=None,
            shard_aligned_blocks=False, cfg_overrides=None, topology="ring"):
    cfg = dataclasses.replace(configs.get(arch), dtype=jnp.bfloat16,
                              **(cfg_overrides or {}))
    shape = shp.SHAPES[shape_name]
    skip = shp.applicable(cfg, shape)
    mesh_tag = "2pod" if multi_pod else "1pod"
    variant = tag or (backend if topology == "ring"
                      else f"{backend}-{topology}")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "backend": backend, "variant": variant, "bits": bits,
           "topology": topology, "pack_mode": pack_mode, "status": None}
    out_path = pathlib.Path(out_dir)
    out_path.mkdir(parents=True, exist_ok=True)
    fname = out_path / f"{arch}__{shape_name}__{mesh_tag}__{variant}.json"
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        fname.write_text(json.dumps(rec, indent=1))
        if verbose:
            print(f"[dryrun] SKIP {arch} x {shape_name}: {skip}")
        return rec

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    chips = mesh_mod.n_chips(mesh)
    t0 = time.time()
    try:
        tr = None
        if shape.kind == "train":
            lowered, tr = lower_train(
                cfg, shape, mesh, backend=backend, bits=bits,
                pack_mode=pack_mode, scales_bf16=scales_bf16,
                shard_aligned_blocks=shard_aligned_blocks, topology=topology)
        else:
            lowered = lower_serve(cfg, shape, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        n_active = cfg.param_count(active_only=True)
        # the dry run models a v5e pod (launch/mesh.py)
        rl = roofline.analyze(compiled, cfg, shape,
                              mesh_mod.n_nodes(mesh), chips,
                              peaks=roofline.PEAKS["TPU v5 lite"])
        rec.update({
            "status": "ok",
            "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "chips": chips,
            "params": cfg.param_count(),
            "params_active": n_active,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            "roofline": rl.as_dict(),
        })
        if tr is not None and getattr(tr, "plan", None) is not None:
            # exact gossip bits-on-wire per round from the compiled plan
            from repro.netsim import metrics as nmetrics
            per_edge = nmetrics.sharded_payload_bits(
                tr, jax.tree_util.tree_leaves(tr.abstract_state().plead.X))
            rec["gossip"] = {
                "plan": tr.plan.name, "hops": len(tr.plan.hops),
                "wire_mode": tr.tcfg.wire_mode,
                "pairs_per_round": tr.plan.pairs_per_round,
                "payload_bits_per_edge": per_edge,
                "bits_per_round": nmetrics.plan_bits_per_round(
                    tr.plan, per_edge),
            }
        if verbose:
            print(f"[dryrun] OK {arch} x {shape_name} x {mesh_tag} "
                  f"({backend}): lower {t_lower:.0f}s compile {t_compile:.0f}s "
                  f"bottleneck={rl.bottleneck} "
                  f"t=(c {rl.t_compute:.3g}, m {rl.t_memory:.3g}, "
                  f"x {rl.t_collective:.3g})s useful={rl.useful_ratio:.2f}")
    except Exception as e:  # record the failure — these are bugs to fix
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
        if verbose:
            print(f"[dryrun] FAIL {arch} x {shape_name} x {mesh_tag}: "
                  f"{type(e).__name__}: {str(e)[:300]}")
    fname.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "ring", "neighbor"])
    ap.add_argument("--topology", default="ring",
                    help="gossip graph (neighbor backend): ring | "
                         "exponential | torus2d | star | expander")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--pack-mode", default="lastdim",
                    choices=["lastdim", "flat"])
    ap.add_argument("--shard-aligned-blocks", action="store_true")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    archs = configs.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes_ = list(shp.SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_fail = 0
    for mp in meshes:
        for a in archs:
            for s in shapes_:
                rec = run_one(a, s, multi_pod=mp, backend=args.backend,
                              bits=args.bits, pack_mode=args.pack_mode,
                              shard_aligned_blocks=args.shard_aligned_blocks,
                              tag=args.tag, out_dir=args.out,
                              topology=args.topology)
                n_fail += rec["status"] == "error"
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
