"""End-to-end decentralized training driver.

CPU-scale by default (reduced configs); pass --full on a real TPU pod.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --nodes 8 --steps 200 --bits 2 --prox l1 --lam 1e-5

All flags are aliases for ExperimentSpec fields (repro.api): the driver
builds a spec, prints it with --print-spec, and runs it through the shared
Runner protocol.  Checkpoints embed the spec, so
``repro.api.load_checkpoint`` reconstructs the exact experiment.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import api
from repro.launch.cache import use_compile_cache


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--local-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--compressor", default="qinf",
                    choices=["qinf", "identity", "randk", "topk"])
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--frac", type=float, default=0.1,
                    help="randk/topk kept fraction")
    ap.add_argument("--allow-biased", action="store_true",
                    help="opt in to biased compressors (topk violates "
                         "Assumption 2; ablations only)")
    ap.add_argument("--prox", default="none")
    ap.add_argument("--lam", type=float, default=1e-5)
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--backend", default="dense",
                    choices=["dense", "neighbor", "ring"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) model config")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--report", default=None,
                    help="write the run's RunReport JSON here")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the resolved ExperimentSpec JSON and exit")
    args = ap.parse_args(argv)

    spec = api.ExperimentSpec.from_flags(args, engine="sharded")
    if args.print_spec:
        print(spec.to_json())
        return None
    runner = api.build(spec)

    t0 = time.time()

    def log_cb(state, metrics, t):
        print(f"step {t:5d}  loss {float(metrics['loss']):.4f}  "
              f"consensus {float(metrics['consensus']):.3e}  "
              f"({(time.time() - t0) / (t + 1):.2f}s/step)")

    state, _ = runner.run(num_steps=args.steps, key=jax.random.key(0),
                          callback=log_cb,
                          log_every=max(1, args.log_every))

    # comm volume comes from the RunReport's exact wire accounting — the
    # SAME number runner.last_report carries, so CLI and report can never
    # disagree (neighbor/ring backends: hops x u8 wire payload, byte-
    # matched against HLO collective-permutes; dense: per-edge payload x
    # W out-degree)
    rep = runner.last_report
    if rep is not None and rep.wire["bits_per_step"]:
        comm_gb = rep.wire["bits_total"] / 8e9
        desc = (f"{args.compressor}, {args.bits}-bit"
                if args.compressor == "qinf" else args.compressor)
        print(f"done: {args.steps} steps; ~{comm_gb:.3f} GB "
              f"communicated/node ({desc}); "
              f"{rep.timing['mean_step_s']:.2f}s/step")
    else:
        print("done")
    if args.report and rep is not None:
        print("run report written to", rep.save(args.report))
    if args.ckpt:
        runner.save(args.ckpt, state, step=args.steps)
        print("checkpoint saved to", args.ckpt)
    return state


if __name__ == "__main__":
    main()
