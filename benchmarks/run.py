"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src:. python -m benchmarks.run [--steps 800] [--quick]

Prints a CSV block per benchmark plus a claim-validation verdict table.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, ".")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


def main(argv=None):
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="iteration count (default 800; 200 with --quick, "
                         "60 with --smoke — an explicit value wins)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced iteration counts (CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="make-ci gate: tiny comm+netsim+wire+sweep runs, "
                         "writes BENCH_comm.json / BENCH_netsim.json / "
                         "BENCH_wire.json / BENCH_sweep.json at repo root "
                         "so the bench trajectory accumulates per PR")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig1,fig2,table3,kernels,"
                         "comm,ablations,netsim,wire,sweep")
    ap.add_argument("--seeds", type=int, default=1,
                    help="seed-axis width for the fig1/fig2 grids (swept "
                         "inside the one-jit groups, curves averaged)")
    ap.add_argument("--json-out", default="experiments/bench_results.json")
    args = ap.parse_args(argv)
    if args.smoke and args.only is None:
        args.only = "comm,netsim,wire,sweep"
    if args.steps is not None:
        steps = args.steps
    else:
        steps = 60 if args.smoke else 200 if args.quick else 800

    from benchmarks import (ablations, bench_comm, bench_kernels,
                            bench_netsim, bench_sweep, bench_wire,
                            fig1_smooth, fig2_nonsmooth, table3_complexity)

    suites = {
        "fig1": ("Fig.1 smooth logistic regression",
                 lambda: fig1_smooth.run(steps, verbose=True,
                                         seeds=args.seeds),
                 fig1_smooth.validate),
        "fig2": ("Fig.2 non-smooth logistic regression",
                 lambda: fig2_nonsmooth.run(steps, verbose=True,
                                            seeds=args.seeds),
                 fig2_nonsmooth.validate),
        "table3": ("Table 2/3 rate-vs-theory",
                   lambda: table3_complexity.run(verbose=True),
                   table3_complexity.validate),
        "kernels": ("Pallas kernel microbench",
                    lambda: bench_kernels.run(verbose=True),
                    bench_kernels.validate),
        "comm": ("Communication accounting",
                 lambda: bench_comm.run(verbose=True),
                 bench_comm.validate),
        "ablations": ("Ablations: bits sweep + topology/kappa_g sweep",
                      lambda: ablations.run(min(500, steps), verbose=True),
                      ablations.validate),
        "netsim": ("Netsim robustness: drop rate x compression bits",
                   lambda: bench_netsim.run(min(400, steps), verbose=True),
                   bench_netsim.validate),
        "wire": ("Wire path: bucketed vs per-leaf gossip (8-dev subprocess)",
                 lambda: bench_wire.run(steps=min(20, steps), verbose=True),
                 bench_wire.validate),
        "sweep": ("Sweep engine: one-jit 16-point grid vs serial loop",
                  lambda: bench_sweep.run(min(60, steps), verbose=True),
                  bench_sweep.validate),
    }
    chosen = (args.only.split(",") if args.only else list(suites))

    all_rows = {}
    all_checks = []
    for key in chosen:
        title, runner, validator = suites[key]
        print(f"\n=== {title} ===")
        t0 = time.time()
        rows = runner()
        checks = validator(rows)
        all_rows[key] = rows
        all_checks.extend((key, *c) for c in checks)
        print(f"--- {key}: {len(rows)} rows in {time.time() - t0:.0f}s ---")
        # CSV block
        if rows:
            cols = [c for c in rows[0] if c != "subopt"]
            print(",".join(cols))
            for r in rows:
                print(",".join(str(r.get(c, "")) for c in cols))

    print("\n=== PAPER-CLAIM VALIDATION ===")
    n_fail = 0
    for key, claim, ok, detail in all_checks:
        mark = "PASS" if ok else "FAIL"
        n_fail += not ok
        print(f"[{mark}] ({key}) {claim}   [{detail}]")
    print(f"\n{len(all_checks) - n_fail}/{len(all_checks)} claims validated")

    # env stamp: every results file records the machine class it ran on,
    # so tools/perf_gate.py history comparisons stay attributable
    from repro.obs import env_info
    env = env_info()

    out = pathlib.Path(args.json_out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"env": env, "rows": all_rows,
         "checks": [{"suite": k, "claim": c, "ok": bool(o), "detail": str(d)}
                    for k, c, o, d in all_checks]}, indent=1, default=str))
    print("results written to", out)
    if args.smoke:
        # per-suite trajectory files at the repo root (one per PR gate)
        for key in ("netsim", "comm", "wire", "sweep"):
            if key not in all_rows:
                continue
            p = pathlib.Path(f"BENCH_{key}.json")
            p.write_text(json.dumps(
                {"suite": key, "steps": steps, "env": env,
                 "rows": all_rows[key],
                 "checks": [{"claim": c, "ok": bool(o), "detail": str(d)}
                            for k, c, o, d in all_checks if k == key]},
                indent=1, default=str))
            print("smoke trajectory written to", p)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
