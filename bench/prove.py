#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` (bench/check.py), on the chip
at a cell's own size.  The benchmark's own runs do not run this.

  python bench/prove.py --workload <cell> --seeds 11,12,13 \\
      --readings program,control,half_batch,no_exchange [--out FILE]

For each seed it runs the plain reference over the cell's first steps in
the configuration's dtype, and once per reading the same steps with one
thing changed, and prints the numbers ``check.compare`` gives for the
changed run against the plain one:

  program      the program itself, through the benchmark's own set-up and
               first steps (no timed window): the sound readings, one
               process for every seed;
  control      the reference in the nearest precision below the
               configuration's: float8_e4m3fn state for bfloat16;
  half_batch   half of each node's batch left out, the mean taken over the
               rest;
  no_exchange  the exchange between chips left out (each node mixes only
               its own payload).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16",
         "float16": "float8_e4m3fn"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--readings", default="control,half_batch")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench_out",
                                                      "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    from bench import check, run, weights
    manifest = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.load_cell(args.workload, manifest)
    try:
        devices = run.tpu_devices(cell["chips"])
    except run.BenchError as e:
        print(f"prove: {e}", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import jax.numpy as jnp
    cfg, tr = cell["cfg"], cell["traffic_params"]
    names = [p for p, _ in weights.leaf_list(cfg)]
    out = open(args.out, "a") if args.out else None
    program = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        readings = {}
        if "program" in args.readings.split(","):
            if program is None:
                program = run.Program(cfg, tr, args.workload, len(devices))
            state, pool, readings["program"] = program.first_steps(seed)
            run.free(state, pool)
        base = run.reference_reading(cfg, tr, seed, devices)
        for what in args.readings.split(","):
            if what == "control":
                readings[what] = run.reference_reading(
                    cfg, tr, seed, devices,
                    dtype=jnp.dtype(LOWER[cfg["torch_dtype"]]))
            elif what != "program":
                readings[what] = run.reference_reading(
                    cfg, tr, seed, devices, faults=(what,))
            nums = check.compare(readings[what], base, names)
            rec = {"workload": args.workload, "seed": seed, "reading": what,
                   "numbers": {k: v[0] for k, v in nums.items()},
                   "worst": {k: v[1] for k, v in nums.items()},
                   "readings": {"reading": readings[what], "reference": base},
                   "seconds": time.time() - t0}
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
