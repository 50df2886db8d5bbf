"""The benchmark's run at a size a test run holds: a tiny configuration on
the CPU, through everything but the look for a chip."""
import json
import os
import time

import _bench_path  # noqa: F401
from _bench_path import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def cell(traffic_name: str) -> dict:
    with open(os.path.join(DATA, "tiny-2L.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           traffic_name + ".json")) as f:
        tr = json.load(f)
    tr.update(local_batch=2, seq_len=32, pool_batches=4)
    with open(os.path.join(DATA, "tiny-limits.json")) as f:
        limits = json.load(f)[traffic_name]
    return {"name": "tiny." + traffic_name, "cfg": cfg, "traffic_params": tr,
            "limits": limits, "chips": tr["nodes"], "per_layer": [],
            "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}]}


def run(c: dict, seed: int, trace_dir: str, seconds: float = 0.3) -> dict:
    import jax
    from bench import run as bench_run
    devices = jax.devices()[:c["traffic_params"]["nodes"]]
    return bench_run.run_cell(c, seed, seconds, False, devices,
                              t_start=time.time(), trace_dir=trace_dir)
