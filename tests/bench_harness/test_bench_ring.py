"""The exchange between chips left out must make ``correct`` false: a tiny
four-node ring on four CPU devices, in a child process of its own."""
import json
import os
import subprocess
import sys

from _bench_path import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


def test_no_exchange_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([HERE, ROOT,
                                           os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, os.path.join(HERE, "ring_fault.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    runs = {r["run"]: r for r in map(json.loads, p.stdout.splitlines())}
    assert runs["sound"]["correct"], runs["sound"]["compared"]
    assert not runs["no_exchange"]["correct"]
    assert runs["no_exchange"]["compared"]["change_gap"]["value"] >= 0.99
