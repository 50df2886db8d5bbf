"""bench/run.py refuses to measure where it cannot: no TPU, too few chips,
or a checkout that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

from _bench_path import ROOT


def _run(cwd, args, pythonpath=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "yi9b-2L.node1.q2", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT, ARGS)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), ARGS)
    assert p.returncode != 0
    assert "not in this checkout" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_workload_exits_nonzero():
    p = _run(ROOT, ["--workload", "no-such-cell", "--seed", "1",
                    "--seconds", "1"])
    assert p.returncode != 0 and "unknown workload" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("chips,msg", [(1, "no TPU"), (4, "no TPU")])
def test_tpu_devices_refuses_cpu(chips, msg):
    from bench import run
    with pytest.raises(run.BenchError, match=msg):
        run.tpu_devices(chips)


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_counts_the_reserved_scratch():
    from bench import run
    devices = [_Device({"peak_bytes_in_use": 7, "peak_bytes_reserved": 6}),
               _Device({"peak_bytes_in_use": 9}), _Device(None)]
    assert run.memory_peak(devices) == 13
