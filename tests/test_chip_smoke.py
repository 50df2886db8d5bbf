"""chip_smoke.py off the chip: its phase at a tiny reduced size on the CPU,
and its refusal to report anything without a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
TINY = dict(arch="qwen3-1.7b", n_layers=1, d_model=64, local_batch=2,
            seq_len=16)


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(devices: int = 1):
    # no PYTHONPATH: chip_smoke.py finds the package next to itself
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_phase_one_node_wire_modes_bitwise_equal(dtype):
    """bf16 is the chip's dtype: the per-leaf path fuses its bf16 leaf
    diffs and dequantized payloads, the bucketed one rounds them into its
    wire buffers, and they agree only because the step honors its declared
    dtypes (api.STEP_COMPILER_OPTIONS)."""
    cs = _load()
    res = cs.phase(1, dict(TINY, params={"dtype": dtype}), audit_wire=False)
    cs.verify(res, 1, on_chip=False)
    assert all(v is None for v in res["diffs"].values())
    assert res["bucketed"]["losses"] == res["per_leaf"]["losses"]


def test_four_node_phase_on_virtual_devices():
    """The --four-chip path on 4 virtual CPU devices: one node per device,
    2 x hops u8 collective-permutes, bitwise-equal wire modes."""
    code = f"""
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", {str(REPO / "chip_smoke.py")!r})
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    res = cs.phase(4, {TINY!r}, audit_wire=True)
    cs.verify(res, 4, on_chip=False)
    assert all(ok for _, ok, _ in res["bucketed"]["compiled_audit"])
    print("FOUR_NODE_OK")
    """
    env = dict(_env(4), PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert "FOUR_NODE_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]


@pytest.mark.parametrize("alone", [False, True])
def test_main_fails_without_tpu(tmp_path, alone):
    """No TPU (JAX_PLATFORMS=cpu), or no repo next to the script: a
    non-zero exit naming the cause, and no result line."""
    script = REPO / "chip_smoke.py"
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path / script.name))
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=_env(), cwd=str(script.parent),
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    want = "repro package" if alone else "no TPU"
    assert want in r.stderr, r.stderr[-2000:]


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_location(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the cache lands and no
    other directory is set; otherwise a fixed path inside the checkout."""
    code = """
    import pathlib, jax, jax.numpy as jnp
    from repro.launch.cache import CACHE_DIR, use_compile_cache
    where = use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == where, where
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    print("CACHE_AT", where)
    if pathlib.Path(where) != CACHE_DIR:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
        print("ENTRIES", len(list(pathlib.Path(where).iterdir())))
    """
    env = dict(_env(), PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jaxcache")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    where = r.stdout.split("CACHE_AT ")[1].split()[0]
    if env_dir:
        assert where == str(tmp_path / "jaxcache")
        assert int(r.stdout.split("ENTRIES ")[1].split()[0]) > 0
    else:
        assert where == str(REPO / ".jax_cache")


def test_compile_cache_hit_keeps_the_programs_op_names(tmp_path):
    """Two builds that differ only in a named scope get their own cache
    entries: a profile reads device time by op name, and a hit on the
    other build would bring its names."""
    code = """
    import jax, jax.numpy as jnp
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def step(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 3
        return f

    for scope in ("first_scope", "second_scope"):
        text = jax.jit(step(scope)).lower(jnp.ones(8)).compile().as_text()
        print(scope, scope in text)
    """
    env = dict(_env(), PYTHONPATH=str(REPO / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "first_scope True" in r.stdout and "second_scope True" in r.stdout
