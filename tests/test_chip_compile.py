"""Compile the fused wire kernels for a described TPU v5e, with no chip.

The TPU compiler is installed with JAX: it lowers and compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse (tiling, VMEM, unsupported vector shapes) — which
interpret-mode tests cannot show.  Widths: 256 and 128 are the bucket
widths of qwen3-1.7b at its published dims; 64 and 32 are what MoE routers
and the reduced test configs produce.  T is the schedule-round count of
the mix (1 static, 4 time-varying).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import quantize as qk

WIDTHS = (256, 128, 64, 32)
ROWS = 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # experimental APIs this test file alone needs
    from jax.experimental import topologies  # repro: allow(compat-only)
    from jax.experimental.compilation_cache import (  # repro: allow(compat-only)
        compilation_cache)
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("block", WIDTHS)
def test_quantize_pack_compiles(one_chip, block, bits):
    fn = functools.partial(qk.qinf_quantize_pack_blocks, bits=bits,
                           block=block, interpret=False)
    hlo = _compile(fn, ((ROWS, block), jnp.float32),
                   ((ROWS, block), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("block", WIDTHS)
def test_unpack_dequant_mix_compiles(one_chip, block, T):
    S, bits = 3, 2
    W = qk.packed_width(block, bits)
    fn = functools.partial(qk.qinf_unpack_dequant_mix_blocks, bits=bits,
                           block=block, out_dtype=jnp.bfloat16,
                           interpret=False)
    hlo = _compile(fn, ((S, ROWS, W), jnp.uint8),
                   ((S, ROWS, 1), jnp.float32), ((T, S), jnp.float32),
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("block", (256, 32))
def test_unpack_dequant_mix_f32_compiles(one_chip, block, T):
    """f32 leaves split each payload into exact halves inside the kernel
    (bitcasts and masks), which bf16 leaves skip."""
    S, bits = 3, 2
    W = qk.packed_width(block, bits)
    fn = functools.partial(qk.qinf_unpack_dequant_mix_blocks, bits=bits,
                           block=block, out_dtype=jnp.float32,
                           interpret=False)
    hlo = _compile(fn, ((S, ROWS, W), jnp.uint8),
                   ((S, ROWS, 1), jnp.float32), ((T, S), jnp.float32),
                   sharding=one_chip)
    assert "tpu_custom_call" in hlo
