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


def _compiled(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _compile(fn, *shapes, sharding):
    return _compiled(fn, *shapes, sharding=sharding).as_text()


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("block", WIDTHS)
def test_quantize_pack_compiles(one_chip, block, bits):
    fn = functools.partial(qk.qinf_quantize_pack_blocks, bits=bits,
                           block=block, interpret=False)
    hlo = _compile(fn, ((ROWS, block), jnp.float32),
                   ((ROWS, block), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [1_024_000, 352_256, 16])
def test_quantize_pack_compiles_at_published_rows(one_chip, rows):
    """yi-9b's embed (and lm_head), stacked MLP and norm leaves as the
    bucketed wire quantizes them: bf16 rows of 256.  The scales leave the
    kernel as a lane-dense row; a (R, 1) f32 column would be laid out at
    128 lanes, 512 B a row of temp, and copied again after the kernel."""
    fn = functools.partial(qk.qinf_quantize_pack_blocks, bits=2, block=256,
                           interpret=False)
    compiled = _compiled(fn, ((rows, 256), jnp.bfloat16),
                         ((rows, 256), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 4 * 2


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
