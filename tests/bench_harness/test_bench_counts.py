"""bench/counts.py against hand arithmetic."""
import json
import os

import numpy as np
import pytest

from _bench_path import ROOT
from bench import counts, weights


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_yi_matmul_weights_and_flops():
    cfg = _cfg("yi-9b-2L")
    layer = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    assert counts.matmul_weights(cfg) == 2 * layer + 4096 * 64000
    assert counts.matmul_weights(cfg) == 608_174_080
    assert counts.flops_per_token(cfg, 1024) == pytest.approx(3.75e9,
                                                              rel=2e-3)


@pytest.mark.parametrize("shape,block,want", [
    ((2, 2048, 6144), 256, 256),
    ((2, 128), 256, 128),          # capped at an even last dim
    ((2, 2048), 256, 256),
    ((7, 255), 256, 256),          # odd: keeps the padded block
])
def test_quant_block(shape, block, want):
    assert counts.quant_block(shape, block) == want


def test_kernel_bytes_by_hand():
    cfg = _cfg("yi-9b-2L")
    # one leaf by hand: lm_head (4096, 64000) bf16, 2-bit codes, blocks of
    # 256 along the last dim, an f32 scale per block
    n = 4096 * 64000
    blocks = 4096 * (64000 // 256)
    payload = n * 2 / 8 + blocks * 4
    assert counts.payload_bytes((4096, 64000), 2, 256) == payload
    # a (2, 128) leaf: the block is capped at 128, one scale per row
    assert counts.payload_bytes((2, 128), 2, 256) == 2 * 128 / 4 + 2 * 4
    # 2 x 64000 x 4096 (embed, lm_head) + 4096 (final norm) + 2 layers x
    # (2 x 4096 + 2 x 4096^2 + 2 x 4096 x 512 + 3 x 4096 x 11008)
    layer = 2 * 4096 + 2 * 4096 ** 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    total_elems = 2 * 64000 * 4096 + 4096 + 2 * layer
    assert total_elems == 870_338_560
    assert sum(int(np.prod(s)) for _, s in weights.leaf_list(cfg)) \
        == total_elems
    qp = counts.quant_pack_bytes(cfg, 2, 256)
    mix0 = counts.mix_bytes(cfg, 2, 256, hops=0)
    mix2 = counts.mix_bytes(cfg, 2, 256, hops=2)
    # quantize+pack: read 2 B, write 1/4 B and the scales; mix with no
    # hop: read one payload, write mix and qself at 2 B each
    assert qp - total_elems * 2 == pytest.approx(mix0 - total_elems * 4)
    assert mix2 - mix0 == pytest.approx(2 * (qp - total_elems * 2))
    assert 1.9e9 < qp < 2.0e9 and 3.6e9 < mix0 < 3.8e9
