"""Operations and bytes the work needs, computed from shapes.

Model FLOPs are the configuration's model kind's (bench/kinds.py): for the
dense decoder, the PaLM paper's appendix B, 6 x the matmul weights per
token plus the attention term.

Kernel bytes are what the algorithm needs, whatever implements it:
quantize+pack reads each leaf once in its dtype and writes ``bits`` bits
per element plus one scale per block; unpack+dequant+mix reads 1 + hops
such payloads and writes the mix (one per schedule round) and the
dequantized self payload once, in the leaf's dtype.  The stochastic
rounding noise and the nibble slot a 2-bit code occupies are not needed by
the algorithm, so they are not counted."""
from __future__ import annotations

import math
from typing import Iterable, Tuple

import numpy as np

from bench import kinds, weights

SCALE_BYTES = 4             # one f32 scale per quantization block


def matmul_weights(cfg: dict) -> int:
    """Weights one token multiplies, by the configuration's model kind."""
    return kinds.of(cfg).matmul_weights(cfg)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one token, forward and backward, by the
    configuration's model kind."""
    return kinds.of(cfg).flops_per_token(cfg, seq_len)


def quant_block(shape: Tuple[int, ...], block: int) -> int:
    """The configured block, capped at an even last dim narrower than it."""
    ld = shape[-1] if shape else 1
    return ld if (ld % 2 == 0 and ld < block) else block


def _blocks(shape, block) -> int:
    blk = quant_block(shape, block)
    rows = int(np.prod(shape[:-1], dtype=np.int64)) if len(shape) > 1 else 1
    return rows * math.ceil(shape[-1] / blk)


def payload_bytes(shape, bits: int, block: int) -> float:
    n = int(np.prod(shape, dtype=np.int64))
    return n * bits / 8 + _blocks(shape, block) * SCALE_BYTES


def _leaves(cfg: dict) -> Iterable[tuple]:
    return (s for _, s in weights.leaf_list(cfg))


def quant_pack_bytes(cfg: dict, bits: int, block: int) -> float:
    """One node's quantize+pack bytes per step, over all its leaves."""
    it = weights.dtype_of(cfg).itemsize
    return sum(int(np.prod(s)) * it + payload_bytes(s, bits, block)
               for s in _leaves(cfg))


def mix_bytes(cfg: dict, bits: int, block: int, hops: int,
              rounds: int = 1) -> float:
    """One node's unpack+dequant+mix bytes per step, over all its leaves."""
    it = weights.dtype_of(cfg).itemsize
    return sum((1 + hops) * payload_bytes(s, bits, block)
               + (rounds + 1) * int(np.prod(s)) * it for s in _leaves(cfg))
