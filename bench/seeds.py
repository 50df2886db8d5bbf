"""PRNG keys from a run's ``--seed``, one stream per purpose.

A seed may exceed 32 bits; both halves go into the key."""
from __future__ import annotations

import zlib

import jax

_MASK = (1 << 32) - 1


def key(seed: int, purpose: str):
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & _MASK)
    k = jax.random.fold_in(k, (seed >> 32) & _MASK)
    return jax.random.fold_in(k, zlib.crc32(purpose.encode()) & 0x7FFFFFFF)
