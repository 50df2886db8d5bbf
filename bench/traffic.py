"""Token batches for a cell, made from the seed on the device.

Each row is a document of ``seq_len + 1`` token ids drawn i.i.d. from a
Zipf law (``zipf_exponent``) over a seeded permutation of the vocabulary,
with the ranks of each row shifted by an offset of its own, so that every
document has its own frequent tokens; tokens are the first ``seq_len``
and labels the last ``seq_len`` of a row.  Every node draws its own rows.  The pool holds ``check_steps`` batches for the steps
the comparison follows, then ``pool_batches`` that the window cycles."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import seeds


def zipf_cdf(vocab: int, exponent: float):
    p = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** (-exponent)
    c = jnp.cumsum(p)
    return c / c[-1]


def key(seed: int):
    return seeds.key(seed, "tokens")


def batches(traffic: dict, vocab: int, key, n_batches: int):
    """``n_batches`` dicts of tokens and labels, each (N, B, S) int32, from
    ``key(seed)`` (traced: call inside jit)."""
    N, B, S = traffic["nodes"], traffic["local_batch"], traffic["seq_len"]
    k_perm, k_shift, k_draw = jax.random.split(key, 3)
    perm = jax.random.permutation(k_perm, vocab).astype(jnp.int32)
    shift = jax.random.randint(k_shift, (n_batches, N, B, 1), 0, vocab,
                               jnp.int32)
    u = jax.random.uniform(k_draw, (n_batches, N, B, S + 1), jnp.float32)
    rank = jnp.searchsorted(zipf_cdf(vocab, traffic["zipf_exponent"]), u)
    rows = perm[(jnp.minimum(rank, vocab - 1).astype(jnp.int32) + shift)
                % vocab]
    return [{"tokens": rows[i, ..., :-1], "labels": rows[i, ..., 1:]}
            for i in range(n_batches)]


def n_batches(traffic: dict) -> int:
    return traffic["check_steps"] + traffic["pool_batches"]
