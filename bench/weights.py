"""A node's parameters, made from the seed on the device.

The layout is the state layout the trainer takes for the configuration's
model kind (``param_shapes`` of bench/reference/<reference>.py, found by
bench/kinds.py); the vocabulary is padded to a multiple of
``padded_vocab_multiple`` rows (the padded logits take part in the softmax,
in the program and in the reference alike).  Leaf j, in the tree's
flattening order, is normal with standard deviation 1/sqrt(fan-in) from
the seed's key folded with j; norm scales are 1."""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import kinds, seeds


def padded_vocab(cfg: dict) -> int:
    m = cfg["padded_vocab_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def param_shapes(cfg: dict) -> Dict[str, object]:
    """{name: (shape, fan_in or None for a norm scale)}, nested like the
    trainer's parameter tree."""
    return kinds.of(cfg).param_shapes(cfg)


def _is_entry(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def leaf_shapes(cfg: dict):
    """The parameter tree with each leaf's shape (tuple leaves)."""
    return jax.tree_util.tree_map(lambda e: e[0], param_shapes(cfg),
                                  is_leaf=_is_entry)


def dtype_of(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


def key(seed: int):
    return seeds.key(seed, "weights")


def init_params(cfg: dict, key):
    """One node's parameters from ``key(seed)`` (traced: call in jit)."""
    entries, treedef = jax.tree_util.tree_flatten(param_shapes(cfg),
                                                  is_leaf=_is_entry)
    dt = dtype_of(cfg)
    out = []
    for j, (shape, fan) in enumerate(entries):
        if fan is None:
            out.append(jnp.ones(shape, dt))
        else:
            k = jax.random.fold_in(key, j)
            out.append((jax.random.normal(k, shape, jnp.float32)
                        / math.sqrt(fan)).astype(dt))
    return jax.tree_util.tree_unflatten(treedef, out)


def stacked_params(cfg: dict, key, n_nodes: int):
    """Every node starts from the same parameters: leaves (N, ...)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n_nodes,) + p.shape),
        init_params(cfg, key))


def leaf_list(cfg: dict) -> Tuple[Tuple[str, tuple], ...]:
    """(path, shape) of every leaf, in the tree's flattening order."""
    flat = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(d, int) for d in x))[0]
    return tuple((jax.tree_util.keystr(p), s) for p, s in flat)
