"""A node's parameters, made from the seed on the device.

The layout is the state layout the trainer takes for a dense decoder:
``embed`` (Vp, D), ``final_norm`` (D,), ``lm_head`` (D, Vp) and the
layer-stacked ``blocks``; the vocabulary is padded to a multiple of
``padded_vocab_multiple`` rows (the padded logits take part in the softmax,
in the program and in the reference alike).  Matrices are normal with
standard deviation 1/sqrt(fan-in), norm scales are 1."""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import seeds


def padded_vocab(cfg: dict) -> int:
    m = cfg["padded_vocab_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def param_shapes(cfg: dict) -> Dict[str, object]:
    """{name: (shape, fan_in or None for a norm scale)}, nested like the
    trainer's parameter tree."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    L, Vp = cfg["num_hidden_layers"], padded_vocab(cfg)
    blocks = {
        "ln1": ((L, D), None),
        "wq": ((L, D, H * hd), D),
        "wk": ((L, D, KV * hd), D),
        "wv": ((L, D, KV * hd), D),
        "wo": ((L, H * hd, D), H * hd),
        "ln2": ((L, D), None),
        "w_gate": ((L, D, F), D),
        "w_up": ((L, D, F), D),
        "w_down": ((L, F, D), F),
    }
    if cfg.get("qk_norm"):
        blocks["q_norm"] = ((L, hd), None)
        blocks["k_norm"] = ((L, hd), None)
    return {"embed": ((Vp, D), D), "final_norm": ((D,), None),
            "lm_head": ((D, Vp), D), "blocks": blocks}


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
