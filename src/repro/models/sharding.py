"""Parameter PartitionSpecs and mesh-axis helpers.

Mesh axes:
  node axes  — ('data',) single-pod, ('pod','data') multi-pod: the
               decentralized graph (leading N dim on training state)
  'model'    — tensor parallelism inside each node
"""
from __future__ import annotations

import re
from typing import Any, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def node_axes(mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis_size(mesh) -> int:
    """Tensor-parallel ways on this mesh (1 when there is no model axis)."""
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)


def spec_mentions(spec: P, axis: str) -> bool:
    """Does any dim entry of ``spec`` name mesh axis ``axis``?"""
    for e in spec:
        if axis in (e if isinstance(e, tuple) else (e,)):
            return True
    return False


def model_local_shape(shape, spec: P, model: int):
    """Per-model-shard shape of a leaf: divide each dim whose spec entry
    names the model axis (``spec`` aligns with ``shape``'s dims)."""
    local = []
    for d, dim in enumerate(shape):
        e = spec[d] if d < len(spec) else None
        sharded = "model" in (e if isinstance(e, tuple) else (e,))
        local.append(dim // model if sharded else dim)
    return tuple(local)


# ---------------------------------------------------------------------------
# Parameter sharding rules: match on the flattened param path.
# Order matters: first match wins.  Specs are for the *per-node* leaf
# (layer-stacked: leading L dim), the trainer prepends the node axis.
# ---------------------------------------------------------------------------

_RULES: Sequence[Tuple[str, Tuple]] = (
    # token / position embeddings: (V|S, D) -> shard vocab dim
    (r"embed|lm_head_b|pos_embed", ("model", None)),
    (r"lm_head$", (None, "model")),                 # (D, V)
    # attention projections, layer-stacked (L, D, H*hd) etc.
    (r"(wq|wk|wv|w_qkv|cross_wk|cross_wv)$", (None, None, "model")),
    (r"(wq|wk|wv)_b$", (None, "model")),            # qkv biases (L, H*hd)
    (r"wo$", (None, "model", None)),
    # latent attention (L, D, kv_lora_rank + rope): one whole row, split
    # into c_kv and the shared rope key; (L, kv_lora_rank, H*(nope + v)):
    # heads
    (r"wkv_a$", (None, None, None)),
    (r"wkv_b$", (None, None, "model")),
    (r"wo_b$", (None, None)),
    # MLP, layer-stacked (L, D, F) / (L, F, D)
    (r"(w_gate|w_up|w_in)$", (None, None, "model")),
    (r"(w_in_b)$", (None, "model")),
    (r"w_down$|w_out$", (None, "model", None)),
    (r"w_out_b$", (None, None)),
    # MoE experts, layer-stacked (L, E, D, F) / (L, E, F, D)
    (r"experts_(gate|up)$", (None, None, None, "model")),
    (r"experts_down$", (None, None, "model", None)),
    (r"router$", (None, None, None)),
    # shared experts (L, D, F)/(L, F, D)
    (r"shared_(gate|up)$", (None, None, "model")),
    (r"shared_down$", (None, "model", None)),
    # RWKV6 projections (L, D, D) -> shard output dim (heads)
    (r"rwkv_(wr|wk|wv|wg|wo)$", (None, None, "model")),
    (r"cm_(wk|wr)$", (None, None, "model")),
    (r"cm_wv$", (None, "model", None)),
    # RG-LRU / recurrent block (L, D, W) projections
    (r"rg_(w_x|w_gate)$", (None, None, "model")),
    (r"rg_w_out$", (None, "model", None)),
    # everything else (norms, decay vectors, conv kernels, gates): replicated
)


def spec_for_path(path: str, ndim: int) -> P:
    for pat, axes in _RULES:
        if re.search(pat, path):
            if len(axes) == ndim:
                return P(*axes)
            if len(axes) < ndim:  # extra leading dims (e.g. superblock stack)
                return P(*((None,) * (ndim - len(axes)) + tuple(axes)))
            # rule has more dims than leaf (unstacked variant)
            return P(*axes[len(axes) - ndim:])
    return P(*((None,) * ndim))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(params, prepend: Tuple = ()) -> Any:
    """PartitionSpec pytree for an UNSTACKED param pytree (leaves without the
    node dim).  ``prepend`` adds leading spec entries for dims the *state*
    will carry in front (e.g. prepend=(('pod','data'),) for the node dim)."""

    def one(path, leaf):
        base = spec_for_path(_path_str(path), leaf.ndim)
        return P(*prepend, *base)

    return jax.tree_util.tree_map_with_path(one, params)


def shard_dim_ok(shape, spec: P, mesh_shape: dict) -> bool:
    for dim, ax in zip(shape, spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        k = int(np.prod([mesh_shape[a] for a in axes]))
        if dim % k != 0:
            return False
    return True
