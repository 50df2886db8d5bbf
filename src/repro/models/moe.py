"""Mixture-of-Experts layers: a softmax top-k router over the routed experts
(+ optional always-on shared experts, DeepSeek-MoE style), on one of two
paths.

``moe_mlp`` — capacity dispatch over every expert (Mixtral,
DeepSeek-MoE-16B): the standard one-hot capacity formulation
(MaxText/Flaxformer style).  Tokens over an expert's capacity are dropped,
the top-k gates are renormalised, and a Switch-style load-balance loss is
returned.  Its matmuls run in float32 over a (B, T, E, C) dispatch tensor.

``held_moe_mlp`` — the dropless layer of a node that holds a static range
of the routed experts, as a chip of an expert-parallel deployment does
(DeepSeek-V2-Lite): the router scores all of them, greedy top-k, gates not
renormalised (its routed scaling factor is 1); every (token, slot) pair
routed to a held expert is computed, grouped by expert through one grouped
matmul per SwiGLU projection (:func:`grouped_matmul`) over a static buffer
of T*k rows, and the pairs routed to experts held elsewhere add nothing
here.
It returns DeepSeek's sequence-level balance loss over all the routed
experts and the load of the held ones.  Its parts carry the named scopes
``moe/route`` (router, top-k, balance loss, grouping, combine) and
``moe/experts`` (the grouped matmuls).

Expert FF dims are tensor-parallel over the 'model' axis; the expert dim
stays unsharded.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

from repro.models import layers as L
from repro.obs.trace import MOE_EXPERTS, MOE_ROUTE

F32 = jnp.float32


def moe_mlp(x, router_w, experts_gate, experts_up, experts_down, *,
            top_k: int, capacity_factor: float = 1.25,
            shared=None):
    """x (B, T, D); experts_* (E, D, F) / (E, F, D); router_w (D, E).

    Returns (out (B,T,D), aux_loss scalar)."""
    B, T, D = x.shape
    E = router_w.shape[-1]
    logits = jnp.einsum("btd,de->bte", x.astype(F32), router_w.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (B,T,E)

    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)            # (B,T,k)
    # normalize the selected gates (Mixtral renormalizes over top-k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    capacity = max(1, int(np.ceil(T * top_k / E * capacity_factor)))

    # expert assignment (B, T, k, E) one-hot
    assign = jax.nn.one_hot(gate_idx, E, dtype=F32)
    # position of each (token, slot) within its expert's queue
    pos_in_expert = (jnp.cumsum(assign.reshape(B, T * top_k, E), axis=1)
                     .reshape(B, T, top_k, E) * assign) - assign
    keep = pos_in_expert < capacity
    assign = assign * keep

    onehot_pos = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                                dtype=F32) * assign[..., None]
    # dispatch (B, T, E, C) / combine weights
    dispatch = jnp.sum(onehot_pos, axis=2)                       # (B,T,E,C)
    combine = jnp.sum(onehot_pos * gate_vals[..., None, None], axis=2)

    xe = jnp.einsum("btd,btec->becd", x.astype(F32), dispatch)   # (B,E,C,D)
    g = jnp.einsum("becd,edf->becf", xe, experts_gate.astype(F32))
    u = jnp.einsum("becd,edf->becf", xe, experts_up.astype(F32))
    ye = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u,
                    experts_down.astype(F32))
    out = jnp.einsum("becd,btec->btd", ye, combine).astype(x.dtype)

    # load-balance auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(dispatch.sum(-1), axis=(0, 1))        # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)

    if shared is not None:
        sg, su, sd = shared
        gsh = jnp.einsum("btd,df->btf", x, sg.astype(x.dtype))
        ush = jnp.einsum("btd,df->btf", x, su.astype(x.dtype))
        out = out + jnp.einsum("btf,fd->btd", jax.nn.silu(gsh) * ush,
                               sd.astype(x.dtype))
    return out, aux


# ---------------------------------------------------------------------------
# Grouped matmul.  A TPU ragged dot takes no batch dims, and the trainer
# vmaps the model over its nodes: under vmap the batch folds into the
# groups (each node's grouped rows in turn, then every node's rows past its
# last group), and the backward is a custom VJP, since a custom_vmap
# function cannot be differentiated in reverse.
# ---------------------------------------------------------------------------

def _fold(b: int, M: int, group_sizes):
    """(dest, inv) permutations of b x M rows: every batch's grouped rows
    in batch order, then the rows past each batch's last group."""
    sums = jnp.sum(group_sizes, axis=1)
    offs = jnp.cumsum(sums) - sums
    r = jnp.arange(M)[None, :]
    i = jnp.arange(b)[:, None]
    dest = jnp.where(r < sums[:, None], offs[:, None] + r,
                     jnp.sum(sums) + i * M - offs[:, None] + r
                     - sums[:, None]).reshape(-1).astype(jnp.int32)
    inv = jnp.zeros((b * M,), jnp.int32).at[dest].set(
        jnp.arange(b * M, dtype=jnp.int32))
    return dest, inv


def _batched(b, batched, *xs):
    return [x if bt else jnp.broadcast_to(x, (b,) + x.shape)
            for x, bt in zip(xs, batched)]


@custom_vmap
def _ragged(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


@_ragged.def_vmap
def _ragged_vmap(b, batched, lhs, rhs, group_sizes):
    lhs, rhs, group_sizes = _batched(b, batched, lhs, rhs, group_sizes)
    if b == 1:
        return _ragged(lhs[0], rhs[0], group_sizes[0])[None], True
    (M, K), (G, _, N) = lhs.shape[1:], rhs.shape[1:]
    dest, inv = _fold(b, M, group_sizes)
    out = _ragged(lhs.reshape(b * M, K)[inv], rhs.reshape(b * G, K, N),
                  group_sizes.reshape(b * G))
    return out[dest].reshape(b, M, N), True


@custom_vmap
def _ragged_vjp(lhs, rhs, group_sizes, dout):
    _, vjp = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, group_sizes),
                     lhs, rhs)
    return vjp(dout)


@_ragged_vjp.def_vmap
def _ragged_vjp_vmap(b, batched, lhs, rhs, group_sizes, dout):
    lhs, rhs, group_sizes, dout = _batched(b, batched, lhs, rhs,
                                           group_sizes, dout)
    if b == 1:
        dl, dr = _ragged_vjp(lhs[0], rhs[0], group_sizes[0], dout[0])
        return (dl[None], dr[None]), (True, True)
    (M, K), (G, _, N) = lhs.shape[1:], rhs.shape[1:]
    dest, inv = _fold(b, M, group_sizes)
    dl, dr = _ragged_vjp(lhs.reshape(b * M, K)[inv],
                         rhs.reshape(b * G, K, N),
                         group_sizes.reshape(b * G),
                         dout.reshape(b * M, N)[inv])
    return (dl[dest].reshape(b, M, K), dr.reshape(b, G, K, N)), (True, True)


def _past_groups_zero(y, group_sizes):
    """y (M, N) with its rows past the last group set to 0.  The TPU ragged
    dot leaves those rows unwritten, in its output and in the gradient of
    its lhs alike, and a select (not a product) drops what they hold."""
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), y, jnp.zeros((), y.dtype))


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (M, K) in groups of ``group_sizes`` (G,) rows, rhs (G, K, N):
    each group's rows times its matrix; rows past the last group are 0
    and cost no matmul.  ``jax.lax.ragged_dot``, under vmap and grad too."""
    return _past_groups_zero(_ragged(lhs, rhs, group_sizes), group_sizes)


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(res, dout):
    lhs, rhs, group_sizes = res
    dl, dr = _ragged_vjp(lhs, rhs, group_sizes, dout)
    return _past_groups_zero(dl, group_sizes), dr, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.checkpoint
def _routed(x, w, order, group_sizes, experts_gate, experts_up,
            experts_down):
    """The held experts' part of the output: x (B, T, D); ``w`` (T k,) each
    (token, slot) pair's gate, 0 where its expert is held elsewhere;
    ``order`` the pairs grouped by held expert, ``group_sizes`` (H,).
    Rematerialized in the backward: its (T k)-row buffers are 8 x the
    pairs a held share of 64 experts gets on average, and would otherwise
    be kept for the backward in every layer."""
    B, T, D = x.shape
    M = w.shape[0]
    k = M // (B * T)
    with jax.named_scope(MOE_ROUTE):
        xs = x.reshape(B * T, D)[order // k]                     # (M, D)
    with jax.named_scope(MOE_EXPERTS):
        g = grouped_matmul(xs, experts_gate.astype(x.dtype), group_sizes)
        u = grouped_matmul(xs, experts_up.astype(x.dtype), group_sizes)
        y = grouped_matmul(jax.nn.silu(g) * u, experts_down.astype(x.dtype),
                           group_sizes)
    with jax.named_scope(MOE_ROUTE):
        # back to (token, slot) order; pairs held elsewhere weigh 0, and
        # their rows, past the last group, are 0 and never computed
        inv = jnp.zeros((M,), jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32))
        y = y[inv].astype(F32).reshape(B * T, k, D)
        out = jnp.einsum("tkd,tk->td", y, w.reshape(B * T, k))
        return out.astype(x.dtype).reshape(B, T, D)


def held_moe_mlp(x, router_w, experts_gate, experts_up, experts_down, *,
                 top_k: int, held: Tuple[int, int], shared=None):
    """The dropless held-expert layer (module docstring).

    x (B, T, D); router_w (D, E) over all E routed experts; experts_*
    (H, D, F) / (H, F, D) for the held experts ``held[0] .. held[1] - 1``
    (H = held[1] - held[0]); ``shared`` (gate, up, down) of the shared
    experts or None.

    Returns (out (B, T, D), balance, (held_pairs, max_load)): ``balance``
    is the sequence-level balance loss, unweighted,
    mean_b sum_e (count_be E / (T k)) mean_t p_bte over all E experts;
    ``held_pairs`` the (token, slot) pairs routed to held experts and
    ``max_load`` the most loaded held expert's pairs over the held mean."""
    B, T, D = x.shape
    E = router_w.shape[-1]
    start, stop = held
    H = stop - start
    M = B * T * top_k
    with jax.named_scope(MOE_ROUTE):
        logits = jnp.einsum("btd,de->bte", x.astype(F32),
                            router_w.astype(F32))
        probs = jax.nn.softmax(logits, axis=-1)                  # (B,T,E)
        gate, idx = jax.lax.top_k(probs, top_k)                  # (B,T,k)
        counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), axis=(1, 2))
        balance = jnp.mean(jnp.sum(counts * (E / (T * top_k))
                                   * jnp.mean(probs, axis=1), axis=-1))
        # (token, slot) pairs, held ones first and grouped by expert; the
        # rest (experts held elsewhere) sort past the last group
        local = idx.reshape(M) - start
        is_held = (local >= 0) & (local < H)
        order = jnp.argsort(jnp.where(is_held, local, H), stable=True)
        group_sizes = jnp.sum(counts[:, start:stop], axis=0).astype(
            jnp.int32)
        held_pairs = jnp.sum(group_sizes)
        max_load = (jnp.max(group_sizes).astype(F32)
                    / jnp.maximum(held_pairs.astype(F32) / H, 1.0))
    out = _routed(x, jnp.where(is_held, gate.reshape(M), 0.0), order,
                  group_sizes, experts_gate, experts_up, experts_down)
    if shared is not None:
        out = out + L.swiglu(x, *shared)
    return out, balance, (held_pairs, max_load)
