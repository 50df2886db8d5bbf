"""Plain reference of decentralized Prox-LEAD with QInf compression.

Each node i keeps X, D, H and Hw (trees like its parameters) on its own
device.  One step, with z = X - eta G - eta D:

  Q_i    = Q(z_i - H_i)            b-bit QInf, blocks along the last axis
  Zhat_i = H_i + Q_i,  Zhat_w,i = Hw_i + sum_j W_ij Q_j
  D     += gamma / (2 eta) (Zhat - Zhat_w)
  X      = z - gamma / 2 (Zhat - Zhat_w)         (no regularizer)
  H      = (1 - alpha) H + alpha Zhat,  Hw = (1 - alpha) Hw + alpha Zhat_w

starting from D = H = Hw = 0 (Liu et al. 2021, Algorithm 1 with its
warm-up folded into the first step).  Every result is rounded to the state
dtype, so the state is held in the precision the configuration states;
QInf draws its own stochastic-rounding noise.  With compressor
``identity`` Q is the identity.  W is the traffic's topology over its
``nodes`` (bench/graph.py)."""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, graph, kinds, seeds

F32 = jnp.float32


def qinf(x, key, bits: int, block: int):
    """Unbiased b-bit QInf of x along its last axis, in x's dtype."""
    shape = x.shape
    blk = counts.quant_block(shape, block)
    nb = math.ceil(shape[-1] / blk)
    xf = x.astype(F32)
    xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, nb * blk - shape[-1])])
    xb = xf.reshape(shape[:-1] + (nb, blk))
    levels = float(2 ** (bits - 1))
    m = jnp.max(jnp.abs(xb), -1, keepdims=True)
    u = jax.random.uniform(key, xb.shape, F32)
    mag = jnp.minimum(jnp.floor(levels * jnp.abs(xb)
                                / jnp.where(m > 0, m, 1.0) + u), levels)
    q = (jnp.sign(xb) * mag * (m / levels)).reshape(
        shape[:-1] + (nb * blk,))
    return q[..., : shape[-1]].astype(x.dtype)


def _round(like):
    return lambda a: a.astype(like.dtype)


@functools.partial(jax.jit, static_argnames=("eta", "bits", "block"))
def _payloads(X, G, D, H, key, *, eta, bits, block):
    """Q(z - H) of every leaf of one node, with z = X - eta G - eta D."""
    out = []
    for j, (x, g, d, h) in enumerate(zip(*map(jax.tree_util.tree_leaves,
                                               (X, G, D, H)))):
        r = _round(x)
        z = r(r(x - r(eta * g)) - r(eta * d))
        diff = r(z - h)
        out.append(diff if bits == 0 else
                   qinf(diff, jax.random.fold_in(key, j), bits, block))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(X),
                                        out)


@functools.partial(jax.jit, static_argnames=("eta", "alpha", "gamma"),
                   donate_argnums=(0, 2, 3, 4))
def _update(X, G, D, H, Hw, Q, received, weights, *, eta, alpha, gamma):
    """One node's new (X, D, H, Hw) from its payloads Q and the payloads
    ``received`` from the nodes it hears, weighted by ``weights`` (its own
    weight first)."""
    def leaf(x, g, d, h, hw, q, *recv):
        r = _round(x)
        wq = sum(w * p.astype(F32) for w, p in zip(weights, (q,) + recv))
        z = r(r(x - r(eta * g)) - r(eta * d))
        zhat, zhat_w = r(h + q), r(hw + r(wq))
        gap = r(zhat - zhat_w)
        return (r(z - r(gamma / 2.0 * gap)),
                r(d + r(gamma / (2.0 * eta) * gap)),
                r(r((1 - alpha) * h) + r(alpha * zhat)),
                r(r((1 - alpha) * hw) + r(alpha * zhat_w)))

    out = jax.tree_util.tree_map(leaf, X, G, D, H, Hw, Q, *received)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


class Reference:
    """The reference run of one cell: ``nodes`` replicas, node i on
    ``devices[i]``, from parameters ``x0`` (a host-independent tree per
    node, already on its device)."""

    def __init__(self, cfg, traffic, seed, x0s, devices, *, faults=()):
        self.cfg, self.tr, self.seed = cfg, traffic, seed
        self.devices = devices
        self.faults = set(faults)
        self.W = graph.weights(traffic["topology"], traffic["nodes"])
        if "no_exchange" in self.faults:
            self.W = np.eye(traffic["nodes"])
        self.X = list(x0s)
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        self.D = [zeros(x) for x in x0s]
        self.H = [zeros(x) for x in x0s]
        self.Hw = [zeros(x) for x in x0s]
        self.k = 0
        self._lg = _loss_grad_fn(kinds.frozen(cfg))

    def step(self, batch):
        """One step on host batch dict of (N, B, S) arrays; returns the
        mean over nodes of each node's loss (a Python float)."""
        tr = self.tr
        eta = tr["eta"]
        bits = tr["bits"] if tr["compressor"] == "qinf" else 0
        N = tr["nodes"]
        grads, losses = [], []
        for i in range(N):
            tok = jax.device_put(batch["tokens"][i], self.devices[i])
            lab = jax.device_put(batch["labels"][i], self.devices[i])
            if "half_batch" in self.faults:
                half = tok.shape[0] // 2
                tok, lab = tok[:half], lab[:half]
            loss, g = self._lg(self.X[i], tok, lab)
            losses.append(loss)
            grads.append(g)
        key = jax.random.fold_in(seeds.key(self.seed, "reference-noise"),
                                 self.k)
        Q = [_payloads(self.X[i], grads[i], self.D[i], self.H[i],
                       jax.device_put(jax.random.fold_in(key, i),
                                      self.devices[i]),
                       eta=eta, bits=bits, block=tr["block"])
             for i in range(N)]
        new = []
        for i in range(N):
            hear = [s for s in range(N) if s != i and self.W[i, s]]
            received = tuple(jax.device_put(Q[s], self.devices[i])
                             for s in hear)
            weights = tuple(float(self.W[i, s]) for s in [i] + hear)
            new.append(_update(self.X[i], grads[i], self.D[i], self.H[i],
                               self.Hw[i], Q[i], received, weights,
                               eta=eta, alpha=tr["alpha"],
                               gamma=tr["gamma"]))
            grads[i] = None
        del Q, grads
        self.X, self.D, self.H, self.Hw = (list(t) for t in zip(*new))
        self.k += 1
        return float(np.mean([float(l) for l in losses]))


@functools.lru_cache(maxsize=None)
def _loss_grad_fn(cfg_json: str):
    """The jitted loss and gradient of the configuration's model kind."""
    cfg = json.loads(cfg_json)
    loss_and_grad = kinds.of(cfg).loss_and_grad
    return jax.jit(lambda p, t, l: loss_and_grad(cfg, p, t, l))
