"""Prox-LEAD as the outer optimizer of decentralized NN training.

State layout: every parameter leaf gains a leading node dim N — node i's
replica.  The forward/backward is vmapped over N (GSPMD shards it over the
node mesh axes); the Prox-LEAD update then gossips with compression.

Gossip backends:
  dense    — paper-faithful: W X as a tensordot over the node dim (GSPMD
             turns it into all-gathers).  Works for any topology, any
             netsim schedule, and fault injection — but ships dequantized
             floats.
  neighbor — wire-honest (beyond-paper, §Perf): the COMM exchange runs
             inside shard_map over the node axes, ppermuting the PACKED
             b-bit payload (u8 codes + byte-cast scales) once per hop of a
             compiled ExchangePlan — ring, exponential graph, torus,
             matchings, any static sparse topology, and finite time-varying
             schedule cycles.  Collective bytes on the wire are the
             compressed payload, not dequantized floats.

Wire modes on the neighbor backend (wire_mode):
  bucketed — default: every leaf's quantization blocks map into ONE packed
             codes buffer + ONE byte-cast scales buffer per node
             (repro.core.bucket), so a hop is exactly 2 collective-permutes
             regardless of leaf count, and quantize+pack / unpack+dequant+
             mix run as fused kernels (repro.kernels).  Bit-for-bit equal
             to per_leaf whenever both modes run the same shard_map
             manualness, i.e. on model-unsharded meshes.  On a
             model-sharded mesh the per-leaf mode stays partial-manual
             (full leaves, one noise draw) while bucketed is full-manual
             (per-shard slices, per-shard draws), so the stochastic-
             rounding streams differ — equal in distribution, not
             bitwise.
  per_leaf — the original path (2 x hops x leaves collectives), kept as
             the parity oracle.  Identity compression always uses it (raw
             float leaves move; there is nothing to bucket).
  ring     — alias of neighbor kept for older configs/CLIs (with the
             default ring topology it compiles to the same two-hop plan the
             original ring-only backend hand-coded).

Time-varying schedules on the neighbor backend: payloads move over the
UNION support every round (a static hop set); per-round weight tables gate
the mixing.  Because the incremental recursion Hw + W Q only tracks W H for
a static W, the sharded state keeps one Hw slot per schedule round t
(leaf shape (N, T, ...)): Hw[t] tracks W_t H exactly via
Hw[t] += alpha * W_t Q — computable locally since every union neighbor's Q
arrives every round — and round k reads slot k % T.  This is the
distributed equivalent of netsim's dense-side Zhat_w = W_k (H + Q)
recomputation (memory cost: T state copies; netsim keeps T small).

The first trainer step folds Algorithm 1's warm-up (lines 1-3) into the
k=1 update with H^1 = 0, D^1 = 0 — identical fixed point, one less special
case in the jitted step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.core import topology as topo_mod
from repro.core.comm import CommState, DenseMixer, comm, init_comm_state
from repro.core.compression import Compressor, Identity, make_compressor
from repro.core.prox import NoneProx, Prox
from repro.core.prox_lead import ProxLEAD, ProxLEADState
from repro.core.oracles import OracleState
from repro.models import transformer as TR
from repro.models.sharding import param_specs

tmap = jax.tree_util.tree_map


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    n_nodes: int
    eta: float = 1e-2
    alpha: float = 0.5
    gamma: float = 1.0
    compressor: str = "qinf"        # identity | qinf | randk | topk
    bits: int = 2
    block: int = 256
    frac: float = 0.1               # randk / topk kept fraction
    allow_biased: bool = False      # opt-in for biased compressors (topk)
    prox: Optional[Prox] = None     # shared non-smooth regularizer
    topology: str = "ring"
    backend: str = "dense"          # dense | neighbor | ring (alias)
    # netsim scenario knobs: time-varying topology schedules run on BOTH
    # the dense and the neighbor (sharded compressed) backend; per-round
    # fault injection (drop_rate) is dense-only
    schedule: str = "static"        # static | alternating | random_matching
    #                               # | markov_drop
    schedule_rounds: int = 32       # T_cycle for the randomized schedules
    schedule_drop: float = 0.0      # markov_drop rate (schedule-level)
    drop_rate: float = 0.0          # i.i.d. LinkDrop fault rate
    fault_seed: int = 0
    pack_mode: str = "lastdim"      # lastdim | flat (§Perf iteration 2)
    wire_mode: str = "bucketed"     # bucketed | per_leaf (§Perf iteration 5)
    scales_bf16: bool = False       # §Perf iteration 3
    shard_aligned_blocks: bool = False  # §Perf iteration 4: block | shard
    tp_ways: int = 16               # model-axis width (for block alignment)
    aux_weight: float = 0.01        # MoE load-balance weight, where the
    #                               # model states none (aux_loss_alpha)
    # beyond-paper: precondition the gradient estimate per node before the
    # Prox-LEAD update (Adam second-moment normalization).  The algorithm
    # sees a preconditioned oracle; compression/gossip are unchanged.
    precondition: str = "none"      # none | adam
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    seed: int = 0


class TrainState(NamedTuple):
    plead: ProxLEADState
    step: jax.Array
    # adam preconditioner moments ((m, v) pytrees) or 0 when unused
    precond: Any = jnp.int32(0)


class DecentralizedTrainer:
    def __init__(self, model_cfg: TR.ModelConfig, tcfg: TrainerConfig,
                 mesh=None):
        self.mcfg = model_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.topo = topo_mod.make_topology(tcfg.topology, tcfg.n_nodes)
        # registry-driven construction: each compressor factory's signature
        # declares which of the flat config knobs it consumes (bits/block
        # for qinf, frac for randk/topk, nothing for identity) — and an
        # unknown compressor name fails loudly in make_compressor instead
        # of silently mapping to an empty kwargs set.
        from repro import registry
        kw = registry.kwargs_subset(
            "compressor", tcfg.compressor,
            {"bits": tcfg.bits, "block": tcfg.block, "frac": tcfg.frac})
        self.compressor: Compressor = make_compressor(tcfg.compressor, **kw)
        # config default, not a by-name component: TrainerConfig carries a
        # prox INSTANCE (or None)   # repro: allow(registry-only-construction)
        self.prox = tcfg.prox or NoneProx()
        self.plan: Optional[topo_mod.ExchangePlan] = None
        self.mixer = self._build_mixer()
        # ProxLEAD.__post_init__ enforces Assumption 2 (rejects biased
        # compressors unless explicitly allowed) for every backend.
        self.alg = ProxLEAD(tcfg.eta, tcfg.alpha, tcfg.gamma, self.compressor,
                            self.prox, self.mixer, oracle=None,  # type: ignore
                            allow_biased=tcfg.allow_biased)

    @property
    def sharded(self) -> bool:
        return self.tcfg.backend in ("ring", "neighbor")

    def _schedule(self):
        tcfg = self.tcfg
        from repro.netsim import make_schedule
        kw = ({"drop": tcfg.schedule_drop}
              if tcfg.schedule == "markov_drop" else {})
        return make_schedule(tcfg.schedule, tcfg.n_nodes,
                             base=tcfg.topology, rounds=tcfg.schedule_rounds,
                             seed=tcfg.seed, **kw)

    def _build_mixer(self):
        tcfg = self.tcfg
        if self.sharded:
            if tcfg.drop_rate > 0:
                raise ValueError(
                    "netsim fault injection (drop_rate) needs "
                    "backend='dense'; the sharded neighbor path covers "
                    "time-varying schedules but not per-round edge faults")
            if tcfg.compressor not in ("identity", "qinf"):
                raise ValueError(
                    f"the sharded neighbor backend packs QInf payloads; "
                    f"compressor {tcfg.compressor!r} needs backend='dense'")
            from repro.optim.wire import WIRE_MODES
            if tcfg.wire_mode not in WIRE_MODES:
                raise ValueError(
                    f"unknown wire_mode {tcfg.wire_mode!r}; "
                    f"have {WIRE_MODES}")
            if tcfg.schedule != "static":
                sched = self._schedule()
                self.plan = topo_mod.compile_plan(sched.W_stack,
                                                  name=sched.name)
                if self.plan.T > 8:
                    import warnings
                    warnings.warn(
                        f"neighbor backend keeps one Hw slot per schedule "
                        f"round: T={self.plan.T} multiplies the Hw state "
                        f"{self.plan.T}x (leaf (N, T, ...)).  Lower "
                        f"schedule_rounds or use backend='dense' if this "
                        f"does not fit memory.", stacklevel=2)
            else:
                self.plan = topo_mod.compile_plan(self.topo.W,
                                                  name=self.topo.name)
            # the dense mixer below backs self.alg, which the sharded path
            # never invokes; keep the static W so init/debug paths work.
            return DenseMixer(self.topo.W)
        scenario = tcfg.schedule != "static" or tcfg.drop_rate > 0
        if not scenario:
            return DenseMixer(self.topo.W)
        from repro.netsim import LinkDrop, SimMixer
        sched = self._schedule()
        # drop_rate is a scalar TrainerConfig knob, not a FaultSpec list
        # repro: allow(registry-only-construction)
        faults = (LinkDrop(tcfg.drop_rate),) if tcfg.drop_rate > 0 else ()
        return SimMixer(sched, faults, jax.random.key(tcfg.fault_seed))

    @property
    def _hw_T(self) -> Optional[int]:
        """Hw schedule-slot count for the sharded backend (None -> plain
        Hw with the same leaf shapes as H)."""
        if self.sharded and self.plan is not None and self.plan.T > 1:
            return self.plan.T
        return None

    # ------------------------------------------------------------------ init
    def init_state(self, key) -> TrainState:
        params = TR.init_params(self.mcfg, key)
        N = self.tcfg.n_nodes
        X = tmap(lambda p: jnp.broadcast_to(p[None], (N,) + p.shape), params)
        return self.state_from_stacked(X)

    def state_from_stacked(self, X) -> TrainState:
        zeros = tmap(jnp.zeros_like, X)
        T = self._hw_T
        if T is None:
            hw0 = tmap(jnp.zeros_like, X)                    # W @ 0 == 0
        else:  # one Hw slot per schedule round: leaf (N, T, ...)
            hw0 = tmap(lambda p: jnp.zeros(
                (p.shape[0], T) + p.shape[1:], p.dtype), X)
        cstate = CommState(zeros, hw0)
        plead = ProxLEADState(X, tmap(jnp.zeros_like, X), cstate,
                              OracleState(jnp.int32(0), jnp.int32(0),
                                          jnp.int32(0)), jnp.int32(1))
        precond = ((tmap(jnp.zeros_like, X), tmap(jnp.zeros_like, X))
                   if self.tcfg.precondition == "adam" else jnp.int32(0))
        return TrainState(plead, jnp.int32(0), precond)

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct state for dry-run lowering (no allocation)."""
        N = self.tcfg.n_nodes
        ap = TR.abstract_params(self.mcfg)
        X = tmap(lambda s: jax.ShapeDtypeStruct((N,) + s.shape, s.dtype), ap)
        zeros = X
        T = self._hw_T
        hw0 = (zeros if T is None else
               tmap(lambda s: jax.ShapeDtypeStruct(
                   (s.shape[0], T) + s.shape[1:], s.dtype), X))
        cstate = CommState(zeros, hw0)
        plead = ProxLEADState(X, zeros, cstate,
                              OracleState(*(jax.ShapeDtypeStruct((), jnp.int32),) * 3),
                              jax.ShapeDtypeStruct((), jnp.int32))
        precond = ((X, X) if self.tcfg.precondition == "adam"
                   else jax.ShapeDtypeStruct((), jnp.int32))
        return TrainState(plead, jax.ShapeDtypeStruct((), jnp.int32), precond)

    @staticmethod
    def _hw_specs(specs):
        """Insert the replicated T slot dim after the node dim of ``specs``
        (the Hw leaf layout for a time-varying plan)."""
        return tmap(lambda s: P(s[0], None, *s[1:]), specs,
                    is_leaf=lambda x: isinstance(x, P))

    def state_specs(self, node_axes: Tuple[str, ...]):
        """PartitionSpec pytree matching abstract_state()."""
        ap = TR.abstract_params(self.mcfg)
        ps = param_specs(ap, prepend=(node_axes,))
        scalar = P()
        hw_ps = ps if self._hw_T is None else self._hw_specs(ps)
        plead = ProxLEADState(ps, ps, CommState(ps, hw_ps),
                              OracleState(scalar, scalar, scalar), scalar)
        precond = ((ps, ps) if self.tcfg.precondition == "adam" else scalar)
        return TrainState(plead, scalar, precond)

    def batch_specs(self, batch_tree, node_axes: Tuple[str, ...]):
        def one(leaf):
            return P(node_axes, *((None,) * (leaf.ndim - 1)))
        return tmap(one, batch_tree)

    # ------------------------------------------------------------------ loss
    def _node_loss(self, params, batch_node):
        logits, _, aux, load = TR.forward_with_load(self.mcfg, params,
                                                    batch_node)
        ce = TR.loss_fn(self.mcfg, logits, batch_node["labels"])
        w = (self.tcfg.aux_weight if self.mcfg.aux_loss_alpha is None
             else self.mcfg.aux_loss_alpha)
        return ce + w * aux, (ce, load)

    def loss_and_grad(self, X, batch):
        """(mean CE over nodes, gradient, expert load): the load of a
        held-expert model is a pair of (N, layers) arrays (held pairs, max
        load), else None."""
        def total(Xs):
            losses, (ces, load) = jax.vmap(self._node_loss)(Xs, batch)
            return jnp.sum(losses), (jnp.mean(ces), load)

        with jax.named_scope(obs.FWD_BWD):
            (tot, (ce, load)), G = jax.value_and_grad(total, has_aux=True)(X)
        return ce, G, load

    # ------------------------------------------------------------------ step
    def train_step(self, state: TrainState, batch) -> Tuple[TrainState, dict]:
        ce, G, load = self.loss_and_grad(state.plead.X, batch)
        precond = state.precond
        if self.tcfg.precondition == "adam":
            with jax.named_scope(obs.UPDATE):
                G, precond = self._adam_precondition(G, precond, state.step)
        with jax.named_scope(obs.NOISE):
            key = jax.random.fold_in(jax.random.key(self.tcfg.seed),
                                     state.step)
        if self.sharded:
            plead = self._sharded_update(state.plead, G, key)
        else:
            with jax.named_scope(obs.UPDATE):
                plead = self.alg.update(state.plead, G, key)
        with jax.named_scope(obs.CONSENSUS):
            consensus = sum(
                jnp.sum((l - l.mean(0, keepdims=True)) ** 2)
                for l in jax.tree_util.tree_leaves(plead.X))
        with jax.named_scope(obs.UPDATE):
            step = state.step + 1
        metrics = {"loss": ce, "consensus": consensus,
                   "step": state.step}
        if load is not None:
            # pairs routed to held experts, over layers and nodes; the most
            # loaded held expert over the mean, the worst layer and node
            metrics["moe_held_pairs"] = jnp.sum(load[0])
            metrics["moe_max_load"] = jnp.max(load[1])
        return TrainState(plead, step, precond), metrics

    def _adam_precondition(self, G, precond, step):
        """Beyond-paper: per-node Adam normalization of the gradient before
        the Prox-LEAD update.  Moments are LOCAL (never communicated), so
        the wire cost is identical; the gossip operates on the
        preconditioned direction."""
        b1, b2, eps = self.tcfg.adam_b1, self.tcfg.adam_b2, self.tcfg.adam_eps
        m, v = precond
        m = tmap(lambda mm, g: b1 * mm + (1 - b1) * g, m, G)
        v = tmap(lambda vv, g: b2 * vv + (1 - b2) * g * g, v, G)
        t = (step + 1).astype(jnp.float32)
        c1 = 1.0 / (1.0 - b1 ** t)
        c2 = 1.0 / (1.0 - b2 ** t)
        Gp = tmap(lambda mm, vv: (mm * c1) / (jnp.sqrt(vv * c2) + eps), m, v)
        return Gp, (m, v)

    # -------------------------------------------- neighbor (shard_map) path
    @property
    def _partial_manual(self) -> bool:
        """Does the gossip shard_map leave the model axis auto (GSPMD)?

        Only on the per-leaf wire path: the bucketed path's cross-dim
        reshapes must not gather the auto model axis, so it runs
        FULL-manual (identity compression always takes the per-leaf
        path)."""
        use_bucket = (self.tcfg.wire_mode == "bucketed"
                      and not isinstance(self.compressor, Identity))
        return not use_bucket

    def _quant_block(self, diff_shape) -> int:
        """Quantization block size, optionally aligned to the model shard.

        ``diff_shape`` is the leaf as the quantizer sees it: the full
        per-node leaf under partial-manual shard_map (model axis auto), the
        model-LOCAL slice under full-manual (the bucketed path) — in the
        latter case the slice is already shard-aligned, so no further
        division by tp_ways applies."""
        tcfg = self.tcfg
        # never pad a row past its own width: a (model-local) last dim
        # below the block size would otherwise ship a full padded block
        # per row on every ppermute (the bucket layout reuses this exact
        # sizing, so neither wire mode ever ships a padded block)
        from repro.core.bucket import default_quant_block
        blk = default_quant_block(diff_shape, tcfg.block)
        if tcfg.shard_aligned_blocks:
            # align quantization blocks to the model-shard boundary: the
            # (.., nb, blk) reshape then never crosses shards, so no gather
            # is induced.  Still a valid Assumption-2 blockwise quantizer
            # (smaller blocks -> slightly more scales, smaller C).
            ld = diff_shape[-1]
            if self._partial_manual and ld % tcfg.tp_ways == 0:
                shard = ld // tcfg.tp_ways
            else:
                shard = ld
            # largest EVEN divisor (nibble packing pairs the last axis);
            # odd shards fall back to pairing-safe 2
            evens = [d for d in range(2, min(tcfg.block, shard) + 1, 2)
                     if shard % d == 0]
            blk = max(evens) if evens else 2
        return blk

    def _sharded_update(self, plead: ProxLEADState, G, key) -> ProxLEADState:
        """Lines 6-10 with the COMM exchange ppermuting packed payloads once
        per hop of the compiled ExchangePlan.

        Runs inside shard_map over the node axes; the model axis stays auto
        (GSPMD).  Requires a concrete mesh.  Every wire payload is u8: the
        packed codes natively, the per-block scales via bitcast — so the
        lowered HLO's collective-permutes are exactly the bits the paper
        counts.  For schedules (T > 1), Hw carries one slot per round
        (see module docstring) and Q moves over the union support."""
        assert self.mesh is not None, "neighbor backend needs a mesh"
        assert self.plan is not None
        tcfg = self.tcfg
        plan = self.plan
        from repro.models.sharding import node_axes as mesh_node_axes
        naxes = mesh_node_axes(self.mesh)
        axis = naxes if len(naxes) > 1 else naxes[0]
        T = plan.T
        eta, alpha, gamma = tcfg.eta, tcfg.alpha, tcfg.gamma
        bits = tcfg.bits
        use_q = not isinstance(self.compressor, Identity)
        # identity ships raw float leaves — nothing to bucket
        use_bucket = use_q and tcfg.wire_mode == "bucketed"
        # The bucketed path concatenates and reshapes leaves across their
        # trailing dims, which under partial-manual shard_map would force
        # GSPMD to gather the auto (model) axis — so it always runs
        # FULL-manual (see below).
        partial_manual = self._partial_manual
        # (1 + n_hops, T, n): row 0 the exact-stochastic self weight, then
        # one row per hop — receiver-indexed, per schedule round.
        wmat_np = np.concatenate(
            [plan.self_weights(np.float32)[None]]
            + [h.weights[None] for h in plan.hops], 0).astype(np.float32)
        hop_pairs = [list(h.pairs) for h in plan.hops]
        if partial_manual:
            model_sharded_leaf = ()
        else:
            # full-manual mode: which leaves does the model axis shard?
            # (tree_flatten order matches local_step's leaves)
            from repro.models.sharding import spec_mentions
            sp_leaves = jax.tree_util.tree_leaves(
                param_specs(TR.abstract_params(self.mcfg)),
                is_leaf=lambda s: isinstance(s, P))
            model_sharded_leaf = tuple(
                spec_mentions(sp, "model") for sp in sp_leaves)

        def pp(x, pairs):
            return jax.lax.ppermute(x, axis, pairs)

        def local_step(X, D, H, Hw, Gl, k_arr, step_k, node_id,
                       model_id=None):
            # leaves have a leading local node dim of size 1; Hw leaves an
            # extra T dim ((1, T, ...)) when the plan is time-varying.
            # node_id is a P(naxes)-sharded iota: its local shard holds this
            # node's index.
            with jax.named_scope(obs.EXCHANGE):
                idx = node_id[0]
                t = jnp.asarray(step_k, jnp.int32) % T
                wmat = jnp.asarray(wmat_np)[:, :, idx]   # (1 + hops, T)
            leaves_X, treedef = jax.tree_util.tree_flatten(X)
            leaves = {
                "X": leaves_X,
                "D": treedef.flatten_up_to(D),
                "H": treedef.flatten_up_to(H),
                "Hw": treedef.flatten_up_to(Hw),
                "G": treedef.flatten_up_to(Gl),
            }
            with jax.named_scope(obs.NOISE):
                key_local = jax.random.fold_in(
                    jax.random.wrap_key_data(k_arr), idx)
            diffs, zs, keys = [], [], []
            for j, (x, d, h, g) in enumerate(zip(
                    leaves["X"], leaves["D"], leaves["H"], leaves["G"])):
                with jax.named_scope(obs.NOISE):
                    kj = jax.random.fold_in(key_local, j)
                    if model_id is not None and model_sharded_leaf[j]:
                        # full-manual mode: decorrelate the stochastic-
                        # rounding draws of the model shards — ONLY for
                        # leaves the model axis actually shards.  Model-
                        # replicated leaves (norms, biases) must draw
                        # identically on every shard or their "replicated"
                        # outputs silently diverge per device (check_rep is
                        # off).
                        kj = jax.random.fold_in(kj, model_id[0])
                with jax.named_scope(obs.UPDATE):
                    z = x - eta * g - eta * d
                    diff = z - h
                zs.append(z)
                diffs.append(diff)
                keys.append(kj)
            # COMM: the wire exchange produces, per leaf, the dequantized
            # self payload and W_t' Q for every round t' of the cycle
            # ((T, ...) — bucketed moves 2 buffers per hop, per_leaf 2 per
            # hop per leaf; identical results bit for bit)
            from repro.optim.wire import WireExchange
            wx = WireExchange(bits=bits, block=tcfg.block,
                              scales_bf16=tcfg.scales_bf16,
                              pack_mode=tcfg.pack_mode,
                              block_for=self._quant_block)
            if not use_q:
                wq_list, qself_list = wx.identity(diffs, wmat, hop_pairs, pp)
            elif use_bucket:
                wq_list, qself_list = wx.bucketed(diffs, keys, wmat,
                                                  hop_pairs, pp)
            else:
                wq_list, qself_list = wx.per_leaf(diffs, keys, wmat,
                                                  hop_pairs, pp)
            nX, nD, nH, nHw = [], [], [], []
            for j, (z, d, h, hw) in enumerate(zip(
                    zs, leaves["D"], leaves["H"], leaves["Hw"])):
                wq_all, q_self = wq_list[j], qself_list[j]
                with jax.named_scope(obs.UPDATE):
                    zhat = h + q_self
                    if T == 1:
                        zhat_w = hw + wq_all[0]
                        hw_new = (1 - alpha) * hw + alpha * zhat_w
                    else:
                        hw_t = jnp.take(hw, t, axis=1)   # slot k % T
                        zhat_w = hw_t + jnp.take(wq_all, t, axis=0)
                        # Hw[t'] tracks W_t' H: H += alpha Q
                        #   =>  += alpha W_t' Q
                        hw_new = hw + alpha * jnp.moveaxis(wq_all, 0, 1)
                    dnew = d + gamma / (2 * eta) * (zhat - zhat_w)
                    v = z - gamma / 2.0 * (zhat - zhat_w)
                    nX.append(self.prox(v, eta))
                    nD.append(dnew)
                    nH.append((1 - alpha) * h + alpha * zhat)
                    nHw.append(hw_new)
            unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
            return unf(nX), unf(nD), unf(nH), unf(nHw)

        # Per-leaf mode: partial-manual shard_map — specs mention ONLY the
        # manual (node) axes, the model-axis sharding of trailing dims
        # stays under GSPMD (auto axes).  The bucketed wire path reshapes
        # across trailing dims, which must not gather the model axis, so
        # it runs FULL-manual: every mesh axis goes manual, specs carry the
        # per-leaf model placement (param_specs), and each model shard
        # quantizes/ppermutes its local slice independently.
        with jax.named_scope(obs.NOISE):
            key_data = jax.random.key_data(key)
        node_ids = jnp.arange(tcfg.n_nodes, dtype=jnp.int32)
        if partial_manual:
            specs = tmap(lambda l: P(naxes, *((None,) * (l.ndim - 1))),
                         plead.X)
            manual = set(naxes)
            extra_in, extra_args = (), ()
        else:
            from repro.models.sharding import model_axis_size
            specs = param_specs(TR.abstract_params(self.mcfg),
                                prepend=(naxes,))
            manual = set(self.mesh.axis_names)
            if model_axis_size(self.mesh) > 1:
                extra_in = (P("model"),)
                extra_args = (jnp.arange(model_axis_size(self.mesh),
                                         dtype=jnp.int32),)
            else:
                # no model sharding -> no shard-id key folding: fold_in(k,
                # 0) != k, and the per-leaf mode runs partial-manual
                # WITHOUT the fold — skipping it keeps the two wire modes
                # bit-for-bit equal on single-model-shard meshes
                extra_in, extra_args = (), ()
        hw_specs = specs if T == 1 else self._hw_specs(specs)
        shmapped = compat.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(specs, specs, specs, hw_specs, specs, P(), P(),
                      P(naxes)) + extra_in,
            out_specs=(specs, specs, specs, hw_specs),
            axis_names=manual, check=False)
        nX, nD, nH, nHw = shmapped(plead.X, plead.D, plead.comm.H,
                                   plead.comm.Hw, G, key_data, plead.k,
                                   node_ids, *extra_args)
        with jax.named_scope(obs.UPDATE):
            k = plead.k + 1
        return ProxLEADState(nX, nD, CommState(nH, nHw), plead.oracle, k)
