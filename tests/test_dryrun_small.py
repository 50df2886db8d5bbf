"""Dry-run machinery tests.

Device-count-sensitive pieces run in subprocesses (the main test process
must keep exactly 1 device).  A small-mesh end-to-end lowering runs with 8
fake devices; the roofline HLO parser is tested in-process on string
fixtures.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.obs import roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=560)


class TestRooflineParser:
    HLO = textwrap.dedent("""\
    HloModule test

    %cond.1 (p: s32[]) -> pred[] {
      %c = s32[] constant(28)
      ROOT %lt = pred[] compare(%p, %c), direction=LT
    }

    %body.1 (p: s32[]) -> s32[] {
      %ag = f32[16,64]{1,0} all-gather(%x), replica_groups=[16,16]<=[16,16]T(1,0), dimensions={0}
      ROOT %out = s32[] add(%p, %one)
    }

    ENTRY %main () -> f32[] {
      %w = (s32[]) while(%init), condition=%cond.1, body=%body.1
      %ar = f32[128]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%sum
      %cp = bf16[256]{0} collective-permute(%z), source_target_pairs={{0,1},{1,0}}
      ROOT %r = f32[] constant(0)
    }
    """)

    def test_loop_multiplier_applied(self):
        out = roofline.collective_bytes(self.HLO)
        # all-gather inside 28-trip loop: 16*64*4 bytes * 15/16 * 28
        expect_ag = 16 * 64 * 4 * 15 / 16 * 28
        assert out["all-gather"] == pytest.approx(expect_ag)

    def test_entry_counted_once(self):
        out = roofline.collective_bytes(self.HLO)
        assert out["all-reduce"] == pytest.approx(2 * 128 * 4 * 3 / 4)
        assert out["collective-permute"] == pytest.approx(256 * 2)

    def test_shape_bytes_tuple(self):
        assert roofline._shape_bytes("(f32[2,2], bf16[4])") == 16 + 8


class TestAnalyticModels:
    def test_flops_scale_with_tokens(self):
        from repro import configs
        from repro.configs import shapes as shp
        cfg = configs.get("yi-9b")
        f_train = roofline.analytic_flops(cfg, shp.SHAPES["train_4k"])
        f_dec = roofline.analytic_flops(cfg, shp.SHAPES["decode_32k"])
        assert f_train > 100 * f_dec

    def test_moe_cheaper_than_dense_equiv(self):
        from repro import configs
        from repro.configs import shapes as shp
        cfg = configs.get("mixtral-8x7b")
        n_all = cfg.param_count()
        n_act = cfg.param_count(active_only=True)
        assert n_act < 0.45 * n_all  # top-2 of 8 experts

    def test_hbm_train_scales_with_state_copies(self):
        # each extra Prox-LEAD state copy costs exactly one read + one
        # write of the per-chip bf16 params, nothing else
        from repro import configs
        from repro.configs import shapes as shp
        cfg = configs.get("yi-9b")
        shape = shp.SHAPES["train_4k"]
        b4 = roofline.analytic_hbm_bytes(cfg, shape, 8, 8, 4.0)
        b6 = roofline.analytic_hbm_bytes(cfg, shape, 8, 8, 6.0)
        per_chip_params = cfg.param_count() * 2.0 * 8 / 8
        assert b6 - b4 == pytest.approx(2 * 2 * per_chip_params)

    def test_hbm_train_total_conserved_across_chip_counts(self):
        # per-chip traffic is an even split: chips x per-chip is invariant
        from repro import configs
        from repro.configs import shapes as shp
        cfg = configs.get("yi-9b")
        shape = shp.SHAPES["train_4k"]
        b8 = roofline.analytic_hbm_bytes(cfg, shape, 8, 8, 4.0)
        b16 = roofline.analytic_hbm_bytes(cfg, shape, 8, 16, 4.0)
        assert 16 * b16 == pytest.approx(8 * b8)
        assert b16 < b8

    def test_hbm_decode_dominated_by_weights_and_cache(self):
        from repro import configs
        from repro.configs import shapes as shp
        cfg = configs.get("yi-9b")
        dec = roofline.analytic_hbm_bytes(
            cfg, shp.SHAPES["decode_32k"], 1, 8, 0.0)
        assert dec > cfg.param_count() * 2.0 / 8  # at least the weights


@pytest.mark.slow
class TestSmallMeshLowering:
    """End-to-end lowering on an 8-device fake mesh (subprocess)."""

    def test_train_and_decode_lower(self):
        code = """
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat, configs
        from repro.configs import shapes as shp
        from repro.optim import DecentralizedTrainer, TrainerConfig
        from repro.models import transformer as TR
        from repro.models.sharding import param_specs

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=2, d_model=128)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        tr = DecentralizedTrainer(cfg, TrainerConfig(n_nodes=4), mesh=mesh)
        state = tr.abstract_state()
        shape = shp.InputShape("t", 64, 8, "train")
        batch = shp.train_input_specs(cfg, shape, 4)
        ns = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        with compat.set_mesh(mesh):
            c = jax.jit(tr.train_step,
                        in_shardings=(ns(tr.state_specs(("data",))),
                                      ns(tr.batch_specs(batch, ("data",))))
                        ).lower(state, batch).compile()
        assert c.memory_analysis().temp_size_in_bytes >= 0
        print("TRAIN_OK")

        params = TR.abstract_params(cfg)
        cache = TR.init_cache(cfg, 8, 64, abstract=True)
        toks = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        with compat.set_mesh(mesh):
            c2 = jax.jit(lambda p, c_, t, q: TR.decode_step(cfg, p, c_, t, q)
                         ).lower(params, cache, toks, pos).compile()
        print("DECODE_OK")
        """
        r = _run_sub(code)
        assert "TRAIN_OK" in r.stdout and "DECODE_OK" in r.stdout, \
            r.stdout + r.stderr[-2000:]

    def test_ring_backend_lowers_with_ppermute(self):
        code = """
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat, configs
        from repro.configs import shapes as shp
        from repro.optim import DecentralizedTrainer, TrainerConfig

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=2, d_model=128)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        tr = DecentralizedTrainer(
            cfg, TrainerConfig(n_nodes=4, backend="ring", bits=2), mesh=mesh)
        state = tr.abstract_state()
        shape = shp.InputShape("t", 64, 8, "train")
        batch = shp.train_input_specs(cfg, shape, 4)
        ns = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        with compat.set_mesh(mesh):
            lowered = jax.jit(
                tr.train_step,
                in_shardings=(ns(tr.state_specs(("data",))),
                              ns(tr.batch_specs(batch, ("data",))))
                ).lower(state, batch)
        txt = lowered.compile().as_text()
        # every GOSSIP payload on the wire must be u8 (packed codes and
        # byte-cast scales).  On a model-sharded mesh GSPMD also emits a
        # few small resharding collective-permutes of its own (present in
        # the dense lowering too), so assert u8 payload bytes dominate.
        import re
        from repro.obs import roofline
        cps = [m.group(1) for m in
               re.finditer(r'=\\s*((?:\\([^)]*\\))|(?:[\\w\\[\\],.{}]+))\\s+'
                           r'collective-permute(?:-start)?\\(',
                           txt)]
        assert cps, "no ppermute found"
        u8 = [c for c in cps if c.startswith("u8[")]
        # default bucketed wire: one codes + one scales buffer per hop
        assert len(u8) == 2 * len(tr.plan.hops), cps[:8]
        u8_bytes = sum(roofline._shape_bytes(c) for c in u8)
        other = sum(roofline._shape_bytes(c) for c in cps
                    if not c.startswith("u8["))
        assert u8_bytes > 4 * other, (u8_bytes, other)
        # per-DEVICE gossip bytes must match the plan accounting even on a
        # model-sharded mesh (model=2: per-shard quantization padding)
        from repro.models.sharding import model_axis_size
        from repro.netsim import metrics as nmetrics
        per_edge = nmetrics.sharded_payload_bits(
            tr, jax.tree_util.tree_leaves(state.plead.X))
        predicted = (len(tr.plan.hops) * per_edge / 8
                     / model_axis_size(mesh))
        assert u8_bytes == predicted, (u8_bytes, predicted)
        print("RING_OK", len(u8), u8_bytes, other)
        """
        r = _run_sub(code)
        assert "RING_OK" in r.stdout, r.stdout + r.stderr[-2000:]

    def test_ring_equals_dense_on_ring_topology(self):
        """The two gossip backends must produce identical updates (C=0)."""
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat, configs
        from repro.data.pipeline import DecentralizedBatches
        from repro.optim import DecentralizedTrainer, TrainerConfig

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=2, d_model=64)
        data = DecentralizedBatches(4, 2, 16, cfg.vocab)
        outs = []
        for backend in ("dense", "ring"):
            tr = DecentralizedTrainer(
                cfg, TrainerConfig(n_nodes=4, backend=backend,
                                   compressor="identity", eta=0.1),
                mesh=mesh)
            state = tr.init_state(jax.random.key(0))
            with compat.set_mesh(mesh):
                step = jax.jit(tr.train_step)
                for t in range(3):
                    state, m = step(state, data.batch_at(t))
            outs.append(jax.device_get(
                jax.tree_util.tree_leaves(state.plead.X)[0]))
        err = float(np.abs(outs[0] - outs[1]).max())
        scale = float(np.abs(outs[0]).max())
        assert err < 1e-4 * max(scale, 1), (err, scale)
        print("EQUIV_OK", err)
        """
        r = _run_sub(code)
        assert "EQUIV_OK" in r.stdout, r.stdout + r.stderr[-2000:]


@pytest.mark.slow
class TestNeighborBackend:
    """NeighborMixer parity + lowering on an 8-device fake mesh.

    The plan math itself (hop decomposition, weight tables, schedule
    reconstruction) is unit-tested device-free in test_topology.py; these
    subprocesses check the real shard_map/ppermute wiring end to end."""

    def test_parity_with_dense_all_topologies(self):
        """Neighbor backend == dense backend to float tolerance with C=0 on
        sparse non-ring graphs AND time-varying schedules; statistical
        agreement under qinf."""
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat, configs
        from repro.data.pipeline import DecentralizedBatches
        from repro.optim import DecentralizedTrainer, TrainerConfig

        mesh = compat.make_mesh((8, 1), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        data = DecentralizedBatches(8, 2, 16, cfg.vocab)

        def run(tcfg, steps=3):
            tr = DecentralizedTrainer(cfg, tcfg, mesh=mesh)
            state = tr.init_state(jax.random.key(0))
            with compat.set_mesh(mesh):
                step = jax.jit(tr.train_step)
                for t in range(steps):
                    state, m = step(state, data.batch_at(t))
            return (jax.device_get(
                jax.tree_util.tree_leaves(state.plead.X)[0]), m)

        cases = [dict(topology="exponential"), dict(topology="torus2d"),
                 dict(schedule="alternating"),
                 dict(schedule="random_matching", schedule_rounds=4)]
        for kw in cases:
            outs = [run(TrainerConfig(n_nodes=8, backend=b,
                                      compressor="identity", eta=0.1,
                                      **kw))[0]
                    for b in ("dense", "neighbor")]
            err = float(np.abs(outs[0] - outs[1]).max())
            scale = float(np.abs(outs[0]).max())
            assert err < 1e-4 * max(scale, 1), (kw, err, scale)
            print("PARITY_OK", kw, err)

        # statistical agreement under qinf: stochastic draws differ between
        # backends, so compare losses, not iterates
        losses = [float(run(TrainerConfig(
                      n_nodes=8, backend=b, topology="exponential",
                      compressor="qinf", bits=2, eta=0.1), steps=5)[1]["loss"])
                  for b in ("dense", "neighbor")]
        assert np.isfinite(losses).all()
        assert abs(losses[0] - losses[1]) < 0.25 * abs(losses[0]), losses
        print("QINF_OK", losses)
        """
        r = _run_sub(code)
        assert "QINF_OK" in r.stdout and r.stdout.count("PARITY_OK") == 4, \
            r.stdout + r.stderr[-2000:]

    def test_model_replicated_leaves_stay_consistent_under_qinf(self):
        """Regression (full-manual path): stochastic-rounding keys
        must be decorrelated across model shards ONLY for model-sharded
        leaves — replicated leaves (norms, biases) drawing different
        randomness per shard silently diverge, since check_rep is off."""
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat, configs
        from repro.data.pipeline import DecentralizedBatches
        from repro.optim import DecentralizedTrainer, TrainerConfig

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        data = DecentralizedBatches(4, 2, 16, cfg.vocab)
        tr = DecentralizedTrainer(cfg, TrainerConfig(
            n_nodes=4, backend="neighbor", compressor="qinf", bits=2,
            eta=0.1), mesh=mesh)
        state = tr.init_state(jax.random.key(0))
        with compat.set_mesh(mesh):
            step = jax.jit(tr.train_step)
            for t in range(2):
                state, m = step(state, data.batch_at(t))
        leaf = state.plead.X["blocks"]["k_norm"]   # model-replicated
        by_node = {}
        for s in leaf.addressable_shards:
            by_node.setdefault(str(s.index[0]), []).append(
                np.asarray(s.data))
        worst = 0.0
        for reps in by_node.values():
            for r in reps[1:]:
                worst = max(worst, float(np.abs(reps[0] - r).max()))
        assert worst == 0.0, worst
        print("REPLICA_OK", worst)
        """
        r = _run_sub(code)
        assert "REPLICA_OK" in r.stdout, r.stdout + r.stderr[-2000:]

    def test_bucketed_bitforbit_equals_per_leaf(self):
        """wire_mode='bucketed' must reproduce the per-leaf path EXACTLY —
        same codes, same scales, same mixes — for a static ring and a
        T > 1 schedule.  Exactness requires both modes to run the same
        shard_map manualness, which holds on a model-unsharded mesh: a
        (4, 2) mesh runs per-leaf partial-manual vs bucketed full-manual
        (noise drawn on different shard geometries — equal in distribution
        only)."""
        code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat, configs
        from repro.data.pipeline import DecentralizedBatches
        from repro.optim import DecentralizedTrainer, TrainerConfig

        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        meshes = [((8, 1), 8)]
        for meshshape, n in meshes:
            mesh = compat.make_mesh(meshshape, ("data", "model"))
            data = DecentralizedBatches(n, 2, 16, cfg.vocab)
            def run(wire_mode, **kw):
                tr = DecentralizedTrainer(cfg, TrainerConfig(
                    n_nodes=n, backend="neighbor", compressor="qinf",
                    bits=2, eta=0.1, wire_mode=wire_mode, **kw), mesh=mesh)
                state = tr.init_state(jax.random.key(0))
                with compat.set_mesh(mesh):
                    step = jax.jit(tr.train_step)
                    for t in range(3):
                        state, m = step(state, data.batch_at(t))
                return state
            for kw in (dict(topology="ring"), dict(schedule="alternating")):
                a, b = run("per_leaf", **kw), run("bucketed", **kw)
                exact = all(
                    bool((np.asarray(x) == np.asarray(y)).all())
                    for x, y in zip(jax.tree_util.tree_leaves(a.plead),
                                    jax.tree_util.tree_leaves(b.plead)))
                assert exact, (meshshape, kw)
                print("BITFORBIT_OK", meshshape, sorted(kw))
        print("BITFORBIT_ALL", 2 * len(meshes))
        """
        r = _run_sub(code)
        assert "BITFORBIT_ALL" in r.stdout, r.stdout + r.stderr[-2000:]
        want = int(r.stdout.split("BITFORBIT_ALL")[1].split()[0])
        assert r.stdout.count("BITFORBIT_OK") == want, \
            r.stdout + r.stderr[-2000:]

    def test_bucketed_collective_count_regression(self):
        """The bucketed path must lower to EXACTLY 2 x hops collective-
        permutes per step — leaf-count independent — with byte-exact
        bucket accounting, on both mesh shapes.  Fails if a change ever
        reintroduces per-leaf collectives on the default wire path."""
        code = """
        import jax, jax.numpy as jnp, dataclasses, re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat, configs
        from repro.configs import shapes as shp
        from repro.optim import DecentralizedTrainer, TrainerConfig
        from repro.obs import roofline
        from repro.netsim import metrics as nmetrics

        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        shape = shp.InputShape("t", 32, 8, "train")
        CP = (r'=\\s*((?:\\([^)]*\\))|(?:[\\w\\[\\],.{}]+))\\s+'
              r'collective-permute(?:-start)?\\(')
        for meshshape, n in (((8, 1), 8), ((4, 2), 4)):
            mesh = compat.make_mesh(meshshape, ("data", "model"))
            for topo in ("ring", "exponential"):
                tr = DecentralizedTrainer(cfg, TrainerConfig(
                    n_nodes=n, backend="neighbor", topology=topo, bits=2,
                    wire_mode="bucketed"), mesh=mesh)
                state = tr.abstract_state()
                batch = shp.train_input_specs(cfg, shape, n)
                ns = lambda t_: jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), t_,
                    is_leaf=lambda x: isinstance(x, P))
                with compat.set_mesh(mesh):
                    txt = jax.jit(tr.train_step,
                        in_shardings=(ns(tr.state_specs(("data",))),
                                      ns(tr.batch_specs(batch, ("data",))))
                        ).lower(state, batch).compile().as_text()
                cps = [m.group(1) for m in re.finditer(CP, txt)]
                u8 = [c for c in cps if c.startswith("u8[")]
                hops = len(tr.plan.hops)
                nleaves = len(jax.tree_util.tree_leaves(state.plead.X))
                assert nleaves > 2 * hops  # the claim is non-trivial
                # gossip collectives: exactly one codes + one scales
                # buffer per hop (GSPMD may add small non-u8 reshards on
                # the model-sharded mesh; the gossip payloads are all u8)
                assert len(u8) == 2 * hops, (meshshape, topo, len(u8))
                assert len(cps) == len(u8) or meshshape == (4, 2), cps
                # bucket accounting is byte-exact vs the HLO
                leaves = jax.tree_util.tree_leaves(state.plead.X)
                per_edge = nmetrics.bucketed_payload_bits(tr, leaves)
                assert per_edge == nmetrics.sharded_payload_bits(tr, leaves)
                from repro.models.sharding import model_axis_size
                u8_bytes = sum(roofline._shape_bytes(c) for c in u8)
                assert u8_bytes == (hops * per_edge / 8
                                    / model_axis_size(mesh)), \\
                    (meshshape, topo)
                print("CP_COUNT_OK", meshshape, topo, len(u8))
        """
        r = _run_sub(code)
        assert r.stdout.count("CP_COUNT_OK") == 4, \
            r.stdout + r.stderr[-2000:]

    def test_neighbor_lowers_u8_with_exact_wire_bits(self):
        """All gossip ppermutes are packed u8 AND the HLO-parsed
        collective-permute bytes equal the plan's exact per-hop
        accounting, ring vs exponential."""
        code = """
        import jax, jax.numpy as jnp, dataclasses, re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat, configs
        from repro.configs import shapes as shp
        from repro.optim import DecentralizedTrainer, TrainerConfig
        from repro.obs import roofline
        from repro.netsim import metrics as nmetrics

        mesh = compat.make_mesh((8, 1), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        shape = shp.InputShape("t", 32, 8, "train")
        measured = {}
        for topo in ("ring", "exponential"):
            tr = DecentralizedTrainer(cfg, TrainerConfig(
                n_nodes=8, backend="neighbor", topology=topo, bits=2),
                mesh=mesh)
            state = tr.abstract_state()
            batch = shp.train_input_specs(cfg, shape, 8)
            ns = lambda t_: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t_,
                is_leaf=lambda x: isinstance(x, P))
            with compat.set_mesh(mesh):
                lowered = jax.jit(tr.train_step,
                    in_shardings=(ns(tr.state_specs(("data",))),
                                  ns(tr.batch_specs(batch, ("data",))))
                    ).lower(state, batch)
            txt = lowered.compile().as_text()
            cps = [m.group(1) for m in
                   re.finditer(r'=\\s*((?:\\([^)]*\\))|(?:[\\w\\[\\],.{}]+))'
                               r'\\s+collective-permute(?:-start)?\\(',
                               txt)]
            bad = [c for c in cps if not c.startswith("u8[")]
            assert cps and not bad, (topo, bad[:5])
            parsed = roofline.collective_bytes(txt)["collective-permute"]
            per_edge = nmetrics.sharded_payload_bits(
                tr, jax.tree_util.tree_leaves(state.plead.X))
            predicted = len(tr.plan.hops) * per_edge / 8
            assert parsed == predicted, (topo, parsed, predicted)
            measured[topo] = parsed
            print("U8_OK", topo, int(parsed))
        assert measured["exponential"] > 2 * measured["ring"]
        print("BITS_OK", measured)
        """
        r = _run_sub(code)
        assert "BITS_OK" in r.stdout and r.stdout.count("U8_OK") == 2, \
            r.stdout + r.stderr[-2000:]


@pytest.mark.slow
class TestKernelRooflineGate:
    """The wire layout vs the exact accounting, on real meshes."""

    def test_wire_roofline_matches_exact_accounting_both_meshes(self):
        """The trainer's static BucketLayout (over its model-local leaf
        shapes) must equal (b) netsim.metrics' bucketed/sharded payload
        accounting, (c) TrainerRunner.bits_per_step, and (d) the bytes the
        compiled HLO physically moves — on both (8,1) and (4,2) meshes.
        If any of these ever drifts, the RunReport's bits stop being
        trustworthy."""
        code = """
        import jax, jax.numpy as jnp, dataclasses, re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import api, compat, configs
        from repro.configs import shapes as shp
        from repro.core import bucket
        from repro.optim import DecentralizedTrainer, TrainerConfig
        from repro.obs import roofline
        from repro.netsim import metrics as nmetrics
        from repro.models.sharding import model_axis_size

        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        shape = shp.InputShape("t", 32, 8, "train")
        CP = (r'=\\s*((?:\\([^)]*\\))|(?:[\\w\\[\\],.{}]+))\\s+'
              r'collective-permute(?:-start)?\\(')
        for meshshape, n in (((8, 1), 8), ((4, 2), 4)):
            mesh = compat.make_mesh(meshshape, ("data", "model"))
            tr = DecentralizedTrainer(cfg, TrainerConfig(
                n_nodes=n, backend="neighbor", topology="ring", bits=2,
                wire_mode="bucketed"), mesh=mesh)
            state = tr.abstract_state()
            leaves = jax.tree_util.tree_leaves(state.plead.X)
            hops = len(tr.plan.hops)
            per_edge = nmetrics.bucketed_payload_bits(tr, leaves)

            # (a)+(b) the static layout == exact payload accounting
            model, locals_ = nmetrics._model_local_shapes(tr, leaves)
            layout = bucket.compute_layout(
                [(1,) + tuple(s) for s in locals_], [l.dtype for l in leaves],
                bits=2, block_for=tr._quant_block)
            assert model * layout.wire_bits == per_edge, meshshape

            # (c) the RunReport's bits accounting
            runner = api.TrainerRunner(tr)
            assert runner.bits_per_step(state) == hops * per_edge

            # (d) the compiled HLO ships exactly those bytes per shard
            batch = shp.train_input_specs(cfg, shape, n)
            ns = lambda t_: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t_,
                is_leaf=lambda x: isinstance(x, P))
            with compat.set_mesh(mesh):
                txt = jax.jit(tr.train_step,
                    in_shardings=(ns(tr.state_specs(("data",))),
                                  ns(tr.batch_specs(batch, ("data",))))
                    ).lower(state, batch).compile().as_text()
            u8 = [m.group(1) for m in re.finditer(CP, txt)
                  if m.group(1).startswith("u8[")]
            u8_bytes = sum(roofline._shape_bytes(c) for c in u8)
            assert u8_bytes * model_axis_size(mesh) == hops * per_edge / 8, \\
                (meshshape, u8_bytes)
            print("ROOFLINE_OK", meshshape, int(per_edge))
        """
        r = _run_sub(code)
        assert r.stdout.count("ROOFLINE_OK") == 2, \
            r.stdout + r.stderr[-2000:]
