"""Spec-built runner parity on real meshes (subprocess, 8 fake devices).

The api_redesign acceptance bar: for a fixed seed, ``build(spec).run(...)``
produces bit-for-bit identical final states vs the hand-built
``DecentralizedTrainer`` on the neighbor backend — both (8, 1) and (4, 2)
meshes, static ring AND a T > 1 schedule.  (The dense-algorithm and netsim
twins of this parity claim run device-free in tests/test_api.py.)
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# the hand-built reference, placed like TrainerRunner places it: state and
# batch under NamedShardings from state_specs / batch_specs
_PLACED = """
from jax.sharding import NamedSharding, PartitionSpec as P


def placed_step(tr, mesh, batch):
    ns = lambda t: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    st = ns(tr.state_specs(("data",)))
    bt = ns(tr.batch_specs(batch, ("data",)))
    init = jax.jit(tr.init_state, out_shardings=st)
    step = jax.jit(tr.train_step, in_shardings=(st, bt),
                   out_shardings=(st, None))
    return init, lambda s, b: step(s, jax.device_put(b, bt))
"""


def _run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = _PLACED + textwrap.dedent(code)
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env,
                          timeout=560)


@pytest.mark.slow
class TestSpecTrainerParity:
    def test_neighbor_backend_bitforbit_both_meshes(self):
        code = """
        import jax, numpy as np
        from repro import api, compat, configs
        from repro.data.pipeline import DecentralizedBatches
        from repro.optim import DecentralizedTrainer, TrainerConfig

        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        for meshshape, n in (((8, 1), 8), ((4, 2), 4)):
            mesh = compat.make_mesh(meshshape, ("data", "model"))
            data = DecentralizedBatches(n, 2, 16, cfg.vocab)
            for scenario in ("static", "alternating"):
                sched_kw = ({} if scenario == "static"
                            else {"schedule": "alternating"})
                # hand-built trainer (the pre-refactor construction path)
                tr = DecentralizedTrainer(cfg, TrainerConfig(
                    n_nodes=n, backend="neighbor", compressor="qinf",
                    bits=2, eta=0.1, **sched_kw), mesh=mesh)
                with compat.set_mesh(mesh):
                    init, step = placed_step(tr, mesh, data.batch_at(0))
                    s_ref = init(jax.random.key(0))
                    for t in range(3):
                        s_ref, _ = step(s_ref, data.batch_at(t))

                # spec-built runner over the same experiment
                spec = api.ExperimentSpec(
                    name=f"parity-{meshshape}-{scenario}", n_nodes=n,
                    algorithm=api.AlgorithmSpec("prox_lead",
                                                eta=api.constant(0.1)),
                    compressor=api.CompressorSpec("qinf", {"bits": 2}),
                    topology=api.TopologySpec(graph="ring",
                                              schedule=scenario),
                    model=api.ModelSpec(arch="qwen3-1.7b", n_layers=1,
                                        d_model=64, local_batch=2,
                                        seq_len=16),
                    execution=api.ExecutionSpec(engine="sharded",
                                                backend="neighbor",
                                                mesh=meshshape))
                assert spec == api.ExperimentSpec.from_json(spec.to_json())
                runner = api.build(spec)
                s_new = runner.init_state(jax.random.key(0))
                with compat.set_mesh(runner.mesh):
                    for t in range(3):
                        s_new, _ = runner.step(
                            s_new, runner.default_data().batch_at(t))

                la = jax.tree_util.tree_leaves(s_ref)
                lb = jax.tree_util.tree_leaves(s_new)
                assert len(la) == len(lb)
                exact = all(bool((np.asarray(a) == np.asarray(b)).all())
                            for a, b in zip(la, lb))
                assert exact, (meshshape, scenario)
                print("SPEC_PARITY_OK", meshshape, scenario)
        print("SPEC_PARITY_ALL")
        """
        r = _run_sub(code)
        assert "SPEC_PARITY_ALL" in r.stdout and \
            r.stdout.count("SPEC_PARITY_OK") == 4, \
            r.stdout + r.stderr[-3000:]

    def test_dense_prox_lead_parity_on_mesh(self):
        """Spec-built dense-backend trainer == hand-built, on a (4, 2)
        mesh under GSPMD (the dense ProxLEAD gossip path)."""
        code = """
        import jax, numpy as np
        from repro import api, compat, configs
        from repro.data.pipeline import DecentralizedBatches
        from repro.optim import DecentralizedTrainer, TrainerConfig

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        data = DecentralizedBatches(4, 2, 16, cfg.vocab)
        tr = DecentralizedTrainer(cfg, TrainerConfig(
            n_nodes=4, compressor="qinf", bits=2, eta=0.1), mesh=mesh)
        with compat.set_mesh(mesh):
            init, step = placed_step(tr, mesh, data.batch_at(0))
            s_ref = init(jax.random.key(0))
            for t in range(3):
                s_ref, _ = step(s_ref, data.batch_at(t))

        spec = api.ExperimentSpec(
            name="parity-dense", n_nodes=4,
            algorithm=api.AlgorithmSpec("prox_lead", eta=api.constant(0.1)),
            compressor=api.CompressorSpec("qinf", {"bits": 2}),
            model=api.ModelSpec(arch="qwen3-1.7b", n_layers=1, d_model=64,
                                local_batch=2, seq_len=16),
            execution=api.ExecutionSpec(engine="sharded", backend="dense",
                                        mesh=(4, 2)))
        runner = api.build(spec)
        s_new = runner.init_state(jax.random.key(0))
        with compat.set_mesh(runner.mesh):
            for t in range(3):
                s_new, _ = runner.step(s_new,
                                       runner.default_data().batch_at(t))
        exact = all(bool((np.asarray(a) == np.asarray(b)).all())
                    for a, b in zip(jax.tree_util.tree_leaves(s_ref),
                                    jax.tree_util.tree_leaves(s_new)))
        assert exact
        print("DENSE_PARITY_OK")
        """
        r = _run_sub(code)
        assert "DENSE_PARITY_OK" in r.stdout, r.stdout + r.stderr[-3000:]
