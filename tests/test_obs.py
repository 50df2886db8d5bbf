"""repro.obs telemetry: meters/spans, RunReport emission per engine,
compile counters, the train step's names in a profile, and the perf
gate.

Device-light by design: everything here runs on the 1-device test process
(the conftest pins device count); the sharded-trainer byte-equalities on
real (8,1)/(4,2) meshes live in tests/test_dryrun_small.py subprocesses.
"""
import copy
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.obs.roofline import PEAKS, peaks_for
from repro.netsim import metrics as nmetrics

V5E = PEAKS["TPU v5 lite"]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_perf_gate():
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(REPO, "tools", "perf_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ===========================================================================
# Meters + spans
# ===========================================================================

class TestMeters:
    def test_inc_set_get(self):
        m = obs.Meters()
        m.inc("a", 2)
        m.inc("a", 3)
        m.set("b", 7)
        m.set("b", 9)                       # gauge: idempotent re-set
        assert m.get("a") == 5
        assert m.get("b") == 9
        assert m.get("missing", -1) == -1
        assert m.as_dict() == {"a": 5, "b": 9}

    def test_ambient_stack(self):
        assert obs.current_meters() is None
        outer, inner = obs.Meters(), obs.Meters()
        with obs.using_meters(outer):
            assert obs.current_meters() is outer
            with obs.using_meters(inner):
                assert obs.current_meters() is inner
            assert obs.current_meters() is outer
        assert obs.current_meters() is None

    def test_thread_safety_of_inc(self):
        m = obs.Meters()

        def work():
            for _ in range(1000):
                m.inc("n")

        ts = [threading.Thread(target=work) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert m.get("n") == 4000

    def test_env_info_keys(self):
        env = obs.env_info()
        assert set(env) >= {"jax", "backend", "device_kind",
                            "device_count", "cpu_count", "x64"}
        assert env["jax"] == jax.__version__
        assert env["device_count"] >= 1


class TestSpan:
    def test_span_records_time_and_count(self):
        m = obs.Meters()
        with obs.using_meters(m):
            with obs.span("work") as sp:
                x = sp.ready(jnp.ones(8) * 2)
        assert float(x[0]) == 2.0
        assert m.get("time/work_s") > 0
        assert m.get("time/work_n") == 1
        assert sp.elapsed_s == m.get("time/work_s")

    def test_span_accumulates(self):
        m = obs.Meters()
        for _ in range(3):
            with obs.span("loop", m):
                pass
        assert m.get("time/loop_n") == 3

    def test_span_without_meters_is_harmless(self):
        with obs.span("orphan") as sp:
            pass
        assert sp.elapsed_s >= 0

    def test_annotate_is_context_manager(self):
        with obs.annotate("probe"):
            pass


# ===========================================================================
# RunReport emission per engine
# ===========================================================================

def _spec(engine: str, steps: int = 3) -> api.ExperimentSpec:
    return api.ExperimentSpec(
        steps=steps, execution=api.ExecutionSpec(engine=engine))


class TestRunReports:
    def test_dense_report_bits_match_accounting(self):
        r = api.build(_spec("dense"))
        state, _ = r.run()
        rep = r.last_report
        assert rep is not None and rep.engine == "dense" and rep.steps == 3
        # bits = per-edge payload (netsim.metrics accounting) x out-degree
        per_edge = nmetrics.payload_bits_per_node(r.algo.compressor, r.X0)
        W = np.abs(np.asarray(r.algo.mixer.W))
        deg = ((W > 1e-12).sum() - (np.diag(W) > 1e-12).sum()) / W.shape[0]
        assert rep.wire["bits_per_step"] == per_edge * deg
        assert rep.wire["bits_total"] == rep.wire["bits_per_step"] * 3
        t = rep.timing
        assert t["total_s"] > 0
        assert t["mean_step_s"] == pytest.approx(t["total_s"] / 3)
        # the modelled wire time exists only for a chip in the peak table:
        # absent on this device, never priced with another chip's peaks
        assert peaks_for(rep.env["device_kind"]) is None
        assert "wire_model_s_per_step" not in t
        t5 = obs.wire_breakdown(t["total_s"], 3, rep.wire["bits_per_step"],
                                V5E)
        assert t5["wire_model_s_per_step"] == pytest.approx(
            rep.wire["bits_per_step"] / 8 / V5E.ici_bw)

    def test_netsim_report_matches_trajectory(self):
        r = api.build(_spec("netsim"))
        final, traj = r.run()
        rep = r.last_report
        assert rep.engine == "netsim" and rep.wire["scope"] == "system"
        assert rep.wire["bits_total"] == traj.total_bits
        assert rep.wire["bits_per_step"] == pytest.approx(
            traj.total_bits / traj.steps)
        # simulate()'s meter hooks landed in the ambient registry
        assert rep.meters["netsim/bits_per_edge_per_round"] == \
            traj.meta["bits_per_edge_per_round"]
        assert rep.meters["time/netsim_scan_n"] == 1

    def test_sweep_report_sums_grid_bits(self):
        import dataclasses as dc
        from repro.sweep import SweepRunner
        base = _spec("netsim", steps=4)
        pts = [dc.replace(base, seed=s) for s in (0, 1)]
        sr = SweepRunner(pts)
        _, res = sr.run()
        rep = sr.last_report
        assert rep.engine == "sweep"
        assert rep.extra["points"] == 2 and rep.extra["traces"] == 1
        assert rep.wire["bits_total"] == float(res.metrics["bits"].sum())

    def test_trainer_dense_backend_bits_accounting(self):
        # shape-only: bits_per_step works on the abstract state, no jit
        from repro import configs
        from repro.optim import DecentralizedTrainer, TrainerConfig
        cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
        tr = DecentralizedTrainer(cfg, TrainerConfig(n_nodes=4))
        runner = api.TrainerRunner(tr)
        state = tr.abstract_state()
        per_edge = nmetrics.payload_bits_per_node(tr.compressor,
                                                  state.plead.X)
        W = np.abs(np.asarray(tr.mixer.W))
        deg = ((W > 1e-12).sum() - (np.diag(W) > 1e-12).sum()) / W.shape[0]
        assert runner.bits_per_step(state) == per_edge * deg

    def test_report_json_roundtrip(self, tmp_path):
        r = api.build(_spec("dense", steps=2))
        r.run()
        rep = r.last_report
        assert obs.RunReport.from_json(rep.to_json()).to_dict() \
            == rep.to_dict()
        p = rep.save(tmp_path / "sub" / "report.json")
        assert obs.RunReport.from_json(p).to_dict() == rep.to_dict()


# ===========================================================================
# WireExchange meter hooks (pure-jnp, pp = identity closure)
# ===========================================================================

class TestWireExchangeMeters:
    def _exchange(self, mode: str):
        from repro.optim.wire import WireExchange
        we = WireExchange(bits=2, block=16)
        diffs = [jnp.ones((1, 4, 32)), jnp.ones((1, 8))]
        keys = list(jax.random.split(jax.random.key(0), len(diffs)))
        hop_pairs = [[(0, 0)], [(0, 0)]]            # 2 hops, self-loops
        wmat = np.full((3, 1), 1 / 3)
        pp = lambda x, pr: x
        m = obs.Meters()
        with obs.using_meters(m):
            if mode == "identity":
                we.identity(diffs, wmat, hop_pairs, pp)
            else:
                getattr(we, mode)(diffs, keys, wmat, hop_pairs, pp)
        return we, diffs, m

    def test_bucketed_records_exact_layout_bytes(self):
        we, diffs, m = self._exchange("bucketed")
        layout = we.layout([d.shape for d in diffs],
                           [d.dtype for d in diffs])
        assert m.get("wire/bytes_per_hop") == layout.wire_bits // 8
        assert m.get("wire/hops") == 2
        assert m.get("wire/collectives_per_step") == 2 * 2
        assert m.get("wire/traces") >= 1

    def test_per_leaf_ships_same_bytes_more_collectives(self):
        we, diffs, m = self._exchange("per_leaf")
        layout = we.layout([d.shape for d in diffs],
                           [d.dtype for d in diffs])
        assert m.get("wire/bytes_per_hop") == layout.wire_bits // 8
        assert m.get("wire/collectives_per_step") == 2 * len(diffs) * 2

    def test_identity_records_raw_float_bytes(self):
        _, diffs, m = self._exchange("identity")
        raw = sum(d.size * d.dtype.itemsize for d in diffs)
        assert m.get("wire/bytes_per_hop") == raw

    def test_no_ambient_meters_is_free(self):
        # must not raise nor leak state when no registry is installed
        assert obs.current_meters() is None
        from repro.optim.wire import WireExchange
        we = WireExchange(bits=2, block=16)
        diffs = [jnp.ones((1, 4, 32))]
        keys = [jax.random.key(0)]
        we.bucketed(diffs, keys, np.ones((2, 1)) / 2, [[(0, 0)]],
                    lambda x, pr: x)


# ===========================================================================
# Compile counters and the train step's names in a profile
# ===========================================================================

def _tiny_trainer_runner():
    """A one-node trainer runner off a mesh, its state and one batch."""
    from repro import configs
    from repro.optim import DecentralizedTrainer, TrainerConfig
    cfg = configs.get("qwen3-1.7b").reduced(n_layers=1, d_model=64)
    tr = DecentralizedTrainer(cfg, TrainerConfig(n_nodes=1))
    runner = api.TrainerRunner(tr)
    tokens = jnp.zeros((1, 2, 8), jnp.int32)
    return runner, runner.init_state(jax.random.key(0)), \
        {"tokens": tokens, "labels": tokens}


class TestCompileCounters:
    def test_first_step_compiles_once_and_second_adds_none(self):
        runner, state, batch = _tiny_trainer_runner()
        m = obs.Meters()
        with obs.using_meters(m):
            state, _ = runner.step(state, batch)
            jax.block_until_ready(state)
        first = m.as_dict()
        # the step's own trace, not those of the jits nested in it
        assert first["compile/step_n"] == 1
        assert first["compile/step_lower_s"] > 0
        assert first["compile/backend_s"] > 0 and first["compile/n"] >= 1
        with obs.using_meters(m):
            state, _ = runner.step(state, batch)
            jax.block_until_ready(state)
        assert m.as_dict() == first

    def test_nothing_recorded_without_meters(self):
        m = obs.Meters()
        with obs.using_meters(m):        # the listeners are registered
            pass
        assert obs.current_meters() is None
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        assert len(m) == 0

    def test_cache_events_and_backend_seconds_forwarded(self):
        m = obs.Meters()
        with obs.using_meters(m):
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
            jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 0.25,
                fun_name="jit(f)")
            # a persistent-cache hit's read lies inside the compile event
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.1)
        assert m.as_dict() == {"compile/backend_s": 0.25, "compile/n": 1,
                               "compile/cache_hits": 1,
                               "compile/cache_misses": 2}

    def test_run_report_carries_the_counters(self):
        runner, state, batch = _tiny_trainer_runner()

        class Data:
            def batch_at(self, t):
                return batch

        runner.run(num_steps=2, data=Data(), state=state)
        rep = runner.last_report
        assert rep.meters["compile/step_n"] == 1
        assert rep.meters["compile/backend_s"] > 0
        assert "roofline" not in rep.to_dict()
        # a report written before the field went still loads
        old = dict(rep.to_dict(), roofline={"utilization": 0.5})
        assert obs.RunReport.from_dict(old).to_dict() == rep.to_dict()


class TestStepNames:
    def test_annotate_marks_steps(self):
        assert isinstance(obs.annotate(obs.STEP, step_num=3),
                          jax.profiler.StepTraceAnnotation)
        assert isinstance(obs.annotate(obs.STEP_CALL),
                          jax.profiler.TraceAnnotation)

    def test_scopes_are_distinct_and_never_nest(self):
        assert len(set(obs.SCOPES)) == len(obs.SCOPES) == 6
        for a in obs.SCOPES:
            for b in obs.SCOPES:
                assert a == b or not ("/" + b + "/").startswith(
                    "/" + a + "/"), (a, b)

    def test_runner_counts_dispatched_steps(self, tmp_path):
        from jax.profiler import ProfileData
        runner, state, batch = _tiny_trainer_runner()
        state, _ = runner.step(state, batch)       # compiled outside
        jax.profiler.start_trace(str(tmp_path))
        for _ in range(2):
            state, _ = runner.step(state, batch)
        jax.block_until_ready(state)
        jax.profiler.stop_trace()
        path = next(tmp_path.rglob("*.xplane.pb"))
        names = [ev.name for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("repro.")]
        assert names.count(obs.STEP) == 2
        assert names.count(obs.STEP_CALL) == 2
        assert runner._dispatched == 3


# ===========================================================================
# perf gate
# ===========================================================================

def _wire_snapshot(speedup=2.0, cp_bucketed=2, ok=True):
    return {
        "suite": "wire", "steps": 60,
        "rows": [{"name": "ring/L=4", "topology": "ring", "hops": 1,
                  "cp_per_leaf": 8, "cp_bucketed": cp_bucketed,
                  "speedup": speedup}],
        "checks": [{"claim": "bucketed faster", "ok": ok, "detail": ""}],
    }


class TestPerfGate:
    def setup_method(self):
        self.pg = _load_perf_gate()

    def _hist(self, *snaps):
        return {"suite": "wire", "records": list(snaps)}

    def test_pass_on_matching_history(self):
        f = self.pg.gate_suite("wire", _wire_snapshot(),
                               self._hist(_wire_snapshot()), tol=0.5)
        assert f and all(ok for _, ok, _ in f)

    def test_injected_speedup_regression_fails_at_tol_zero(self):
        cur, base = _wire_snapshot(speedup=1.99), _wire_snapshot(speedup=2.0)
        bad = self.pg.gate_suite("wire", cur, self._hist(base), tol=0.0)
        assert any(not ok for _, ok, _ in bad)
        # ...but survives the documented walltime tolerance
        good = self.pg.gate_suite("wire", cur, self._hist(base), tol=0.5)
        assert all(ok for _, ok, _ in good)

    def test_exact_collective_count_regression_fails_at_any_tol(self):
        cur = _wire_snapshot(cp_bucketed=4)        # per-leaf crept back in
        f = self.pg.gate_suite("wire", cur, self._hist(_wire_snapshot()),
                               tol=1.0)
        assert any("cp_bucketed" in claim for claim, ok, _ in f if not ok)

    def test_snapshot_claim_failure_fails(self):
        f = self.pg.gate_suite("wire", _wire_snapshot(ok=False),
                               self._hist(_wire_snapshot()), tol=0.5)
        assert any(not ok for _, ok, _ in f)

    def test_missing_row_fails(self):
        cur = _wire_snapshot()
        cur["rows"] = []
        f = self.pg.gate_suite("wire", cur, self._hist(_wire_snapshot()),
                               tol=0.5)
        assert any(not ok for _, ok, _ in f)

    def test_no_history_passes_with_note(self):
        f = self.pg.gate_suite("wire", _wire_snapshot(),
                               self._hist(), tol=0.0)
        assert all(ok for _, ok, _ in f)

    def test_ratio_floor_uses_best_of_history(self):
        hist = self._hist(_wire_snapshot(speedup=1.2),
                          _wire_snapshot(speedup=2.4))
        f = self.pg.gate_suite("wire", _wire_snapshot(speedup=1.3),
                               hist, tol=0.5)
        assert all(ok for _, ok, _ in f)       # 1.3 >= 0.5 * 2.4
        f0 = self.pg.gate_suite("wire", _wire_snapshot(speedup=1.1),
                                hist, tol=0.5)
        assert any(not ok for _, ok, _ in f0)  # 1.1 < 1.2

    def test_sweep_parity_flip_fails(self):
        snap = {"suite": "sweep", "steps": 60,
                "rows": [{"mode": "sweep-map", "traces": 1,
                          "speedup_vs_serial": 3.0,
                          "parity_vs_serial": True}],
                "checks": []}
        cur = copy.deepcopy(snap)
        cur["rows"][0]["parity_vs_serial"] = False
        f = self.pg.gate_suite("sweep", cur,
                               {"suite": "sweep", "records": [snap]},
                               tol=1.0)
        assert any("parity" in claim for claim, ok, _ in f if not ok)

    def test_update_appends_history(self, tmp_path):
        p = tmp_path / "wire.json"
        self.pg.append_history(p, "wire", _wire_snapshot())
        self.pg.append_history(p, "wire", _wire_snapshot(speedup=2.5))
        hist = json.loads(p.read_text())
        assert len(hist["records"]) == 2
        assert all("date" in r for r in hist["records"])

    def test_committed_history_gates_green(self):
        """The repo's own snapshots must pass against the repo's own
        committed history — the `make ci` configuration."""
        hist_dir = os.path.join(REPO, "benchmarks", "history")
        if not os.path.isdir(hist_dir):
            pytest.skip("no committed history yet")
        for suite in self.pg.SUITES:
            snap_path = os.path.join(REPO, f"BENCH_{suite}.json")
            hist_path = os.path.join(hist_dir, f"{suite}.json")
            if not (os.path.exists(snap_path) and os.path.exists(hist_path)):
                pytest.skip(f"no snapshot/history for {suite}")
            current = json.loads(open(snap_path).read())
            hist = json.loads(open(hist_path).read())
            findings = self.pg.gate_suite(suite, current, hist, tol=0.5)
            bad = [(c, d) for c, ok, d in findings if not ok]
            assert not bad, bad
