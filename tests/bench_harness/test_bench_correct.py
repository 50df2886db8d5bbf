"""``correct`` on a tiny cell: true for the program as it is, false for
the control and for each fault of the timed path the cell can have."""
import jax
import jax.numpy as jnp
import pytest

import tiny
from bench import check, run, weights


def _unchanged(monkeypatch):
    from repro.optim import decentralized as dec
    orig = dec.DecentralizedTrainer.train_step

    def train_step(self, state, batch):
        _, metrics = orig(self, state, batch)
        return state._replace(step=state.step + 1), metrics
    monkeypatch.setattr(dec.DecentralizedTrainer, "train_step", train_step)


def _half_batch(monkeypatch):
    from repro.optim import decentralized as dec
    orig = dec.DecentralizedTrainer.loss_and_grad

    def loss_and_grad(self, X, batch):
        half = batch["tokens"].shape[1] // 2
        return orig(self, X, jax.tree_util.tree_map(lambda a: a[:, :half],
                                                    batch))
    monkeypatch.setattr(dec.DecentralizedTrainer, "loss_and_grad",
                        loss_and_grad)


def test_sound_run_is_correct(tmp_path):
    res = tiny.run(tiny.cell("node1.q2"), 3_000_000_019, str(tmp_path))
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["metrics"]) == ["tokens_per_s", "setup_s"]
    assert all(v["limit"] is not None for v in res["compared"].values())


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_caught(fault, monkeypatch, tmp_path):
    {"unchanged": _unchanged, "half_batch": _half_batch}[fault](monkeypatch)
    res = tiny.run(tiny.cell("node1.q2"), 3_000_000_019, str(tmp_path))
    assert not res["correct"], res["compared"]
    if fault == "unchanged":
        assert res["compared"]["change_gap"]["value"] == pytest.approx(1.0)


def test_control_is_caught():
    c = tiny.cell("node1.q2")
    cfg, tr = c["cfg"], c["traffic_params"]
    devices = jax.devices()[:1]
    base = run.reference_reading(cfg, tr, 7, devices)
    ctrl = run.reference_reading(cfg, tr, 7, devices,
                                 dtype=jnp.float8_e4m3fn)
    nums = check.compare(ctrl, base, [p for p, _ in weights.leaf_list(cfg)])
    ok, rows = check.judge(nums, c["limits"])
    assert not ok, rows
