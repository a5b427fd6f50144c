"""A run with the timed path broken underneath comes out not correct, and
so does each cell's control. The runs skip the harness's look for a chip
and drive the rest of a run on the CPU, at the configurations' widths
with a few envs and requests."""
import argparse
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness  # noqa: E402

SMALL_TRAIN = {"n_envs": 8, "unroll": 4, "superstep": 3}
SMALL_REPLAY = {"replay_capacity": 4096, "batch_size": 32}


def small_cell(name, **traffic):
    cell = harness.cell(name)
    if cell["traffic"]["driver"] == "train":
        cell["traffic"] = dict(cell["traffic"], **SMALL_TRAIN)
    else:
        cell["traffic"] = dict(cell["traffic"], rate_rps=100,
                               check_requests=32)
    if "replay_capacity" in cell["sizes"]:
        cell["sizes"] = dict(cell["sizes"], **SMALL_REPLAY)
    cell["traffic"].update(traffic)
    return cell


def run(cell, seed=11, seconds=1.0):
    drv = harness.load_module(
        harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    args = argparse.Namespace(trace=0, seconds=seconds)
    out = drv.run(cell, args, harness.seed31(seed), jax.devices()[:1],
                  harness.Spans(), time.perf_counter(), None)
    return harness.checks_pass(out["checks"]), out["checks"]


@pytest.mark.parametrize("name", ["impala-drltrunk.learn",
                                  "dqn-mlp.replay1m",
                                  "impala-drltrunk.serve"])
def test_sound_run_is_correct(name):
    ok, checks = run(small_cell(name))
    assert ok, checks


def _unchanged_state(monkeypatch):
    from repro.core.trainer import Trainer
    real = Trainer._superstep

    def superstep(self, k, donate=None):
        fn = real(self, k, donate=False)
        return lambda state, sim, its, delays: (state,) + tuple(
            fn(state, sim, its, delays)[1:])

    monkeypatch.setattr(Trainer, "_superstep", superstep)


def _half_batch(monkeypatch):
    """IMPALA learns from half of the envs; DQN from half of each drawn
    batch. The mean is taken over the rest."""
    import repro.core.algos  # noqa: F401
    from repro.core.algos.impala import IMPALAAgent
    from repro.core.replay import PrioritizedReplay
    real_step = IMPALAAgent.learner_step

    def half_envs(self, state, traj, boot, key, *a, **kw):
        b = traj["reward"].shape[1] // 2
        traj = jax.tree_util.tree_map(lambda x: x[:, :b], traj)
        return real_step(self, state, traj, boot[:b], key, *a, **kw)

    real_sample = PrioritizedReplay.sample

    def half_draw(self, state, key, n):
        batch, idx, w = real_sample(self, state, key, n)
        h = lambda x: x[:n // 2]
        return jax.tree_util.tree_map(h, batch), h(idx), h(w)

    monkeypatch.setattr(IMPALAAgent, "learner_step", half_envs)
    monkeypatch.setattr(PrioritizedReplay, "sample", half_draw)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["impala-drltrunk.learn",
                                  "dqn-mlp.replay1m"])
def test_training_fault_is_caught(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    ok, checks = run(small_cell(name))
    assert not ok, checks


def _altered_answers(monkeypatch, alter):
    from repro.core.serving import ServeEngine
    real = ServeEngine.step

    def step(self, now=None):
        out = real(self, now)
        for r in out:
            alter(r)
        return out

    monkeypatch.setattr(ServeEngine, "step", step)


def test_altered_answer_is_caught(monkeypatch):
    def flip(r):
        if r["id"] % 7 == 3:
            r["action"] = 1 - r["action"]

    _altered_answers(monkeypatch, flip)
    ok, checks = run(small_cell("impala-drltrunk.serve"))
    assert not ok, checks


def test_zeroed_value_is_caught(monkeypatch):
    _altered_answers(monkeypatch, lambda r: r.update(value=0.0 * r["value"]))
    ok, checks = run(small_cell("impala-drltrunk.serve"))
    assert not ok, checks


CONTROL_CASES = ["impala-drltrunk.learn", "dqn-mlp.replay1m",
                 "impala-drltrunk.serve"]
# the trunk's control is caught through first-rollout actions drawn the
# other way, which takes the learn cell's 8,192 draws a rollout
CONTROL_TRAFFIC = {"impala-drltrunk.learn": {"n_envs": 256, "unroll": 32,
                                             "superstep": 1}}


@pytest.mark.parametrize("name", CONTROL_CASES)
def test_control_is_not_correct(name):
    cell = small_cell(name, **CONTROL_TRAFFIC.get(name, {}))
    got = cell["code"].control_run(cell, harness.seed31(5))["checks"]
    limits = cell["traffic"]["limits"]
    assert any(v > limits[k] for k, v in got.items()), got
