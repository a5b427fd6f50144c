"""Pallas-fused learner hot path (PR 3): single-forward rollouts pin
the legacy trajectories, zero-copy (donated) supersteps pin the
non-donated numerics, fused prioritized sampling trains DQN end-to-end,
and the benchmark JSON schema round-trips."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.networks import MLPPolicy
from repro.core.rollout import rollout
from repro.core.trainer import Trainer, TrainerConfig
from repro.envs import CartPole, Pendulum


# ------------------------------------------------- single-forward rollout
class _CountingPolicy:
    """MLPPolicy wrapper counting trunk evaluations at trace time."""

    def __init__(self, inner, fused):
        self._inner = inner
        self.discrete = inner.discrete
        self.calls = 0
        if fused:
            self.sample_value = self._sample_value

    def init(self, key):
        return self._inner.init(key)

    def apply(self, params, obs):
        self.calls += 1
        return self._inner.apply(params, obs)

    def sample(self, params, obs, key):
        pi, _ = self.apply(params, obs)
        return self._inner._dist_sample(params, pi, key)

    def _sample_value(self, params, obs, key):
        pi, v = self.apply(params, obs)
        a, logp = self._inner._dist_sample(params, pi, key)
        return a, logp, v


@pytest.mark.parametrize("env_cls", [CartPole, Pendulum])
def test_rollout_single_forward_identical_trajectories(env_cls, rng):
    """Regression for the double forward pass (sample + apply per env
    step): the fused sample_value path runs ONE trunk evaluation per
    step and produces BITWISE the same trajectory."""
    env = env_cls()
    pol = MLPPolicy.for_spec(env.spec, hidden=(16,))
    params = pol.init(rng)
    state = env.reset_batch(rng, 4)
    trajs, counts = {}, {}
    for fused in (False, True):
        cpol = _CountingPolicy(pol, fused)
        trajs[fused], _ = rollout(cpol, params, env, rng, state, 6)
        counts[fused] = cpol.calls
    # lax.scan traces the step body once: the trace-time call count IS
    # the per-step forward count
    assert counts[True] == 1 and counts[False] == 2, counts
    for k in trajs[False]:
        assert np.array_equal(np.asarray(trajs[False][k]),
                              np.asarray(trajs[True][k])), k


def test_qpolicy_sample_value_matches_sample_apply_pair(rng):
    """DQN's adapter: one q evaluation reproduces the 3-evaluation
    sample/apply pair bitwise (same ε-greedy key discipline)."""
    from repro.core.agent import make
    env = CartPole()
    ag = make("dqn", env=env, hidden=(16,))
    state = ag.init(rng)
    actor = ag.actor_policy(state, 0)
    obs = jax.random.normal(rng, (8, env.spec.obs_dim))
    a1, lp1 = ag.policy.sample(actor, obs, rng)
    _, v1 = ag.policy.apply(actor, obs)
    a2, lp2, v2 = ag.policy.sample_value(actor, obs, rng)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(lp1), np.asarray(lp2))
    assert np.array_equal(np.asarray(v1), np.asarray(v2))


def test_rollout_fallback_for_policies_without_sample_value(rng):
    """Policies exposing only sample/apply still roll out (two-forward
    fallback)."""
    env = CartPole()
    pol = MLPPolicy.for_spec(env.spec, hidden=(16,))
    cpol = _CountingPolicy(pol, fused=False)
    assert not hasattr(cpol, "sample_value")
    traj, _ = rollout(cpol, pol.init(rng), env, rng, env.reset_batch(
        rng, 2), 3)
    assert traj["obs"].shape[:2] == (3, 2)


# --------------------------------------------------- zero-copy supersteps
@pytest.mark.parametrize("algo", ["dqn", "impala"])
def test_donated_superstep_numerically_unchanged(algo):
    """cfg.donate only changes buffer ownership, never numerics: full
    fit histories agree bitwise-ish across donate on/off."""
    env = CartPole()

    def run(donate):
        cfg = TrainerConfig(algo=algo, iters=6, superstep=3, n_envs=8,
                            unroll=6, log_every=2, seed=5, donate=donate,
                            algo_kwargs=(
                                {"hidden": (16,), "replay_capacity": 512,
                                 "warmup": 1} if algo == "dqn"
                                else {"hidden": (16,)}))
        return Trainer(env, cfg).fit()

    s1, h1 = run(True)
    s2, h2 = run(False)
    assert len(h1) == len(h2)
    for r1, r2 in zip(h1, h2):
        assert r1.keys() == r2.keys()
        for k in r1:  # array_equal: NaN (pre-first-episode) == NaN
            np.testing.assert_array_equal(np.float64(r1[k]),
                                          np.float64(r2[k]), err_msg=k)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


def test_donated_superstep_aliases_buffers():
    """The donated program actually aliases its carried state: XLA's
    memory analysis reports a nonzero donated-alias footprint covering
    at least the replay store."""
    env = CartPole()
    base = dict(algo="dqn", iters=4, superstep=2, n_envs=4, unroll=4,
                algo_kwargs={"replay_capacity": 1024, "hidden": (8,)})
    tr_on = Trainer(env, TrainerConfig(donate=True, **base))
    tr_off = Trainer(env, TrainerConfig(donate=False, **base))
    ma_on = tr_on.lower(2).compile().memory_analysis()
    ma_off = tr_off.lower(2).compile().memory_analysis()
    assert ma_off.alias_size_in_bytes == 0
    replay_store_bytes = 1024 * (4 * 4 * 2 + 4 + 4 + 1)
    assert ma_on.alias_size_in_bytes >= replay_store_bytes


# ------------------------------------------------ fused sampling training
def test_dqn_trains_with_fused_sampling():
    """DQN with the Gumbel-top-k sampler (kernel on TPU, ref oracle
    here) trains end-to-end through the unchanged Trainer: its TD loss
    at the last iteration clears an absolute bar.

    The bar is on the loss, not the return. Iteration 0's return
    averages only the episodes that finish inside the first unroll
    (lucky near-goal starts, ~0.98), so it is no baseline. And 30
    iterations are too few for the return to show learning: with
    epsilon annealed over 20 steps the greedy policy has not yet
    propagated the goal reward, and the return at iterations 20-29
    sits near the -0.16 timeout with either sampler (the same run
    reaches ~0.98 from iteration 60; test_trainer.py's
    test_dqn_improves_on_gridworld pins convergence). The loss does
    show it: the same run with lr=0 (a learner that fits nothing) ends
    at a TD loss of ~0.06 on the CPU; the trained learner must end
    below half of that (it reads ~0.007; its single-iteration losses
    over iterations 20-29 stay under 0.03)."""
    from repro.envs import GridWorld
    env = GridWorld(n=4, max_steps=16)
    cfg = TrainerConfig(algo="dqn", iters=30, superstep=10, n_envs=16,
                        unroll=8, log_every=10,
                        algo_kwargs={"warmup": 3, "eps_decay_steps": 20,
                                     "target_update": 10,
                                     "fused_sampling": True,
                                     "replay_capacity": 4096})
    _, hist = Trainer(env, cfg).fit()
    assert all(np.isfinite(r["loss"]) for r in hist)
    assert np.isfinite(hist[-1]["episode_return"]), hist
    assert hist[-1]["loss"] < 0.03, hist


# ------------------------------------------------------- bench JSON schema
def test_write_bench_json_schema(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "REPO_ROOT", str(tmp_path))
    rows = [("x/y", 12.345, "k=1"), ("x/z", None, "x2.0")]
    path = common.write_bench_json("unittest", rows, quick=True)
    doc = json.loads(open(path).read())
    assert doc["schema"] == "repro-bench/v1"
    assert doc["benchmark"] == "unittest"
    assert doc["meta"] == {"quick": True}
    assert doc["rows"][0] == {"name": "x/y", "us_per_call": 12.35,
                              "derived": "k=1"}
    assert doc["rows"][1]["us_per_call"] is None
    assert os.path.basename(path) == "BENCH_unittest.json"
    # out_dir redirects away from REPO_ROOT (how CLI tests avoid
    # clobbering the committed full-run files)
    sub = tmp_path / "elsewhere"
    sub.mkdir()
    path2 = common.write_bench_json("unittest", rows, out_dir=str(sub),
                                    quick=True)
    assert os.path.dirname(path2) == str(sub)
    assert json.loads(open(path2).read()) == doc
