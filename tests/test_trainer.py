"""Unified Agent/Trainer API under the Distribution Plan API: registry
round-trip, fused-vs-unfused equivalence, the (collective x sync) smoke
matrix as 1-D plans on a fake 4-device mesh, the hierarchical 2-D plan
matrix on 8 fake devices (incl. flat-vs-nested bitwise parity), the
ZeRO shard-axis bitwise-parity matrix (all four algorithms), elastic
actor shards, CLI contract, and the learning-sanity claims."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import agent as agent_api
from repro.core.distribution import DistPlan
from repro.core.trainer import Trainer, TrainerConfig
from repro.envs import CartPole, GridWorld

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ALGOS = ("a3c", "dqn", "impala", "ppo")


# ------------------------------------------------------------- registry
def test_registry_lists_all_algorithms():
    assert set(ALGOS) <= set(agent_api.available())


@pytest.mark.parametrize("name", ALGOS)
def test_registry_roundtrip(name):
    """Every algorithm constructs by name, inits a TrainState pytree,
    and serves behavior params for any (clipped) delay."""
    env = CartPole()
    ag = agent_api.make(name, env=env, ring_size=3)
    state = ag.init(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(state)
    assert isinstance(jax.tree_util.tree_unflatten(treedef, leaves),
                      agent_api.TrainState)
    fresh = ag.actor_policy(state, 0)
    stale = ag.actor_policy(state, 99)  # clipped to ring depth
    for a, b in zip(jax.tree_util.tree_leaves(fresh),
                    jax.tree_util.tree_leaves(stale)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b)  # init: whole ring identical


def test_unknown_algo_raises():
    with pytest.raises(KeyError, match="unknown algorithm"):
        agent_api.make("nope", env=CartPole())


def test_ring_rotation_tracks_policy_lag():
    """After one learner step, delay-0 params are the new ones and
    delay-1 params are the previous ones."""
    env = CartPole()
    ag = agent_api.make("impala", env=env, ring_size=2,
                        hidden=(8,))
    state = ag.init(jax.random.PRNGKey(0))
    old = state.params
    key = jax.random.PRNGKey(1)
    env_state = env.reset_batch(key, 4)
    from repro.core.rollout import rollout
    traj, env_state = rollout(ag.policy, ag.actor_policy(state, 0), env,
                              key, env_state, 4)
    boot = jax.vmap(env.obs)(env_state)
    state, metrics = ag.learner_step(state, traj, boot, key)
    assert jnp.isfinite(metrics["loss"])
    lagged = ag.actor_policy(state, 1)
    for a, b in zip(jax.tree_util.tree_leaves(lagged),
                    jax.tree_util.tree_leaves(old)):
        np.testing.assert_allclose(a, b)
    newest = ag.actor_policy(state, 0)
    diff = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(newest),
        jax.tree_util.tree_leaves(old)))
    assert diff > 0


# --------------------------------------------------- episode accounting
def test_episode_accounting_exact_and_carried():
    """The episode_return metric is the mean return of episodes that
    COMPLETED this iteration; the per-env accumulator carries across
    iteration boundaries and zero-completion iterations report the last
    known value (NaN before any episode ever finished)."""
    run0 = jnp.zeros((2,))
    nan = jnp.full((), jnp.nan)
    rew = jnp.ones((3, 2))
    none_done = jnp.zeros((3, 2), bool)
    # iteration 1: nothing finishes -> NaN, accumulators keep counting
    run, ret = Trainer._episode_stats(run0, nan, {"reward": rew,
                                                  "done": none_done})
    assert np.isnan(float(ret))
    np.testing.assert_allclose(run, [3.0, 3.0])
    # iteration 2: env0 finishes at t=1 (episode return 3+1+1=5) and
    # restarts; env1 keeps running
    done = jnp.array([[False, False], [True, False], [False, False]])
    run, ret = Trainer._episode_stats(run, ret, {"reward": rew,
                                                 "done": done})
    assert float(ret) == pytest.approx(5.0)
    np.testing.assert_allclose(run, [1.0, 6.0])
    # iteration 3: nothing finishes -> last value carried, not a raw
    # sum; the accumulators keep growing ([1,6] + 3 steps of reward)
    run, ret = Trainer._episode_stats(run, ret, {"reward": rew,
                                                 "done": none_done})
    assert float(ret) == pytest.approx(5.0)
    np.testing.assert_allclose(run, [4.0, 9.0])
    # two completions in one block -> mean of both episode returns
    done2 = jnp.array([[True, True], [False, False], [False, False]])
    _, ret = Trainer._episode_stats(run, ret, {"reward": rew,
                                               "done": done2})
    assert float(ret) == pytest.approx(((4 + 1) + (9 + 1)) / 2)


# (the DistPlan schema unit tests — parse round-trips incl. the shard
# role grammar, validation errors, delay schedules — live in
# tests/test_distribution.py)


# ------------------------------------------- fused superstep equivalence
def test_fused_superstep_equals_unfused():
    """Acceptance: K fused iterations in one scan produce the same
    params and metrics as per-iteration dispatch for a fixed seed."""
    env = CartPole()

    def run(fused):
        cfg = TrainerConfig(algo="impala", iters=8, superstep=4,
                            n_envs=8, unroll=8, log_every=4, seed=1,
                            algo_kwargs={"hidden": (16,)})
        return Trainer(env, cfg).fit(fused=fused)

    s_fused, h_fused = run(True)
    s_unfused, h_unfused = run(False)
    for a, b in zip(jax.tree_util.tree_leaves(s_fused.params),
                    jax.tree_util.tree_leaves(s_unfused.params)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    assert [r["iter"] for r in h_fused] == [r["iter"] for r in h_unfused]
    for rf, ru in zip(h_fused, h_unfused):
        assert rf["loss"] == pytest.approx(ru["loss"], rel=1e-3)


# -------------------------------------------------- elastic actor shards
def _hist_equal(h1, h2):
    """Bitwise history comparison; NaN (pre-first-episode) == NaN."""
    if len(h1) != len(h2):
        return False
    for r1, r2 in zip(h1, h2):
        if r1.keys() != r2.keys():
            return False
        for k in r1:
            if not np.array_equal(np.float64(r1[k]), np.float64(r2[k]),
                                  equal_nan=True):
                return False
    return True


def test_plan_elastic_actors_vary_shards_deterministically():
    """plan.actors cycles the env-shard count per superstep window; the
    per-shape numerics are pinned: two identical runs agree bitwise,
    the shard trace is exactly the schedule, and the unfused fit
    reshards at the same iteration boundaries (same numerics, one
    schedule entry per cfg.superstep iterations)."""
    env = CartPole()

    def run(fused=True):
        cfg = TrainerConfig(algo="impala", iters=9, superstep=3,
                            n_envs=8, unroll=6, log_every=1, seed=2,
                            plan=DistPlan.flat(1, actors=(8, 4, 8)),
                            algo_kwargs={"hidden": (8,)})
        tr = Trainer(env, cfg)
        state, hist = tr.fit(fused=fused)
        return state, hist, tr.actor_shards

    s1, h1, shards1 = run()
    s2, h2, shards2 = run()
    assert shards1 == [8, 4, 8] and shards2 == shards1
    assert _hist_equal(h1, h2)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s3, h3, shards3 = run(fused=False)
    assert shards3 == [8] * 3 + [4] * 3 + [8] * 3  # per-dispatch trace
    assert _hist_equal(h3, h1)
    for a, b in zip(jax.tree_util.tree_leaves(s3.params),
                    jax.tree_util.tree_leaves(s1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_constant_actors_schedule_is_bitwise_noop():
    """A constant actors= schedule equal to n_envs never reshards and
    is bitwise the plain run — elasticity is invisible to the agent."""
    env = CartPole()

    def run(plan):
        cfg = TrainerConfig(algo="impala", iters=6, superstep=3,
                            n_envs=8, unroll=6, log_every=1, seed=0,
                            plan=plan, algo_kwargs={"hidden": (8,)})
        tr = Trainer(env, cfg)
        state, hist = tr.fit()
        return state, hist

    s_c, h_c = run(DistPlan.flat(1, actors=(8,)))
    s_p, h_p = run(None)
    assert _hist_equal(h_c, h_p)
    for a, b in zip(jax.tree_util.tree_leaves(s_c.params),
                    jax.tree_util.tree_leaves(s_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------- collective x sync smoke (1-D plans, 4 devs)
_MATRIX_SCRIPT = textwrap.dedent("""
    import itertools, json, math
    import repro.envs as envs
    from repro.core.distribution import DistPlan
    from repro.core.trainer import Trainer, TrainerConfig
    env = envs.make("cartpole")
    out = {}
    for coll, sync in itertools.product(("allreduce", "ps", "gossip"),
                                        ("bsp", "asp", "ssp")):
        plan = DistPlan.flat(4, collective=coll, sync=sync, max_delay=2)
        cfg = TrainerConfig(algo="impala", iters=6, superstep=3,
                            n_envs=8, unroll=8, plan=plan,
                            log_every=2, algo_kwargs={"hidden": (8,)})
        _, hist = Trainer(env, cfg).fit()
        last = hist[-1]
        # episode_return is NaN until the first episode completes (the
        # honest boundary accounting) — require losses always finite
        # and the final return real
        out[f"{coll}/{sync}"] = {
            "loss": last["loss"], "ret": last["episode_return"],
            "finite": (all(math.isfinite(r["loss"]) for r in hist)
                       and math.isfinite(last["episode_return"]))}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def matrix_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _MATRIX_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_matrix_covers_all_combinations(matrix_results):
    assert len(matrix_results) == 9


def test_matrix_all_finite_and_nondegenerate(matrix_results):
    for combo, res in matrix_results.items():
        assert res["finite"], combo
        assert res["ret"] > 0, (combo, res)  # CartPole returns positive


def test_matrix_sync_topologies_agree(matrix_results):
    """ps and allreduce are mathematically identical aggregations — the
    same training run must come out (numerically) the same."""
    for sync in ("bsp", "asp", "ssp"):
        a = matrix_results[f"allreduce/{sync}"]["loss"]
        p = matrix_results[f"ps/{sync}"]["loss"]
        assert a == pytest.approx(p, rel=1e-3), (sync, a, p)


# ----------------------- hierarchical 2-D plan matrix (8 fake devices)
_PLAN_MATRIX_SCRIPT = textwrap.dedent("""
    import itertools, json, math
    import jax, numpy as np
    import repro.envs as envs
    from repro.core.distribution import AxisSpec, DistPlan
    from repro.core.trainer import Trainer, TrainerConfig
    env = envs.make("cartpole")

    def fit(plan):
        cfg = TrainerConfig(algo="impala", iters=6, superstep=3,
                            n_envs=8, unroll=8, plan=plan,
                            log_every=1, seed=0,
                            algo_kwargs={"hidden": (8,)})
        return Trainer(env, cfg).fit()

    def bitwise(s1, s2):
        return all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                                   jax.tree_util.tree_leaves(s2.params)))

    def hist_eq(h1, h2):   # NaN-aware (pre-first-episode returns)
        return all(r1.keys() == r2.keys()
                   and all(np.array_equal(np.float64(r1[k]),
                                          np.float64(r2[k]),
                                          equal_nan=True) for k in r1)
                   for r1, r2 in zip(h1, h2)) and len(h1) == len(h2)

    out = {}
    # acceptance: flat 4-worker allreduce/bsp == (1,4) nesting == (2,2)
    # hierarchical intra+inter allreduce, bitwise
    s_flat, h_flat = fit(DistPlan.flat(4))
    s_14, h_14 = fit(DistPlan(axes=(AxisSpec("hosts", 1),
                                    AxisSpec("workers", 4))))
    s_22, h_22 = fit(DistPlan.grid(2, 2))
    out["parity"] = {
        "flat_vs_1x4": bitwise(s_flat, s_14) and hist_eq(h_flat, h_14),
        "flat_vs_2x2": bitwise(s_flat, s_22) and hist_eq(h_flat, h_22)}
    # hierarchical combos: inter-host collective x per-axis sync
    for inter, isync in itertools.product(("ps", "gossip"),
                                          ("bsp", "asp", "ssp")):
        plan = DistPlan.grid(2, 2, inter=inter, intra="allreduce",
                             inter_sync=isync, intra_sync="asp",
                             max_delay=2)
        _, hist = fit(plan)
        out[f"2x2/{inter}/{isync}"] = {
            "loss": hist[-1]["loss"], "ret": hist[-1]["episode_return"],
            "finite": (all(math.isfinite(r["loss"]) for r in hist)
                       and math.isfinite(hist[-1]["episode_return"]))}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def plan_matrix_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _PLAN_MATRIX_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_plan_matrix_flat_vs_nested_bitwise_parity(plan_matrix_results):
    """Acceptance: a (hosts=2, workers=2) plan with intra-host allreduce
    + inter-host allreduce under bsp trains bitwise-identically to the
    legacy flat 4-worker allreduce path (and so does the (1,4)
    nesting) — the hierarchy is purely descriptive."""
    assert plan_matrix_results["parity"]["flat_vs_1x4"]
    assert plan_matrix_results["parity"]["flat_vs_2x2"]


@pytest.mark.slow
def test_plan_matrix_hierarchical_combos_train(plan_matrix_results):
    combos = [k for k in plan_matrix_results if k.startswith("2x2/")]
    assert len(combos) == 6
    for combo in combos:
        res = plan_matrix_results[combo]
        assert res["finite"], combo
        assert res["ret"] > 0, (combo, res)


# ------------- ZeRO shard-axis bitwise parity (all four algorithms,
# 8 fake devices): a size-1 shard axis is a no-op vs today's trainer,
# and a size-2 sharded fit — after its in-step all-gather — matches the
# flat replicated plan f32-bitwise. opt_state moments at size 2 may
# drift by codegen ulps (FMA contraction differs between the vector-
# chunk and tree-shaped programs) while the params they produce stay
# bitwise, so size-2 pins params/ring/history and size-1 additionally
# pins the (reassembled, tree-shaped) opt_state.
_SHARD_PARITY_SCRIPT = textwrap.dedent("""
    import json
    import jax, numpy as np
    import repro.envs as envs
    from repro.core.distribution import DistPlan
    from repro.core.trainer import Trainer, TrainerConfig

    env = envs.make("cartpole")
    KW = {"a3c": {"hidden": (8,)}, "impala": {"hidden": (8,)},
          "ppo": {"hidden": (8,)},
          "dqn": {"hidden": (8,), "replay_capacity": 512, "warmup": 1}}

    def fit(algo, plan):
        cfg = TrainerConfig(algo=algo, iters=4, superstep=2, n_envs=8,
                            unroll=6, plan=plan, log_every=1, seed=0,
                            algo_kwargs=KW[algo])
        return Trainer(env, cfg).fit()

    def eq(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return False
        if a.dtype.kind == "f":
            return bool(np.array_equal(a, b, equal_nan=True))
        return bool(np.array_equal(a, b))

    def bitwise(t1, t2):
        l1 = jax.tree_util.tree_leaves(t1)
        l2 = jax.tree_util.tree_leaves(t2)
        return len(l1) == len(l2) and all(eq(a, b)
                                          for a, b in zip(l1, l2))

    def hist_eq(h1, h2):
        return len(h1) == len(h2) and all(
            r1.keys() == r2.keys() and all(
                np.array_equal(np.float64(r1[k]), np.float64(r2[k]),
                               equal_nan=True) for k in r1)
            for r1, r2 in zip(h1, h2))

    out = {}
    for algo in ("a3c", "dqn", "impala", "ppo"):
        s4, h4 = fit(algo, DistPlan.flat(4))
        s41, h41 = fit(algo, DistPlan.parse(
            "workers=4:allreduce:bsp,shard=1:allreduce:bsp:shard"))
        s8, h8 = fit(algo, DistPlan.flat(8))
        s42, h42 = fit(algo, DistPlan.zero(4, 2))
        out[algo] = {
            "size1_params": bitwise(s4.params, s41.params),
            "size1_opt": bitwise(s4.opt_state, s41.opt_state),
            "size1_ring": bitwise(s4.ring, s41.ring),
            "size1_hist": hist_eq(h4, h41),
            "size2_params": bitwise(s8.params, s42.params),
            "size2_ring": bitwise(s8.ring, s42.ring),
            "size2_hist": hist_eq(h8, h42)}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def shard_parity_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _SHARD_PARITY_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("algo", ALGOS)
def test_shard_axis_size1_is_bitwise_noop(shard_parity_results, algo):
    """Acceptance: appending a size-1 shard axis to the flat 4-worker
    plan trains bitwise-identically to today's trainer — params,
    opt_state (tree-shaped, by the size-1 short-circuit), actor ring
    and metric history all match exactly."""
    res = shard_parity_results[algo]
    for key in ("size1_params", "size1_opt", "size1_ring", "size1_hist"):
        assert res[key], (algo, key, res)


@pytest.mark.slow
@pytest.mark.parametrize("algo", ALGOS)
def test_shard_axis_size2_matches_replicated_after_allgather(
        shard_parity_results, algo):
    """Acceptance: a (workers=4, shard=2) ZeRO plan — reduce-scatter,
    1/2-slice optimizer update, all-gather — produces f32-bitwise the
    params (and actor ring and history) of the flat replicated
    8-worker plan on the same 8 devices."""
    res = shard_parity_results[algo]
    for key in ("size2_params", "size2_ring", "size2_hist"):
        assert res[key], (algo, key, res)


# ------------- ZeRO-3 (zero3-role axis) bitwise parity (all four
# algorithms, 8 fake devices): params are STORED sharded and gathered
# per use, so the fit must still match the flat replicated plan
# f32-bitwise on the MLP policy — gather(local_shard(vec)) is the
# identity on the padded flat params, and adamw keeps the zero padding
# zero. Size-2 pins params/ring/history (reassembled opt moments carry
# the same chunk-vs-tree codegen-ulp caveat as ZeRO-2); the size-1
# zero3 axis short-circuits to the unwrapped agent and additionally
# pins opt_state.
_ZERO3_PARITY_SCRIPT = textwrap.dedent("""
    import json
    import jax, numpy as np
    import repro.envs as envs
    from repro.core.distribution import DistPlan
    from repro.core.trainer import Trainer, TrainerConfig

    env = envs.make("cartpole")
    KW = {"a3c": {"hidden": (8,)}, "impala": {"hidden": (8,)},
          "ppo": {"hidden": (8,)},
          "dqn": {"hidden": (8,), "replay_capacity": 512, "warmup": 1}}

    def fit(algo, plan):
        cfg = TrainerConfig(algo=algo, iters=4, superstep=2, n_envs=8,
                            unroll=6, plan=plan, log_every=1, seed=0,
                            algo_kwargs=KW[algo])
        return Trainer(env, cfg).fit()

    def eq(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return False
        return bool(np.array_equal(a, b, equal_nan=a.dtype.kind == "f"))

    def bitwise(t1, t2):
        l1 = jax.tree_util.tree_leaves(t1)
        l2 = jax.tree_util.tree_leaves(t2)
        return len(l1) == len(l2) and all(eq(a, b)
                                          for a, b in zip(l1, l2))

    def hist_eq(h1, h2):
        return len(h1) == len(h2) and all(
            r1.keys() == r2.keys() and all(
                np.array_equal(np.float64(r1[k]), np.float64(r2[k]),
                               equal_nan=True) for k in r1)
            for r1, r2 in zip(h1, h2))

    out = {}
    for algo in ("a3c", "dqn", "impala", "ppo"):
        s4, h4 = fit(algo, DistPlan.flat(4))
        s41, h41 = fit(algo, DistPlan.parse(
            "workers=4:allreduce:bsp,shard=1:allreduce:bsp:zero3"))
        s8, h8 = fit(algo, DistPlan.flat(8))
        s42, h42 = fit(algo, DistPlan.zero3(4, 2))
        out[algo] = {
            "size1_params": bitwise(s4.params, s41.params),
            "size1_opt": bitwise(s4.opt_state, s41.opt_state),
            "size1_ring": bitwise(s4.ring, s41.ring),
            "size1_hist": hist_eq(h4, h41),
            "size2_params": bitwise(s8.params, s42.params),
            "size2_ring": bitwise(s8.ring, s42.ring),
            "size2_hist": hist_eq(h8, h42)}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def zero3_parity_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _ZERO3_PARITY_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("algo", ALGOS)
def test_zero3_axis_size1_is_bitwise_noop(zero3_parity_results, algo):
    """Acceptance: a size-1 zero3 axis appended to the flat 4-worker
    plan is a bitwise no-op — params, opt_state, actor ring and metric
    history all match today's trainer exactly."""
    res = zero3_parity_results[algo]
    for key in ("size1_params", "size1_opt", "size1_ring", "size1_hist"):
        assert res[key], (algo, key, res)


@pytest.mark.slow
@pytest.mark.parametrize("algo", ALGOS)
def test_zero3_size2_matches_replicated_bitwise(zero3_parity_results,
                                                algo):
    """Acceptance: a (workers=4, shard=2:zero3) plan — params stored as
    1/2 chunks, all-gathered per use inside learner_step and
    actor_policy — produces f32-bitwise the params, actor ring and
    history of the flat replicated 8-worker plan on the same devices,
    for all four algorithms."""
    res = zero3_parity_results[algo]
    for key in ("size2_params", "size2_ring", "size2_hist"):
        assert res[key], (algo, key, res)


# -------------------------------------------------------- CLI contract
def test_cli_a3c_with_topology_and_sync_flags():
    """Legacy flags survive and lower onto a 1-D plan; A3C is reachable
    from the CLI via the registry."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train", "--algo", "a3c",
         "--env", "cartpole", "--topology", "allreduce", "--sync", "asp",
         "--iters", "4", "--superstep", "2", "--n-envs", "8",
         "--unroll", "4", "--log-every", "2"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["algo"] == "a3c"
    assert out["plan"] == "workers=1:allreduce:asp"
    assert out["history"]


def test_cli_plan_flag_runs_hierarchical_mesh():
    """--plan parses the hierarchical grammar, forces enough fake
    devices before jax loads, and reports the plan + elastic shards."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train",
         "--plan", "hosts=2:allreduce:bsp,workers=2:allreduce:bsp",
         "--actors", "8,16", "--iters", "4", "--superstep", "2",
         "--n-envs", "8", "--unroll", "4", "--log-every", "2"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 4
    assert out["plan"].startswith("hosts=2:allreduce:bsp,workers=2")
    assert out["actor_shards"] == [8, 16]
    assert out["history"]


def test_cli_rejects_unknown_topology():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train",
         "--topology", "star"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=120)
    assert r.returncode != 0
    assert "--topology" in r.stderr


def test_cli_rejects_malformed_plan():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train",
         "--plan", "workers:4"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=120)
    assert r.returncode != 0
    assert "plan" in r.stderr.lower()


def test_cli_plan_zero3_role_round_trips_and_reports_partition():
    """--plan accepts a zero3-role axis, trains through the wrapped
    agent, and the output JSON echoes the plan verbatim plus the
    resolved ZeRO partition (axis, shard count, chunk sizes)."""
    spec = "workers=2:allreduce:bsp,shard=2:allreduce:bsp:zero3"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train",
         "--plan", spec, "--iters", "4", "--superstep", "2",
         "--n-envs", "8", "--unroll", "4", "--log-every", "2"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["plan"] == spec
    assert out["n_devices"] == 4
    assert out["partition"]["n_shards"] == 2
    assert out["partition"]["axis"] == "shard"
    assert out["partition"]["chunk"] * 2 == out["partition"]["padded"]
    assert out["history"]


def test_cli_rejects_zero3_on_wrong_collective_naming_segment():
    """A zero3 axis on a non-allreduce collective dies in DistPlan
    validation with an error naming the offending axis."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train",
         "--plan", "workers=2:allreduce:bsp,s=2:gossip:bsp:zero3"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=120)
    assert r.returncode != 0
    assert "'s'" in r.stderr and "allreduce" in r.stderr


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """The CLIs' compile cache: a set JAX_COMPILATION_CACHE_DIR is the
    cache and the helper sets nothing; unset, the cache goes to the
    fixed <repo>/.jax_cache."""
    from repro.launch.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    repo_cache = os.path.realpath(
        os.path.join(os.path.dirname(__file__), "..", ".jax_cache"))
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert str(enable_compile_cache()) == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert str(enable_compile_cache()) == repo_cache
        assert jax.config.jax_compilation_cache_dir == repo_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ------------------------------------------- learning sanity (migrated)
def test_impala_policy_lag_vtrace_beats_naive():
    """Survey §6.1: under policy lag, V-trace correction must not be
    worse than the uncorrected learner (measured by final return)."""
    env = CartPole()
    rets = {}
    for use_vtrace in (True, False):
        cfg = TrainerConfig(algo="impala", iters=40, superstep=10,
                            n_envs=16, unroll=16, policy_lag=4, seed=3,
                            log_every=40,
                            algo_kwargs={"hidden": (32,),
                                         "use_vtrace": use_vtrace})
        _, hist = Trainer(env, cfg).fit()
        rets[use_vtrace] = hist[-1]["episode_return"]
    assert rets[True] >= 0.6 * rets[False], rets


def test_dqn_improves_on_gridworld():
    """Late-training return must clear a near-optimal absolute bar.

    The first logged entry is NOT a random-policy baseline: iteration 0
    averages only the episodes that happen to finish inside the first
    unroll (lucky near-goal starts), so it reads ~0.96-0.98 while the
    true exploration-phase return — visible mid-history once longer
    episodes complete — sits near 0 or below. Comparing final vs first
    is therefore meaningless; instead assert the converged policy
    (eps annealed to its floor) reliably navigates to the goal, which a
    non-learning policy at the same epsilon cannot (it times out at
    ~-0.16 per episode)."""
    env = GridWorld(n=4, max_steps=16)
    cfg = TrainerConfig(algo="dqn", iters=100, superstep=10, n_envs=16,
                        unroll=8, log_every=10,
                        algo_kwargs={"warmup": 5, "eps_decay_steps": 60,
                                     "target_update": 20})
    _, hist = Trainer(env, cfg).fit()
    late = [h["episode_return"] for h in hist[-2:]]
    assert sum(late) / len(late) > 0.9, hist
