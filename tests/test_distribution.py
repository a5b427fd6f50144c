"""Distribution Plan API schema tests: parse/describe round-trips
(incl. the `role` grammar and hypothesis property round-trips),
validation errors naming the offending input, delay schedules, the
flatten-and-pad partitioning + ZeRO sharded-optimizer math (under vmap
named axes, no mesh needed), and the --plan CLI error contract.

Absorbed the DistPlan schema unit tests that previously lived in
tests/test_trainer.py (the multi-device Trainer parity/smoke matrices
stay there — they spawn fake-device subprocesses)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis or skip-fallback

from repro.core.agent import flatten_and_pad
from repro.core.distribution import AxisSpec, DistPlan
from repro.core.topology import (all_gather_shards, local_shard,
                                 reduce_scatter_mean,
                                 zero_sharded_optimizer)
from repro.core.trainer import Trainer, TrainerConfig
from repro.envs import CartPole
from repro.optim import adamw, clip_by_global_norm

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SETTINGS = dict(max_examples=25, deadline=None)


# ------------------------------------------- schema (from test_trainer)
def test_plan_defaults_to_flat_single_worker():
    plan = DistPlan.flat()
    assert plan.axis_names == ("workers",)
    assert plan.mesh_shape == (1,)
    assert plan.n_devices == 1 and plan.ring_extra == 0
    assert plan.shard_axis is None and plan.shard_size == 1


def test_plan_parse_round_trip():
    s = "hosts=2:allreduce:bsp,workers=4:gossip:asp"
    plan = DistPlan.parse(s, max_delay=3)
    assert plan.axis_names == ("hosts", "workers")
    assert plan.mesh_shape == (2, 4)
    assert plan.axes[1].collective == "gossip"
    assert plan.axes[1].sync == "asp"
    assert plan.describe() == s
    assert plan.ring_extra == 3  # bsp(0) + asp(max_delay=3)


def test_plan_ring_extra_adds_across_axes():
    plan = DistPlan(axes=(
        AxisSpec("hosts", 2, sync="asp", max_delay=5),
        AxisSpec("workers", 2, sync="ssp", max_delay=5,
                 staleness_bound=2)))
    assert plan.ring_extra == 5 + 2
    cfg = TrainerConfig(plan=plan, policy_lag=1)
    assert cfg.ring_size == 1 + 7 + 1


def test_plan_delay_schedule_adds_per_axis():
    plan = DistPlan(axes=(
        AxisSpec("hosts", 2, sync="asp", max_delay=3),
        AxisSpec("workers", 4, sync="bsp")))
    d = plan.make_delay_schedule(10, jax.random.PRNGKey(0))
    assert d.shape == (10, 2, 4)
    # bsp inner axis adds nothing: delays constant across workers
    np.testing.assert_array_equal(
        np.asarray(d),
        np.broadcast_to(np.asarray(d)[:, :, :1], d.shape))
    assert int(d.max()) <= 3


def test_plan_flat_delay_schedule_matches_legacy_sync():
    """The 1-D plan consumes the key exactly as sync.make_delays did —
    the legacy schedule is bitwise what the plan produces."""
    from repro.core.sync import SyncConfig, make_delays
    key = jax.random.PRNGKey(3)
    plan = DistPlan.flat(4, sync="ssp", max_delay=6, staleness_bound=2)
    legacy = make_delays(SyncConfig("ssp", 4, 6, 2), 20, key)
    np.testing.assert_array_equal(
        np.asarray(plan.make_delay_schedule(20, key)), np.asarray(legacy))


def test_plan_validation_errors():
    with pytest.raises(ValueError, match="collective"):
        AxisSpec("workers", 2, collective="star")
    with pytest.raises(ValueError, match="sync"):
        AxisSpec("workers", 2, sync="eventual")
    with pytest.raises(ValueError, match="duplicate"):
        DistPlan(axes=(AxisSpec("w", 2), AxisSpec("w", 2)))
    with pytest.raises(ValueError, match="actors"):
        DistPlan.flat(1, actors=(4, 0))
    with pytest.raises(ValueError, match="divide"):
        Trainer(CartPole(), TrainerConfig(n_envs=6,
                                          plan=DistPlan.flat(4)))
    with pytest.raises(ValueError, match="actors"):
        Trainer(CartPole(), TrainerConfig(
            n_envs=8, plan=DistPlan.flat(4, actors=(8, 6))))


def test_plan_device_validation_names_count_and_shape():
    """Requesting a plan shape larger than the visible device count must
    raise a clear error naming both — never silently slice devices."""
    with pytest.raises(RuntimeError) as e:
        Trainer(CartPole(), TrainerConfig(n_envs=64,
                                          plan=DistPlan.flat(64)))
    msg = str(e.value)
    assert "64 devices" in msg and "workers=64" in msg
    assert "xla_force_host_platform_device_count" in msg


def test_plan_device_validation_off_cpu_names_platform():
    """On an accelerator the fake-CPU-device advice does not apply: the
    error names the platform and the chips it found instead."""
    import types
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    with pytest.raises(RuntimeError) as e:
        DistPlan.flat(4).validate_devices([chip])
    msg = str(e.value)
    assert "4 devices" in msg and "1 tpu device(s) (TPU v5 lite)" in msg
    assert "xla_force_host_platform_device_count" not in msg


# --------------------------------------------------- shard-role grammar
def test_plan_parse_shard_role_round_trip():
    s = "workers=4:allreduce:bsp,shard=2:allreduce:bsp:shard"
    plan = DistPlan.parse(s)
    assert plan.axes[1].role == "shard"
    assert plan.shard_axis is plan.axes[1]
    assert plan.shard_size == 2
    assert plan.data_axes == (plan.axes[0],)
    assert plan.describe() == s
    # role `data` is the default and stays silent in describe()
    assert DistPlan.parse(plan.describe()) == plan


def test_plan_zero_constructor_matches_parse():
    assert DistPlan.zero(4, 2) == DistPlan.parse(
        "workers=4:allreduce:bsp,shard=2:allreduce:bsp:shard")


def test_plan_shard_role_validation():
    with pytest.raises(ValueError, match="role"):
        AxisSpec("w", 2, role="fsdp")
    # a shard axis must ride the fused allreduce (its pmean + local
    # slice IS the reduce-scatter)
    with pytest.raises(ValueError, match="allreduce"):
        AxisSpec("shard", 2, collective="gossip", role="shard")
    with pytest.raises(ValueError, match="at most one shard"):
        DistPlan(axes=(AxisSpec("s1", 2, role="shard"),
                       AxisSpec("s2", 2, role="shard")))


def test_plan_parse_zero3_role_round_trip():
    s = "workers=2:allreduce:bsp,shard=2:allreduce:bsp:zero3"
    plan = DistPlan.parse(s)
    assert plan.axes[1].role == "zero3"
    assert plan.shard_axis is plan.axes[1]  # zero3 IS the shard-role axis
    assert plan.shard_size == 2
    assert plan.data_axes == (plan.axes[0],)
    assert plan.describe() == s
    assert DistPlan.parse(plan.describe()) == plan


def test_plan_zero3_constructor_matches_parse():
    assert DistPlan.zero3(2, 2) == DistPlan.parse(
        "workers=2:allreduce:bsp,shard=2:allreduce:bsp:zero3")


def test_plan_zero3_role_validation():
    # the zero3 params all-gather rides the fused allreduce too
    with pytest.raises(ValueError, match="allreduce") as e:
        AxisSpec("shard", 2, collective="ps", role="zero3")
    assert "'shard'" in str(e.value)
    # gather-per-use reads the lag ring in lockstep: zero3 requires bsp
    with pytest.raises(ValueError, match="bsp") as e:
        AxisSpec("shard", 2, collective="allreduce", sync="asp",
                 role="zero3")
    assert "'shard'" in str(e.value)
    # shard and zero3 both claim the single shard-role slot
    with pytest.raises(ValueError, match="at most one shard"):
        DistPlan(axes=(AxisSpec("s1", 2, role="shard"),
                       AxisSpec("s2", 2, role="zero3")))


def test_plan_parse_zero3_rejections_name_offending_segment():
    for spec, frag in [
            ("w=2:allreduce:bsp,s=2:gossip:bsp:zero3", "'s'"),
            ("w=2:allreduce:bsp,s=2:allreduce:ssp:zero3", "'s'"),
            ("s1=2:allreduce:bsp:zero3,s2=2:allreduce:bsp:zero3",
             "at most one shard")]:
        with pytest.raises(ValueError) as e:
            DistPlan.parse(spec)
        assert frag in str(e.value), (spec, str(e.value))


def test_plan_parse_replay_role_round_trip():
    s = "workers=2:allreduce:bsp,replay=2:allreduce:bsp:replay"
    plan = DistPlan.parse(s)
    assert plan.axes[1].role == "replay"
    assert plan.replay_axis is plan.axes[1]
    assert plan.replay_size == 2
    assert plan.shard_axis is None  # replay is NOT the shard-role slot
    # replay members replicate their data position's rollout: the
    # simulation grid collapses the axis to 1
    assert plan.sim_shape == (2, 1) and plan.sim_devices == 2
    assert plan.describe() == s
    assert DistPlan.parse(plan.describe()) == plan


def test_plan_replay_constructor_matches_parse():
    assert DistPlan.replay(2, 2) == DistPlan.parse(
        "workers=2:allreduce:bsp,replay=2:allreduce:bsp:replay")


def test_plan_replay_composes_with_zero3_in_grammar():
    """shard/zero3 and replay occupy orthogonal role slots: one plan may
    carry both (the fit-parity pin lives in tests/test_replay_service)."""
    plan = DistPlan.parse(
        "workers=2:allreduce:bsp,shard=2:allreduce:bsp:zero3,"
        "replay=2:allreduce:bsp:replay")
    assert plan.shard_axis.name == "shard"
    assert plan.replay_axis.name == "replay"
    assert plan.sim_shape == (2, 2, 1) and plan.sim_devices == 4


def test_plan_replay_role_validation():
    # the merge/assembly collectives ride the fused allreduce domain
    with pytest.raises(ValueError, match="allreduce") as e:
        AxisSpec("rp", 2, collective="gossip", role="replay")
    assert "'rp'" in str(e.value)
    # one logical buffer -> lockstep members only
    with pytest.raises(ValueError, match="bsp") as e:
        AxisSpec("rp", 2, collective="allreduce", sync="asp",
                 role="replay")
    assert "'rp'" in str(e.value)
    with pytest.raises(ValueError, match="at most one replay"):
        DistPlan(axes=(AxisSpec("r1", 2, role="replay"),
                       AxisSpec("r2", 2, role="replay")))


def test_plan_parse_replay_rejections_name_offending_axis():
    for spec, frag in [
            ("w=2:allreduce:bsp,r=2:ps:bsp:replay", "'r'"),
            ("w=2:allreduce:bsp,r=2:allreduce:ssp:replay", "'r'"),
            ("r1=2:allreduce:bsp:replay,r2=2:allreduce:bsp:replay",
             "at most one replay")]:
        with pytest.raises(ValueError) as e:
            DistPlan.parse(spec)
        assert frag in str(e.value), (spec, str(e.value))


def test_plan_parse_rejects_bad_segments_naming_them():
    for spec, frag in [
            ("", "empty plan"),
            ("   ", "empty plan"),
            ("workers:4", "workers:4"),
            ("workers=x", "'x' is not an integer"),
            ("workers=4:allreduce:bsp:shard:x", "too many"),
            ("w=2:allreduce:bsp:zero", "role"),
            ("w=2,x=1,", "''")]:
        with pytest.raises(ValueError) as e:
            DistPlan.parse(spec)
        assert frag in str(e.value), (spec, str(e.value))


def test_plan_parse_rejects_duplicate_axis_names():
    with pytest.raises(ValueError) as e:
        DistPlan.parse("w=2:allreduce,w=2:gossip")
    assert "'w'" in str(e.value) and "duplicate" in str(e.value)


# ----------------------------------------- hypothesis plan round-trips
_NAMES = ("a", "b", "hosts", "workers", "shard", "x1", "grp")


@given(data=st.data())
@settings(**SETTINGS)
def test_plan_parse_describe_round_trip_property(data):
    """parse(describe(plan)) == plan for random axis tuples including
    ALL role slots (shard/zero3 and replay may coexist) — the CLI
    grammar is a faithful serialization."""
    n_axes = data.draw(st.integers(1, 4), label="n_axes")
    names = data.draw(st.permutations(list(_NAMES)), label="names")
    max_delay = data.draw(st.integers(0, 6), label="max_delay")
    staleness = data.draw(st.integers(0, 6), label="staleness")
    shard_at = data.draw(st.one_of(st.none(),
                                   st.integers(0, n_axes - 1)),
                         label="shard_at")
    replay_at = data.draw(st.one_of(st.none(),
                                    st.integers(0, n_axes - 1)),
                          label="replay_at")
    if replay_at == shard_at:  # orthogonal slots, distinct axes
        replay_at = None
    axes = []
    for i in range(n_axes):
        if i == shard_at:
            coll = "allreduce"
            role = data.draw(st.sampled_from(("shard", "zero3")),
                             label="shard_role")
        elif i == replay_at:
            coll, role = "allreduce", "replay"
        else:
            coll = data.draw(
                st.sampled_from(("allreduce", "ps", "gossip")))
            role = "data"
        sync = ("bsp" if role in ("zero3", "replay")  # bsp-only roles
                else data.draw(st.sampled_from(("bsp", "asp", "ssp"))))
        axes.append(AxisSpec(
            names[i], data.draw(st.integers(1, 8)), coll, sync,
            max_delay, staleness, role))
    plan = DistPlan(axes=tuple(axes))
    s = plan.describe()
    again = DistPlan.parse(s, max_delay=max_delay,
                           staleness_bound=staleness)
    assert again == plan
    assert again.describe() == s


@given(data=st.data())
@settings(**SETTINGS)
def test_plan_parse_malformed_segment_named_property(data):
    """Malformed axis segments raise ValueError naming the segment."""
    bad = data.draw(st.sampled_from(
        ("nosize", "w=three", "w=2:allreduce:bsp:data:extra")))
    spec = "ok=2:allreduce:bsp," + bad
    with pytest.raises(ValueError) as e:
        DistPlan.parse(spec)
    assert bad in str(e.value)


# -------------------------------- flatten-and-pad + sharded optimizer
def test_shard_flatten_and_pad_round_trip():
    tree = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}
    vec, size, unravel = flatten_and_pad(tree, 4)
    assert size == 9 and vec.shape == (12,)  # padded to multiple of 4
    np.testing.assert_array_equal(np.asarray(vec[9:]), 0.0)
    back = unravel(vec[:size])
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    with pytest.raises(ValueError, match="empty"):
        flatten_and_pad({}, 2)


def test_shard_reduce_scatter_allgather_round_trip_under_vmap():
    """local_shard / all_gather_shards invert each other on a
    replicated vector (the trainer's situation: every shard member
    holds the same params), and reduce_scatter_mean is pmean + local
    chunk — exercised through vmap named axes (the same collective
    primitives shard_map lowers)."""
    n = 4
    vec = jax.random.normal(jax.random.PRNGKey(0), (8,))
    rep = jnp.broadcast_to(vec, (n, 8))

    gathered = jax.vmap(
        lambda v: all_gather_shards(local_shard(v, "s", n), "s"),
        axis_name="s")(rep)
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(rep))

    vecs = jax.random.normal(jax.random.PRNGKey(1), (n, 8))
    rs = jax.vmap(lambda v: reduce_scatter_mean(v, "s", n),
                  axis_name="s")(vecs)
    mean = np.asarray(vecs).mean(axis=0)
    for i in range(n):
        np.testing.assert_allclose(np.asarray(rs[i]),
                                   mean[2 * i:2 * i + 2], rtol=1e-6)


def test_shard_zero_optimizer_matches_replicated():
    """The ZeRO wrapper (reduce-scattered grads -> 1/n-slice update ->
    all-gathered params) reproduces the replicated optimizer's params
    over several steps — including the global-norm-clip `pre` path —
    with opt_state living as 1/n chunks. Tolerance is one f32 ulp: the
    vmap'd chunk program and the plain tree program may FMA-contract
    differently (the end-to-end f32-bitwise pin, where both sides run
    under shard_map, lives in tests/test_trainer.py)."""
    n = 2
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 4)
    params = {"w": jax.random.normal(ks[0], (3, 3)),
              "b": jax.random.normal(ks[1], (2,))}  # 11 -> pad to 12
    opt = clip_by_global_norm(adamw(1e-2), 0.5)
    sh = zero_sharded_optimizer(opt, "s", n)

    stack = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.stack([a] * n), t)
    p_sh = stack(params)
    s_sh = stack(sh.init(params))     # all-zero chunks, like the Trainer
    p_rep, s_rep = params, opt.init(params)
    step = jax.jit(jax.vmap(sh.apply, axis_name="s"))
    for i in range(4):
        grads = {"w": 3 * jax.random.normal(ks[2], (3, 3)) * (i + 1),
                 "b": jax.random.normal(ks[3], (2,))}
        p_sh, s_sh = step(p_sh, s_sh, stack(grads))
        p_rep, s_rep = opt.apply(p_rep, s_rep, grads)
        for k in params:  # every shard member holds the full params
            for m in range(n):
                np.testing.assert_allclose(
                    np.asarray(p_sh[k][m]), np.asarray(p_rep[k]),
                    rtol=3e-7, atol=3e-7)
    # opt_state moments really are 1/n chunks (6 of padded 12 elements)
    assert s_sh["m"].shape == (n, 6) and s_sh["v"].shape == (n, 6)


def test_shard_size1_optimizer_is_inner_passthrough():
    """Sharding into one chunk is the identity: the wrapper delegates
    to the inner optimizer, keeping the tree-shaped opt_state (the
    size-1 bitwise no-op guarantee by construction)."""
    params = {"w": jnp.ones((2, 2))}
    opt = adamw(1e-3)
    sh = zero_sharded_optimizer(opt, "s", 1)
    st_ = sh.init(params)
    assert st_["m"]["w"].shape == (2, 2)  # tree form, not a chunk
    g = {"w": jnp.full((2, 2), 0.5)}
    p1, s1 = opt.apply(params, opt.init(params), g)
    p2, s2 = sh.apply(params, st_, g)
    for a, b in zip(jax.tree_util.tree_leaves((p1, s1)),
                    jax.tree_util.tree_leaves((p2, s2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shard_trainer_rejects_optless_agent():
    """A shard-role axis on an agent without `.opt` raises a clear
    error naming the algorithm and the axis (third-party agents must
    expose their optimizer to shard)."""
    import repro.core.agent as agent_api

    class NoOpt(agent_api.Agent):
        def __init__(self, env, **kw):
            pass

    agent_api.register("_no_opt", NoOpt)
    try:
        with pytest.raises(ValueError, match="_no_opt.*opt|opt.*_no_opt"):
            Trainer(CartPole(), TrainerConfig(
                algo="_no_opt", n_envs=8, plan=DistPlan.zero(1, 2)))
    finally:
        agent_api._REGISTRY.pop("_no_opt", None)


# -------------------------------------------------- CLI --plan contract
def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train", *args],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=600)


def test_cli_plan_rejects_empty():
    r = _run_cli("--plan", "")
    assert r.returncode != 0
    assert "empty --plan" in r.stderr


def test_cli_plan_rejects_duplicate_axis_names():
    r = _run_cli("--plan", "w=2:allreduce,w=2:gossip")
    assert r.returncode != 0
    assert "duplicate plan axis name 'w'" in r.stderr


def test_cli_plan_rejects_bad_role():
    r = _run_cli("--plan", "w=2:allreduce:bsp:fsdp")
    assert r.returncode != 0
    assert "role" in r.stderr


def test_cli_plan_shard_role_trains_and_reports_partition():
    """--plan with a shard-role segment forces the fake devices, trains
    through the ZeRO path and reports the partition (axis, shard count,
    flat/padded/chunk sizes) in the output JSON."""
    import json
    r = _run_cli("--plan", "workers=2:allreduce:bsp,"
                 "shard=2:allreduce:bsp:shard",
                 "--iters", "4", "--superstep", "2", "--n-envs", "8",
                 "--unroll", "4", "--log-every", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 4
    assert out["plan"].endswith("shard=2:allreduce:bsp:shard")
    part = out["partition"]
    assert part["axis"] == "shard" and part["n_shards"] == 2
    assert part["padded"] % 2 == 0
    assert part["chunk"] * 2 == part["padded"]
    assert out["history"]
