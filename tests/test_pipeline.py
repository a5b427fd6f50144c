"""Decoupled actor-learner pipeline (repro.core.pipeline + the
Trainer's ``pipeline=`` mode): queue-op unit tests (capacity-1 ring,
wraparound past capacity, guarded pop-on-empty/push-on-full), the
sync-discipline -> queue-depth mapping, the depth-0 bitwise-parity
matrix vs the fused path for all four algorithms on a 4-device mesh,
chunked-vs-one-shot fit parity, the elastic-actors guard, the CLI
contract, and HostPipelined composability (the deliberately queue-free
Fig. 5a baseline)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distribution import AxisSpec, DistPlan
from repro.core.pipeline import (queue_capacity, queue_init, queue_pop,
                                 queue_push, queue_size)
from repro.core.sync import SyncConfig, pipeline_depth
from repro.core.trainer import Trainer, TrainerConfig
from repro.envs import CartPole
from repro.envs.host_env import HostPipelined

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ALGOS = ("a3c", "dqn", "impala", "ppo")


def _item(i):
    """A small two-leaf trajectory stand-in, value-tagged by `i`
    (works for Python ints and traced scalars alike)."""
    i = jnp.asarray(i, jnp.int32)
    return {"x": jnp.full((3, 2), i.astype(jnp.float32)),
            "n": i}


# ------------------------------------------------------ queue op units
def test_queue_init_shapes_capacity_and_emptiness():
    q = queue_init(_item(0), 4)
    assert queue_capacity(q) == 4
    assert int(queue_size(q)) == 0
    assert q["buf"]["x"].shape == (4, 3, 2)
    assert q["buf"]["n"].shape == (4,)
    assert q["buf"]["n"].dtype == jnp.int32


def test_queue_init_rejects_nonpositive_capacity():
    with pytest.raises(ValueError, match="capacity"):
        queue_init(_item(0), 0)


def test_queue_capacity1_ring_roundtrip():
    """The depth<=1 workhorse: one slot, push/pop alternation, both
    guards exercised. Push-on-full REFUSES (never overwrites); pop-on-
    empty returns the stale slot with ok=False and moves nothing."""
    q = queue_init(_item(0), 1)
    q, ok = queue_push(q, _item(7))
    assert bool(ok) and int(queue_size(q)) == 1
    # full: the second push is refused, slot keeps the first item
    q, ok = queue_push(q, _item(8))
    assert not bool(ok) and int(queue_size(q)) == 1
    np.testing.assert_array_equal(q["buf"]["x"][0], np.full((3, 2), 7.0))
    q, item, ok = queue_pop(q)
    assert bool(ok) and int(item["n"]) == 7
    assert int(queue_size(q)) == 0
    # empty: pop is a guarded no-op returning the stale head slot
    q2, stale, ok = queue_pop(q)
    assert not bool(ok) and int(stale["n"]) == 7
    assert int(queue_size(q2)) == 0
    assert int(q2["head"]) == int(q["head"])


def test_queue_wraparound_is_fifo_past_capacity():
    """More pushes than capacity: the monotonic counters wrap the slot
    index (slot = counter % capacity) and FIFO order survives."""
    q = queue_init(_item(0), 2)
    popped = []
    q, _ = queue_push(q, _item(0))
    q, _ = queue_push(q, _item(1))
    for i in range(2, 6):  # 6 total pushes through a 2-slot ring
        q, item, ok = queue_pop(q)
        assert bool(ok)
        popped.append(int(item["n"]))
        q, ok = queue_push(q, _item(i))
        assert bool(ok)
    q, item, _ = queue_pop(q)
    popped.append(int(item["n"]))
    q, item, _ = queue_pop(q)
    popped.append(int(item["n"]))
    assert popped == [0, 1, 2, 3, 4, 5]
    assert int(q["head"]) == int(q["tail"]) == 6  # counters never reset


def test_queue_ops_compose_under_scan():
    """Total functions: a jitted lax.scan alternating pop-then-push
    (the depth>=1 tick order) keeps the item stream exact."""
    q = queue_init(_item(0), 3)
    q, _ = queue_push(q, _item(0))
    q, _ = queue_push(q, _item(1))

    def tick(q, i):
        q, item, ok = queue_pop(q)
        q, _ = queue_push(q, _item(i + 2))
        return q, (item["n"], ok)

    @jax.jit
    def run(q):
        return jax.lax.scan(tick, q, jnp.arange(8))

    q, (ns, oks) = run(q)
    np.testing.assert_array_equal(ns, np.arange(8))
    assert bool(oks.all())
    assert int(queue_size(q)) == 2  # steady state: depth items in flight


# ------------------------------------------------- sync -> depth mapping
def test_sync_pipeline_depth_mapping():
    assert pipeline_depth(SyncConfig("bsp", max_delay=9)) == 0
    assert pipeline_depth(SyncConfig("asp", max_delay=3)) == 3
    assert pipeline_depth(SyncConfig("ssp", max_delay=4,
                                     staleness_bound=2)) == 2
    # ssp never exceeds the asp worst case it is a bounded form of
    assert pipeline_depth(SyncConfig("ssp", max_delay=1,
                                     staleness_bound=5)) == 1
    with pytest.raises(ValueError):
        pipeline_depth(SyncConfig("yolo"))


def test_plan_pipeline_depth_sums_over_axes():
    assert DistPlan.flat(4).pipeline_depth == 0  # bsp default
    assert DistPlan.flat(2, sync="ssp", staleness_bound=2,
                         max_delay=4).pipeline_depth == 2
    assert DistPlan.flat(2, sync="asp", max_delay=3).pipeline_depth == 3
    two = DistPlan(axes=(
        AxisSpec("hosts", 2, sync="ssp", staleness_bound=1, max_delay=4),
        AxisSpec("workers", 2, sync="asp", max_delay=2)))
    assert [ax.pipeline_depth for ax in two.axes] == [1, 2]
    assert two.pipeline_depth == 3  # staleness budgets add across levels


def test_trainer_resolves_depth_and_capacity():
    env = CartPole()
    ssp = DistPlan.flat(1, sync="ssp", staleness_bound=2, max_delay=2)

    def mk(pipeline, plan=None):
        return Trainer(env, TrainerConfig(
            algo="impala", iters=2, superstep=2, n_envs=4, unroll=4,
            plan=plan, pipeline=pipeline, algo_kwargs={"hidden": (8,)}))

    off = mk(False, ssp)
    assert off.pipeline_depth == 0 and off.pipeline_capacity is None
    bsp = mk(True)  # default plan is bsp -> lockstep: no queue at all
    assert bsp.pipeline_depth == 0 and bsp.pipeline_capacity is None
    _, sim, _ = bsp._init_all()
    assert set(sim) == {"env", "ep_run", "ep_last"}
    deep = mk(True, ssp)
    assert deep.pipeline_depth == 2 and deep.pipeline_capacity == 2
    # the queue rides in sim with `depth` slots and starts empty
    state, sim, _ = deep._init_all()
    assert set(sim) == {"env", "ep_run", "ep_last", "queue"}
    q = sim["queue"]
    assert queue_capacity(q) == 2 and int(queue_size(q)) == 0
    # the prologue fills it to steady state (depth items)
    (sim,) = deep._fill()(state, sim, jnp.arange(2, dtype=jnp.int32),
                          jnp.zeros((2,), jnp.int32))
    assert int(queue_size(sim["queue"])) == 2


def test_pipeline_rejects_varying_actor_schedule():
    """The queue's item shape is fixed at compile time, so elastic
    actor resharding cannot ride a pipelined fit; constant schedules
    (a no-op reshard) stay allowed."""
    env = CartPole()
    with pytest.raises(ValueError, match="actor"):
        Trainer(env, TrainerConfig(
            algo="impala", iters=4, superstep=2, n_envs=8, unroll=4,
            plan=DistPlan.flat(1, actors=(8, 4)), pipeline=True,
            algo_kwargs={"hidden": (8,)}))
    Trainer(env, TrainerConfig(  # constant schedule: fine
        algo="impala", iters=4, superstep=2, n_envs=8, unroll=4,
        plan=DistPlan.flat(1, actors=(8,)), pipeline=True,
        algo_kwargs={"hidden": (8,)}))


# ---------------- depth-0 bitwise parity matrix (4 fake devices) + ssp
_PIPE_PARITY_SCRIPT = textwrap.dedent("""
    import json, math
    import jax, numpy as np
    import repro.envs as envs
    from repro.core.distribution import DistPlan
    from repro.core.trainer import Trainer, TrainerConfig

    env = envs.make("cartpole")
    KW = {"a3c": {"hidden": (8,)}, "impala": {"hidden": (8,)},
          "ppo": {"hidden": (8,)},
          "dqn": {"hidden": (8,), "replay_capacity": 512, "warmup": 1}}

    def fit(algo, plan, pipeline):
        cfg = TrainerConfig(algo=algo, iters=4, superstep=2, n_envs=8,
                            unroll=6, plan=plan, log_every=1, seed=0,
                            pipeline=pipeline, algo_kwargs=KW[algo])
        return Trainer(env, cfg).fit()

    def eq(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return (a.shape == b.shape and a.dtype == b.dtype
                and bool(np.array_equal(a, b, equal_nan=True)))

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
        # depth 0 (bsp, 4 workers): pipelined must be bitwise the fused
        # lockstep program — params, actor ring AND metric history
        s_f, h_f = fit(algo, DistPlan.flat(4), pipeline=False)
        s_p, h_p = fit(algo, DistPlan.flat(4), pipeline=True)
        # depth 1 (ssp): genuinely overlapped — just pin it trains
        ssp = DistPlan.flat(4, sync="ssp", staleness_bound=1,
                            max_delay=1)
        _, h_s = fit(algo, ssp, pipeline=True)
        out[algo] = {
            "d0_params": bitwise(s_f.params, s_p.params),
            "d0_ring": bitwise(s_f.ring, s_p.ring),
            "d0_hist": hist_eq(h_f, h_p),
            "ssp_finite": all(math.isfinite(r["loss"]) for r in h_s)}
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def pipe_parity_results():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _PIPE_PARITY_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("algo", ALGOS)
def test_pipelined_depth0_bitwise_fused(pipe_parity_results, algo):
    """Acceptance: under a bsp plan (depth 0, no queue) the pipelined
    fit is f32-bitwise the fused superstep — params, actor-param ring,
    and history — for all four algorithms on a 4-device mesh."""
    res = pipe_parity_results[algo]
    for key in ("d0_params", "d0_ring", "d0_hist"):
        assert res[key], (algo, key, res)


@pytest.mark.parametrize("algo", ALGOS)
def test_pipelined_ssp_trains_finite(pipe_parity_results, algo):
    """Depth 1 (ssp bound): the genuinely-overlapped pipeline trains
    with finite losses for every algorithm."""
    assert pipe_parity_results[algo]["ssp_finite"]


# ----------------------------------- chunked-vs-one-shot fit parity
def _hist_equal(h1, h2):
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


def _chunk_pair(algo, pipeline, plan=None, seed=0):
    """(two k=2 dispatches, one k=4 dispatch) of the same 4 iterations."""
    env = CartPole()
    kw = {"hidden": (8,)}
    if algo == "dqn":
        kw.update(replay_capacity=256, warmup=1)

    def run(superstep):
        cfg = TrainerConfig(algo=algo, iters=4, superstep=superstep,
                            n_envs=8, unroll=6, plan=plan, log_every=1,
                            seed=seed, pipeline=pipeline, algo_kwargs=kw)
        return Trainer(env, cfg).fit()

    return run(2), run(4)


def _assert_bitwise(s1, s2):
    for a, b in zip(jax.tree_util.tree_leaves((s1.params, s1.ring)),
                    jax.tree_util.tree_leaves((s2.params, s2.ring))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("algo", ("impala", "dqn"))
def test_chunked_fit_bitwise_fused(algo):
    """Two k=2 supersteps == one k=4 superstep, bitwise, on the fused
    path: the dispatch boundary is invisible to the numerics."""
    (s2, h2), (s4, h4) = _chunk_pair(algo, pipeline=False)
    _assert_bitwise(s2, s4)
    assert _hist_equal(h2, h4)


@pytest.mark.parametrize("algo", ("impala", "ppo"))
def test_chunked_fit_bitwise_pipelined_lockstep(algo):
    """Pipelined bsp (depth 0) keeps the same chunk invariance bitwise:
    it runs the fused program itself."""
    (s2, h2), (s4, h4) = _chunk_pair(algo, pipeline=True)
    _assert_bitwise(s2, s4)
    assert _hist_equal(h2, h4)


@pytest.mark.parametrize("algo", ALGOS)
def test_chunked_fit_parity_pipelined_depth1(algo):
    """Depth >= 1: the queue rides in `sim` across dispatches (no
    drain) and the ticks are scanned like the fused ones, so two k=2
    dispatches equal one k=4 dispatch bitwise."""
    plan = DistPlan.flat(1, sync="ssp", staleness_bound=1, max_delay=1)
    (s2, h2), (s4, h4) = _chunk_pair(algo, pipeline=True, plan=plan)
    _assert_bitwise(s2, s4)
    assert _hist_equal(h2, h4)


# ------------------------------------------------------- CLI contract
def test_cli_pipeline_flag_reports_depth_and_capacity():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.rl_train", "--algo", "dqn",
         "--plan", "workers=2:allreduce:ssp", "--staleness-bound", "1",
         "--pipeline", "--iters", "4", "--superstep", "2", "--n-envs",
         "8", "--unroll", "4", "--log-every", "2"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["pipeline"] is True
    assert out["pipeline_depth"] == 1
    assert out["pipeline_capacity"] == 1
    assert out["history"]


# ------------------- HostPipelined: the queue-free Fig. 5a baseline
def test_host_pipelined_stays_unregistered_and_queue_free():
    """HostPipelined is the survey's Fig. 5a CPU-simulation baseline:
    every env step round-trips through the host, so experience
    generation is CLOSED-LOOP — step t+1's input is step t's output via
    host memory, and no trajectory can be produced ahead of time. That
    is exactly the coupling the trajectory queue exists to break, so
    the wrapper deliberately stays out of the registry (no `envs.make`
    name) and owns no queue machinery of its own."""
    import repro.envs as envs
    assert not any("host" in name for name in envs.available())
    env = HostPipelined(CartPole())
    assert not hasattr(env, "queue") and not hasattr(env, "prefetch")


def test_host_pipelined_composes_with_pipelined_trainer():
    """Composability: the wrapper still runs under pipeline=True — the
    io_callback round-trip simply executes inside the producer program,
    serializing it (the measured Fig. 5a cost) without changing the
    numerics vs the on-device env."""
    plan = DistPlan.flat(1, sync="ssp", staleness_bound=1, max_delay=1)

    def run(env):
        cfg = TrainerConfig(algo="impala", iters=2, superstep=2,
                            n_envs=4, unroll=4, plan=plan, log_every=1,
                            seed=0, pipeline=True,
                            algo_kwargs={"hidden": (8,)})
        return Trainer(env, cfg).fit()

    _, h_host = run(HostPipelined(CartPole()))
    _, h_dev = run(CartPole())
    assert all(np.isfinite(r["loss"]) for r in h_host)
    assert _hist_equal(h_host, h_dev)
