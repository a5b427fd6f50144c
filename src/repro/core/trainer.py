"""Unified Trainer: one fused driver executing a declarative DistPlan.

Composes the survey's three acceleration axes over any registered Agent
(repro.core.agent); *how* the run is distributed is no longer a flat
`topology: str` over one worker axis but a `repro.core.distribution.
DistPlan` — a hierarchy of named mesh axes, each with its own
collective (§3) and sync discipline (§6):

  * batch simulation (§4.2): the shared rollout engine fuses env
    dynamics + policy inference into the training program;
  * system topology (§3, Fig. 3): with a multi-device plan the whole
    iteration runs per-device inside a `shard_map` over the plan's
    mesh; the plan compiles per-axis collectives into the
    `grad_tx`/`param_tx` hooks (e.g. intra-host allreduce + inter-host
    gossip);
  * synchronization (§6, Fig. 6): per-axis bsp/asp/ssp render as a
    deterministic policy-lag schedule (`plan.make_delay_schedule`)
    whose per-axis delays ADD, indexing each agent's actor-param ring;
  * elastic actors (ElegantRL-Podracer): `plan.actors` varies the env
    shard count between supersteps — agents only consume `traj`, so
    `fit` reshards the simulation carry host-side and the agents never
    see the change;
  * sharded learner states (§5 memory ceiling, ZeRO-2): a `shard`-role
    axis partitions the agent's optimizer state 1/N per device
    (`topology.zero_sharded_optimizer`): gradients reduce-scatter over
    the axis (the pmean half fuses into `grad_tx`), the per-coordinate
    update runs on the local flattened slice, and params all-gather
    before the next rollout — f32-bitwise the replicated plan, and a
    size-1 shard axis is a bitwise no-op;
  * sharded replay memory (§3, Gorila's Replay Memory): a `replay`-role
    axis turns the agent's prioritized buffer into ONE logical buffer
    over the axis (`repro.core.replay_service`), 1/N capacity per
    member. The group replicates its data position's rollout/learner
    compute (envs, RNG streams and grad/metric collectives all range
    over the non-replay "sim grid"), so the axis adds replay capacity
    — not sample throughput — and the fit stays f32-bitwise the flat
    data plan; a size-1 replay axis is left unwrapped (a data axis by
    construction).

`fit(fused=True)` scans `superstep` iterations (rollout -> learner_step
-> lag-ring rotate) inside ONE jitted `lax.scan`: the Python loop
dispatches iters/K programs and reads metrics back once per superstep
instead of blocking on `float(...)` every iteration.  `fit(fused=False)`
runs the identical iteration body one step at a time — numerically
equivalent (tests/test_trainer.py) but host-bound; the speedup is
measured in benchmarks/fused_superstep.py.

**Pipelined mode** (`TrainerConfig.pipeline=True`, the survey §2
actor/learner decoupling — Gorila/Ape-X, SRL's description/execution
split): the superstep body is split at the trajectory seam into a
rollout *producer* and a learner *consumer* joined by a fixed-capacity
device-resident trajectory queue (repro.core.pipeline) riding in the
carry. The queue depth is what the plan's per-axis sync discipline
admits (`DistPlan.pipeline_depth`): bsp -> 0, ssp -> staleness_bound,
asp -> max_delay. At depth 0 the tick degenerates to push-then-pop
through one slot — lockstep, f32-bitwise the fused path (pinned in
tests/test_pipeline.py). At depth >= 1 the producer runs `depth`
iterations AHEAD: tick t pops the trajectory produced at tick t-depth
(no data dependency on this tick's rollout) and produces the
trajectory for iteration t+depth, so XLA's scheduler is free to
execute simulation of iteration t+depth concurrently with the learner
update of iteration t — the staleness the fused path only *models* as
sampled policy-lag delays becomes real overlapped compute, with the
actor-param ring supplying the lagged policy. Ticks are unrolled (not
scanned): scan bodies execute serially, which would hide the
producer/consumer independence from the scheduler. Walltime overlap is
measured in benchmarks/pipeline_overlap.py -> BENCH_pipeline.json.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import agent as agent_api
from repro.core.agent import flatten_and_pad
from repro.core.distribution import DistPlan
from repro.core.pipeline import queue_init, queue_pop, queue_push
from repro.core.rollout import rollout
from repro.core.topology import (ZeRO3Agent, replicate_for,
                                 restore_worker_dim, strip_worker_dim,
                                 zero_sharded_optimizer)


@dataclasses.dataclass
class TrainerConfig:
    algo: str = "impala"
    iters: int = 60
    superstep: int = 10        # K iterations fused per jitted dispatch
    n_envs: int = 32           # total envs (split across devices)
    unroll: int = 32           # rollout length T per iteration
    plan: Optional[DistPlan] = None  # distribution plan; None = 1 worker
    policy_lag: int = 0        # deterministic actor-param lag floor
    seed: int = 0
    log_every: int = 10
    donate: bool = True        # zero-copy supersteps: donate state/sim
    pipeline: bool = False     # decoupled actor-learner trajectory queue
    algo_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def resolved_plan(self) -> DistPlan:
        return self.plan if self.plan is not None else DistPlan.flat()

    @property
    def ring_size(self) -> int:
        """Actor-param history depth the plan's sync hierarchy can reach
        into (per-axis staleness adds)."""
        return self.policy_lag + self.resolved_plan().ring_extra + 1


class Trainer:
    """Drives any registered Agent under a DistPlan; see module doc."""

    def __init__(self, env, cfg: TrainerConfig):
        plan = cfg.resolved_plan()
        # envs shard over the SIMULATION grid (an active replay-role
        # axis replicates rollouts — it adds replay capacity, not
        # sample throughput), so divisibility is against sim_devices
        if cfg.n_envs % plan.sim_devices:
            raise ValueError(f"n_envs={cfg.n_envs} must divide evenly "
                             f"across the plan's {plan.sim_devices} "
                             f"simulation devices (mesh "
                             f"{plan.mesh_shape}, env grid "
                             f"{plan.sim_shape})")
        if plan.actors is not None:
            bad = [n for n in plan.actors if n % plan.sim_devices]
            if bad:
                raise ValueError(
                    f"actors= schedule entries {bad} must divide evenly "
                    f"across the plan's {plan.sim_devices} simulation "
                    f"devices")
        if cfg.pipeline and plan.actors is not None \
                and len(set(plan.actors)) > 1:
            raise ValueError(
                f"pipeline=True cannot combine with a varying elastic "
                f"actors= schedule {plan.actors}: the trajectory queue's "
                f"buffer shape is fixed per compile, so in-flight "
                f"trajectories cannot be resharded — use a constant "
                f"schedule or fused mode")
        self.env = env
        self.cfg = cfg
        self.plan = plan
        self.agent = agent_api.make(cfg.algo, env=env,
                                    ring_size=cfg.ring_size,
                                    total_iters=cfg.iters,
                                    **cfg.algo_kwargs)
        # ZeRO-2 learner-state sharding (shard-role axis): the agent's
        # optimizer state lives 1/N per device over the shard axis; the
        # wrapper reduce-scatters (pmean fused into grad_tx + local
        # slice), updates the slice, and all-gathers params. A size-1
        # shard axis is left unwrapped: sharding into one chunk is the
        # identity, so the axis degenerates to a data axis and the
        # bitwise no-op guarantee holds BY CONSTRUCTION (same program
        # as the nested data-plan parity pinned in tests).
        self.partition = None    # populated by _init_all when sharded
        self._part_unravel = None
        self._part_unravels = None
        shard = plan.shard_axis
        self._sharded = (shard is not None and shard.size > 1
                         and plan.n_devices > 1)
        # full ZeRO-3 (zero3-role axis): params stored sharded too and
        # gathered per use; executed by wrapping the agent below
        self._zero3 = self._sharded and shard.role == "zero3"
        if self._zero3 and cfg.pipeline:
            raise ValueError(
                f"pipeline=True cannot combine with the zero3-role axis "
                f"{shard.name!r}: the trajectory queue's item template "
                f"is shape-traced outside the mesh program, where the "
                f"gather-per-use actor params have no axis environment "
                f"— use role 'shard' (ZeRO-2) or fused mode")
        if self._sharded and not hasattr(self.agent, "opt"):
            raise ValueError(
                f"algorithm {cfg.algo!r} exposes no `.opt` optimizer — "
                f"required to execute the shard-role axis "
                f"{shard.name!r} (ZeRO learner-state sharding)")
        # sharded replay service (replay-role axis): the agent's
        # prioritized buffer becomes ONE logical buffer over the axis,
        # 1/N capacity per member, behind the same add_batch/sample/
        # update_priorities interface. A size-1 replay axis is left
        # unwrapped — it degenerates to a data axis and the bitwise
        # no-op guarantee holds BY CONSTRUCTION (sim grid, RNG streams
        # and collectives all treat it as data).
        rax = plan.replay_axis
        self._replay = (rax is not None and rax.size > 1
                        and plan.n_devices > 1)
        if self._replay and cfg.pipeline:
            raise ValueError(
                f"pipeline=True cannot combine with the replay-role "
                f"axis {rax.name!r}: the decoupled superstep reorders "
                f"the add_batch/sample interleaving against the "
                f"sharded buffer and that combination has no validated "
                f"parity — use the fused superstep (pipeline=False) or "
                f"drop the replay axis")
        self._replay_service = None
        self.partition_replay = None
        if self._replay:
            from repro.core.replay import PrioritizedReplay
            from repro.core.replay_service import ShardedPrioritizedReplay
            flat_replay = getattr(self.agent, "replay", None)
            if not isinstance(flat_replay, PrioritizedReplay):
                raise ValueError(
                    f"replay axis {rax.name!r}: algorithm {cfg.algo!r} "
                    f"does not carry a PrioritizedReplay on its learner "
                    f"hot path (agent.replay) — the sharded replay "
                    f"service backs that seam only (DQN; ERL's "
                    f"evolutionary buffer rides its own loop)")
            if not flat_replay.fused:
                raise ValueError(
                    f"replay axis {rax.name!r}: the sharded replay "
                    f"service decomposes the fused Gumbel-top-k draw "
                    f"per shard; the legacy categorical path "
                    f"(fused_sampling=False) has no such decomposition "
                    f"— drop fused_sampling=False or the replay axis")
            # capacity % axis size raises here, naming the axis
            self._replay_service = ShardedPrioritizedReplay(
                flat_replay.capacity, rax.name, rax.size,
                alpha=flat_replay.alpha, beta=flat_replay.beta,
                eps=flat_replay.eps)
            # swap the seam on the RAW agent (before any ZeRO-3 wrap:
            # the wrapper forwards learner_step to this inner agent)
            self.agent.replay = self._replay_service
            self.partition_replay = {
                "axis": rax.name, "n_shards": rax.size,
                "capacity": flat_replay.capacity,
                "chunk": self._replay_service.chunk}
        # metrics reduce over the sim grid only: replay-group members
        # compute identical metrics by construction, and averaging
        # duplicates would change the float association vs the flat plan
        self._pmean_axes = tuple(
            a.name for a in plan.axes
            if not (a.role == "replay" and a.size > 1))
        self.mesh = None
        self._grad_tx = self._param_tx = None
        if plan.n_devices > 1:
            # validate_devices raises the clear device-count error
            # instead of silently slicing a too-short device list
            self.mesh = plan.build_mesh(jax.devices())
            self._grad_tx, self._param_tx = plan.compile_collectives()
        if self._sharded:
            self.agent.opt = zero_sharded_optimizer(
                self.agent.opt, shard.name, shard.size)
        if self._zero3:
            # wrap AFTER the opt swap: the wrapper's inner.init then
            # produces the chunk-shaped opt_state ZeRO-3 stores
            self.agent = ZeRO3Agent(self.agent, shard.name, shard.size)
        self._base_key = jax.random.PRNGKey(cfg.seed)
        self._step_cache = {}
        self.actor_shards = []   # actual env count per superstep dispatch
        # trajectory-queue depth the plan's sync hierarchy admits for
        # the decoupled actor-learner pipeline; 0 (lockstep) unless
        # cfg.pipeline asks for the split superstep
        self.pipeline_depth = plan.pipeline_depth if cfg.pipeline else 0

    @property
    def pipeline_capacity(self) -> Optional[int]:
        """Ring capacity of the trajectory queue (None when fused):
        steady state holds exactly `pipeline_depth` in-flight
        trajectories; depth 0 still needs the one lockstep slot."""
        return max(self.pipeline_depth, 1) if self.cfg.pipeline else None

    # ---- episode accounting (carried across iterations) --------------
    @staticmethod
    def _episode_stats(ep_run, ep_last, traj):
        """Exact per-episode returns from a (T, B) reward/done block.

        `ep_run` carries each env's within-episode reward sum across
        iteration boundaries, so `episode_return` is the mean return of
        episodes that *completed* this iteration — never a raw reward
        sum. With zero completions the last known value (NaN before the
        first episode ever finishes) is reported instead of a silently
        wrong number."""
        def acct(carry, xs):
            run, tot, cnt = carry
            r, d = xs
            run = run + r
            tot = tot + jnp.where(d, run, 0.0).sum()
            cnt = cnt + d.sum()
            run = jnp.where(d, 0.0, run)
            return (run, tot, cnt), None

        (ep_run, tot, cnt), _ = jax.lax.scan(
            acct, (ep_run, jnp.zeros(()), jnp.zeros((), jnp.int32)),
            (traj["reward"], traj["done"]))
        ep_ret = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), ep_last)
        return ep_run, ep_ret

    # ---- producer/consumer halves (shared by fused + pipelined) ------
    def _iter_key(self, it):
        """(k_roll, k_learn) for iteration `it` — one deterministic
        stream per iteration, independent of which program (fused tick,
        producer, consumer) derives it, so the pipelined split consumes
        randomness bitwise-identically to the fused scan."""
        key = jax.random.fold_in(self._base_key, it)
        if self.mesh is not None:
            # per-device RNG stream keyed by the FLAT device index of
            # the SIMULATION grid, so a (hosts, workers) nesting folds
            # the same stream ids as the flat plan and every member of
            # a replay group draws its data position's stream
            # (bitwise-parity invariants; sim_index == linear_index on
            # plans without an active replay axis)
            key = jax.random.fold_in(key, self.plan.sim_index())
        return jax.random.split(key)

    def _produce(self, state, env_state, it, delay=None):
        """Rollout-producer half: one trajectory for iteration `it`
        plus its bootstrap observation (the queue item — boot_obs must
        ride along because the consumer never sees the env state).
        `delay` defaults to the deterministic policy-lag floor: in
        pipelined mode the producer always acts with the newest params
        available, and any extra staleness is structural (the queue
        depth), not sampled."""
        delay = self.cfg.policy_lag if delay is None else delay
        with jax.named_scope("rollout"):
            k_roll, _ = self._iter_key(it)
            actor = self.agent.actor_policy(state, delay)
            traj, env_state = rollout(self.agent.policy, actor, self.env,
                                      k_roll, env_state, self.cfg.unroll)
            boot_obs = jax.vmap(self.env.obs)(env_state)
        return {"traj": traj, "boot": boot_obs}, env_state

    def _consume(self, state, ep_run, ep_last, item, it):
        """Learner-consumer half: one learner_step on a queue item plus
        the episode accounting (which must see trajectories in
        consumption order, so it lives on this side of the seam)."""
        with jax.named_scope("learner"):
            _, k_learn = self._iter_key(it)
            state, metrics = self.agent.learner_step(
                state, item["traj"], item["boot"], k_learn,
                grad_tx=self._grad_tx, param_tx=self._param_tx)
            ep_run, ep_ret = self._episode_stats(ep_run, ep_last,
                                                 item["traj"])
            metrics = dict(metrics, episode_return=ep_ret)
            if self.mesh is not None and self._pmean_axes:
                metrics = {k: jax.lax.pmean(v, self._pmean_axes)
                           for k, v in metrics.items()}
        return state, ep_run, ep_ret, metrics

    # ---- one training iteration (shared by fused/unfused paths) ------
    def _iteration(self, carry, xs):
        state, sim = carry
        it, delay = xs
        item, env_state = self._produce(state, sim["env"], it, delay)
        state, ep_run, ep_ret, metrics = self._consume(
            state, sim["ep_run"], sim["ep_last"], item, it)
        sim = {"env": env_state, "ep_run": ep_run, "ep_last": ep_ret}
        return (state, sim), metrics

    # ---- superstep: k fused iterations in one program ----------------
    def _superstep(self, k: int, donate: bool = None):
        """Jitted k-iteration program. With `donate` (cfg.donate by
        default) the `state`/`sim` argument buffers are donated to
        their same-shaped outputs, so the carried pytrees — DQN's
        capacity×transition replay store, the actor-param ring, env
        state — update in place instead of being copied once per
        dispatch (zero-copy superstep; measured in
        benchmarks/hotpath.py)."""
        donate = self.cfg.donate if donate is None else donate
        cache_key = (k, donate)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        donate_argnums = (0, 1) if donate else ()

        def body(state, sim, its, delays):
            (state, sim), metrics = jax.lax.scan(
                self._iteration, (state, sim), (its, delays))
            return state, sim, metrics

        if self.mesh is None:
            fn = jax.jit(body, donate_argnums=donate_argnums)
        else:
            from jax.experimental.shard_map import shard_map
            nd = len(self.plan.axes)

            def worker(state, sim, its, delays):
                # shard_map keeps one length-1 dim per mesh axis on the
                # sharded leaves — strip before the body, restore after
                state, sim, metrics = body(
                    strip_worker_dim(state, nd),
                    strip_worker_dim(sim, nd),
                    its, delays.reshape(delays.shape[0]))
                return (restore_worker_dim(state, nd),
                        restore_worker_dim(sim, nd), metrics)

            w = P(*self.plan.axis_names)
            fn = jax.jit(shard_map(
                worker, mesh=self.mesh,
                in_specs=(w, w, P(), P(None, *self.plan.axis_names)),
                out_specs=(w, w, P()), check_rep=False),
                donate_argnums=donate_argnums)
        self._step_cache[cache_key] = fn
        return fn

    # ---- pipelined superstep: decoupled producer/consumer ------------
    def _pipe_tick(self, state, sim, queue, it, delay):
        """One pipelined tick for consumer iteration `it`.

        depth 0: lockstep — push-then-pop through a one-slot queue is
        the identity on the item stream, so the round-trip is compiled
        away (the queue rides the carry untouched). This is not just an
        optimization: the buffer write would force XLA to materialize
        `traj` instead of fusing it into the consumer's reductions,
        drifting ~1 ulp from the fused program and breaking the depth-0
        bitwise guarantee (tests/test_pipeline.py pins it).

        depth d >= 1: pop FIRST (the popped item — produced d ticks ago
        — depends only on the carry-in queue, never on this tick's
        rollout), then produce iteration `it + d` and push. The two
        halves share only the carry-in `state`, so XLA schedules the
        rollout of iteration it+d concurrently with the learner update
        of iteration it; the tick's critical path is
        max(t_produce, t_consume) instead of their sum."""
        d = self.pipeline_depth
        if d == 0:
            item_c, env_state = self._produce(state, sim["env"], it,
                                              delay)
        else:
            queue, item_c, _ = queue_pop(queue)
            item_p, env_state = self._produce(state, sim["env"], it + d,
                                              delay)
            queue, _ = queue_push(queue, item_p)
        state, ep_run, ep_ret, metrics = self._consume(
            state, sim["ep_run"], sim["ep_last"], item_c, it)
        sim = {"env": env_state, "ep_run": ep_run, "ep_last": ep_ret}
        return state, sim, queue, metrics

    def _pipeline_superstep(self, k: int, donate: bool = None):
        """Jitted k-tick pipelined program (the consumer-side lowering;
        the queue rides the carry and is donated with state/sim).

        At depth >= 1 ticks are UNROLLED — a lax.scan body executes
        serially under the XLA schedulers, which would hide the
        producer/consumer independence `_pipe_tick` sets up. At depth 0
        there is nothing to overlap (lockstep by definition), so ticks
        run under lax.scan like the fused path: unrolling lets XLA fuse
        across tick boundaries and drift ~1 ulp from the scanned
        program, which would break the depth-0 bitwise guarantee."""
        donate = self.cfg.donate if donate is None else donate
        cache_key = ("pipe", k, donate)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        donate_argnums = (0, 1, 2) if donate else ()

        def body(state, sim, queue, its, delays):
            if self.pipeline_depth == 0:
                def tick(carry, xs):
                    state, sim, queue = carry
                    state, sim, queue, m = self._pipe_tick(
                        state, sim, queue, *xs)
                    return (state, sim, queue), m
                (state, sim, queue), metrics = jax.lax.scan(
                    tick, (state, sim, queue), (its, delays))
                return state, sim, queue, metrics
            per = []
            for j in range(k):
                state, sim, queue, m = self._pipe_tick(
                    state, sim, queue, its[j], delays[j])
                # fence the carry at tick boundaries: without it XLA
                # fuses across ticks and a k-tick program drifts ~1 ulp
                # from k dispatches of 1-tick programs (chunked fits
                # stop being bitwise one-shot fits — the fence restores
                # that for value-based learners; policy-gradient
                # learners with internal epoch scans keep ~1-ulp chunk
                # variance, pinned as allclose in tests). The fence adds
                # no serialization the dataflow didn't already have —
                # produce(t+1) reads consume(t)'s state — so the
                # within-tick produce/consume independence survives.
                state, sim, queue = jax.lax.optimization_barrier(
                    (state, sim, queue))
                per.append(m)
            metrics = {key: jnp.stack([m[key] for m in per])
                       for key in per[0]}
            return state, sim, queue, metrics

        if self.mesh is None:
            fn = jax.jit(body, donate_argnums=donate_argnums)
        else:
            from jax.experimental.shard_map import shard_map
            nd = len(self.plan.axes)

            def worker(state, sim, queue, its, delays):
                state, sim, queue, metrics = body(
                    strip_worker_dim(state, nd),
                    strip_worker_dim(sim, nd),
                    strip_worker_dim(queue, nd), its,
                    delays.reshape(delays.shape[0]))
                return (restore_worker_dim(state, nd),
                        restore_worker_dim(sim, nd),
                        restore_worker_dim(queue, nd), metrics)

            w = P(*self.plan.axis_names)
            fn = jax.jit(shard_map(
                worker, mesh=self.mesh,
                in_specs=(w, w, w, P(), P(None, *self.plan.axis_names)),
                out_specs=(w, w, w, P()), check_rep=False),
                donate_argnums=donate_argnums)
        self._step_cache[cache_key] = fn
        return fn

    def _producer_program(self, k: int):
        """Jitted k-iteration rollout-only program (the producer-side
        lowering): fills the queue with trajectories for iterations
        its[0..k-1] before the first pipelined tick runs. `state` is
        read-only here — the first tick still needs its buffers, so only
        sim/queue are donated."""
        cache_key = ("fill", k)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        donate_argnums = (1, 2) if self.cfg.donate else ()

        def body(state, sim, queue, its, delays):
            env_state = sim["env"]
            for j in range(k):
                item, env_state = self._produce(state, env_state,
                                                its[j], delays[j])
                queue, _ = queue_push(queue, item)
            sim = {"env": env_state, "ep_run": sim["ep_run"],
                   "ep_last": sim["ep_last"]}
            return sim, queue

        if self.mesh is None:
            fn = jax.jit(body, donate_argnums=donate_argnums)
        else:
            from jax.experimental.shard_map import shard_map
            nd = len(self.plan.axes)

            def worker(state, sim, queue, its, delays):
                sim, queue = body(
                    strip_worker_dim(state, nd),
                    strip_worker_dim(sim, nd),
                    strip_worker_dim(queue, nd), its,
                    delays.reshape(delays.shape[0]))
                return (restore_worker_dim(sim, nd),
                        restore_worker_dim(queue, nd))

            w = P(*self.plan.axis_names)
            fn = jax.jit(shard_map(
                worker, mesh=self.mesh,
                in_specs=(w, w, w, P(), P(None, *self.plan.axis_names)),
                out_specs=(w, w), check_rep=False),
                donate_argnums=donate_argnums)
        self._step_cache[cache_key] = fn
        return fn

    def _consumer_program(self, k: int):
        """Jitted k-iteration learner-only program (the consumer-side
        lowering): pops one queued trajectory per iteration and runs
        learner_step + episode accounting on it. `fit` never calls this
        — the pipelined tick fuses both halves — but it is the serial
        half of the decoupled baseline benchmarks/pipeline_overlap.py
        measures the pipelined program against, and the natural drain
        primitive for a future multi-host split (ROADMAP)."""
        cache_key = ("drain", k)
        if cache_key in self._step_cache:
            return self._step_cache[cache_key]
        donate_argnums = (0, 1, 2) if self.cfg.donate else ()

        def body(state, sim, queue, its):
            ep_run, ep_last = sim["ep_run"], sim["ep_last"]
            per = []
            for j in range(k):
                queue, item, _ = queue_pop(queue)
                state, ep_run, ep_ret, m = self._consume(
                    state, ep_run, ep_last, item, its[j])
                ep_last = ep_ret
                per.append(m)
            metrics = {key: jnp.stack([m[key] for m in per])
                       for key in per[0]}
            sim = {"env": sim["env"], "ep_run": ep_run,
                   "ep_last": ep_last}
            return state, sim, queue, metrics

        if self.mesh is None:
            fn = jax.jit(body, donate_argnums=donate_argnums)
        else:
            from jax.experimental.shard_map import shard_map
            nd = len(self.plan.axes)

            def worker(state, sim, queue, its):
                state, sim, queue, metrics = body(
                    strip_worker_dim(state, nd),
                    strip_worker_dim(sim, nd),
                    strip_worker_dim(queue, nd), its)
                return (restore_worker_dim(state, nd),
                        restore_worker_dim(sim, nd),
                        restore_worker_dim(queue, nd), metrics)

            w = P(*self.plan.axis_names)
            fn = jax.jit(shard_map(
                worker, mesh=self.mesh,
                in_specs=(w, w, w, P()),
                out_specs=(w, w, w, P()), check_rep=False),
                donate_argnums=donate_argnums)
        self._step_cache[cache_key] = fn
        return fn

    def _init_queue(self, state, sim):
        """Empty trajectory queue sized for `pipeline_capacity` items.

        Item shapes come from a shape-only trace (eval_shape) of the
        producer on PER-DEVICE inputs — a dedicated closure with a dummy
        key, because `_iter_key` folds in `plan.linear_index()`
        (axis_index), which only exists inside shard_map. Under a mesh
        the queue leaves get the same leading mesh dims as state/sim so
        one `P(*axis_names)` spec shards every carry argument alike."""
        cap = self.pipeline_capacity
        nd = 0 if self.mesh is None else len(self.plan.axes)
        sds = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[nd:], a.dtype), t)

        def one_item(state, env_state):
            actor = self.agent.actor_policy(state, self.cfg.policy_lag)
            traj, env_state = rollout(
                self.agent.policy, actor, self.env,
                jax.random.PRNGKey(0), env_state, self.cfg.unroll)
            return {"traj": traj,
                    "boot": jax.vmap(self.env.obs)(env_state)}

        item = jax.eval_shape(one_item, sds(state), sds(sim["env"]))
        if self.mesh is None:
            return queue_init(item, cap)
        lead = self.plan.mesh_shape
        buf = jax.tree_util.tree_map(
            lambda s: jnp.zeros(lead + (cap,) + tuple(s.shape), s.dtype),
            item)
        return {"buf": buf, "head": jnp.zeros(lead, jnp.int32),
                "tail": jnp.zeros(lead, jnp.int32)}

    # ---- state/schedule construction ---------------------------------
    def _shard_sim(self, sim):
        """Reshape a host-layout sim carry (flat env batch) into the
        plan's mesh layout: one leading dim per mesh axis, row-major, so
        device (i0, i1, ...) owns the same contiguous env slice its flat
        linear index would."""
        if self.mesh is None:
            return sim
        shape = self.plan.mesh_shape
        sshape = self.plan.sim_shape   # active replay axis -> 1
        per = sim["ep_run"].shape[0] // self.plan.sim_devices
        # reshape over the sim grid, then broadcast across the replay
        # axis: replay-group members REPLICATE their data position's
        # envs (identity when the sim grid is the whole mesh)
        lay = lambda a: jnp.broadcast_to(
            a.reshape(sshape + (per,) + a.shape[1:]),
            shape + (per,) + a.shape[1:])
        return {"env": jax.tree_util.tree_map(lay, sim["env"]),
                "ep_run": lay(sim["ep_run"]),
                "ep_last": jnp.broadcast_to(sim["ep_last"], shape)}

    def _init_all(self):
        cfg = self.cfg
        k_init, k_env, k_delay = jax.random.split(self._base_key, 3)
        state = self.agent.init(k_init)
        shard = self.plan.shard_axis
        if self._zero3:
            # the wrapper's init already ran flatten_and_pad PER ENTRY
            # (one entry per transformer block + remainder when the
            # agent yields a partition list; a single entry otherwise)
            # and caches the geometry + unravels on itself
            self._part_unravels = list(self.agent._unravels)
            self._part_unravel = self._part_unravels[0]
            self.partition = {
                "axis": shard.name, "n_shards": shard.size,
                "size": self.agent._size, "padded": self.agent._padded,
                "chunk": self.agent._chunk,
                "sizes": list(self.agent._sizes),
                "chunks": list(self.agent._chunks),
                "entries": self.agent.n_entries,
                "listwise": self.agent._listwise}
        elif self._sharded:
            # record the flatten-and-pad partition of the optimizer
            # target (agent.partition_spec) for reporting, benchmarks
            # and the end-of-fit opt_state reassembly; padded size is
            # divisible by the shard size by construction
            vec, size, unravel = flatten_and_pad(
                self.agent.partition_spec(state), shard.size)
            self._part_unravel = unravel
            self._part_unravels = [unravel]
            self.partition = {
                "axis": shard.name, "n_shards": shard.size,
                "size": int(size), "padded": int(vec.size),
                "chunk": int(vec.size // shard.size),
                "listwise": False}
        # simulation-side carry: batched env state + episode accounting
        # (ep_last starts NaN: no episode has finished yet)
        sim = {"env": self.env.reset_batch(k_env, cfg.n_envs),
               "ep_run": jnp.zeros((cfg.n_envs,)),
               "ep_last": jnp.full((), jnp.nan)}
        delays = (self.plan.make_delay_schedule(cfg.iters, k_delay)
                  + cfg.policy_lag)
        if self.mesh is not None:
            rstate = None
            if self._replay:
                # pull the flat host replay out of the state (None is an
                # empty pytree — it rides through either layout path
                # untouched), shard it 1/N and spread the shards along
                # the replay mesh axis while everything else replicates
                rstate = self._replay_service.shard_state(
                    state.extra["replay"])
                state = self._swap_replay(state, None)
            state = (self._lay_out_zero3(state) if self._zero3
                     else replicate_for(self.mesh, self.plan.axis_names,
                                        state))
            if rstate is not None:
                state = self._swap_replay(state,
                                          self._spread_replay(rstate))
            sim = self._shard_sim(sim)
        else:
            delays = delays.reshape(cfg.iters)
        return state, sim, delays

    @staticmethod
    def _swap_replay(state, rstate):
        extra = dict(state.extra)
        extra["replay"] = rstate
        return agent_api.TrainState(state.params, state.opt_state,
                                    extra, state.ring, state.steps)

    def _spread_replay(self, tree):
        """Mesh layout for host sharded replay leaves (leading
        (n_shards,) dim from `shard_state`): distribute that dim along
        the replay mesh axis — the device at replay index r owns chunk
        r — and replicate over every other axis (the `_lay_out_zero3`
        spread pattern)."""
        names = self.plan.axis_names
        shape = self.plan.mesh_shape
        k = names.index(self.plan.replay_axis.name)

        def spread(a):
            lead = [1] * len(names)
            lead[k] = a.shape[0]
            a = a.reshape(tuple(lead) + a.shape[1:])
            return jnp.broadcast_to(a, shape + a.shape[len(names):])

        return jax.tree_util.tree_map(spread, tree)

    def _lay_out_zero3(self, state):
        """Mesh layout for a HOST-layout ZeRO-3 TrainState: chunked
        leaves (params["zero3"] entries (n_shards, chunk_e); ring
        entries (n_shards, ring_size, chunk_e)) distribute their
        leading dim along the shard mesh axis — device at shard index i
        owns chunk i — while every other leaf replicates like
        `replicate_for`."""
        names = self.plan.axis_names
        shape = self.plan.mesh_shape
        k = names.index(self.partition["axis"])

        def spread(a):
            lead = [1] * len(names)
            lead[k] = a.shape[0]
            a = a.reshape(tuple(lead) + a.shape[1:])
            return jnp.broadcast_to(a, shape + a.shape[len(names):])

        repl = lambda t: jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p, shape + p.shape), t)
        return agent_api.TrainState(
            {"zero3": jax.tree_util.tree_map(
                spread, state.params["zero3"]),
             "rest": repl(state.params["rest"])},
            repl(state.opt_state), repl(state.extra),
            jax.tree_util.tree_map(spread, state.ring),
            repl(state.steps))

    # ---- elastic actor shards (plan.actors) ---------------------------
    def _reshard_envs(self, sim, n_total, key):
        """Grow/shrink the env-shard count between supersteps. Shrinking
        drops the trailing shards (their in-flight episode accumulators
        with them); growing resets fresh envs into the new slots. The
        agents never see this — they only consume `traj`."""
        lead = 0 if self.mesh is None else len(self.plan.axes)
        nd = self.plan.sim_devices
        per_new = n_total // nd
        per_cur = sim["ep_run"].shape[lead]
        if per_new == per_cur:
            return sim
        keep = (slice(None),) * lead
        if per_new < per_cur:
            env = jax.tree_util.tree_map(
                lambda a: a[keep + (slice(0, per_new),)], sim["env"])
            ep_run = sim["ep_run"][keep + (slice(0, per_new),)]
        else:
            fresh = {"env": self.env.reset_batch(
                         key, (per_new - per_cur) * nd),
                     "ep_run": jnp.zeros(((per_new - per_cur) * nd,)),
                     "ep_last": sim["ep_last"]}
            fresh = self._shard_sim(fresh)
            env = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=lead),
                sim["env"], fresh["env"])
            ep_run = jnp.concatenate([sim["ep_run"], fresh["ep_run"]],
                                     axis=lead)
        return {"env": env, "ep_run": ep_run, "ep_last": sim["ep_last"]}

    def lower(self, k: int = None, donate: bool = None):
        """Lower (without running) one superstep — lets benchmarks
        inspect the collective schedule (HLO) per plan and the donation
        plan (compile().memory_analysis())."""
        k = self.cfg.superstep if k is None else k
        state, sim, delays = self._init_all()
        its = jnp.arange(k, dtype=jnp.int32)
        return self._superstep(k, donate).lower(state, sim, its,
                                                delays[:k])

    def lower_pipelined(self, k: int = None, donate: bool = None):
        """Lower (without running) one pipelined superstep — the
        consumer-side program with the trajectory queue in its carry."""
        k = self.cfg.superstep if k is None else k
        state, sim, delays = self._init_all()
        queue = self._init_queue(state, sim)
        its = jnp.arange(k, dtype=jnp.int32)
        return self._pipeline_superstep(k, donate).lower(
            state, sim, queue, its,
            jnp.full_like(delays[:k], self.cfg.policy_lag))

    # ---- the driver --------------------------------------------------
    def fit(self, fused: bool = True):
        """Train for cfg.iters iterations. Returns (TrainState, history);
        with a multi-device plan the returned state is device 0's
        replica."""
        cfg = self.cfg
        state, sim, delays = self._init_all()
        queue = None
        if cfg.pipeline:
            # the pipelined producer acts at the constant policy_lag
            # floor — structural queue staleness replaces the sampled
            # delay schedule — but the delay still enters the program
            # as an INPUT so the ring read lowers to the same dynamic
            # slice as the fused path (depth-0 bitwise guarantee)
            delays = jnp.full_like(delays, cfg.policy_lag)
            # prologue: fill the queue so the producer starts `depth`
            # iterations ahead of the consumer. The queue then PERSISTS
            # across superstep dispatches (no drain at chunk
            # boundaries), so chunked fits equal one-shot fits. The
            # producer over-runs by `depth` wasted rollouts at the tail
            # — the price of a uniform tick program.
            queue = self._init_queue(state, sim)
            if self.pipeline_depth:
                fill = self._producer_program(self.pipeline_depth)
                its0 = jnp.arange(self.pipeline_depth, dtype=jnp.int32)
                sim, queue = fill(state, sim, queue, its0,
                                  delays[:self.pipeline_depth])
        K = cfg.superstep if fused else 1
        history = []
        start = 0
        self.actor_shards = []
        while start < cfg.iters:
            k = min(K, cfg.iters - start)
            # the actors= schedule is indexed by cfg.superstep-iteration
            # window (not dispatch count), so fused and unfused runs
            # reshard at the same iteration boundaries and stay
            # numerically equivalent
            s_idx = start // cfg.superstep
            n_envs = self.plan.actor_schedule(s_idx, cfg.n_envs)
            # reshard key offset far above any iteration index so elastic
            # env resets never alias an iteration's rollout stream
            sim = self._reshard_envs(
                sim, n_envs,
                jax.random.fold_in(self._base_key, (1 << 20) + s_idx))
            self.actor_shards.append(n_envs)
            its = jnp.arange(start, start + k, dtype=jnp.int32)
            if cfg.pipeline:
                step = self._pipeline_superstep(k)
                state, sim, queue, metrics = step(
                    state, sim, queue, its, delays[start:start + k])
            else:
                step = self._superstep(k)
                state, sim, metrics = step(state, sim, its,
                                           delays[start:start + k])
            metrics = jax.device_get(metrics)  # ONE host sync per chunk
            for j in range(k):
                it = start + j
                if it % cfg.log_every == 0 or it == cfg.iters - 1:
                    history.append({"iter": it, **{
                        name: round(float(v[j]), 4)
                        for name, v in sorted(metrics.items())}})
            start += k
        if self.mesh is not None:
            first = (0,) * len(self.plan.axes)
            take0 = lambda t: jax.tree_util.tree_map(
                lambda a: a[first], t)
            rfull = None
            if self._replay:
                # reassemble the logical buffer from every replay shard
                # (row 0 of the other axes) BEFORE the generic device-0
                # extraction, which would keep only chunk 0 — then
                # splice the flat host form back in: fit()'s result and
                # checkpoints stay plan-independent
                nd = len(self.plan.axes)
                k = self.plan.axis_names.index(
                    self.plan.replay_axis.name)
                idx = tuple(slice(None) if i == k else 0
                            for i in range(nd))
                rfull = self._replay_service.unshard_state(
                    jax.tree_util.tree_map(lambda a: a[idx],
                                           state.extra["replay"]))
                state = self._swap_replay(state, None)
            if self._zero3:
                state = self._unshard_zero3(state, take0)
            elif self.partition is not None:
                # checkpoint-shaped result: reassemble the ZeRO shards
                # into the replicated-form opt_state before dropping
                # the mesh dims (device 0 for everything else)
                state = agent_api.TrainState(
                    take0(state.params),
                    self._unshard_opt_state(state.opt_state),
                    take0(state.extra), take0(state.ring),
                    take0(state.steps))
            else:
                state = take0(state)
            if rfull is not None:
                state = self._swap_replay(state, rfull)
        return state, history

    def _unshard_zero3(self, state, take0):
        """Reassemble a mesh-layout ZeRO-3 TrainState into the inner
        agent's replicated tree form (checkpoint shape): each partition
        entry's param and ring chunks are gathered along the shard axis
        (row 0 of every data axis), trimmed of padding and unraveled,
        then merged (restacking the per-block entries when the agent is
        layer-wise); opt_state goes through the ZeRO-2/per-entry
        reassembly; rest/extra/steps come from device 0."""
        p = self.partition
        nd = len(self.plan.axes)
        k = self.plan.axis_names.index(p["axis"])
        idx = tuple(slice(None) if i == k else 0 for i in range(nd))
        merge = (lambda es: self.agent.merge_partition_list(
            es, materialize=True)) if p["listwise"] else (
            lambda es: es[0])
        E = p["entries"]
        sub = merge([self._part_unravels[e](
            state.params["zero3"][e][idx].reshape(-1)[:p["sizes"][e]])
            for e in range(E)])
        params = self.agent.replace_partition(
            take0(state.params["rest"]), sub)
        slots = []
        for d in range(self.agent.ring_size):
            # ring entry e at idx: (n_shards, ring_size, chunk_e)
            slots.append(merge([self._part_unravels[e](
                state.ring[e][idx][:, d, :].reshape(-1)[:p["sizes"][e]])
                for e in range(E)]))
        ring = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *slots)
        return agent_api.TrainState(
            params, self._unshard_opt_state(state.opt_state),
            take0(state.extra), ring, take0(state.steps))

    def _unshard_opt_state(self, opt_state):
        """Reassemble a ZeRO-sharded opt_state (leaves carrying one
        leading mesh dim per axis) into the replicated tree form:
        chunk-shaped leaves are gathered along the shard axis (row 0 of
        every data axis), concatenated in shard order, trimmed of the
        flatten-and-pad padding and unraveled back into the partition
        target's pytree shape; other leaves (e.g. the step counter)
        come from device 0. A shard axis of size 1 therefore returns
        bitwise the replicated-trainer opt_state — checkpoints keep
        their shape across plans.

        Layer-wise ZeRO-3 opt_states are a LIST over partition entries
        of inner states (one chunk per entry): congruent leaf positions
        are gathered per entry, unraveled with that entry's unravel and
        merged back into the partition-shaped tree (scalars like the
        step counter are identical across entries — entry 0 is
        taken)."""
        p = self.partition
        nd = len(self.plan.axes)
        k = self.plan.axis_names.index(p["axis"])
        idx = tuple(slice(None) if i == k else 0 for i in range(nd))

        if p.get("listwise"):
            E = p["entries"]
            flats = [jax.tree_util.tree_flatten(opt_state[e])
                     for e in range(E)]
            leaves0, treedef = flats[0]
            out = []
            for i in range(len(leaves0)):
                per = [flats[e][0][i] for e in range(E)]
                if all(per[e].shape[nd:] == (p["chunks"][e],)
                       for e in range(E)):
                    out.append(self.agent.merge_partition_list(
                        [self._part_unravels[e](
                            per[e][idx].reshape(-1)[:p["sizes"][e]])
                         for e in range(E)], materialize=True))
                else:
                    out.append(per[0][(0,) * nd])
            return jax.tree_util.tree_unflatten(treedef, out)

        def leaf(a):
            if a.shape[nd:] == (p["chunk"],):
                return self._part_unravel(
                    a[idx].reshape(-1)[:p["size"]])
            return a[(0,) * nd]

        return jax.tree_util.tree_map(leaf, opt_state)
