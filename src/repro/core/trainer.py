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

`fit` runs `superstep` iterations (rollout -> learner_step -> lag-ring
rotate) under ONE jitted `lax.scan` (`_superstep`): the host dispatches
iters/K programs and reads the metrics back once per dispatch. Where
the dispatches fall does not change the numerics: K dispatches of one
iteration equal one dispatch of K, bitwise (tests/test_trainer.py).

**Pipelined mode** (`TrainerConfig.pipeline=True`, the survey §2
actor/learner decoupling — Gorila/Ape-X, SRL's description/execution
split): the iteration is split at the trajectory seam into a rollout
*producer* and a learner *consumer* joined by a fixed-capacity
device-resident trajectory queue (repro.core.pipeline), held in the
simulation carry as `sim["queue"]`. The queue depth d is what the
plan's per-axis sync discipline admits (`DistPlan.pipeline_depth`):
bsp -> 0, ssp -> staleness_bound, asp -> max_delay. At depth 0 there is
no queue and the superstep is the one above. At depth d >= 1 each tick
pops the trajectory produced d ticks earlier, produces iteration it+d
with the newest params and pushes it: the learner consumes a
trajectory d iterations old, so the staleness the lockstep path models
as sampled policy lag is structural. A prologue fills the queue with
iterations 0..d-1; the queue then persists across dispatches, so a
chunked fit equals a one-shot fit bitwise (tests/test_pipeline.py).
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
                f"schedule or pipeline=False")
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
                f"— use role 'shard' (ZeRO-2) or pipeline=False")
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
                f"parity — use pipeline=False or "
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
        # the decoupled actor-learner pipeline; 0 (lockstep, no queue)
        # unless cfg.pipeline asks for it
        self.pipeline_depth = plan.pipeline_depth if cfg.pipeline else 0

    @property
    def pipeline_capacity(self) -> Optional[int]:
        """Slots of the trajectory queue in `sim`, which holds exactly
        `pipeline_depth` in-flight trajectories (None at depth 0: no
        queue)."""
        return self.pipeline_depth or None

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

    # ---- producer/consumer halves of a tick ---------------------------
    def _iter_key(self, it):
        """(k_roll, k_learn) for iteration `it` — one deterministic
        stream per iteration, whichever tick or program derives it, so
        a pipelined fit draws the same randomness for an iteration as
        a lockstep one."""
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

    def _produce(self, state, env_state, it, delay):
        """Rollout-producer half: one trajectory for iteration `it`,
        acting with the params `delay` steps old, plus its bootstrap
        observation (the queue item — boot_obs must ride along because
        the consumer never sees the env state)."""
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

    # ---- one tick: a training iteration, pipelined at depth >= 1 ----
    def _tick(self, carry, xs):
        """One scanned tick for consumer iteration `it`. At depth 0 it
        produces and consumes iteration `it`. At depth d >= 1 it pops
        the item produced d ticks earlier, produces iteration `it + d`,
        pushes it, and consumes the popped item."""
        state, sim = carry
        it, delay = xs
        d = self.pipeline_depth
        if d == 0:
            item, env_state = self._produce(state, sim["env"], it, delay)
        else:
            queue, item, _ = queue_pop(sim["queue"])
            item_p, env_state = self._produce(state, sim["env"], it + d,
                                              delay)
            queue, _ = queue_push(queue, item_p)
            sim = dict(sim, queue=queue)
        state, ep_run, ep_ret, metrics = self._consume(
            state, sim["ep_run"], sim["ep_last"], item, it)
        sim = dict(sim, env=env_state, ep_run=ep_run, ep_last=ep_ret)
        return (state, sim), metrics

    # ---- programs: k ticks in one dispatch, and the queue's fill -----
    def _jit(self, body, sharded_out, donate_argnums):
        """Jit `body(state, sim, its, delays)` for the plan. Under a mesh
        the body runs per device in one `shard_map`: `state`, `sim` and
        the results flagged in `sharded_out` carry one leading dim per
        mesh axis, the others are replicated."""
        if self.mesh is None:
            return jax.jit(body, donate_argnums=donate_argnums)
        from jax.experimental.shard_map import shard_map
        nd = len(self.plan.axes)

        def worker(state, sim, its, delays):
            # shard_map keeps one length-1 dim per mesh axis on the
            # sharded leaves — strip before the body, restore after
            out = body(strip_worker_dim(state, nd),
                       strip_worker_dim(sim, nd),
                       its, delays.reshape(delays.shape[0]))
            return tuple(restore_worker_dim(o, nd) if sh else o
                         for o, sh in zip(out, sharded_out))

        w = P(*self.plan.axis_names)
        return jax.jit(shard_map(
            worker, mesh=self.mesh,
            in_specs=(w, w, P(), P(None, *self.plan.axis_names)),
            out_specs=tuple(w if sh else P() for sh in sharded_out),
            check_rep=False), donate_argnums=donate_argnums)

    def _superstep(self, k: int, donate: bool = None):
        """Jitted k-tick program `(state, sim, its, delays) -> (state,
        sim, metrics)`. With `donate` (cfg.donate by default) the
        `state`/`sim` argument buffers are donated to their same-shaped
        outputs, so the carried pytrees — DQN's capacity×transition
        replay store, the actor-param ring, env state, the trajectory
        queue — update in place instead of being copied once per
        dispatch."""
        donate = self.cfg.donate if donate is None else donate
        cache_key = (k, donate)
        if cache_key not in self._step_cache:
            def body(state, sim, its, delays):
                (state, sim), metrics = jax.lax.scan(
                    self._tick, (state, sim), (its, delays))
                return state, sim, metrics

            self._step_cache[cache_key] = self._jit(
                body, (True, True, False), (0, 1) if donate else ())
        return self._step_cache[cache_key]

    def _fill(self):
        """Jitted prologue `(state, sim, its, delays) -> (sim,)` of a
        pipelined fit: produces iterations its[0..d-1] into the empty
        queue. `state` is only read, so only `sim` is donated."""
        d = self.pipeline_depth

        def body(state, sim, its, delays):
            env_state, queue = sim["env"], sim["queue"]
            for j in range(d):
                item, env_state = self._produce(state, env_state, its[j],
                                                delays[j])
                queue, _ = queue_push(queue, item)
            return (dict(sim, env=env_state, queue=queue),)

        return self._jit(body, (True,), (1,) if self.cfg.donate else ())

    def _init_queue(self, state):
        """Empty trajectory queue of `pipeline_depth` slots, shaped for
        one device. Item shapes come from a shape-only trace of the
        rollout with a dummy key, because `_iter_key` folds in the
        device's index, which exists only inside shard_map."""
        per = self.cfg.n_envs // self.plan.sim_devices

        def one_item(state, key):
            actor = self.agent.actor_policy(state, self.cfg.policy_lag)
            traj, env_state = rollout(
                self.agent.policy, actor, self.env, key,
                self.env.reset_batch(key, per), self.cfg.unroll)
            return {"traj": traj,
                    "boot": jax.vmap(self.env.obs)(env_state)}

        item = jax.eval_shape(one_item, state, jax.random.PRNGKey(0))
        return queue_init(item, self.pipeline_depth)

    # ---- state/schedule construction ---------------------------------
    def _shard_sim(self, sim):
        """Reshape a host-layout sim carry (flat env batch) into the
        plan's mesh layout: one leading dim per mesh axis, row-major, so
        device (i0, i1, ...) owns the same contiguous env slice its flat
        linear index would. `ep_last` and the (empty, one device's)
        trajectory queue are replicated."""
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
        rep = lambda a: jnp.broadcast_to(a, shape + a.shape)
        return {name: jax.tree_util.tree_map(
                    lay if name in ("env", "ep_run") else rep, tree)
                for name, tree in sim.items()}

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
        if self.pipeline_depth:
            sim["queue"] = self._init_queue(state)
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
            fresh = self._shard_sim({
                "env": self.env.reset_batch(key, (per_new - per_cur) * nd),
                "ep_run": jnp.zeros(((per_new - per_cur) * nd,))})
            env = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=lead),
                sim["env"], fresh["env"])
            ep_run = jnp.concatenate([sim["ep_run"], fresh["ep_run"]],
                                     axis=lead)
        return dict(sim, env=env, ep_run=ep_run)

    def lower(self, k: int = None, donate: bool = None):
        """Lower (without running) one superstep — lets benchmarks
        inspect the collective schedule (HLO) per plan and the donation
        plan (compile().memory_analysis()); at depth >= 1 the trajectory
        queue rides in `sim`."""
        k = self.cfg.superstep if k is None else k
        state, sim, delays = self._init_all()
        its = jnp.arange(k, dtype=jnp.int32)
        return self._superstep(k, donate).lower(state, sim, its,
                                                delays[:k])

    # ---- the driver --------------------------------------------------
    def fit(self):
        """Train for cfg.iters iterations, one dispatch per cfg.superstep
        iterations. Returns (TrainState, history); with a multi-device
        plan the returned state is device 0's replica."""
        cfg = self.cfg
        state, sim, delays = self._init_all()
        d = self.pipeline_depth
        if d:
            # the producer acts with the newest params (the policy_lag
            # floor): the staleness is the queue depth, not sampled
            delays = jnp.full_like(delays, cfg.policy_lag)
            # prologue: produce iterations 0..d-1. The queue then
            # persists across dispatches; the producer over-runs by d
            # wasted rollouts at the tail.
            (sim,) = self._fill()(state, sim,
                                  jnp.arange(d, dtype=jnp.int32),
                                  delays[:d])
        history = []
        self.actor_shards = []
        for s_idx, start in enumerate(range(0, cfg.iters, cfg.superstep)):
            k = min(cfg.superstep, cfg.iters - start)
            n_envs = self.plan.actor_schedule(s_idx, cfg.n_envs)
            # reshard key offset far above any iteration index so elastic
            # env resets never alias an iteration's rollout stream
            sim = self._reshard_envs(
                sim, n_envs,
                jax.random.fold_in(self._base_key, (1 << 20) + s_idx))
            self.actor_shards.append(n_envs)
            its = jnp.arange(start, start + k, dtype=jnp.int32)
            state, sim, metrics = self._superstep(k)(
                state, sim, its, delays[start:start + k])
            metrics = jax.device_get(metrics)  # ONE host sync per chunk
            for j in range(k):
                it = start + j
                if it % cfg.log_every == 0 or it == cfg.iters - 1:
                    history.append({"iter": it, **{
                        name: round(float(v[j]), 4)
                        for name, v in sorted(metrics.items())}})
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
