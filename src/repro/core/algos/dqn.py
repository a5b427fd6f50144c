"""DQN with (prioritized) replay and target network — the Gorila/Ape-X
learner (survey §3.1). Actor and learner are separate jitted functions
so the driver can place them on different workers."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.agent import Agent, TrainState, register
from repro.core.replay import UniformReplay, PrioritizedReplay
from repro.models.layers import dense_init
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class DQN:
    obs_dim: int
    n_actions: int
    hidden: tuple = (64, 64)
    gamma: float = 0.99
    target_update: int = 100
    double: bool = True
    prioritized: bool = True
    replay_capacity: int = 10000
    fused_sampling: bool = True  # Gumbel-top-k kernel path (replay.py);
    #                              False = legacy categorical escape
    #                              hatch (WITH replacement). Default
    #                              since the kernel parity pin of PR 3.
    net: object = None  # pluggable q-net adapter (init/apply -> (q, _));
    #                     None = the house MLP below. Lets the trunk
    #                     policy (networks.TrunkPolicy) serve as q-net.

    @property
    def replay(self):
        return (PrioritizedReplay(self.replay_capacity,
                                  fused=self.fused_sampling)
                if self.prioritized
                else UniformReplay(self.replay_capacity))

    # -- q network -----------------------------------------------------
    def init(self, key):
        if self.net is not None:
            net = self.net.init(key)
        else:
            sizes = (self.obs_dim,) + self.hidden + (self.n_actions,)
            ks = jax.random.split(key, len(sizes))
            net = [{"w": dense_init(ks[i], (sizes[i], sizes[i + 1])),
                    "b": jnp.zeros((sizes[i + 1],))}
                   for i in range(len(sizes) - 1)]
        return {"online": net,
                "target": jax.tree_util.tree_map(jnp.copy, net),
                "steps": jnp.zeros((), jnp.int32)}

    def q_values(self, net, obs):
        if self.net is not None:
            return self.net.apply(net, obs)[0]
        h = obs
        for lay in net[:-1]:
            h = jax.nn.relu(h @ lay["w"] + lay["b"])
        return h @ net[-1]["w"] + net[-1]["b"]

    # -- actor ----------------------------------------------------------
    def act(self, params, obs, key, epsilon):
        q = self.q_values(params["online"], obs)
        greedy = jnp.argmax(q, axis=-1)
        rand = jax.random.randint(key, greedy.shape, 0, self.n_actions)
        take_rand = jax.random.uniform(key, greedy.shape) < epsilon
        return jnp.where(take_rand, rand, greedy)

    # -- learner ---------------------------------------------------------
    def td_errors(self, params, batch):
        q = self.q_values(params["online"], batch["obs"])
        qa = jnp.take_along_axis(q, batch["action"][..., None].astype(
            jnp.int32), -1)[..., 0]
        qn_t = self.q_values(params["target"], batch["next_obs"])
        if self.double:
            qn_o = self.q_values(params["online"], batch["next_obs"])
            a_star = jnp.argmax(qn_o, axis=-1)
            q_next = jnp.take_along_axis(qn_t, a_star[..., None],
                                         -1)[..., 0]
        else:
            q_next = qn_t.max(axis=-1)
        target = batch["reward"] + self.gamma * (
            1.0 - batch["done"].astype(jnp.float32)) * q_next
        return jax.lax.stop_gradient(target) - qa

    def loss(self, params, batch, is_weights=None):
        td = self.td_errors(params, batch)
        w = jnp.ones_like(td) if is_weights is None else is_weights
        return jnp.mean(w * jnp.square(td)), td

    @functools.partial(jax.jit, static_argnames=("self", "optimizer"))
    def learner_step(self, params, opt_state, replay_state, key,
                     optimizer, batch_size=64):
        if self.prioritized:
            batch, idx, w = self.replay.sample(replay_state, key,
                                               batch_size)
        else:
            batch, idx = self.replay.sample(replay_state, key, batch_size)
            w = None
        def loss_online(online):
            return self.loss(dict(params, online=online), batch, w)

        (loss, td), grads = jax.value_and_grad(
            loss_online, has_aux=True)(params["online"])
        online, opt_state = optimizer.apply(params["online"], opt_state,
                                            grads)
        params = dict(params, online=online)
        if self.prioritized:
            replay_state = self.replay.update_priorities(replay_state,
                                                         idx, td)
        steps = params["steps"] + 1
        target = jax.tree_util.tree_map(
            lambda t, o: jnp.where(steps % self.target_update == 0, o, t),
            params["target"], params["online"])
        params = dict(params, steps=steps, target=target)
        return params, opt_state, replay_state, loss


class _QPolicy:
    """Adapter exposing a DQN net to the shared rollout engine: behavior
    params are {"net": online-net, "eps": exploration rate} so ε rides
    through `actor_policy` and the rollout stays algorithm-agnostic."""

    discrete = True

    def __init__(self, dqn: DQN):
        self.dqn = dqn

    def apply(self, params, obs):
        q = self.dqn.q_values(params["net"], obs)
        return q, q.max(axis=-1)

    def sample(self, params, obs, key):
        a = self.dqn.act({"online": params["net"]}, obs, key,
                         params["eps"])
        q = self.dqn.q_values(params["net"], obs)
        logp = jnp.take_along_axis(jax.nn.log_softmax(q),
                                   a[..., None], -1)[..., 0]
        return a, logp

    def sample_value(self, params, obs, key):
        """ε-greedy draw + log-prob + value from ONE q evaluation (the
        sample/apply pair evaluated the net three times); same key
        discipline as DQN.act, so actions are bitwise unchanged."""
        q = self.dqn.q_values(params["net"], obs)
        greedy = jnp.argmax(q, axis=-1)
        rand = jax.random.randint(key, greedy.shape, 0,
                                  self.dqn.n_actions)
        take_rand = jax.random.uniform(key, greedy.shape) < params["eps"]
        a = jnp.where(take_rand, rand, greedy)
        logp = jnp.take_along_axis(jax.nn.log_softmax(q),
                                   a[..., None], -1)[..., 0]
        return a, logp, q.max(axis=-1)


class DQNAgent(Agent):
    """DQN/Ape-X behind the unified protocol: the rollout trajectory is
    flattened into transitions and pushed into a per-worker on-device
    replay carried inside TrainState.extra; one (prioritized) TD update
    runs per iteration after `warmup` iterations of pure collection."""

    def __init__(self, env, ring_size=1, total_iters=None, lr=1e-3,
                 hidden=(64, 64), prioritized=True, replay_capacity=20000,
                 batch_size=64, warmup=8, eps_start=1.0, eps_end=0.05,
                 eps_decay_steps=None, policy="mlp", trunk_kwargs=None,
                 **algo_kwargs):
        spec = env.spec
        self.obs_space = spec.observation
        net = None
        if policy == "trunk":
            from repro.core.networks import TrunkPolicy
            net = TrunkPolicy.for_spec(spec, **(trunk_kwargs or {}))
        elif policy != "mlp":
            raise ValueError(f"unknown policy {policy!r}: expected "
                             f"'mlp' or 'trunk'")
        self.dqn = DQN(spec.obs_dim, spec.n_actions, hidden=tuple(hidden),
                       prioritized=prioritized,
                       replay_capacity=replay_capacity, net=net,
                       **algo_kwargs)
        self.policy = _QPolicy(self.dqn)
        # the Trainer swaps this for a ShardedPrioritizedReplay when its
        # DistPlan carries an active replay-role axis; init() keeps the
        # flat host form either way (plan-independent checkpoints)
        self.replay = self.dqn.replay
        self.opt = adamw(lr)
        self.ring_size = ring_size
        self.batch_size = batch_size
        self.warmup = warmup
        self.eps_start = eps_start
        self.eps_end = eps_end
        if eps_decay_steps is None:  # anneal over 60% of the run
            eps_decay_steps = max(1, int(0.6 * total_iters)) \
                if total_iters else 200
        self.eps_decay_steps = eps_decay_steps

    def init(self, key):
        params = self.dqn.init(key)
        obs_zero = jnp.zeros(self.obs_space.shape,
                             self.obs_space.dtype)
        example = {"obs": obs_zero,
                   "action": jnp.zeros((), jnp.int32),
                   "reward": jnp.zeros(()),
                   "next_obs": obs_zero,
                   "done": jnp.zeros((), bool)}
        return TrainState(params, self.opt.init(params["online"]),
                          {"replay": self.dqn.replay.init(example)},
                          self._ring_init(params["online"]),
                          jnp.zeros((), jnp.int32))

    def partition_spec(self, state):
        """Only the online net is optimizer-updated (opt_state mirrors
        it); target net + step counter ride outside the shard."""
        return state.params["online"]

    def replace_partition(self, params, sub):
        return dict(params, online=sub)

    def actor_policy(self, state, delay=0):
        frac = jnp.clip(state.steps.astype(jnp.float32)
                        / self.eps_decay_steps, 0.0, 1.0)
        eps = self.eps_start + frac * (self.eps_end - self.eps_start)
        return {"net": self._ring_read(state.ring, delay), "eps": eps}

    def learner_step(self, state, traj, boot_obs, key,
                     grad_tx=None, param_tx=None):
        # traj -> transitions; the rollout surfaces the TRUE successor
        # obs (pre-autoreset at episode boundaries), so replayed
        # transitions are exact even across resets.
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        transitions = {"obs": flat(traj["obs"]),
                       "action": flat(traj["action"]).astype(jnp.int32),
                       "reward": flat(traj["reward"]),
                       "next_obs": flat(traj["next_obs"]),
                       "done": flat(traj["done"])}
        replay = self.replay
        rstate = replay.add_batch(state.extra["replay"], transitions)

        if self.dqn.prioritized:
            batch, idx, w = replay.sample(rstate, key, self.batch_size)
        else:
            batch, idx = replay.sample(rstate, key, self.batch_size)
            w = None

        def loss_online(online):
            return self.dqn.loss(dict(state.params, online=online),
                                 batch, w)

        (loss, td), grads = jax.value_and_grad(
            loss_online, has_aux=True)(state.params["online"])
        if grad_tx is not None:
            grads = grad_tx(grads)
        with jax.named_scope("optimizer"):
            online, opt_state = self.opt.apply(state.params["online"],
                                               state.opt_state, grads)
        if param_tx is not None:
            online = param_tx(online)
        warm = state.steps >= self.warmup
        if self.dqn.prioritized:
            # keep the Ape-X max-priority inserts during warmup — |td|
            # from the untrained net would under-prioritize early data
            updated = replay.update_priorities(rstate, idx, td)
            rstate = dict(rstate, prio=jnp.where(warm, updated["prio"],
                                                 rstate["prio"]))
        qsteps = state.params["steps"] + 1
        target = jax.tree_util.tree_map(
            lambda t, o: jnp.where(qsteps % self.dqn.target_update == 0,
                                   o, t),
            state.params["target"], online)
        new_params = {"online": online, "target": target, "steps": qsteps}
        # pure-collection warmup: keep filling the replay, hold the params
        sel = lambda new, old: jax.tree_util.tree_map(
            lambda a, b: jnp.where(warm, a, b), new, old)
        params = sel(new_params, state.params)
        opt_state = sel(opt_state, state.opt_state)
        return TrainState(params, opt_state, {"replay": rstate},
                          self._ring_push(state.ring, params["online"]),
                          state.steps + 1), {"loss": jnp.where(warm, loss,
                                                               0.0)}


register("dqn", DQNAgent)
