"""PPO with GAE; DD-PPO mode = decentralized synchronous gradient
exchange over a worker axis (survey §3.2 / §6.2)."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.advantages import gae
from repro.core.agent import PolicyGradientAgent, TrainState, register
from repro.core.networks import make_policy
from repro.optim import adamw, clip_by_global_norm

__all__ = ["gae", "PPO", "PPOAgent"]  # gae re-exported for back-compat


@dataclasses.dataclass(frozen=True)
class PPO:
    policy: object
    clip_eps: float = 0.2
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    gamma: float = 0.99
    lam: float = 0.95

    def loss(self, params, batch):
        """batch: flattened {obs, action, logp, adv, ret}."""
        logp, v, ent = self.policy.log_prob(params, batch["obs"],
                                            batch["action"])
        ratio = jnp.exp(logp - batch["logp"])
        adv = batch["adv"]
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - self.clip_eps,
                           1 + self.clip_eps) * adv
        pg = -jnp.mean(jnp.minimum(unclipped, clipped))
        vf = jnp.mean(jnp.square(v - batch["ret"]))
        return pg + self.vf_coef * vf - self.ent_coef * jnp.mean(ent)

    def make_batch(self, params, traj, last_obs):
        """traj: time-major rollout dict. Computes GAE (through the
        core.advantages kernel seam — Pallas on TPU, scan ref
        elsewhere) and flattens."""
        _, boot = self.policy.apply(params, last_obs)
        adv, ret = gae(traj["reward"], traj["value"], traj["done"], boot,
                       self.gamma, self.lam, use_kernel=True)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        return {"obs": flat(traj["obs"]), "action": flat(traj["action"]),
                "logp": flat(traj["logp"]), "adv": flat(adv),
                "ret": flat(ret)}

    @functools.partial(jax.jit, static_argnames=("self", "optimizer",
                                                 "n_epochs", "n_minibatch"))
    def update(self, params, opt_state, batch, key, optimizer,
               n_epochs=4, n_minibatch=4):
        n = batch["obs"].shape[0]
        mb = n // n_minibatch

        def epoch(carry, key_e):
            params, opt_state = carry
            perm = jax.random.permutation(key_e, n)

            def minibatch(carry, i):
                params, opt_state = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
                mbatch = jax.tree_util.tree_map(lambda a: a[idx], batch)
                loss, grads = jax.value_and_grad(self.loss)(params, mbatch)
                params, opt_state = optimizer.apply(params, opt_state,
                                                    grads)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                minibatch, (params, opt_state), jnp.arange(n_minibatch))
            return (params, opt_state), losses.mean()

        (params, opt_state), losses = jax.lax.scan(
            epoch, (params, opt_state), jax.random.split(key, n_epochs))
        return params, opt_state, losses.mean()


class PPOAgent(PolicyGradientAgent):
    """PPO behind the unified protocol (shares init with the other
    policy-gradient agents; the learner is its own epoch/minibatch
    scan). With n_workers > 1 the Trainer's grad_tx all-reduces every
    minibatch gradient — DD-PPO's decentralized synchronous exchange
    (survey §3.2)."""

    def __init__(self, env, ring_size=1, total_iters=None, lr=3e-4,
                 hidden=(64, 64), n_epochs=4, n_minibatch=4,
                 max_grad_norm=0.5, policy="mlp", trunk_kwargs=None,
                 **algo_kwargs):
        self.policy = make_policy(env.spec, policy, hidden,
                                  **(trunk_kwargs or {}))
        self.algo = PPO(self.policy, **algo_kwargs)
        self.opt = clip_by_global_norm(adamw(lr), max_grad_norm)
        self.n_epochs = n_epochs
        self.n_minibatch = n_minibatch
        self.ring_size = ring_size

    def learner_step(self, state, traj, boot_obs, key,
                     grad_tx=None, param_tx=None):
        batch = self.algo.make_batch(state.params, traj, boot_obs)
        n = batch["obs"].shape[0]
        mb = n // self.n_minibatch

        def epoch(carry, key_e):
            params, opt_state = carry
            perm = jax.random.permutation(key_e, n)

            def minibatch(carry, i):
                params, opt_state = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
                mbatch = jax.tree_util.tree_map(lambda a: a[idx], batch)
                loss, grads = jax.value_and_grad(self.algo.loss)(params,
                                                                 mbatch)
                if grad_tx is not None:
                    grads = grad_tx(grads)
                with jax.named_scope("optimizer"):
                    params, opt_state = self.opt.apply(params, opt_state,
                                                       grads)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                minibatch, (params, opt_state),
                jnp.arange(self.n_minibatch))
            return (params, opt_state), losses.mean()

        (params, opt_state), losses = jax.lax.scan(
            epoch, (state.params, state.opt_state),
            jax.random.split(key, self.n_epochs))
        if param_tx is not None:
            params = param_tx(params)
        return TrainState(params, opt_state, state.extra,
                          self._ring_push(state.ring, params),
                          state.steps + 1), {"loss": losses.mean()}


register("ppo", PPOAgent)
