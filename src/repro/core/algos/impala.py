"""IMPALA actor-learner with V-trace (survey §3.2/§6.1).

The defining property — *policy lag* between the behavior policy (actor
params) and target policy (learner params) — is first-class: the driver
keeps actor params a configurable number of updates behind, and V-trace
corrects for the lag. tests/test_impala.py shows uncorrected actor-critic
degrades under lag while V-trace does not (the survey's §6.1 claim).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.agent import PolicyGradientAgent, register
from repro.core.networks import make_policy
from repro.core.vtrace import vtrace, epsilon_correction
from repro.optim import adamw, clip_by_global_norm


@dataclasses.dataclass(frozen=True)
class IMPALA:
    policy: object
    gamma: float = 0.99
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    clip_rho: float = 1.0
    clip_c: float = 1.0
    use_vtrace: bool = True
    use_eps_correction: bool = False

    def loss(self, params, traj, bootstrap_obs):
        """traj: time-major {obs, action, logp(behavior), reward, done}."""
        T, B = traj["reward"].shape
        obs_flat = traj["obs"].reshape((-1,) + traj["obs"].shape[2:])
        act_flat = traj["action"].reshape((-1,)
                                          + traj["action"].shape[2:])
        logp_t, v_t, ent = self.policy.log_prob(params, obs_flat, act_flat)
        if self.use_eps_correction:
            logp_t = epsilon_correction(logp_t)
        logp_t = logp_t.reshape(T, B)
        v_t = v_t.reshape(T, B)
        ent = ent.reshape(T, B)
        _, boot = self.policy.apply(params, bootstrap_obs)
        discounts = self.gamma * (1.0 - traj["done"].astype(jnp.float32))
        if self.use_vtrace:
            log_rhos = logp_t - traj["logp"]
            vs, pg_adv = vtrace(jax.lax.stop_gradient(log_rhos), discounts,
                                traj["reward"],
                                jax.lax.stop_gradient(v_t), boot,
                                self.clip_rho, self.clip_c, use_kernel=True)
        else:  # naive on-policy targets computed from off-policy data
            def disc_ret(acc, xs):
                r, d = xs
                acc = r + d * acc
                return acc, acc
            _, vs = jax.lax.scan(disc_ret, boot,
                                 (traj["reward"], discounts),
                                 reverse=True)
            vs = jax.lax.stop_gradient(vs)
            vs_tp1 = jnp.concatenate([vs[1:], boot[None]], axis=0)
            pg_adv = jax.lax.stop_gradient(
                traj["reward"] + discounts * vs_tp1
                - jax.lax.stop_gradient(v_t))
        pg_loss = -jnp.mean(logp_t * pg_adv)
        vf_loss = jnp.mean(jnp.square(v_t - vs))
        return pg_loss + self.vf_coef * vf_loss \
            - self.ent_coef * jnp.mean(ent)

    @functools.partial(jax.jit, static_argnames=("self", "optimizer"))
    def learner_step(self, params, opt_state, traj, bootstrap_obs,
                     optimizer):
        loss, grads = jax.value_and_grad(self.loss)(params, traj,
                                                    bootstrap_obs)
        params, opt_state = optimizer.apply(params, opt_state, grads)
        return params, opt_state, loss


class IMPALAAgent(PolicyGradientAgent):
    """IMPALA behind the unified protocol. The Trainer's §6 delay
    schedule supplies the policy lag that V-trace corrects for."""

    def __init__(self, env, ring_size=1, total_iters=None, lr=1e-3,
                 hidden=(64, 64), max_grad_norm=1.0, policy="mlp",
                 trunk_kwargs=None, **algo_kwargs):
        self.policy = make_policy(env.spec, policy, hidden,
                                  **(trunk_kwargs or {}))
        self.algo = IMPALA(self.policy, **algo_kwargs)
        self.opt = clip_by_global_norm(adamw(lr), max_grad_norm)
        self.ring_size = ring_size


register("impala", IMPALAAgent)
