"""Plain IMPALA (Espeholt et al. 2018) with V-trace over the LFM2 policy
trunk (`bench/reference/lfm2.py`), following the training loop from the
seed as `bench/reference/impala.py` does over the repo's own trunk: the
same loss, V-trace (its `vtrace`), rollout, keys, envs (`cartpole`) and
clipped AdamW (`optim`). See that file for the equations and the keys'
protocol.

The expert bias is a constant of the forward pass: it lies outside the
gradient, so Adam leaves it as it is.
"""
import functools

import jax
import jax.numpy as jnp

from bench.reference import cartpole, lfm2, optim
from bench.reference.impala import vtrace


def loss(params, traj, boot_obs, sz, hp, dtype, precision, fault, wkey):
    T, B = traj["reward"].shape
    fwd = functools.partial(lfm2.forward, sz=sz, dtype=dtype,
                            precision=precision, fault=fault, wkey=wkey)
    logits, v = fwd(params, traj["obs"].reshape(T * B, -1))
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, traj["action"].reshape(-1, 1),
                               -1)[:, 0].reshape(T, B)
    ent = -jnp.sum(jnp.exp(logp_all) * logp_all, -1).reshape(T, B)
    v = v.reshape(T, B)
    _, boot = fwd(params, boot_obs)
    disc = hp["gamma"] * (1.0 - traj["done"].astype(jnp.float32))
    sg = jax.lax.stop_gradient
    vs, adv = vtrace(sg(logp) - traj["logp"], disc, traj["reward"], sg(v),
                     sg(boot))
    vs, adv = sg(vs), sg(adv)
    return (-jnp.mean(logp * adv) + hp["vf_coef"] * jnp.mean((v - vs) ** 2)
            - hp["ent_coef"] * jnp.mean(ent))


def random_states(key, n, T):
    """(T n, F) states of `n` envs started from `key` and stepped `T`
    times under uniformly random actions."""
    def step(env, k):
        ka, kr = jax.random.split(k)
        a = jax.random.bernoulli(ka, 0.5, (n,)).astype(jnp.int32)
        env2, _, _, _ = cartpole.step_autoreset(env, a, kr)
        return env2, env["s"]

    k_env, k_steps = jax.random.split(key)
    _, s = jax.lax.scan(step, cartpole.reset_batch(k_env, n),
                        jax.random.split(k_steps, T))
    return s.reshape(T * n, -1)


def rollout(params, env, key, T, sz, dtype, precision, fault, wkey):
    """The rollout: env leaves (n, ...) -> (traj (T, n), env)."""
    def step(env, k):
        obs = env["s"]
        ka, kr = jax.random.split(k)
        logits, _ = lfm2.forward(params, obs, sz, dtype, precision, fault,
                                 wkey)
        a = jax.random.categorical(ka, logits)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits), a[:, None],
                                   -1)[:, 0]
        env, _, reward, done = cartpole.step_autoreset(env, a, kr)
        return env, {"obs": obs, "action": a, "logp": logp,
                     "reward": reward, "done": done}

    env, traj = jax.lax.scan(step, env, jax.random.split(key, T))
    return traj, env


@functools.partial(jax.jit, static_argnames=("key_seed", "n", "T", "iters",
                                             "szt", "hpt", "dtype",
                                             "precision", "fault"))
def follow(params, seed, wkey, *, key_seed, n, T, iters, szt, hpt, dtype,
           precision, fault=""):
    """Run `iters` iterations, the envs started from `seed` and the
    loop's keys drawn from `key_seed`. Returns the loss of each, the
    parameters after the last, the optimizer state and the first
    rollout's observations (T, n, F). `wkey` is the weights' key (the
    "all_experts" fault draws the absent experts from it)."""
    sz, hp = dict(szt), dict(hpt)
    _, k_env, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    env = cartpole.reset_batch(k_env, n)
    base = jax.random.PRNGKey(key_seed)
    grad = jax.value_and_grad(loss)

    def iteration(carry, it):
        params, opt, env = carry
        k_roll, _ = jax.random.split(jax.random.fold_in(base, it))
        traj, env = rollout(params, env, k_roll, T, sz, dtype, precision,
                            fault, wkey)
        lval, grads = grad(params, traj, env["s"], sz, hp, dtype, precision,
                           fault, wkey)
        params, opt = optim.update(params, opt, grads, hp["lr"],
                                   hp["max_grad_norm"])
        return (params, opt, env), (lval, traj["obs"])

    (params, opt, _), (losses, obs) = jax.lax.scan(
        iteration, (params, optim.init(params), env),
        jnp.arange(iters, dtype=jnp.int32))
    return losses, params, opt, obs[0]
