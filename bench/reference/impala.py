"""Plain IMPALA (Espeholt et al. 2018) with V-trace, following the
training loop from the seed: each iteration rolls the policy out for T
steps in every env, then takes one clipped-AdamW step on

    loss = -mean(log pi(a|s) * adv) + c_v mean((V(s) - vs)^2)
           - c_e mean(entropy)

with V-trace targets (rho-bar = c-bar = 1):

    rho_t = min(1, pi/mu),  delta_t = rho_t (r_t + g_t V_{t+1} - V_t)
    vs_t  = V_t + delta_t + g_t c_t (vs_{t+1} - V_{t+1})
    adv_t = rho_t (r_t + g_t vs_{t+1} - V_t),   g_t = gamma (1 - done_t)

The keys follow the loop's stated protocol: iteration `it` uses
`fold_in(PRNGKey(key_seed), it)`, split into (rollout, learner); the
rollout splits its key T ways and each step's key into (action, reset).
The initial envs are `reset_batch(split(PRNGKey(seed), 3)[1], n)`.
Actions are drawn as `jax.random.categorical(action key, logits)`.
"""
import functools

import jax
import jax.numpy as jnp

from bench.reference import cartpole, optim, trunk


def vtrace(log_rhos, disc, rewards, values, boot):
    rhos = jnp.minimum(1.0, jnp.exp(log_rhos))
    cs = jnp.minimum(1.0, jnp.exp(log_rhos))
    v_next = jnp.concatenate([values[1:], boot[None]], 0)
    deltas = rhos * (rewards + disc * v_next - values)

    def back(acc, x):
        delta, g, c = x
        acc = delta + g * c * acc
        return acc, acc

    _, acc = jax.lax.scan(back, jnp.zeros_like(boot), (deltas, disc, cs),
                          reverse=True)
    vs = values + acc
    vs_next = jnp.concatenate([vs[1:], boot[None]], 0)
    return vs, rhos * (rewards + disc * vs_next - values)


def loss(params, traj, boot_obs, sz, hp, dtype, precision):
    T, B = traj["reward"].shape
    logits, v = trunk.forward(params, traj["obs"].reshape(T * B, -1), sz,
                              dtype, precision)
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, traj["action"].reshape(-1, 1),
                               -1)[:, 0].reshape(T, B)
    ent = -jnp.sum(jnp.exp(logp_all) * logp_all, -1).reshape(T, B)
    v = v.reshape(T, B)
    _, boot = trunk.forward(params, boot_obs, sz, dtype, precision)
    disc = hp["gamma"] * (1.0 - traj["done"].astype(jnp.float32))
    sg = jax.lax.stop_gradient
    vs, adv = vtrace(sg(logp) - traj["logp"], disc, traj["reward"], sg(v),
                     sg(boot))
    vs, adv = sg(vs), sg(adv)
    return (-jnp.mean(logp * adv) + hp["vf_coef"] * jnp.mean((v - vs) ** 2)
            - hp["ent_coef"] * jnp.mean(ent))


def rollout(params, env, key, T, sz, dtype, precision):
    """The rollout: env leaves (n, ...) -> (traj (T, n), env)."""
    def step(env, k):
        obs = env["s"]
        ka, kr = jax.random.split(k)
        logits, value = trunk.forward(params, obs, sz, dtype, precision)
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
def follow(params, seed, *, key_seed, n, T, iters, szt, hpt, dtype,
           precision, fault=""):
    """Run `iters` iterations, the envs started from `seed` and the
    loop's keys drawn from `key_seed`. Returns the loss of each, the
    parameters after the last, the optimizer state and the envs' state.

    `fault` "half_batch" plants a fault for reading the limits against:
    the learner takes the first half of the envs only."""
    sz, hp = dict(szt), dict(hpt)
    _, k_env, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    env = cartpole.reset_batch(k_env, n)
    base = jax.random.PRNGKey(key_seed)

    def learn(params, opt, traj, boot_obs):
        lval, grads = jax.value_and_grad(loss)(params, traj, boot_obs, sz,
                                               hp, dtype, precision)
        params, opt = optim.update(params, opt, grads, hp["lr"],
                                   hp["max_grad_norm"])
        return params, opt, lval

    def iteration(carry, it):
        params, opt, env = carry
        k_roll, _ = jax.random.split(jax.random.fold_in(base, it))
        traj, env = rollout(params, env, k_roll, T, sz, dtype, precision)
        boot_obs = env["s"]
        if fault == "half_batch":
            traj = jax.tree_util.tree_map(lambda a: a[:, :n // 2], traj)
            boot_obs = boot_obs[:n // 2]
        params, opt, lval = learn(params, opt, traj, boot_obs)
        return (params, opt, env), lval

    (params, opt, env), losses = jax.lax.scan(
        iteration, (params, optim.init(params), env),
        jnp.arange(iters, dtype=jnp.int32))
    return losses, params, opt, env
