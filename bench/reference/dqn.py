"""Plain prioritized double DQN (Mnih et al. 2015; van Hasselt et al. 2016;
Schaul et al. 2016) with Ape-X inserts (Horgan et al. 2018), following
the training loop from the seed. Each iteration:

1. rolls the online net out for T steps in every env, epsilon-greedy
   with epsilon = e0 + min(1, t / t_decay) (e1 - e0) at learner step t;
2. writes the T x n transitions into the ring at its pointer, each with
   the largest priority so far (at least 1);
3. draws n_batch slots without replacement, proportional to
   p^alpha, by Gumbel-top-k: the n_batch largest alpha log(p + eps) + g,
   with importance weights (N P(i))^-beta normalized to a largest of 1;
4. takes one AdamW step on mean(w * td^2), td = r + gamma (1 - d)
   Q_target(s', argmax_a Q_online(s', a)) - Q_online(s, a), with the
   target cut from the gradient; writes |td| + eps back as the drawn
   slots' priorities; copies the online net to the target every
   `target_update` steps.

The keys follow the loop's stated protocol (see impala.py); the draw's
Gumbel noise is `jax.random.gumbel(learner key, (C,))`, and an action
draw uses one key for both `randint` and `uniform`.

`fill` makes the full replay the benchmark starts from: CartPole
transitions from uniformly drawn states inside the episode limits,
uniform actions, their true successors, and priorities drawn as |td|
of a unit normal.
"""
import functools

import jax
import jax.numpy as jnp

from bench.reference import cartpole, optim
from bench.reference.trunk import mm

LIMITS = jnp.array([2.4, 3.0, 12 * jnp.pi / 180, 3.5], jnp.float32)


def init(key, sz):
    sizes = (sz["obs_dim"],) + tuple(sz["hidden"]) + (sz["n_actions"],)
    ks = jax.random.split(key, len(sizes))
    return [{"w": jax.random.truncated_normal(
                 ks[i], -2.0, 2.0, (sizes[i], sizes[i + 1])) * sizes[i] ** -0.5,
             "b": jnp.zeros((sizes[i + 1],))}
            for i in range(len(sizes) - 1)]


def q_values(net, obs, dtype=jnp.float32, precision="highest"):
    h = obs.astype(dtype)
    for i, lay in enumerate(net):
        h = mm("bi,io->bo", h, lay["w"].astype(dtype), precision) + lay[
            "b"].astype(dtype)
        if i < len(net) - 1:
            h = jax.nn.relu(h)
    return h.astype(jnp.float32)


def fill(key, capacity):
    k_s, k_a, k_p = jax.random.split(key, 3)
    s = jax.random.uniform(k_s, (capacity, 4), minval=-1.0) * LIMITS
    a = jax.random.randint(k_a, (capacity,), 0, 2)
    state = {"s": s, "t": jnp.zeros((capacity,), jnp.int32)}
    _, nxt, reward, done = jax.vmap(cartpole.step)(state, a)
    store = {"obs": s, "action": a, "reward": reward, "next_obs": nxt,
             "done": done}
    prio = jnp.abs(jax.random.normal(k_p, (capacity,))) + 1e-6
    return {"store": store, "prio": prio, "ptr": jnp.zeros((), jnp.int32),
            "size": jnp.full((), capacity, jnp.int32)}


def draw(prio, size, key, n, alpha, beta, eps):
    C = prio.shape[0]
    valid = jnp.arange(C) < jnp.maximum(size, 1)
    logits = jnp.where(valid, alpha * jnp.log(prio + eps), -jnp.inf)
    _, idx = jax.lax.top_k(logits + jax.random.gumbel(key, (C,)), n)
    p = jnp.exp(logits[idx] - logits.max()) / jnp.sum(
        jnp.where(valid, jnp.exp(logits - logits.max()), 0.0))
    w = (jnp.maximum(size, 1) * p + 1e-12) ** -beta
    return idx, w / w.max()


def td_errors(online, target, b, gamma, dtype, precision):
    q = q_values(online, b["obs"], dtype, precision)
    qa = jnp.take_along_axis(q, b["action"][:, None], -1)[:, 0]
    a_star = jnp.argmax(q_values(online, b["next_obs"], dtype, precision), -1)
    q_next = jnp.take_along_axis(
        q_values(target, b["next_obs"], dtype, precision), a_star[:, None],
        -1)[:, 0]
    y = b["reward"] + gamma * (1.0 - b["done"].astype(jnp.float32)) * q_next
    return jax.lax.stop_gradient(y) - qa


@functools.partial(jax.jit, static_argnames=("key_seed", "n", "T", "iters",
                                             "step0", "hpt", "dtype",
                                             "precision", "fault"))
def follow(online, replay, seed, *, key_seed, n, T, iters, step0, hpt, dtype,
           precision, fault=""):
    """Run `iters` iterations, the envs started from `seed`, the loop's
    keys drawn from `key_seed` and the learner counter starting at
    `step0`. Returns (losses, online net, optimizer state, replay).
    `fault` "half_batch" learns from the first half of each drawn batch
    (and writes back only its priorities)."""
    hp = dict(hpt)
    _, k_env, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    env = cartpole.reset_batch(k_env, n)
    base = jax.random.PRNGKey(key_seed)
    C = replay["prio"].shape[0]

    def iteration(carry, it):
        online, target, opt, replay, env, t = carry
        k_roll, k_learn = jax.random.split(jax.random.fold_in(base, it))
        eps_t = hp["eps_start"] + jnp.clip(
            t.astype(jnp.float32) / hp["eps_decay_steps"], 0.0, 1.0) * (
            hp["eps_end"] - hp["eps_start"])

        def step(env, k):
            obs = env["s"]
            ka, kr = jax.random.split(k)
            greedy = jnp.argmax(q_values(online, obs, dtype, precision), -1)
            a = jnp.where(jax.random.uniform(ka, (n,)) < eps_t,
                          jax.random.randint(ka, (n,), 0, 2), greedy)
            env, nxt, reward, done = cartpole.step_autoreset(env, a, kr)
            return env, {"obs": obs, "action": a, "reward": reward,
                         "next_obs": nxt, "done": done}

        env, tr = jax.lax.scan(step, env, jax.random.split(k_roll, T))
        tr = jax.tree_util.tree_map(lambda x: x.reshape((T * n,) + x.shape[2:]),
                                    tr)
        slots = (replay["ptr"] + jnp.arange(T * n)) % C
        top = jnp.maximum(replay["prio"].max(), 1.0)
        replay = {"store": jax.tree_util.tree_map(
                      lambda s, x: s.at[slots].set(x), replay["store"], tr),
                  "prio": replay["prio"].at[slots].set(top),
                  "ptr": (replay["ptr"] + T * n) % C,
                  "size": jnp.minimum(replay["size"] + T * n, C)}
        idx, w = draw(replay["prio"], replay["size"], k_learn, hp["batch"],
                      hp["alpha"], hp["beta"], hp["prio_eps"])
        if fault == "half_batch":
            idx, w = idx[:hp["batch"] // 2], w[:hp["batch"] // 2]
        b = jax.tree_util.tree_map(lambda s: s[idx], replay["store"])

        def lossf(on):
            td = td_errors(on, target, b, hp["gamma"], dtype, precision)
            return jnp.mean(w * td * td), td

        (lval, td), g = jax.value_and_grad(lossf, has_aux=True)(online)
        online, opt = optim.update(online, opt, g, hp["lr"])
        replay = dict(replay, prio=replay["prio"].at[idx].set(
            jnp.abs(td) + hp["prio_eps"]))
        q_t = t + 1 - step0 + hp["qsteps0"]
        target = jax.tree_util.tree_map(
            lambda a, b_: jnp.where(q_t % hp["target_update"] == 0, a, b_),
            online, target)
        return (online, target, opt, replay, env, t + 1), lval

    init_c = (online, jax.tree_util.tree_map(jnp.copy, online),
              optim.init(online), replay, env, jnp.int32(step0))
    (online, _, opt, replay, _, _), losses = jax.lax.scan(
        iteration, init_c, jnp.arange(iters, dtype=jnp.int32))
    return losses, online, opt, replay
