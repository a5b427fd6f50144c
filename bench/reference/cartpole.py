"""Plain CartPole-v1 (Barto, Sutton & Anderson 1983; the Gym v1 physics),
written from its equations, with the episode cap of 200 steps that the
configurations state.

The random draws follow the stated key protocol of the training loop:
an env reset with key `k` draws its state as
`uniform(split(k)[1], (4,), -0.05, 0.05)`; a batch of `n` resets splits
its key `n` ways; a stepped batch resets the envs that ended with
`split(key_t)[1]` split `n` ways.
"""
import jax
import jax.numpy as jnp

GRAVITY, MASSCART, MASSPOLE, LENGTH, FORCE, TAU = 9.8, 1.0, 0.1, 0.5, 10.0, 0.02
X_LIM, THETA_LIM, MAX_STEPS = 2.4, 12 * jnp.pi / 180, 200


def reset(key):
    _, k_state = jax.random.split(key)
    return {"s": jax.random.uniform(k_state, (4,), minval=-0.05,
                                    maxval=0.05),
            "t": jnp.zeros((), jnp.int32)}


def reset_batch(key, n):
    return jax.vmap(reset)(jax.random.split(key, n))


def step(state, action):
    """-> (state, obs, reward, done) for one env."""
    x, x_dot, th, th_dot = state["s"]
    force = jnp.where(action > 0, FORCE, -FORCE)
    total = MASSCART + MASSPOLE
    pml = MASSPOLE * LENGTH
    cos, sin = jnp.cos(th), jnp.sin(th)
    temp = (force + pml * th_dot ** 2 * sin) / total
    th_acc = (GRAVITY * sin - cos * temp) / (
        LENGTH * (4.0 / 3.0 - MASSPOLE * cos ** 2 / total))
    x_acc = temp - pml * th_acc * cos / total
    s = jnp.stack([x + TAU * x_dot, x_dot + TAU * x_acc,
                   th + TAU * th_dot, th_dot + TAU * th_acc])
    t = state["t"] + 1
    done = ((jnp.abs(s[0]) > X_LIM) | (jnp.abs(s[2]) > THETA_LIM)
            | (t >= MAX_STEPS))
    return {"s": s, "t": t}, s, jnp.float32(1.0), done


def step_autoreset(state, action, key):
    """Batched step; ended envs restart. The returned obs is the one the
    step produced (the terminal one where an episode ended)."""
    new, obs, reward, done = jax.vmap(step)(state, action)
    fresh = reset_batch(key, done.shape[0])
    keep = lambda a, b: jnp.where(
        done.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
    return (jax.tree_util.tree_map(keep, fresh, new), obs,
            jnp.broadcast_to(reward, done.shape), done)
