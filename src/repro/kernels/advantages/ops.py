"""Jit'd wrappers: pad batch, call the Pallas reverse-scan kernel, and
express GAE / n-step returns in terms of it (elementwise prologues fuse
into the surrounding XLA program; the serial recursion runs in-kernel).

`discounted_return` is differentiable (A3C's value loss differentiates
its n-step targets through the bootstrap value): the forward pass is
the kernel, and the backward pass is `jax.vjp` of the scan reference.
"""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.advantages.kernel import discounted_return_tb
from repro.kernels.advantages.ref import discounted_return_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def discounted_return(base, coef, init, bb=128):
    T, B = base.shape
    bb = min(bb, B)
    pad = (-B) % bb
    if pad:
        p2 = ((0, 0), (0, pad))
        base, coef = (jnp.pad(a, p2) for a in (base, coef))
        init = jnp.pad(init, ((0, pad),))
    out = discounted_return_tb(base.astype(jnp.float32),
                               coef.astype(jnp.float32),
                               init.astype(jnp.float32), bb=bb)
    return out[:, :B]


def _fwd(base, coef, init, bb):
    return discounted_return(base, coef, init, bb), (base, coef, init)


def _bwd(bb, res, g):
    _, vjp = jax.vjp(discounted_return_ref, *res)
    return vjp(g)


discounted_return.defvjp(_fwd, _bwd)


def gae(rewards, values, dones, bootstrap, gamma=0.99, lam=0.95, bb=128):
    """Time-major (T,B). Returns (advantages, returns)."""
    values_tp1 = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
    nonterm = 1.0 - dones.astype(jnp.float32)
    deltas = rewards + gamma * nonterm * values_tp1 - values
    adv = discounted_return(deltas, gamma * lam * nonterm,
                            jnp.zeros_like(bootstrap), bb)
    return adv, adv + values


def nstep_return(rewards, dones, bootstrap, gamma=0.99, bb=128):
    discounts = gamma * (1.0 - dones.astype(jnp.float32))
    return discounted_return(rewards, discounts, bootstrap, bb)
