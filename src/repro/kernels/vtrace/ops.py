"""Jit'd wrapper: pad batch, call the Pallas V-trace kernel.

V-trace targets are constants of the loss (both outputs are
stop-gradient, as in the ref), so the inputs are cut from the
gradient too: no tangent reaches the kernel, which has no JVP rule.
"""
import jax
import jax.numpy as jnp

from repro.kernels.vtrace.kernel import vtrace_tb


def vtrace(log_rhos, discounts, rewards, values, bootstrap,
           clip_rho=1.0, clip_c=1.0, bb=128):
    log_rhos, discounts, rewards, values, bootstrap = jax.lax.stop_gradient(
        (log_rhos, discounts, rewards, values, bootstrap))
    T, B = log_rhos.shape
    bb = min(bb, B)
    pad = (-B) % bb
    if pad:
        p2 = ((0, 0), (0, pad))
        log_rhos, discounts, rewards, values = (
            jnp.pad(a, p2) for a in (log_rhos, discounts, rewards, values))
        bootstrap = jnp.pad(bootstrap, ((0, pad),))
    vs, adv = vtrace_tb(log_rhos.astype(jnp.float32),
                        discounts.astype(jnp.float32),
                        rewards.astype(jnp.float32),
                        values.astype(jnp.float32),
                        bootstrap.astype(jnp.float32),
                        clip_rho=clip_rho, clip_c=clip_c, bb=bb)
    return vs[:, :B], adv[:, :B]
