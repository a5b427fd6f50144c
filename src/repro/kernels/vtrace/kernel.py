"""Pallas-TPU V-trace kernel.

The backward recursion is inherently serial in T, but embarrassingly
parallel in batch — grid (nb,) tiles the batch across cores while the
whole (T, bb) trajectory block sits in VMEM (T≤2048, bb=128 → ~4 MiB for
the four inputs). One fori_loop runs the recursion, reading one (1, bb)
row per input and writing one row per output through the refs each
step. The loop carries V_{t+1} and vs_{t+1} (both V_T at the start), so
the policy-gradient advantage is formed in the same step as vs_t.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _kernel(rho_ref, disc_ref, rew_ref, val_ref, boot_ref,
            vs_ref, adv_ref, *, T, clip_rho, clip_c):
    def step(i, carry):
        acc, v_tp1, vs_tp1 = carry
        row = pl.ds(T - 1 - i, 1)
        w = jnp.exp(rho_ref[row, :])
        rho = jnp.minimum(clip_rho, w)
        c = jnp.minimum(clip_c, w)
        disc = disc_ref[row, :]
        rew = rew_ref[row, :]
        val = val_ref[row, :]
        delta = rho * (rew + disc * v_tp1 - val)
        acc = delta + disc * c * acc
        vs = val + acc
        vs_ref[row, :] = vs
        adv_ref[row, :] = rho * (rew + disc * vs_tp1 - val)
        return acc, val, vs

    boot = boot_ref[...]                                   # (1,bb)
    jax.lax.fori_loop(0, T, step, (jnp.zeros_like(boot), boot, boot))


@functools.partial(jax.jit, static_argnames=("clip_rho", "clip_c", "bb"))
def vtrace_tb(log_rhos, discounts, rewards, values, bootstrap,
              clip_rho=1.0, clip_c=1.0, bb=128):
    """Inputs (T,B) f32 time-major, bootstrap (B,); B % bb == 0
    (wrapper pads). Returns (vs, pg_adv)."""
    T, B = log_rhos.shape
    nb = B // bb
    kernel = functools.partial(_kernel, T=T, clip_rho=clip_rho,
                               clip_c=clip_c)
    spec = pl.BlockSpec((T, bb), lambda ib: (0, ib))
    vs, adv = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, bb), lambda ib: (0, ib))],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((T, B), jnp.float32),
                   jax.ShapeDtypeStruct((T, B), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
    )(log_rhos, discounts, rewards, values, bootstrap[None])
    return vs, adv
