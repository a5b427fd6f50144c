"""Pallas-TPU reverse-scan kernel for GAE / n-step returns.

One kernel serves every advantage estimator reducible to the linear
recurrence `out_t = base_t + coef_t * out_{t+1}` (see ref.py): the
recursion is serial in T but embarrassingly parallel in batch, so the
grid (nb,) tiles the batch across cores while the whole (T, bb) block
sits in VMEM (same decomposition as kernels/vtrace). One fori_loop runs
the recursion, reading and writing one (1, bb) row of the VMEM blocks
per step through the refs (Mosaic lowers no dynamic slice of an
in-register array).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _kernel(base_ref, coef_ref, init_ref, out_ref, *, T):
    def step(i, acc):
        row = pl.ds(T - 1 - i, 1)
        acc = base_ref[row, :] + coef_ref[row, :] * acc
        out_ref[row, :] = acc
        return acc

    jax.lax.fori_loop(0, T, step, init_ref[...])


@functools.partial(jax.jit, static_argnames=("bb",))
def discounted_return_tb(base, coef, init, bb=128):
    """Inputs (T,B) f32 time-major, init (B,); B % bb == 0 (wrapper
    pads). Returns out (T,B) with out_t = base_t + coef_t*out_{t+1}."""
    T, B = base.shape
    nb = B // bb
    spec = pl.BlockSpec((T, bb), lambda ib: (0, ib))
    return pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid=(nb,),
        in_specs=[spec, spec, pl.BlockSpec((1, bb), lambda ib: (0, ib))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((T, B), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
    )(base, coef, init[None])
