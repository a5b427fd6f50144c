"""Pallas-TPU grouped matmul (per-expert matmul for MoE FFN).

Grid (E, nc, nf, nd): the contraction axis d is innermost/"arbitrary" with
an f32 VMEM accumulator; (expert, row-tile, col-tile) are parallel.
VMEM per step: bc*bd (x) + bd*bf (w) + bc*bf (acc) — defaults
128·512·4·3 ≈ 0.8 MiB. MXU-aligned tiles (multiples of 128)."""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _kernel(xref, wref, oref, accref, *, nd):
    idd = pl.program_id(3)

    @pl.when(idd == 0)
    def _init():
        accref[...] = jnp.zeros_like(accref)

    accref[...] += jax.lax.dot_general(
        xref[0].astype(jnp.float32), wref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(idd == nd - 1)
    def _fin():
        oref[0] = accref[...].astype(oref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bd"))
def gmm_ecd(x, w, *, bc=128, bf=128, bd=512):
    """x: (E,C,d); w: (E,d,f); C%bc==0, f%bf==0, d%bd==0 (wrapper pads)."""
    E, C, d = x.shape
    f = w.shape[-1]
    nc, nf, nd = C // bc, f // bf, d // bd
    kernel = functools.partial(_kernel, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, ic, if_, id_: (e, ic, id_)),
            pl.BlockSpec((1, bd, bf), lambda e, ic, if_, id_: (e, id_, if_)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf),
                               lambda e, ic, if_, id_: (e, ic, if_)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_mode(),
    )(x, w)
