"""Pallas-TPU chunked WKV-6 kernel.

Grid (B, H, nc) — chunk axis innermost/"arbitrary"; the (N,N) recurrent
state lives in VMEM scratch and is re-initialized whenever a new (b,h)
row starts (ic==0). Within a chunk, decay products are pairwise
exp(cum_t − cum_j) (differences of non-positive logs — no overflow), so
the intra-chunk part is dense matmul work for the MXU rather than a
length-T serial dependence; only the chunk boundary is sequential.
VMEM per step: 4·L·N inputs + L·L·N decay tensor + N·N state
(L=64, N=64 → ~1.3 MiB f32).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode


def _kernel(rref, kref, vref, wref, uref, yref, Sref, *, L, N):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        Sref[...] = jnp.zeros_like(Sref)

    r = rref[0, :, 0, :].astype(jnp.float32)        # (L,N)
    k = kref[0, :, 0, :].astype(jnp.float32)
    v = vref[0, :, 0, :].astype(jnp.float32)
    lw = wref[0, :, 0, :].astype(jnp.float32)
    u = uref[0].astype(jnp.float32)                 # (N,)
    S = Sref[...]

    c = jnp.cumsum(lw, axis=0)
    cprev = c - lw
    dmat = cprev[:, None, :] - c[None, :, :]        # (t, j, N) <= 0 for t>j
    tri = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    decay = jnp.where(tri[..., None], jnp.exp(dmat), 0.0)
    score = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)  # (t,j)
    sdiag = jnp.sum(r * u[None, :] * k, axis=-1)    # (t,)
    y = jax.lax.dot_general(score, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + sdiag[:, None] * v
    y = y + jax.lax.dot_general(r * jnp.exp(cprev), S,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    cl = c[-1]
    kd = k * jnp.exp(cl[None, :] - c)
    S_new = jnp.exp(cl)[:, None] * S + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    Sref[...] = S_new
    yref[0, :, 0, :] = y.astype(yref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_btHN(r, k, v, logw, u, *, chunk=64):
    """r,k,v,logw: (B,T,H,N); u: (H,N); T % chunk == 0 (wrapper pads).
    Zero initial state. Returns y (B,T,H,N) f32."""
    B, T, H, N = r.shape
    nc = T // chunk
    kernel = functools.partial(_kernel, L=chunk, N=N)
    spec = pl.BlockSpec((1, chunk, 1, N), lambda b, h, ic: (b, ic, h, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, N), lambda b, h, ic: (h, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(r, k, v, logw, u)
