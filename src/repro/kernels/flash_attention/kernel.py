"""Pallas-TPU flash attention (causal / sliding-window, GQA-aware).

Grid (B, H, nq, nk); the kv axis is the innermost ("arbitrary") dimension
— online-softmax running stats (m, l, acc) live in VMEM scratch and the
output tile is finalized on the last kv step. BlockSpec tiling keeps the
working set at  bq*D + bk*D (k) + bk*D (v) + bq*bk (scores)  in VMEM;
default bq=bk=128 and D<=256 stays well under 16 MiB. The kv-head
index_map folds GQA (q head h reads kv head h//G) so grouped K/V are
never materialized per-head.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

NEG_INF = -1e30


def _kernel(qref, kref, vref, oref, mref, lref, accref, *,
            bq, bk, nk, causal, window, scale, valid_len):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        mref[...] = jnp.full_like(mref, NEG_INF)
        lref[...] = jnp.zeros_like(lref)
        accref[...] = jnp.zeros_like(accref)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    run = True
    if causal:  # skip fully-masked upper-triangle blocks
        run = (ik * bk) <= (iq * bq + bq - 1)
    if window:
        run = jnp.logical_and(run, (ik + 1) * bk - 1
                              > iq * bq - window)
    if valid_len is not None:  # skip blocks entirely past the real tail
        run = jnp.logical_and(run, (ik * bk) < valid_len)

    @pl.when(run)
    def _compute():
        q = qref[0, 0].astype(jnp.float32) * scale
        k = kref[0, 0].astype(jnp.float32)
        v = vref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask = mask & (kpos <= qpos)
        if window:
            mask = mask & (kpos > qpos - window)
        if valid_len is not None:  # zero-padded keys must not be attended
            mask = mask & (kpos < valid_len)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = mref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        lref[...] = lref[...] * alpha + p.sum(axis=-1)
        accref[...] = accref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        mref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(lref[...], 1e-30)
        oref[0, 0] = (accref[...] / l[:, None]).astype(oref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "valid_len"))
def flash_attention_hsd(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                        valid_len=None):
    """q: (B,H,S,D); k,v: (B,KVH,S,D), S % bq == 0 (wrapper pads).
    `valid_len` (static) masks key positions >= valid_len so a
    zero-padded tail is never attended — required for correctness when
    the wrapper pads a non-causal (or any) input."""
    B, H, S, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    nq, nk = S // bq, S // bk
    scale = D ** -0.5
    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                               window=window, scale=scale,
                               valid_len=valid_len)
    scratch = [pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq, D), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_mode(),
    )(q, k, v)
    return out
