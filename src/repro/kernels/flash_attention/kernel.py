"""Pallas-TPU flash attention (causal / sliding-window, GQA-aware).

Two grids; `plan` picks one from the shapes alone.

Tiled, when the sequence spans more than one q or kv block: grid
(B, H, nq, nk); the kv axis is the innermost ("arbitrary") dimension
— online-softmax running stats (m, l, acc) live in VMEM scratch and the
output tile is finalized on the last kv step. BlockSpec tiling keeps the
working set at  bq*D + bk*D (k) + bk*D (v) + bq*bk (scores)  in VMEM;
default bq=bk=128 and D<=256 stays well under 16 MiB. The kv-head
index_map folds GQA (q head h reads kv head h//G) so grouped K/V are
never materialized per-head.

Short, when the whole sequence fits one q block and one kv block (S <=
bq and S <= bk; the trunk's feature mode attends over S = obs_dim
positions): grid (ceil(B / bb),). A step reads bb whole sequences with
all H query heads and their KVH kv heads, blocks (bb, H, S, D) and
(bb, KVH, S, D) with S unpadded (a block dim equal to the array's is
legal), and scores each head with one batched product over the bb
samples. A plain masked softmax replaces the online one: there is one
kv block. On the tiled grid such a sequence would cost a grid step
(~0.6 us on v5e) per sample and head, however little each step does.

bb is the most samples that divide B and whose blocks fit
SHORT_VMEM_BYTES: q and o at H heads, k and v at KVH heads, each (S, D)
slab counted in whole (8, 128) tiles of 4-byte types ((16, 128) for
2-byte; a (4, 64) float32 slab as 4 KiB, not 1 KiB) and every block
double-buffered by the pipeline. That keeps a quarter of v5e's 16 MiB
default scoped VMEM for the scores and products; at S=4, H=4, KVH=2,
D=64 float32, bb = 128, and the serving buckets (B = 1, 4, 16) take one
step; at H=32, KVH=8 (LFM2) bb = 16 for B = 32 and 1,024. No block runs
past B, so no step reads or writes outside the arrays.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

NEG_INF = -1e30
SHORT_VMEM_BYTES = 12 * 2 ** 20  # double-buffered blocks of a short step


def plan(B, H, KVH, S, D, *, bq=128, bk=128, itemsize=4):
    """-> (grid, bb): the grid `flash_attention_hsd` runs for these
    shapes and, on the short grid, the samples a step (None on the
    tiled one). See the module docstring."""
    if S > bq or S > bk:
        return (B, H, S // bq, S // bk), None
    sublanes = 32 // itemsize  # (8, 128) tiles for 4-byte types
    slab = -(-S // sublanes) * sublanes * (-(-D // 128) * 128) * itemsize
    per_sample = 2 * (2 * H + 2 * KVH) * slab  # double-buffered
    cap = max(1, SHORT_VMEM_BYTES // per_sample)
    bb = max(n for n in range(1, min(B, cap) + 1) if B % n == 0)
    return (B // bb,), bb


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


def _short_kernel(qref, kref, vref, oref, *, G, causal, window, scale,
                  valid_len):
    _, H, S, _ = qref.shape
    qpos = jax.lax.broadcasted_iota(jnp.int32, (1, S, S), 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, S, S), 2)
    mask = jnp.ones((1, S, S), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window:
        mask = mask & (kpos > qpos - window)
    if valid_len is not None:  # zero-padded keys must not be attended
        mask = mask & (kpos < valid_len)
    for h in range(H):
        q = qref[:, h].astype(jnp.float32) * scale
        k = kref[:, h // G].astype(jnp.float32)
        v = vref[:, h // G].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        o = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        oref[:, h] = (o / p.sum(axis=-1, keepdims=True)).astype(oref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "valid_len"))
def flash_attention_hsd(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                        valid_len=None):
    """q: (B,H,S,D); k,v: (B,KVH,S,D). S <= bq and S <= bk runs the
    short grid at any S; a longer S needs S % bq == S % bk == 0 (the
    wrapper pads). `valid_len` (static) masks key positions >=
    valid_len so a zero-padded tail is never attended — required for
    correctness when the wrapper pads a non-causal (or any) input."""
    B, H, S, D = q.shape
    KVH = k.shape[1]
    G = H // KVH
    scale = D ** -0.5
    grid, bb = plan(B, H, KVH, S, D, bq=bq, bk=bk,
                    itemsize=q.dtype.itemsize)
    if bb is not None:
        kernel = functools.partial(_short_kernel, G=G, causal=causal,
                                   window=window, scale=scale,
                                   valid_len=valid_len)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bb, H, S, D), lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((bb, KVH, S, D), lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((bb, KVH, S, D), lambda i: (i, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((bb, H, S, D), lambda i: (i, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret_mode(),
        )(q, k, v)
    nk = grid[3]
    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                               window=window, scale=scale,
                               valid_len=valid_len)
    scratch = [pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq, D), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
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
