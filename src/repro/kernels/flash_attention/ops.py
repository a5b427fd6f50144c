"""Jit'd public wrapper matching the model's (B,S,KVH,G,D) layout.

It transposes to the kernel's (B, H, S, D). A sequence that fits one
block (S rounded up to 8 is at most bq and bk) runs unpadded on the
kernel's short grid, a step per bb samples with all heads; a longer one
is padded to a multiple of bq and runs on the tiled (B, H, nq, nk) grid
(see kernel.py).

`flash_attention` is differentiable: its forward pass is the Pallas
kernel, and its backward pass (a `jax.custom_vjp`) recomputes the
attention through `jax.vjp` of the pure-jnp oracle `attention_ref`, so
the backward pass materializes the (S, S) score matrix. A Pallas
backward kernel would remove that.
"""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_hsd
from repro.kernels.flash_attention.ref import attention_ref_grouped


def flash_attention(qg, k, v, *, causal=True, window=0, bq=128, bk=128):
    """qg: (B,S,KVH,G,D); k,v: (B,S,KVH,D). Returns (B,S,KVH,G,D)."""
    return _flash(qg, k, v, causal, window, bq, bk)


def _flash_fwd_kernel(qg, k, v, causal, window, bq, bk):
    B, S, KVH, G, D = qg.shape
    # a sequence that fits one block (the trunk's feature mode attends
    # over a handful of positions) is the block: no padding
    tile = -(-S // 8) * 8
    bq, bk = min(bq, tile), min(bk, tile)
    if bq == bk == tile:
        bq = bk = S
    q = qg.transpose(0, 2, 3, 1, 4).reshape(B, KVH * G, S, D)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    pad = (-S) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    o = flash_attention_hsd(q, kk, vv, causal=causal, window=window,
                            bq=bq, bk=bk,
                            valid_len=S if pad else None)
    o = o[:, :, :S]
    return o.reshape(B, KVH, G, S, D).transpose(0, 3, 1, 2, 4)


_flash = jax.custom_vjp(_flash_fwd_kernel, nondiff_argnums=(3, 4, 5, 6))


def _fwd(qg, k, v, causal, window, bq, bk):
    return _flash_fwd_kernel(qg, k, v, causal, window, bq, bk), (qg, k, v)


def _bwd(causal, window, bq, bk, res, g):
    ref = functools.partial(attention_ref_grouped, causal=causal,
                            window=window)
    _, vjp = jax.vjp(ref, *res)
    return vjp(g)


_flash.defvjp(_fwd, _bwd)
