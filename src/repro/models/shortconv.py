"""Gated short convolution (LFM2's `conv` layer), written from its
equations:

    B, C, h = split3(x W_in)                    (d -> 3d, no bias)
    y_t     = C_t * sum_j w_j * (B * h)_{t-(L-1)+j},   j = 0..L-1
    out     = y W_out

a depthwise causal convolution of kernel length L (`conv_cache`, no
bias) over the gated input B * h, gated again by C. The state is the
last L - 1 gated inputs: a prefill emits it and each decode step
consumes and updates it, so decoding needs no cache that grows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_conv(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    return {"in_proj": dense_init(k1, (d, 3 * d)),
            "conv_w": dense_init(k2, (cfg.conv_cache, d)),
            "out_proj": dense_init(k3, (d, d))}


def init_state(cfg, batch, dtype):
    return jnp.zeros((batch, cfg.conv_cache - 1, cfg.d_model), dtype)


def conv_seq(cfg, p, x, state=None):
    """x: (B, S, d); state: (B, L-1, d) gated inputs before x (zeros
    when None). Returns (out (B, S, d), state after x). A decode step is
    the same call at S = 1."""
    B, S, d = x.shape
    dt = x.dtype
    L = cfg.conv_cache
    if state is None:
        state = init_state(cfg, B, dt)
    with jax.named_scope("conv"):
        bch = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt))
        b, c, h = jnp.split(bch, 3, axis=-1)
        u = jnp.concatenate([state.astype(dt), b * h], axis=1)  # (B,S+L-1,d)
        w = p["conv_w"].astype(dt)
        y = sum(u[:, j:j + S] * w[j] for j in range(L))
        out = jnp.einsum("bsd,de->bse", c * y, p["out_proj"].astype(dt))
    return out, u[:, S:]
