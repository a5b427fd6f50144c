"""Model-component correctness: attention variants vs naive oracle, MoE
sort-dispatch vs dense oracle, mamba chunked scan vs per-step scan."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels.flash_attention.ref import attention_ref
from repro.models.attention import (causal_attention, local_attention,
                                    flash_block_attention)


def _qkv(rng, B, S, KVH, G, D):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, KVH, G, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    return q, k, v


def _to_ref(qg):
    B, S, KVH, G, D = qg.shape
    return qg.transpose(0, 2, 3, 1, 4).reshape(B, KVH * G, S, D)


@pytest.mark.parametrize("S,nq,bk", [(64, 4, 16), (100, 8, 32),
                                     (256, 2, 128)])
def test_causal_attention_matches_naive(S, nq, bk, rng):
    B, KVH, G, D = 2, 2, 2, 16
    q, k, v = _qkv(rng, B, S, KVH, G, D)
    o = causal_attention(q, k, v, jnp.int32(0), n_q_chunks=nq, block_k=bk)
    r = attention_ref(_to_ref(q), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=True)
    r = r.reshape(B, KVH, G, S, D).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,w", [(64, 16), (128, 32), (96, 32)])
def test_local_attention_matches_naive(S, w, rng):
    B, KVH, G, D = 1, 1, 4, 16
    q, k, v = _qkv(rng, B, S, KVH, G, D)
    o = local_attention(q, k, v, jnp.int32(0), window=w)
    r = attention_ref(_to_ref(q), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=True, window=w)
    r = r.reshape(B, KVH, G, S, D).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


def test_flash_block_attention_valid_len(rng):
    """kv_valid_len masks trailing cache slots."""
    B, S, KVH, G, D = 1, 8, 1, 1, 16
    q, k, v = _qkv(rng, B, S, KVH, G, D)
    o_full = flash_block_attention(q, k[:, :6], v[:, :6],
                                   jnp.arange(S), 0, causal=False,
                                   window=0, block_k=8)
    o_mask = flash_block_attention(q, k, v, jnp.arange(S), 0,
                                   causal=False, window=0, block_k=8,
                                   kv_valid_len=6)
    np.testing.assert_allclose(o_full, o_mask, atol=1e-5)


# ----------------------------------------------------------------- MoE
def test_moe_sort_dispatch_matches_dense_oracle(rng):
    from repro.models.moe import init_moe, apply_moe, \
        apply_moe_dense_oracle
    cfg = get_config("deepseek-moe-16b").reduced()
    p = init_moe(cfg, rng)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 24, cfg.d_model))
    out, aux = apply_moe(cfg, p, x)
    ref = apply_moe_dense_oracle(cfg, p, x)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)
    assert float(aux) > 0


# ---------------------------------------------------------------- mamba
def test_mamba_chunked_matches_step_scan(rng):
    from repro.models.mamba import ssm_scan_chunked
    B, T, di, N = 2, 40, 8, 4
    ks = jax.random.split(rng, 4)
    u = jax.random.normal(ks[0], (B, T, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, di)))
    Bm = jax.random.normal(ks[2], (B, T, N))
    Cm = jax.random.normal(ks[3], (B, T, N))
    A = -jnp.exp(jax.random.normal(rng, (di, N)) * 0.3)
    h0 = jnp.zeros((B, di, N))
    y_c, s_c = ssm_scan_chunked(u, dt, Bm, Cm, A, h0, chunk=8)
    # per-step oracle
    def step(h, xs):
        ut, dtt, bt, ct = xs
        a = jnp.exp(dtt[:, :, None] * A)
        h = a * h + (dtt * ut)[:, :, None] * bt[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, ct)
    xs = (u.transpose(1, 0, 2), dt.transpose(1, 0, 2),
          Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2))
    s_r, y_r = jax.lax.scan(step, h0, xs)
    np.testing.assert_allclose(y_c, y_r.transpose(1, 0, 2), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(s_c, s_r, atol=2e-4, rtol=1e-3)


def test_rwkv_decode_chain_matches_seq(rng):
    """Token-by-token chunk=1 decode equals one chunked pass."""
    from repro.models.rwkv6 import wkv_chunked
    B, T, H, N = 1, 12, 2, 8
    ks = jax.random.split(rng, 4)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) for i in range(3))
    logw = -jnp.exp(0.3 * jax.random.normal(ks[3], (B, T, H, N)))
    u = 0.2 * jnp.ones((H, N))
    S0 = jnp.zeros((B, H, N, N))
    y_all, _ = wkv_chunked(r, k, v, logw, u, S0, chunk=4)
    S = S0
    ys = []
    for t in range(T):
        y_t, S = wkv_chunked(r[:, t:t+1], k[:, t:t+1], v[:, t:t+1],
                             logw[:, t:t+1], u, S, chunk=1)
        ys.append(y_t)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_all, atol=2e-4,
                               rtol=1e-3)
