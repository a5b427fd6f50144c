"""Per-layer block: token mixer (attn/local/MLA/RWKV/Mamba/conv) + channel
mixer (dense SwiGLU or MoE), pre-norm residual. Whisper decoder blocks add
cross-attention. One entry point per execution mode."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ATTN_LOCAL, MLA, RWKV, MAMBA, CONV
from repro.models import attention as attn
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import shortconv
from repro.models.layers import (init_norm, apply_norm, init_mlp, apply_mlp,
                                 init_mlp_gelu, apply_mlp_gelu)


def init_block(cfg, key, kind: str, is_moe: bool, has_cross: bool = False,
               gelu_mlp: bool = False):
    ks = jax.random.split(key, 6)
    p = {"norm1": init_norm(cfg)}
    if kind in (ATTN, ATTN_LOCAL):
        p["mixer"] = attn.init_attn(cfg, ks[0], kind)
    elif kind == MLA:
        p["mixer"] = attn.init_attn(cfg, ks[0], MLA)
    elif kind == RWKV:
        p["mixer"] = rwkv_mod.init_rwkv(cfg, ks[0])
        p["norm2"] = init_norm(cfg)
        return p  # rwkv channel-mix params live inside the mixer
    elif kind == MAMBA:
        p["mixer"] = mamba_mod.init_mamba(cfg, ks[0])
    elif kind == CONV:
        p["mixer"] = shortconv.init_conv(cfg, ks[0])
    else:
        raise ValueError(kind)
    if has_cross:
        p["xnorm"] = init_norm(cfg)
        p["xattn"] = attn.init_cross_attn(cfg, ks[1])
    p["norm2"] = init_norm(cfg)
    if is_moe:
        p["ffn"] = moe_mod.init_moe(cfg, ks[2])
    elif gelu_mlp:
        p["ffn"] = init_mlp_gelu(cfg, ks[2])
    else:
        p["ffn"] = init_mlp(cfg, ks[2])
    return p


def init_cache(cfg, kind: str, batch: int, capacity: int, dtype,
               has_cross: bool = False, enc_tokens: int = 0):
    """Zero/empty cache entry for one layer."""
    H, KVH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if kind in (ATTN, ATTN_LOCAL):
        C = min(capacity, cfg.window) if kind == ATTN_LOCAL else capacity
        c = {"k": jnp.zeros((batch, C, KVH, D), dtype),
             "v": jnp.zeros((batch, C, KVH, D), dtype)}
    elif kind == MLA:
        c = {"ckv": jnp.zeros((batch, capacity, cfg.kv_lora_rank), dtype),
             "kr": jnp.zeros((batch, capacity, cfg.rope_head_dim), dtype)}
    elif kind == RWKV:
        c = {"S": jnp.zeros((batch, H, D, D), jnp.float32),
             "shift_tm": jnp.zeros((batch, cfg.d_model), dtype),
             "shift_cm": jnp.zeros((batch, cfg.d_model), dtype)}
    elif kind == MAMBA:
        di = cfg.ssm_expand * cfg.d_model
        c = {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, di), dtype),
             "ssm": jnp.zeros((batch, di, cfg.ssm_state), jnp.float32)}
    elif kind == CONV:
        c = {"conv": shortconv.init_state(cfg, batch, dtype)}
    else:
        raise ValueError(kind)
    if has_cross:
        c["ek"] = jnp.zeros((batch, enc_tokens, KVH, D), dtype)
        c["ev"] = jnp.zeros((batch, enc_tokens, KVH, D), dtype)
    return c


def _cross_kv(cfg, p, enc_out):
    dt = enc_out.dtype
    ek = jnp.einsum("btd,dhk->bthk", enc_out, p["xattn"]["wk"].astype(dt))
    ev = jnp.einsum("btd,dhk->bthk", enc_out, p["xattn"]["wv"].astype(dt))
    return ek, ev


def apply_block_seq(cfg, p, kind, is_moe, x, pos0, opts, *,
                    cache_capacity=0, enc_out=None, cache_in=None,
                    gelu_mlp=False, causal=True):
    """Train (cache_capacity=0) / prefill (>0) path. Returns
    (x, cache, aux_loss). `cache_in` supplies initial recurrent states
    (zeros when None)."""
    B = x.shape[0]
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    eps = cfg.norm_eps
    h = apply_norm(p["norm1"], x, eps)
    if kind == RWKV:
        st = cache_in or init_cache(cfg, RWKV, B, 0, x.dtype)
        o, tm = rwkv_mod.rwkv_time_mix_seq(
            cfg, p["mixer"], h, {"S": st["S"], "shift": st["shift_tm"]})
        x = x + o
        h2 = apply_norm(p["norm2"], x, eps)
        o2, shift_cm = rwkv_mod.rwkv_channel_mix(cfg, p["mixer"], h2,
                                                 st["shift_cm"])
        x = x + o2
        if cache_capacity:
            cache = {"S": tm["S"], "shift_tm": tm["shift"],
                     "shift_cm": shift_cm}
        return x, cache, aux
    if kind == MAMBA:
        st = cache_in or init_cache(cfg, MAMBA, B, 0, x.dtype)
        o, new_st = mamba_mod.mamba_seq(cfg, p["mixer"], h, st)
        if cache_capacity:
            cache.update(new_st)
    elif kind == CONV:
        o, st = shortconv.conv_seq(cfg, p["mixer"], h,
                                   cache_in["conv"] if cache_in else None)
        if cache_capacity:
            cache["conv"] = st
    elif kind == MLA:
        o, c = attn.mla_seq(cfg, p["mixer"], h, pos0, opts,
                            cache_capacity=cache_capacity)
        if c:
            cache.update(c)
    else:
        o, c = attn.gqa_seq(cfg, p["mixer"], h, pos0, kind, opts,
                            cache_capacity=cache_capacity, causal=causal)
        if c:
            cache.update(c)
    x = x + o
    if enc_out is not None and "xattn" in p:
        hx = apply_norm(p["xnorm"], x, eps)
        ek, ev = _cross_kv(cfg, p, enc_out)
        ox, _ = attn.gqa_seq(cfg, p["xattn"], hx, pos0, ATTN, opts,
                             cross_kv=(ek, ev))
        x = x + ox
        if cache_capacity:
            cache["ek"], cache["ev"] = ek, ev
    h2 = apply_norm(p["norm2"], x, eps)
    if is_moe:
        o2, aux = moe_mod.apply_moe(cfg, p["ffn"], h2)
    elif gelu_mlp:
        o2 = apply_mlp_gelu(p["ffn"], h2)
    else:
        o2 = apply_mlp(p["ffn"], h2)
    return x + o2, cache, aux


def apply_block_decode(cfg, p, kind, is_moe, x, cache, pos, opts,
                       gelu_mlp=False):
    """One-token decode. Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.norm_eps
    h = apply_norm(p["norm1"], x, eps)
    new_cache = dict(cache)
    if kind == RWKV:
        o, tm = rwkv_mod.rwkv_time_mix_seq(
            cfg, p["mixer"], h, {"S": cache["S"],
                                 "shift": cache["shift_tm"]}, chunk=1)
        x = x + o
        h2 = apply_norm(p["norm2"], x, eps)
        o2, shift_cm = rwkv_mod.rwkv_channel_mix(cfg, p["mixer"], h2,
                                                 cache["shift_cm"])
        new_cache = {"S": tm["S"], "shift_tm": tm["shift"],
                     "shift_cm": shift_cm}
        return x + o2, new_cache, aux
    if kind == MAMBA:
        o, st = mamba_mod.mamba_decode(
            cfg, p["mixer"], h, {"conv": cache["conv"],
                                 "ssm": cache["ssm"]})
        new_cache.update(st)
    elif kind == CONV:
        o, new_cache["conv"] = shortconv.conv_seq(cfg, p["mixer"], h,
                                                  cache["conv"])
    elif kind == MLA:
        o, c = attn.mla_decode(cfg, p["mixer"], h,
                               {"ckv": cache["ckv"], "kr": cache["kr"]},
                               pos, opts)
        new_cache.update(c)
    else:
        o, c = attn.gqa_decode(cfg, p["mixer"], h,
                               {"k": cache["k"], "v": cache["v"]},
                               pos, kind, opts)
        new_cache.update(c)
    x = x + o
    if "xattn" in p and "ek" in cache:
        hx = apply_norm(p["xnorm"], x, eps)
        ox, _ = attn.gqa_decode(cfg, p["xattn"], hx, None, pos, ATTN, opts,
                                cross_kv=(cache["ek"], cache["ev"]))
        x = x + ox
    h2 = apply_norm(p["norm2"], x, eps)
    if is_moe:
        o2, aux = moe_mod.apply_moe(cfg, p["ffn"], h2)
    elif gelu_mlp:
        o2 = apply_mlp_gelu(p["ffn"], h2)
    else:
        o2 = apply_mlp(p["ffn"], h2)
    return x + o2, new_cache, aux
