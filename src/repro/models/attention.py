"""Attention token mixers: GQA (full + sliding-window) and MLA.

Three execution paths per mixer:
  * seq (train / prefill): full-sequence causal attention, computed with a
    memory-bounded blockwise online-softmax ("flash" in pure jnp — the
    Pallas kernel in repro.kernels.flash_attention is the TPU version and
    is validated against the same oracle). The causal quadratic is chunked
    over the query axis in a *static python loop* so each chunk only ever
    lowers matmuls against its own prefix — keeping HLO FLOPs within ~6%
    of the true causal cost (important for the roofline terms).
  * local (sliding window): exact banded block attention — O(S*w) compute.
  * decode: one query token against a KV cache (ring buffer for local
    layers so a 500k context only needs a `window`-sized cache).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ATTN_LOCAL, MLA
from repro.models.layers import dense_init, apply_rope, init_norm, apply_norm

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnOpts:
    dtype: jnp.dtype = jnp.bfloat16
    block_k: int = 512       # kv block for online softmax
    n_q_chunks: int = 8      # static causal query chunks
    use_kernels: bool = False  # route seq attention through Pallas


# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------

def init_attn(cfg, key, kind: str):
    ks = jax.random.split(key, 8)
    hd = cfg.head_dim
    if kind == MLA:
        rq = cfg.q_lora_rank or cfg.d_model
        rkv = cfg.kv_lora_rank
        hr = cfg.rope_head_dim
        p = {
            "wdq": dense_init(ks[0], (cfg.d_model, rq)),
            "q_norm": {"scale": jnp.ones((rq,), jnp.float32)},
            "wuq": dense_init(ks[1], (rq, cfg.n_heads, hd)),
            "wqr": dense_init(ks[2], (rq, cfg.n_heads, hr)),
            "wdkv": dense_init(ks[3], (cfg.d_model, rkv)),
            "kv_norm": {"scale": jnp.ones((rkv,), jnp.float32)},
            "wkr": dense_init(ks[4], (cfg.d_model, hr)),
            "wuk": dense_init(ks[5], (rkv, cfg.n_heads, hd)),
            "wuv": dense_init(ks[6], (rkv, cfg.n_heads, hd)),
            "wo": dense_init(ks[7], (cfg.n_heads, hd, cfg.d_model)),
        }
        return p
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads, hd)),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads, hd)),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads, hd)),
        "wo": dense_init(ks[3], (cfg.n_heads, hd, cfg.d_model)),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def init_cross_attn(cfg, key):
    """Whisper decoder cross-attention (same shapes as MHA)."""
    return init_attn(cfg, key, ATTN)


# ---------------------------------------------------------------------------
# Blockwise online-softmax attention core (pure jnp "flash")
# ---------------------------------------------------------------------------

def emit_ring(k, C):
    """Lay out per-position entries k (B,S,...) into a ring cache of
    capacity C such that position p sits in slot p % C. Requires C >= S
    (pad right) or S % C == 0 (keep last C — slots align)."""
    S = k.shape[1]
    if C >= S:
        widths = [(0, 0)] * k.ndim
        widths[1] = (0, C - S)
        return jnp.pad(k, widths)
    assert S % C == 0, f"ring cache needs S%C==0, got S={S} C={C}"
    return k[:, -C:]


def _pad_axis(x, axis, to_multiple):
    n = x.shape[axis]
    pad = (-n) % to_multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def flash_block_attention(q, k, v, q_pos, kv_pos0, *, causal: bool,
                          window: int, block_k: int, kv_valid_len=None):
    """q: (B,Sq,KVH,G,D) k/v: (B,T,KVH,Dk|Dv); returns (B,Sq,KVH,G,Dv).

    kv positions are kv_pos0 + arange(T); entries at index >= kv_valid_len
    (a traced scalar or None) are masked out. Online softmax over kv
    blocks via lax.scan keeps live memory at one (…, Sq, block_k) tile.
    """
    B, Sq, KVH, G, D = q.shape
    Dv = v.shape[-1]
    scale = D ** -0.5
    k, T0 = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    T = k.shape[1]
    nk = T // block_k
    kpos = kv_pos0 + jnp.arange(T)
    if kv_valid_len is None:
        kv_valid = jnp.arange(T) < T0
    else:
        kv_valid = jnp.arange(T) < kv_valid_len

    kb = k.reshape(B, nk, block_k, KVH, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, block_k, KVH, Dv).transpose(1, 0, 2, 3, 4)
    kposb = kpos.reshape(nk, block_k)
    kvalb = kv_valid.reshape(nk, block_k)

    qf = q.astype(jnp.float32) * scale

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, kp, kval = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kblk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        mask = kval[None, :]
        if causal:
            mask = mask & (kp[None, :] <= q_pos[:, None])
        if window:
            mask = mask & (kp[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, KVH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kb, vb, kposb, kvalb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,Sq,KVH,G,Dv)


def causal_attention(q, k, v, pos0, *, n_q_chunks: int, block_k: int):
    """Exact-ish causal full attention, q:(B,S,KVH,G,D) k,v:(B,S,KVH,D).

    Static python loop over query chunks; chunk i only multiplies against
    its own static kv prefix — HLO flops ≈ true causal flops (overcount
    bounded by 1/(2*n_q_chunks))."""
    B, S, KVH, G, D = q.shape
    nq = max(1, min(n_q_chunks, S // max(1, min(block_k, S))))
    cs = -(-S // nq)  # ceil
    outs = []
    for i in range(nq):
        lo, hi = i * cs, min((i + 1) * cs, S)
        if lo >= S:
            break
        qc = q[:, lo:hi]
        qpos = pos0 + jnp.arange(lo, hi)
        kv_hi = hi  # causal prefix
        o = flash_block_attention(
            qc, k[:, :kv_hi], v[:, :kv_hi], qpos, pos0,
            causal=True, window=0, block_k=min(block_k, kv_hi))
        outs.append(o)
    return jnp.concatenate(outs, axis=1)


def local_attention(q, k, v, pos0, *, window: int):
    """Exact banded sliding-window attention, O(S*window).

    Reshape the sequence into blocks of `window`; each query block attends
    to [previous block ‖ own block] with the in-window mask."""
    B, S, KVH, G, D = q.shape
    w = window
    q, S0 = _pad_axis(q, 1, w)
    k, _ = _pad_axis(k, 1, w)
    v, _ = _pad_axis(v, 1, w)
    S = q.shape[1]
    nb = S // w
    qb = q.reshape(B, nb, w, KVH, G, D)
    kb = k.reshape(B, nb, w, KVH, D)
    vb = v.reshape(B, nb, w, KVH, D)
    # previous block (block -1 is zeros, fully masked out by position)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (B,nb,2w,KVH,D)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    scale = D ** -0.5
    s = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qb.astype(jnp.float32) * scale,
                   k2.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    qpos = jnp.arange(S).reshape(nb, w)                       # (nb,w)
    kpos = (jnp.arange(2 * w)[None] - w) + (jnp.arange(nb) * w)[:, None]
    valid = (kpos[:, None, :] <= qpos[..., None]) \
        & (kpos[:, None, :] > qpos[..., None] - w) \
        & (kpos[:, None, :] >= 0) & (kpos[:, None, :] < S0) \
        & (qpos[..., None] < S0)
    s = jnp.where(valid[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnhgqk,bnkhd->bnqhgd", p, v2.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, S, KVH, G, D)[:, :S0]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA mixer
# ---------------------------------------------------------------------------

def _qkv(cfg, p, x):
    """Projections, with each head's q and k RMS-normed (before rope)
    where the config has q/k norm."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "q_norm" in p:
        q = apply_norm(p["q_norm"], q, cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def gqa_seq(cfg, p, x, pos0, kind, opts: AttnOpts, cache_capacity=0,
            cross_kv=None, causal=True):
    """Full-sequence GQA. Returns (out, cache) — cache sized
    `cache_capacity` (0 = no cache emitted, train mode)."""
    B, S, _ = x.shape
    H, KVH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KVH
    q, k, v = _qkv(cfg, p, x)
    if cross_kv is not None:
        ek, ev = cross_kv  # (B,Te,KVH,D) — whisper cross attention
        qg = q.reshape(B, S, KVH, G, D)
        o = flash_block_attention(qg, ek, ev, jnp.zeros((S,), jnp.int32),
                                  jnp.array(0), causal=False, window=0,
                                  block_k=min(opts.block_k, ek.shape[1]))
        o = o.reshape(B, S, H, D)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
        return out, None
    positions = pos0 + jnp.arange(S)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    qg = q.reshape(B, S, KVH, G, D)
    if not causal:  # encoder self-attention — single non-causal pass
        o = flash_block_attention(qg, k, v, positions, pos0, causal=False,
                                  window=0, block_k=min(opts.block_k, S))
    elif kind == ATTN_LOCAL:
        o = local_attention(qg, k, v, pos0, window=cfg.window)
    elif opts.use_kernels:
        # core dispatcher: Pallas flash attention on TPU, the shared
        # ref oracle elsewhere (interpret-mode Pallas is orders of
        # magnitude slower than the oracle on CPU)
        from repro.core.attention import attention as core_attention
        o = core_attention(qg, k, v, causal=True, use_kernel=True)
    else:
        o = causal_attention(qg, k, v, pos0, n_q_chunks=opts.n_q_chunks,
                             block_k=opts.block_k)
    o = o.reshape(B, S, H, D)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    cache = None
    if cache_capacity:
        C = cache_capacity
        if kind == ATTN_LOCAL:
            C = min(C, cfg.window)
        cache = {"k": emit_ring(k, C), "v": emit_ring(v, C)}
    return out, cache


def gqa_decode(cfg, p, x, cache, pos, kind, opts: AttnOpts,
               cross_kv=None):
    """One-token decode. x: (B,1,d); cache {'k','v'}: (B,C,KVH,D); pos:
    scalar int32 — position of this token. Ring-buffer write at pos % C.
    Assumes the cache is full (pos >= C), true for the assigned decode
    shapes (cache length == context length)."""
    B = x.shape[0]
    H, KVH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KVH
    dt = x.dtype
    q, k, v = _qkv(cfg, p, x)
    if cross_kv is not None:
        ek, ev = cross_kv
        s = jnp.einsum("bohk,bthk->bhot", q.reshape(B, 1, H, D) * D**-0.5,
                       jnp.repeat(ek, G, axis=2).astype(dt))
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
        o = jnp.einsum("bhot,bthk->bohk", w, jnp.repeat(ev, G, axis=2))
        out = jnp.einsum("bohk,hkd->bod", o, p["wo"].astype(dt))
        return out, cache
    q = apply_rope(q, pos[None] if pos.ndim == 0 else pos, cfg.rope_theta)
    k = apply_rope(k, pos[None] if pos.ndim == 0 else pos, cfg.rope_theta)
    C = cache["k"].shape[1]
    slot = (pos % C).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    qg = q.reshape(B, 1, KVH, G, D).astype(jnp.float32) * D**-0.5
    s = jnp.einsum("bqhgd,bthd->bhgqt", qg, ck.astype(jnp.float32),
                   preferred_element_type=jnp.float32)  # (B,KVH,G,1,C)
    if kind == ATTN_LOCAL:
        pass  # ring holds exactly the window — everything valid
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqt,bthd->bqhgd", w, cv.astype(jnp.float32))
    o = o.reshape(B, 1, H, D).astype(dt)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA mixer (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------

def _mla_q(cfg, p, x):
    dt = x.dtype
    cq = jnp.einsum("bsd,dr->bsr", x, p["wdq"].astype(dt))
    cq = apply_norm(p["q_norm"], cq)
    q_nope = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"].astype(dt))
    q_rope = jnp.einsum("bsr,rhk->bshk", cq, p["wqr"].astype(dt))
    return q_nope, q_rope


def _mla_latents(cfg, p, x, positions):
    dt = x.dtype
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(dt))
    ckv = apply_norm(p["kv_norm"], ckv)
    kr = jnp.einsum("bsd,dk->bsk", x, p["wkr"].astype(dt))
    kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return ckv, kr


def mla_seq(cfg, p, x, pos0, opts: AttnOpts, cache_capacity=0):
    """Full-sequence MLA: expand latents to per-head K/V and reuse the
    causal flash path (q/k concat [nope‖rope])."""
    B, S, _ = x.shape
    H, D, HR = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    dt = x.dtype
    positions = pos0 + jnp.arange(S)
    q_nope, q_rope = _mla_q(cfg, p, x)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv, kr = _mla_latents(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wuk"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["wuv"].astype(dt))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)            # (B,S,H,D+HR)
    k = jnp.concatenate([k_nope, jnp.repeat(kr[:, :, None], H, 2)], axis=-1)
    qg = q.reshape(B, S, H, 1, D + HR)
    o = causal_attention(qg, k, v, pos0, n_q_chunks=opts.n_q_chunks,
                         block_k=opts.block_k)
    o = o.reshape(B, S, H, D)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    cache = None
    if cache_capacity:
        C = cache_capacity
        cache = {"ckv": emit_ring(ckv, C), "kr": emit_ring(kr, C)}
    return out, cache


def mla_decode(cfg, p, x, cache, pos, opts: AttnOpts):
    """Absorbed-matmul MLA decode: score against the compressed latent
    cache directly — the cache per token is only (r_kv + rope_dim)."""
    B = x.shape[0]
    H, D, HR = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    dt = x.dtype
    q_nope, q_rope = _mla_q(cfg, p, x)
    q_rope = apply_rope(q_rope, pos[None] if pos.ndim == 0 else pos,
                        cfg.rope_theta)
    ckv_t, kr_t = _mla_latents(cfg, p, x, pos[None] if pos.ndim == 0
                               else pos)
    C = cache["ckv"].shape[1]
    slot = (pos % C).astype(jnp.int32)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_t, slot, 1)
    kr = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_t, slot, 1)
    # absorb W_uk into q:  (B,1,H,D) x (r,H,D) -> (B,1,H,r)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"].astype(dt))
    scale = (D + HR) ** -0.5
    s = (jnp.einsum("bshr,btr->bhst", q_abs.astype(jnp.float32),
                    ckv.astype(jnp.float32)) +
         jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                    kr.astype(jnp.float32))) * scale
    w = jax.nn.softmax(s, axis=-1)                            # (B,H,1,C)
    o_lat = jnp.einsum("bhst,btr->bshr", w, ckv.astype(jnp.float32))
    o = jnp.einsum("bshr,rhk->bshk", o_lat.astype(dt), p["wuv"].astype(dt))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return out, {"ckv": ckv, "kr": kr}
