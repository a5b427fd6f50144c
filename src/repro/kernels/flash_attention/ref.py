"""Pure-jnp oracle for flash attention (naive softmax, O(S^2) memory)."""
import jax.numpy as jnp
import jax

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=0):
    """q: (B,H,Sq,D); k,v: (B,KVH,Sk,D); GQA by head folding.
    Returns (B,H,Sq,D) float32 math."""
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    G = H // KVH
    kk = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * D ** -0.5, kk)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (kpos <= qpos + (Sk - Sq))
    if window:
        mask = mask & (kpos > qpos + (Sk - Sq) - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv).astype(q.dtype)


def attention_ref_grouped(qg, k, v, *, causal=True, window=0):
    """`attention_ref` in the model's grouped-query layout.
    qg: (B, S, KVH, G, D); k, v: (B, S, KVH, D). Returns (B, S, KVH, G,
    D)."""
    B, S, KVH, G, D = qg.shape
    q = jnp.moveaxis(qg.reshape(B, S, KVH * G, D), 1, 2)  # (B, H, S, D)
    o = attention_ref(q, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
                      causal=causal, window=window)
    return jnp.moveaxis(o, 1, 2).reshape(B, S, KVH, G, D)
