"""Plain AdamW (Loshchilov & Hutter 2019) behind global-norm gradient
clipping, in float32, written from the update equations:

    g  <- g * min(1, c / max(||g||, 1e-9))
    m  <- b1 m + (1 - b1) g          v <- b2 v + (1 - b2) g^2
    p  <- p - lr * (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)
"""
import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def init(params):
    zeros = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"t": jnp.zeros((), jnp.int32), "m": zeros, "v": zeros}


def clip(grads, max_norm):
    if max_norm is None:
        return grads
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return tmap(lambda g: g * scale, grads)


def update(params, state, grads, lr, max_norm=None, b1=0.9, b2=0.999,
           eps=1e-8):
    grads = clip(grads, max_norm)
    t = state["t"] + 1
    m = tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    params = tmap(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
                  params, m, v)
    return params, {"t": t, "m": m, "v": v}
