"""Mixture-of-experts FFN: top-k router + dropless sort-based grouped
matmul.

The router scores every expert (softmax, or sigmoid with an optional
constant expert bias that only chooses experts), picks the top k, and
weights each chosen expert by its score normalised over the k (times
`routed_scale`). A layer holds experts `held` = [lo, hi) of them, one
chip's share under expert parallelism: it computes only their part of
the result, for the tokens routed to them, and the router keeps all
its outputs. Nothing is dropped: the held (token, expert) assignments
are sorted by expert and run through a grouped matmul over ragged
groups (`moe_gmm`: XLA's ragged dot, which on a TPU is a kernel of its
own, forward and backward), then weighted and added back to their
tokens.

Named scopes `moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine`
mark the four steps in a device trace.

Survey tie-in (§5.4 load balancing): the router emits the standard
load-balance auxiliary loss; benchmarks/fig6 uses the router stats.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(cfg, key):
    m = cfg.moe
    ks = jax.random.split(key, 4)
    d, f, n = cfg.d_model, m.d_ff, m.n_held
    p = {
        "router": dense_init(ks[0], (d, m.n_experts)),
        "wi": dense_init(ks[1], (n, d, f)),
        "wg": dense_init(ks[2], (n, d, f)),
        "wo": dense_init(ks[3], (n, f, d)),
    }
    if m.expert_bias:
        p["expert_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.n_shared:
        kb = jax.random.split(jax.random.fold_in(key, 7), 3)
        p["shared"] = {
            "wi": dense_init(kb[0], (d, f * m.n_shared)),
            "wg": dense_init(kb[1], (d, f * m.n_shared)),
            "wo": dense_init(kb[2], (f * m.n_shared, d)),
        }
    return p


def _rows_in_groups(x, group_sizes):
    return (jnp.arange(x.shape[0]) < jnp.sum(group_sizes))[:, None]


@jax.custom_vjp
def _gmm(x, w, group_sizes):
    y = jax.lax.ragged_dot(x, w, group_sizes)
    return jnp.where(_rows_in_groups(x, group_sizes), y, 0)


def _gmm_fwd(x, w, group_sizes):
    return _gmm(x, w, group_sizes), (x, w, group_sizes)


def _gmm_bwd(res, g):
    x, w, group_sizes = res
    rows = _rows_in_groups(x, group_sizes)
    _, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, group_sizes),
                     x, w)
    dx, dw = vjp(jnp.where(rows, g, 0))
    return jnp.where(rows, dx, 0), dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@jax.jit
def moe_gmm(x, w, group_sizes):
    """Grouped matmul over ragged groups: x (M, d) sorted by group, w
    (G, d, f), group_sizes (G,) int32 -> (M, f); group g multiplies its
    next group_sizes[g] rows by w[g], and rows past the groups give
    zeros. On a TPU the compiler runs the ragged dot as its own kernel,
    forward and backward, which a device trace names
    `%ragged-dot-none.N` (beside a small `%ragged-dot-metadata.N` that
    lays out the groups). That kernel leaves the rows past the groups
    as it finds them, in its output and in the rows' gradient, so both
    are set to zero here (unset, they turned the parameters to NaN in
    the first training step on a v5e)."""
    return _gmm(x, w.astype(x.dtype), group_sizes)


def route(cfg, p, xt):
    """xt: (T, d) -> (gates (T, E) float32 scores, topi (T, K) chosen
    experts, topw (T, K) their weights)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(xt.dtype))
    if m.score == "sigmoid":
        gates = jax.nn.sigmoid(logits.astype(jnp.float32))
    else:
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    choose = gates
    if m.expert_bias:
        choose = gates + jax.lax.stop_gradient(p["expert_bias"])
    _, topi = jax.lax.top_k(choose, m.top_k)                  # (T,K)
    topv = jnp.take_along_axis(gates, topi, axis=-1)
    if m.score == "sigmoid":
        topw = topv / (topv.sum(-1, keepdims=True) + 1e-6)
    else:
        topw = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return gates, topi, topw * m.routed_scale


def apply_moe(cfg, p, x):
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    dt = x.dtype
    out, aux = _dispatch_tokens(cfg, p, x.reshape(B * S, d))
    out = out.reshape(B, S, d)
    if m.n_shared:
        sp = p["shared"]
        hs = jnp.einsum("bsd,df->bsf", x, sp["wi"].astype(dt))
        gs = jnp.einsum("bsd,df->bsf", x, sp["wg"].astype(dt))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * hs,
                               sp["wo"].astype(dt))
    return out, aux


def _dispatch_tokens(cfg, p, xt):
    """Held experts' part of the routed result for a flat (T, d) block."""
    m = cfg.moe
    T, d = xt.shape
    E, K = m.n_experts, m.top_k
    lo, hi = m.held_range()
    dt = xt.dtype

    with jax.named_scope("moe.route"):
        gates, topi, topw = route(cfg, p, xt)
        # load-balance aux loss (Switch-style)
        density = jnp.mean(
            jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
        aux = jnp.sum(density * jnp.mean(gates, axis=0)) * E \
            * m.aux_loss_coef

    with jax.named_scope("moe.dispatch"):
        fe = topi.reshape(-1)                                  # (T*K,)
        held = (fe >= lo) & (fe < hi)
        local = jnp.where(held, fe - lo, hi - lo)   # absent ones sort last
        order = jnp.argsort(local)                             # stable
        tok = order // K
        group_sizes = jnp.bincount(local, length=hi - lo).astype(jnp.int32)
        xs = xt[tok]                                           # (T*K, d)

    with jax.named_scope("moe.experts"):
        h = moe_gmm(xs, p["wi"], group_sizes)
        g = moe_gmm(xs, p["wg"], group_sizes)
        o = moe_gmm(jax.nn.silu(g) * h, p["wo"], group_sizes)

    with jax.named_scope("moe.combine"):
        w = jnp.where(held, topw.reshape(-1), 0.0)[order]
        out = jnp.zeros((T, d), dt).at[tok].add(o * w[:, None].astype(dt))
    return out, aux


def apply_moe_dense_oracle(cfg, p, x):
    """O(T*E) dense oracle of the held experts' part — math-identical
    to apply_moe (nothing is dropped). Used by tests only."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    dt = x.dtype
    lo, hi = m.held_range()
    _, topi, topw = route(cfg, p, xt)
    comb = jnp.zeros((xt.shape[0], m.n_experts), jnp.float32)
    comb = jax.vmap(lambda c, i, v: c.at[i].add(v))(comb, topi, topw)
    h = jnp.einsum("td,edf->tef", xt, p["wi"].astype(dt))
    g = jnp.einsum("td,edf->tef", xt, p["wg"].astype(dt))
    o = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h, p["wo"].astype(dt))
    out = jnp.einsum("ted,te->td", o.astype(jnp.float32),
                     comb[:, lo:hi]).astype(dt)
    if m.n_shared:
        sp = p["shared"]
        hs = jnp.einsum("td,df->tf", xt, sp["wi"].astype(dt))
        gs = jnp.einsum("td,df->tf", xt, sp["wg"].astype(dt))
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(gs) * hs,
                               sp["wo"].astype(dt))
    return out.reshape(B, S, d)
