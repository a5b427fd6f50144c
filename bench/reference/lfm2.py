"""Plain LFM2 (LFM2-8B-A1B) policy trunk over a feature observation,
written from the equations of the upstream `modeling_lfm2_moe.py`:

    x_i    = obs_i * W_feat[i] + b_feat[i]           (one position per feature)
    block  = x + op(RMSNorm(x)),  then  x + ffn(RMSNorm(x))     (eps 1e-5)
    conv   : B, C, h = split3(x W_in);  y = C * causal_depthwise_conv_L(B * h);
             out = y W_out                            (L = 3, no bias)
    attn   : causal grouped-query attention, q and k RMS-normed per head
             (eps 1e-5) before rotary positions (theta 1e6), scores
             scaled by head_dim^-1/2, head h reads kv head h // G
    dense  : SwiGLU  W_o(silu(x W_g) * x W_i)   (the leading dense layers)
    moe    : s = sigmoid(x W_r) over all E experts; the chosen are
             top_k(s + expert_bias); weight_e = s_e / (sum of the chosen
             s + 1e-6) * routed_scale; out = sum over the chosen experts
             this chip holds of weight_e * W_o,e(silu(x W_g,e) * x W_i,e)
    heads  : h = RMSNorm(x)[last position];  logits = h W_pi + b_pi,
             value = h W_v + b_v

No kernel, cache or batching trick. The experts are a dense oracle: each
held expert runs on every token and is weighted by its combine weight,
nought where it was not chosen (a scan over the experts, each step
recomputed in the backward pass so the oracle's memory stays one
expert's). Attention is the full (S, S) softmax. Matrix products run at
the configuration's precision (`trunk.mm`); `dtype` bfloat16 casts the
weights and activations for the control.

Faults, for reading the limits against: "all_experts" also computes the
experts this chip does not hold (their weights drawn from the same key
stream as the held ones, `wkey`), "no_expert_bias" chooses the experts
without the bias.

`init` makes the benchmark's weights from a key, in the tree the
program's policy expects: the leading dense layers under "prefix", the
rest under "tail", a tied token table that feature observations never
read. Entries are truncated normal scaled by the first dimension's size
to the power -1/2 (0.01 on the policy head), RMSNorm scales one, and
the expert bias normal at `bias_scale`. Expert e of layer r is drawn
from its own key, `expert_key(wkey, r, e)`. `place` then reorders each
expert layer's router columns and bias so that the experts this chip
holds take its balanced share of the load on a sample of tokens, as an
expert-parallel deployment places its experts over its chips.
"""
import jax
import jax.numpy as jnp

from bench.reference.trunk import _normal, _rope, mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def expert_key(wkey, r, e):
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(wkey, 0x5E), r), e)


def expert(key, d, f):
    """One expert's (W_i, W_g, W_o)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return _normal(k1, (d, f)), _normal(k2, (d, f)), _normal(k3, (f, d))


def init(key, sz):
    d, H, KVH, hd = sz["d_model"], sz["n_heads"], sz["n_kv_heads"], \
        sz["head_dim"]
    F, V, A = sz["obs_dim"], sz["vocab"], sz["n_actions"]
    lo, hi = sz["held"]
    layers = []
    for r, kind in enumerate(sz["layer_types"]):
        k = jax.random.split(jax.random.fold_in(key, r), 8)
        blk = {"norm1": {"scale": jnp.ones((d,))},
               "norm2": {"scale": jnp.ones((d,))}}
        if kind == "conv":
            blk["mixer"] = {"in_proj": _normal(k[0], (d, 3 * d)),
                            "conv_w": _normal(k[1], (sz["conv_L"], d)),
                            "out_proj": _normal(k[2], (d, d))}
        else:
            blk["mixer"] = {"wq": _normal(k[0], (d, H, hd)),
                            "wk": _normal(k[1], (d, KVH, hd)),
                            "wv": _normal(k[2], (d, KVH, hd)),
                            "wo": _normal(k[3], (H, hd, d)),
                            "q_norm": {"scale": jnp.ones((hd,))},
                            "k_norm": {"scale": jnp.ones((hd,))}}
        if r < sz["first_dense"]:
            ff = sz["d_ff"]
            blk["ffn"] = {"wi": _normal(k[4], (d, ff)),
                          "wg": _normal(k[5], (d, ff)),
                          "wo": _normal(k[6], (ff, d))}
        else:
            ws = [expert(expert_key(key, r, e), d, sz["expert_d_ff"])
                  for e in range(lo, hi)]
            blk["ffn"] = {
                "router": _normal(k[4], (d, sz["n_experts"])),
                "expert_bias": sz["bias_scale"] * jax.random.normal(
                    k[5], (sz["n_experts"],)),
                **{n: jnp.stack([w[i] for w in ws])
                   for i, n in enumerate(("wi", "wg", "wo"))}}
        layers.append(blk)
    kh = jax.random.split(jax.random.fold_in(key, 0x7A11), 4)
    fd = sz["first_dense"]
    return {"lm": {"embed": {"tok": _normal(kh[0], (V, d), d ** -0.5)},
                   "final_norm": {"scale": jnp.ones((d,))},
                   "prefix": layers[:fd], "tail": layers[fd:]},
            "pi": {"w": _normal(kh[1], (d, A), 0.01), "b": jnp.zeros((A,))},
            "v": {"w": _normal(kh[2], (d, 1)), "b": jnp.zeros((1,))},
            "feat": {"w": _normal(kh[3], (F, d)), "b": jnp.zeros((F, d))}}


def _conv(m, h, L, P):
    S = h.shape[1]
    b, c, g = jnp.split(P("bsd,de->bse", h, m["in_proj"]), 3, -1)
    u = jnp.pad(b * g, ((0, 0), (L - 1, 0), (0, 0)))
    y = sum(u[:, j:j + S] * m["conv_w"][j] for j in range(L))
    return P("bsd,de->bse", c * y, m["out_proj"])


def _attn(m, h, sz, P):
    H, KVH, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    S, eps = h.shape[1], sz["eps"]
    q = _rms(P("bsd,dhk->bshk", h, m["wq"]), m["q_norm"]["scale"], eps)
    k = _rms(P("bsd,dhk->bshk", h, m["wk"]), m["k_norm"]["scale"], eps)
    v = P("bsd,dhk->bshk", h, m["wv"])
    q, k = _rope(q, sz["rope_theta"]), _rope(k, sz["rope_theta"])
    k, v = jnp.repeat(k, H // KVH, axis=2), jnp.repeat(v, H // KVH, axis=2)
    s = P("bqhk,bshk->bhqs", q * hd ** -0.5, k)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    e = jnp.exp(s - s.max(-1, keepdims=True))
    o = (P("bhqs,bshk->bhqk", e, v) / e.sum(-1)[..., None]).transpose(
        0, 2, 1, 3)
    return P("bshk,hkd->bsd", o, m["wo"])


def route(f, xt, sz, P, bias=True):
    """-> (chosen (T, K), their weights (T, K))."""
    s = jax.nn.sigmoid(P("td,de->te", xt, f["router"]).astype(jnp.float32))
    choose = s + f["expert_bias"].astype(jnp.float32) if bias else s
    _, top = jax.lax.top_k(choose, sz["top_k"])
    w = jnp.take_along_axis(s, top, -1)
    return top, w / (w.sum(-1, keepdims=True) + 1e-6) * sz["routed_scale"]


def _expert_step(xt, weights, P):
    """The step of a scan over experts that adds comb[:, e] *
    expert_e(xt); `weights(xs)` gives (W_i, W_g, W_o) of the step's
    slice `xs`, whose last entry is the combine column."""
    def body(acc, xs):
        wi, wg, wo = weights(xs)
        o = P("tf,fd->td", jax.nn.silu(P("td,df->tf", xt, wg))
              * P("td,df->tf", xt, wi), wo)
        return acc + xs[-1][:, None].astype(acc.dtype) * o, None

    return body


def _moe(f, h, sz, P, fault, wkey, r, stats):
    B, S, d = h.shape
    xt = h.reshape(B * S, d)
    E, (lo, hi) = sz["n_experts"], sz["held"]
    top, w = route(f, xt, sz, P, bias=fault != "no_expert_bias")
    if stats is not None:
        plain, _ = route(f, xt, sz, P, bias=False)
        stats.append((jnp.mean((top >= lo) & (top < hi)), jnp.mean(
            jnp.any(jnp.sort(top, -1) != jnp.sort(plain, -1), -1))))
    comb = jnp.zeros((B * S, E), jnp.float32).at[
        jnp.arange(B * S)[:, None], top].set(w)
    held = _expert_step(xt, lambda xs: xs[:3], P)
    out, _ = jax.lax.scan(jax.checkpoint(held), jnp.zeros_like(xt),
                          (f["wi"], f["wg"], f["wo"], comb[:, lo:hi].T))
    if fault == "all_experts":
        absent = [e for e in range(E) if not lo <= e < hi]
        dt = xt.dtype

        def drawn(xs):
            ws = expert(expert_key(wkey, r, xs[0]), d, sz["expert_d_ff"])
            return tuple(a.astype(dt) for a in ws)

        ids = jnp.asarray(absent, jnp.int32)
        out, _ = jax.lax.scan(jax.checkpoint(_expert_step(xt, drawn, P)),
                              out, (ids, comb[:, ids].T))
    return out.reshape(B, S, d)


def _placed(f, h, sz, P):
    """The expert layer `f` with its router's columns and its bias put in
    the order of a balanced placement over the chips, by the loads that
    the tokens `h` put on each of the E experts: longest first, each to
    the chip with the least load so far that holds fewer than its share
    (ties to the lower chip, then the lower expert), so that chip c
    holds experts [c n, (c + 1) n) after the reordering."""
    E, (lo, hi) = sz["n_experts"], sz["held"]
    n = hi - lo
    top, _ = route(f, h.reshape(-1, h.shape[-1]), sz, P)
    load = jnp.zeros((E,), jnp.float32).at[top.reshape(-1)].add(1.0)
    order = jnp.argsort(-load, stable=True)

    def put(i, c):
        tot, cnt, chip = c
        e = order[i]
        b = jnp.argmin(jnp.where(cnt < n, tot, jnp.inf))
        return tot.at[b].add(load[e]), cnt.at[b].add(1), chip.at[e].set(b)

    _, _, chip = jax.lax.fori_loop(
        0, E, put, (jnp.zeros((E // n,)), jnp.zeros((E // n,), jnp.int32),
                    jnp.zeros((E,), jnp.int32)))
    perm = jnp.argsort(chip, stable=True)
    return dict(f, router=f["router"][:, perm],
                expert_bias=f["expert_bias"][perm])


def trunk(params, obs, sz, dtype=jnp.float32, precision="highest",
          fault="", wkey=None, stats=None, placed=None):
    """obs (B, F) -> (the final RMSNorm's output at the last position,
    the weights in `dtype`). `stats`, a list, gains `routing`'s pair for
    each expert layer; `placed`, a list, gains each expert layer placed
    by `_placed` on its tokens, which the layer then runs with."""
    P = lambda eq, a, b: mm(eq, a, b, precision)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = sz["eps"]
    x = obs.astype(dtype)[..., None] * p["feat"]["w"] + p["feat"]["b"]
    layers = p["lm"]["prefix"] + p["lm"]["tail"]
    for r, kind in enumerate(sz["layer_types"]):
        blk = layers[r]
        h = _rms(x, blk["norm1"]["scale"], eps)
        x = x + (_conv(blk["mixer"], h, sz["conv_L"], P) if kind == "conv"
                 else _attn(blk["mixer"], h, sz, P))
        h = _rms(x, blk["norm2"]["scale"], eps)
        f = blk["ffn"]
        if r < sz["first_dense"]:
            x = x + P("bsf,fd->bsd", jax.nn.silu(P("bsd,df->bsf", h, f["wg"]))
                      * P("bsd,df->bsf", h, f["wi"]), f["wo"])
        else:
            if placed is not None:
                f = _placed(f, h, sz, P)
                placed.append(f)
            x = x + _moe(f, h, sz, P, fault, wkey, r, stats)
    return _rms(x, p["lm"]["final_norm"]["scale"], eps)[:, -1], p


def forward(params, obs, sz, dtype=jnp.float32, precision="highest",
            fault="", wkey=None):
    """obs (B, F) -> (logits (B, A), value (B,)), in float32."""
    P = lambda eq, a, b: mm(eq, a, b, precision)
    h, p = trunk(params, obs, sz, dtype, precision, fault, wkey)
    logits = P("bd,da->ba", h, p["pi"]["w"]) + p["pi"]["b"]
    value = (P("bd,da->ba", h, p["v"]["w"]) + p["v"]["b"])[:, 0]
    return logits.astype(jnp.float32), value.astype(jnp.float32)


def place(params, obs, sz, precision="highest"):
    """`params` with each expert layer's router columns and bias
    reordered (`_placed`), layer after layer, on the tokens of `obs`:
    the experts this chip holds take its balanced share of the load,
    as an expert-parallel deployment places its experts. The experts'
    own weights stay where they are."""
    placed = []
    trunk(params, obs, sz, precision=precision, placed=placed)
    tail = [dict(blk, ffn=f) for blk, f in zip(params["lm"]["tail"], placed)]
    return {**params, "lm": {**params["lm"], "tail": tail}}


def routing(params, obs, sz, precision="highest"):
    """Per expert layer, on the tokens of `obs`: the share of the
    (token, choice) assignments that go to the held experts, and the
    share of tokens whose chosen experts change without the bias."""
    stats = []
    trunk(params, obs, sz, precision=precision, stats=stats)
    return stats


def layer_flops(sz, kind, moe):
    """Matrix-product FLOPs of one block at one position, forward; the
    experts at the held share of the top-k (K * held / E rows a token)."""
    d, H, KVH, hd = sz["d_model"], sz["n_heads"], sz["n_kv_heads"], \
        sz["head_dim"]
    if kind == "conv":
        mix = 2 * (3 * d * d + d * d) + 2 * sz["conv_L"] * d
    else:
        mix = 2 * (d * H * hd + 2 * d * KVH * hd + H * hd * d)
    if not moe:
        return mix + 2 * 3 * d * sz["d_ff"]
    lo, hi = sz["held"]
    rows = sz["top_k"] * (hi - lo) / sz["n_experts"]
    return mix + 2 * d * sz["n_experts"] + rows * 2 * 3 * d * sz["expert_d_ff"]


def forward_flops(sz):
    """FLOPs of one forward pass over one observation: the blocks'
    products at every position, full (S, S) attention scores and mixing
    (4 S^2 hd per head), the feature lift and both heads."""
    S, d, H, hd = sz["obs_dim"], sz["d_model"], sz["n_heads"], sz["head_dim"]
    total = 2 * S * d + 2 * d * (sz["n_actions"] + 1)
    for r, kind in enumerate(sz["layer_types"]):
        total += S * layer_flops(sz, kind, r >= sz["first_dense"])
        if kind != "conv":
            total += 4 * H * S * S * hd
    return total
