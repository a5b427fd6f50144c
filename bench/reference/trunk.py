"""Plain transformer policy trunk over a feature observation, written from
its equations:

    x_i   = obs_i * W_feat[i] + b_feat[i]                 (one position per feature)
    block = x + Attn(RMSNorm(x)),  then  x + SwiGLU(RMSNorm(x))
    Attn  : causal grouped-query attention, rotary positions (theta 1e4),
            scores scaled by head_dim^-1/2, head h reads kv head h // G
    heads : h = RMSNorm(x)[last position];  logits = h W_pi + b_pi,
            value = h W_v + b_v

No kernel, cache or batching trick: every attention is the full (S, S)
softmax, exp(s - max) mixed with the values and then divided by its sum.
Matrix products run at the configuration's precision (`mm`); `dtype`
bfloat16 casts the weights and activations for the control.

`init` makes the benchmark's weights from a key. Its tree has the
names and shapes the program's policy expects (a token table and an
unembedding that feature observations never read included), with
truncated-normal entries scaled by the first dimension's size to the
power -1/2 (0.01 on the policy head).
"""
import jax
import jax.numpy as jnp

EPS = 1e-6


def mm(eq, a, b, precision):
    """A matrix product at a stated precision: "highest" (float32, six
    bf16 passes on a TPU), "bf16_inputs" (inputs rounded to bfloat16,
    products summed in float32), "default" (the platform's default for
    float32: bf16_inputs on a TPU, highest elsewhere), or for bfloat16
    operands bfloat16 in and out."""
    if precision == "default":
        precision = ("bf16_inputs" if jax.default_backend() == "tpu"
                     else "highest")
    if a.dtype == jnp.bfloat16:
        return jnp.einsum(eq, a, b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)
    if precision == "bf16_inputs":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision(precision))


def _normal(key, shape, scale=None):
    scale = shape[0] ** -0.5 if scale is None else scale
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * scale


def init(key, sz):
    d, H, KVH, hd, ff = (sz["d_model"], sz["n_heads"], sz["n_kv_heads"],
                         sz["head_dim"], sz["d_ff"])
    L, F, V, A = sz["n_layers"], sz["obs_dim"], sz["vocab"], sz["n_actions"]
    ks = iter(jax.random.split(key, 16))

    def stacked(shape, scale=None):
        k = next(ks)
        return jax.vmap(lambda kk: _normal(kk, shape, scale))(
            jax.random.split(k, L))

    block = {"norm1": {"scale": jnp.ones((L, d))},
             "mixer": {"wq": stacked((d, H, hd)),
                       "wk": stacked((d, KVH, hd)),
                       "wv": stacked((d, KVH, hd)),
                       "wo": stacked((H, hd, d))},
             "norm2": {"scale": jnp.ones((L, d))},
             "ffn": {"wi": stacked((d, ff)), "wg": stacked((d, ff)),
                     "wo": stacked((ff, d))}}
    return {"lm": {"embed": {"tok": _normal(next(ks), (V, d), d ** -0.5),
                             "unembed": _normal(next(ks), (d, V))},
                   "final_norm": {"scale": jnp.ones((d,))},
                   "stack": {"t0": block}},
            "pi": {"w": _normal(next(ks), (d, A), 0.01),
                   "b": jnp.zeros((A,))},
            "v": {"w": _normal(next(ks), (d, 1)), "b": jnp.zeros((1,))},
            "feat": {"w": _normal(next(ks), (F, d)),
                     "b": jnp.zeros((F, d))}}


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs    # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def forward(params, obs, sz, dtype=jnp.float32, precision="highest"):
    """obs (B, F) -> (logits (B, A), value (B,)), in float32."""
    P = lambda eq, a, b: mm(eq, a, b, precision)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    H, KVH, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    G = H // KVH
    x = obs.astype(dtype)[..., None] * p["feat"]["w"] + p["feat"]["b"]
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    stack = p["lm"]["stack"]["t0"]
    for r in range(sz["n_layers"]):
        blk = jax.tree_util.tree_map(lambda a: a[r], stack)
        h = _rms(x, blk["norm1"]["scale"])
        m = blk["mixer"]
        q = _rope(P("bsd,dhk->bshk", h, m["wq"]), sz["rope_theta"])
        k = _rope(P("bsd,dhk->bshk", h, m["wk"]), sz["rope_theta"])
        v = P("bsd,dhk->bshk", h, m["wv"])
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        s = P("bqhk,bshk->bhqs", q * hd ** -0.5, k)
        s = jnp.where(causal, s, -jnp.inf)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        o = (P("bhqs,bshk->bhqk", e, v)
             / e.sum(-1)[..., None]).transpose(0, 2, 1, 3)
        x = x + P("bshk,hkd->bsd", o, m["wo"])
        h = _rms(x, blk["norm2"]["scale"])
        f = blk["ffn"]
        x = x + P("bsf,fd->bsd", jax.nn.silu(P("bsd,df->bsf", h, f["wg"]))
                  * P("bsd,df->bsf", h, f["wi"]), f["wo"])
    h = _rms(x, p["lm"]["final_norm"]["scale"])[:, -1]
    logits = P("bd,da->ba", h, p["pi"]["w"]) + p["pi"]["b"]
    value = (P("bd,da->ba", h, p["v"]["w"]) + p["v"]["b"])[:, 0]
    return logits.astype(jnp.float32), value.astype(jnp.float32)


def layer_flops(sz):
    """Matrix-product FLOPs of one block at one position, forward."""
    d, H, KVH, hd, ff = (sz["d_model"], sz["n_heads"], sz["n_kv_heads"],
                         sz["head_dim"], sz["d_ff"])
    return 2 * (d * H * hd + 2 * d * KVH * hd + H * hd * d + 3 * d * ff)


def forward_flops(sz):
    """FLOPs of one forward pass over one observation: the blocks'
    products at every position, full (S, S) attention scores and mixing
    (4 S^2 hd per head), the feature lift and both heads."""
    S, d, H, hd = sz["obs_dim"], sz["d_model"], sz["n_heads"], sz["head_dim"]
    per_layer = S * layer_flops(sz) + 4 * H * S * S * hd
    return (sz["n_layers"] * per_layer + 2 * S * d
            + 2 * d * (sz["n_actions"] + 1))
