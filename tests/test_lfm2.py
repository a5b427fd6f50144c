"""LFM2-8B-A1B's mechanisms against the plain reference
(`bench/reference/lfm2.py`) at a small size on seeded random weights:
the gated short-conv block (prefill, then decode through its state),
q/k-normed attention, sigmoid routing with an expert bias, the dropless
dispatch under a skewed router, one chip's share of the experts, and the
whole trunk as the IMPALA policy."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import lfm2 as ref  # noqa: E402
from repro.configs import CONV, get_config  # noqa: E402
from repro.models import moe, shortconv  # noqa: E402
from repro.models.attention import AttnOpts, gqa_seq  # noqa: E402
from repro.models.model import LanguageModel, ModelOpts  # noqa: E402

P = lambda eq, a, b: ref.mm(eq, a, b, "highest")  # noqa: E731
CFG = get_config("lfm2-8b-a1b").reduced()
OPTS = ModelOpts(dtype="float32", remat=False)


def _sz(cfg, held=None, n_layers=6):
    m = cfg.moe
    return {"n_layers": n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "expert_d_ff": m.d_ff, "n_experts": m.n_experts,
            "held": held or (0, m.n_experts), "top_k": m.top_k,
            "first_dense": m.first_dense,
            "layer_types": tuple("conv" if k == "conv" else "attn"
                                 for k in cfg.pattern()[:n_layers]),
            "conv_L": cfg.conv_cache, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "routed_scale": m.routed_scale,
            "vocab": cfg.vocab, "bias_scale": 0.05, "obs_dim": 4,
            "n_actions": 2}


def _moe_cfg(n_experts=8, top_k=2, held=None):
    return dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, n_experts=n_experts, top_k=top_k, held=held))


def _moe_params(cfg, key, held=None, bias_scale=0.05):
    """Reference-drawn weights of one expert layer (the experts `held`)."""
    sz = dict(_sz(cfg, held), bias_scale=bias_scale, first_dense=0,
              layer_types=("conv",), n_layers=1)
    return ref.init(key, sz)["lm"]["tail"][0]["ffn"], sz


def test_conv_prefill_then_decode_matches_reference(rng):
    """Prefill S tokens, then decode three through the carried state:
    every output equals the reference's full causal convolution."""
    d, S = CFG.d_model, 9
    p = shortconv.init_conv(CFG, rng)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, S, d))
    full = ref._conv(p, x, CFG.conv_cache, P)
    out, st = shortconv.conv_seq(CFG, p, x[:, :S - 3])
    outs = [out]
    for t in range(S - 3, S):
        o, st = shortconv.conv_seq(CFG, p, x[:, t:t + 1], st)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), full, atol=1e-5,
                               rtol=1e-5)


def test_conv_lm_decode_steps_match_forward(rng):
    """A language model of conv layers (dense, then with experts):
    prefill, then four decode steps through the conv states, agree with
    the full forward. (Attention's decode reads a full ring cache, so
    only one step of it is exact: tests/test_archs_smoke.py.)"""
    cfg = dataclasses.replace(CFG, layer_pattern=(CONV,), n_layers=3)
    m = LanguageModel(cfg, OPTS)
    p = m.init(rng)
    B, S, n = 2, 10, 4
    tok = jax.random.randint(rng, (B, S + n), 0, cfg.vocab)
    full, _ = m.forward(p, tok)
    _, cache = m.prefill(p, tok[:, :S], cache_capacity=S + n)
    for i in range(n):
        lg, cache = m.decode_step(p, tok[:, S + i:S + i + 1], cache,
                                  jnp.int32(S + i))
        np.testing.assert_allclose(lg[:, 0], full[:, S + i], atol=2e-4,
                                   rtol=1e-4)


def test_qk_norm_attention_matches_reference(rng):
    from repro.models.attention import init_attn
    p = init_attn(CFG, rng, "attn")
    assert p["q_norm"]["scale"].shape == (CFG.head_dim,)
    p["q_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.fold_in(rng, 3), (CFG.head_dim,))
    x = jax.random.normal(jax.random.fold_in(rng, 1), (3, 4, CFG.d_model))
    out, _ = gqa_seq(CFG, p, x, jnp.int32(0), "attn", AttnOpts(
        dtype=jnp.float32, use_kernels=True))
    want = ref._attn(p, x, _sz(CFG), P)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    # without the norm the answer moves
    bare = {k: v for k, v in p.items() if not k.endswith("_norm")}
    other, _ = gqa_seq(CFG, bare, x, jnp.int32(0), "attn", AttnOpts(
        dtype=jnp.float32))
    assert not np.allclose(other, want, atol=1e-3)


def test_routing_with_bias_matches_reference(rng):
    cfg = _moe_cfg()
    f, sz = _moe_params(cfg, rng, bias_scale=0.2)
    xt = jax.random.normal(jax.random.fold_in(rng, 1), (64, cfg.d_model))
    _, topi, topw = moe.route(cfg, f, xt)
    want_i, want_w = ref.route(f, xt, sz, P)
    np.testing.assert_array_equal(topi, want_i)
    np.testing.assert_allclose(topw, want_w, atol=1e-6)
    plain, _ = ref.route(f, xt, sz, P, bias=False)
    changed = np.any(np.sort(topi, -1) != np.sort(plain, -1), -1)
    assert changed.mean() > 0.1, changed.mean()
    # the bias chooses and does not weight: weights are the chosen
    # sigmoid scores, normalised
    s = jax.nn.sigmoid(xt @ f["router"])
    w = jnp.take_along_axis(s, topi, -1)
    np.testing.assert_allclose(topw, w / (w.sum(-1, keepdims=True) + 1e-6),
                               atol=1e-6)


def test_moe_dropless_under_skewed_router(rng):
    """Every token picks the same experts: all of them reach their
    experts (the capacity dispatch this replaced dropped most of them),
    as the reference and the dense oracle say."""
    cfg = _moe_cfg(n_experts=8, top_k=2)
    f, sz = _moe_params(cfg, rng)
    f = dict(f, router=jnp.zeros_like(f["router"]),
             expert_bias=jnp.zeros((8,)).at[jnp.array([3, 5])].set(1.0))
    x = jax.random.normal(jax.random.fold_in(rng, 1), (4, 16, cfg.d_model))
    out, _ = moe.apply_moe(cfg, f, x)
    _, topi, _ = moe.route(cfg, f, x.reshape(-1, cfg.d_model))
    assert set(np.unique(topi)) == {3, 5}
    np.testing.assert_allclose(out, moe.apply_moe_dense_oracle(cfg, f, x),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out, ref._moe(f, x, sz, P, "", None, 0, None),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_expert_shares_add_up_to_uncut_layer(score, rng):
    """Four chips' shares of 8 experts, 2 each: the parts add up to the
    whole layer, the program's and the reference's."""
    cfg = _moe_cfg(n_experts=8, top_k=3)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, score=score, expert_bias=score == "sigmoid"))
    f, sz = _moe_params(cfg, rng)
    if score == "softmax":
        f = {k: v for k, v in f.items() if k != "expert_bias"}
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 12, cfg.d_model))
    whole, _ = moe.apply_moe(cfg, f, x)
    parts = 0.0
    for lo in range(0, 8, 2):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(lo, lo + 2)))
        fs = dict(f, **{k: f[k][lo:lo + 2] for k in ("wi", "wg", "wo")})
        part, _ = moe.apply_moe(share, fs, x)
        parts = parts + part
    np.testing.assert_allclose(parts, whole, atol=1e-5, rtol=1e-5)
    if score == "sigmoid":
        np.testing.assert_allclose(
            whole, ref._moe(f, x, sz, P, "", None, 0, None), atol=1e-5,
            rtol=1e-5)


def test_expert_bias_gets_no_gradient(rng):
    cfg = _moe_cfg()
    f, _ = _moe_params(cfg, rng)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 8, cfg.d_model))
    g = jax.grad(lambda f: jnp.sum(moe.apply_moe(cfg, f, x)[0] ** 2))(f)
    assert float(jnp.max(jnp.abs(g["expert_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0


def test_lfm2_trunk_policy_matches_reference(rng):
    """The IMPALA policy trunk at the cut (6 layers: conv + dense twice,
    attention, three convs, with experts; half the experts held) on the
    reference's weights gives the reference's logits and values."""
    import repro.envs as envs
    from repro.core.networks import TrunkPolicy
    pol = TrunkPolicy.for_spec(envs.make("cartpole").spec,
                               arch="lfm2-8b-a1b", reduced=True, n_layers=6,
                               vocab=256, experts_held=(0, 2))
    cfg = pol.lm.cfg
    assert cfg.n_layers == 6 and cfg.vocab == 256
    assert cfg.moe.n_held == 2 and cfg.moe.n_experts == 4
    sz = dict(_sz(cfg, held=(0, 2)), vocab=256)
    params = ref.init(rng, sz)
    want = jax.eval_shape(pol.init, rng)
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(params)
    obs = jax.random.normal(jax.random.fold_in(rng, 1), (16, 4))
    logits, v = pol.apply(params, obs)
    rl, rv = ref.forward(params, obs, sz)
    np.testing.assert_allclose(logits, rl, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(v, rv, atol=1e-5, rtol=1e-4)
