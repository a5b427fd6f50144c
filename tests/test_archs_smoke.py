"""Per-architecture smoke tests: reduced variant (<=4 experts, d<=512,
one super-block) runs a forward AND one train step on CPU; output shapes
and finiteness asserted. Decode consistency vs full forward is also
checked (MoE dispatch drops nothing, so it holds for MoE too)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import list_archs, get_config
from repro.models import build_model
from repro.models.model import ModelOpts
from repro.optim import adamw

ARCHS = [a for a in list_archs() if a != "paper-drl-trunk"]
OPTS = ModelOpts(dtype="float32", remat=False)


def _frontend(cfg, B):
    if cfg.frontend == "vision_stub":
        return 0.1 * jnp.ones((B, cfg.frontend_tokens,
                               cfg.frontend_dim or cfg.d_model))
    if cfg.frontend == "audio_stub":
        return 0.1 * jnp.ones((B, cfg.enc_tokens, cfg.d_model))
    return None


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_finite(arch, rng):
    m = build_model(arch, OPTS, reduced=True)
    cfg = m.cfg
    assert cfg.n_layers <= max(2, len(cfg.layer_pattern))
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    p = m.init(rng)
    B, S = 2, 16
    tok = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    logits, aux = m.forward(p, tok, _frontend(cfg, B))
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch, rng):
    m = build_model(arch, OPTS, reduced=True)
    cfg = m.cfg
    p = m.init(rng)
    opt = adamw(1e-3)
    ostate = opt.init(p)
    batch = {"tokens": jax.random.randint(rng, (2, 17), 0, cfg.vocab)}
    fe = _frontend(cfg, 2)
    if fe is not None:
        batch["frontend"] = fe
    (loss, metrics), grads = jax.value_and_grad(
        m.loss, has_aux=True)(p, batch)
    assert bool(jnp.isfinite(loss))
    gn = sum(jnp.sum(jnp.abs(g)) for g in jax.tree_util.tree_leaves(grads))
    assert bool(jnp.isfinite(gn)) and float(gn) > 0
    p2, _ = opt.apply(p, ostate, grads)
    leaves = jax.tree_util.tree_leaves(p2)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in leaves)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch, rng):
    cfg = get_config(arch).reduced()
    m = build_model(cfg, OPTS)
    p = m.init(rng)
    B, S = 2, 12
    tok = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab)
    fe = _frontend(cfg, B)
    full, _ = m.forward(p, tok, fe)
    lg_pre, cache = m.prefill(p, tok[:, :S], fe)
    assert jnp.allclose(full[:, S - 1], lg_pre[:, 0], atol=2e-4), \
        "prefill last-token logits must equal forward"
    npx = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0
    lg_dec, _ = m.decode_step(p, tok[:, S:S + 1], cache,
                              jnp.int32(S + npx))
    err = float(jnp.max(jnp.abs(full[:, S] - lg_dec[:, 0])))
    assert err < 2e-3, f"decode/forward mismatch {err}"


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_shapes(arch):
    from repro.configs.base import SHAPES
    m = build_model(arch, OPTS)
    for name, shape in SHAPES.items():
        specs = m.input_specs(shape)
        assert specs, f"{arch} {name} produced empty specs"
        if shape.mode == "decode":
            assert "cache" in specs and "pos" in specs
