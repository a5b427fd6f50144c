"""The lfm2-8b-a1b configuration at a small size on the CPU: hand counts
of its FLOPs and grouped-matmul work, a sound run through the Trainer
that the plain reference finds correct, and its bfloat16 control and
both faults (all experts computed, routing without the expert bias)
that it does not."""
import argparse
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness  # noqa: E402
from bench.reference import lfm2  # noqa: E402

NAME = "lfm2-8b-a1b.learn"
TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "expert_d_ff": 6, "n_experts": 4,
        "held": (0, 2), "top_k": 2, "first_dense": 1,
        "layer_types": ("conv", "attn"), "conv_L": 3, "obs_dim": 3,
        "n_actions": 2}


def small_cell():
    """The cell at the program's reduced LFM2 widths (hidden 128, 4 of
    4 experts routed top-2, this chip holding 2), 6 layers as at the
    cut, a few envs."""
    from repro.configs import get_config
    cell = harness.cell(NAME)
    r = get_config("lfm2-8b-a1b").reduced()
    cell["sizes"] = dict(
        cell["sizes"], hidden_size=r.d_model,
        num_attention_heads=r.n_heads, num_key_value_heads=r.n_kv_heads,
        head_dim=r.head_dim, intermediate_size=r.d_ff,
        moe_intermediate_size=r.moe.d_ff,
        num_experts_published=r.moe.n_experts, experts_held=[0, 2],
        num_experts=2, num_experts_per_tok=r.moe.top_k,
        num_dense_layers=r.moe.first_dense, vocab_size=256,
        arch_reduced=True)
    cell["traffic"] = dict(cell["traffic"], n_envs=8, unroll=4, superstep=3)
    return cell


def test_forward_flops_hand_count():
    # layer 0, conv + dense: in/out projections 2*(3*64 + 64), the
    # convolution 2*3*8, SwiGLU 2*3*8*16; layer 1, attention + experts:
    # q 8*8, k and v 8*4 each, o 8*8 (x2), router 2*8*4, and
    # top-2 x 2/4 held = 1 expert row of 2*3*8*6
    conv = 2 * (3 * 64 + 64) + 2 * 3 * 8 + 2 * 3 * 8 * 16
    attn = 2 * (64 + 32 + 32 + 64) + 2 * 8 * 4 + 1 * 2 * 3 * 8 * 6
    assert lfm2.layer_flops(TINY, "conv", False) == conv
    assert lfm2.layer_flops(TINY, "attn", True) == attn
    scores = 4 * 2 * 3 * 3 * 4
    assert lfm2.forward_flops(TINY) == (2 * 3 * 8 + 2 * 8 * 3
                                        + 3 * (conv + attn) + scores)


def test_training_flops_and_gmm_work():
    cell = harness.cell(NAME)
    code, sizes = cell["code"], cell["sizes"]
    t = dict(cell["traffic"], n_envs=4, unroll=2)
    fwd = lfm2.forward_flops(dict(code._szt(sizes)))
    # rollout 8 forwards, learner 8 x (forward + backward), boot 4
    assert code.flops_per_iter(sizes, t) == fwd * (8 + 24 + 4)
    w = code.gmm_work(10, 8, 6, 2)
    assert w == {"flops": 2 * 10 * 8 * 6, "bytes": 4 * (80 + 2 * 48 + 60)}
    k = code.kernels(sizes, t, iters=3)
    # per iteration: 2 rollout steps, the learner and the bootstrap, in
    # the one attention layer and, 3 products each, the 4 expert layers;
    # the learner's backward takes two ragged dots a product
    assert k["flash_attention"]["calls"] == 3 * 4
    assert k["moe_gmm"]["calls"] == 3 * 4 * (4 * 3 + 2 * 3)
    # rows: tokens (4 positions a sample) x top-4 x 8 held / 32
    rows = [4 * b for b in (4, 4, 8, 4, 8, 8)]
    d, f = 2048, 1792
    flops = sum(2 * r * d * f * 3 for r in rows)
    assert k["moe_gmm"]["flops"] == 3 * 4 * flops
    wbytes = 4 * 8 * d * f * 3 * 6        # every call reads the 8 experts
    xbytes = sum(4 * r * (d + f) * 3 for r in rows)
    assert k["moe_gmm"]["bytes"] == 3 * 4 * (wbytes + xbytes)


def test_sound_run_is_correct():
    """The Trainer's superstep over TrunkPolicy(lfm2) against
    `bench/reference/impala_lfm2.py`: first loss and parameter change."""
    cell = small_cell()
    drv = harness.load_module(harness.BENCH / "drivers" / "train.py")
    args = argparse.Namespace(trace=0, seconds=0.5)
    out = drv.run(cell, args, harness.seed31(11), jax.devices()[:1],
                  harness.Spans(), time.perf_counter(), None)
    assert harness.checks_pass(out["checks"]), out["checks"]
    assert out["checks"][0]["value"] < 1e-5, out["checks"]


@pytest.mark.parametrize("fault", ["", "all_experts", "no_expert_bias"])
def test_control_and_faults_are_not_correct(fault):
    cell = small_cell()
    got = cell["code"].control_run(cell, harness.seed31(5), fault)
    limits = cell["traffic"]["limits"]
    assert any(v > limits[k] for k, v in got["checks"].items()), got
    held = [h for h, _ in got["routing"]]
    assert len(held) == 5 and all(0 < h < 1 for h in held)


def assert_renumbered(f, g):
    """g is the expert layer f with its experts renumbered: its router's
    columns and its bias entries, by one permutation."""
    bf, bg = np.asarray(f["expert_bias"]), np.asarray(g["expert_bias"])
    perm = [int(np.argmin(np.abs(bf - b))) for b in bg]
    assert sorted(perm) == list(range(len(bf)))
    close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
        a, b, rtol=1e-6, atol=1e-7)
    close(bg, bf[perm])
    close(np.asarray(g["router"]), np.asarray(f["router"])[:, perm])


def test_placement_balances_the_chips():
    """One expert layer placed on diverse tokens: the 4 chips' loads
    differ by at most one expert's, and by less than in the order drawn,
    and the layer is the same layer with its experts renumbered."""
    sz = dict(TINY, n_experts=8, held=(0, 2), routed_scale=1.0)
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    f = {"router": jax.random.normal(k[0], (8, 8)) * 8 ** -0.5,
         "expert_bias": 0.02 * jax.random.normal(k[1], (8,))}
    h = jax.random.normal(k[2], (64, 8, 8))
    P = lambda eq, a, b: lfm2.mm(eq, a, b, "highest")  # noqa: E731
    g = lfm2._placed(f, h, sz, P)
    top, _ = lfm2.route(g, h.reshape(-1, 8), sz, P)
    load = np.bincount(np.asarray(top).ravel(), minlength=8)
    chips = load.reshape(4, 2).sum(1)
    top, _ = lfm2.route(f, h.reshape(-1, 8), sz, P)
    before = np.bincount(np.asarray(top).ravel(), minlength=8)
    assert chips.max() - chips.min() <= load.max(), (chips, load)
    assert np.ptp(chips) < np.ptp(before.reshape(4, 2).sum(1)), before
    assert_renumbered(f, g)


def test_place_only_renumbers_the_experts():
    """The benchmark's weights are the reference's draw with the policy
    head at zero, the feature lift's bias drawn, and each expert layer's
    router columns and bias renumbered by one permutation; the experts'
    weights and every other leaf stay."""
    cell = small_cell()
    code, sizes = cell["code"], cell["sizes"]
    sz = dict(code._szt(sizes))
    key = code._wkey(harness.seed31(9))
    raw = jax.jit(lambda k: lfm2.init(k, sz))(key)
    got = code.weights(sizes, harness.seed31(9))
    for a, b in zip(raw["lm"]["tail"], got["lm"]["tail"]):
        assert_renumbered(a["ffn"], b["ffn"])
    assert not np.any(got["pi"]["w"])
    b = np.asarray(got["feat"]["b"])
    assert 0.5 < b.std() / sizes["feature_bias_scale"] < 1.5
    strip = lambda p: {**p, "pi": p["pi"]["b"], "feat": p["feat"]["w"],  # noqa: E731
                       "lm": {**p["lm"], "tail": [
                           {**blk, "ffn": {n: v for n, v in blk["ffn"].items()
                                           if n not in ("router",
                                                        "expert_bias")}}
                           for blk in p["lm"]["tail"]]}}
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        strip(raw), strip(got))
