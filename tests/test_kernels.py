"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle in each kernel's ref.py (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.advantages import ops as adv_ops
from repro.kernels.advantages.ref import (discounted_return_ref, gae_ref,
                                          nstep_return_ref)
from repro.kernels.flash_attention.kernel import flash_attention_hsd
from repro.kernels.flash_attention.ops import flash_attention
from repro.models.moe import moe_gmm
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.replay_sample.kernel import TB
from repro.kernels.replay_sample.ops import prioritized_sample
from repro.kernels.replay_sample.ref import (prioritized_sample_ref,
                                             prioritized_weights_ref,
                                             shard_gumbel_topk_ref)
from repro.kernels.vtrace.ops import vtrace as vtrace_k
from repro.kernels.vtrace.ref import vtrace_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("B,H,KVH,S,D,causal,window", [
    (2, 4, 2, 256, 64, True, 0),
    (1, 4, 1, 256, 64, True, 64),      # sliding window, GQA kv=1
    (2, 2, 2, 128, 32, False, 0),      # non-causal (encoder)
    (1, 8, 4, 384, 128, True, 128),    # non-multiple S (padding path)
    (1, 2, 1, 512, 256, True, 0),      # gemma-style head_dim=256
])
def test_flash_attention_sweep(B, H, KVH, S, D, causal, window, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KVH, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KVH, S, D), jnp.float32)
    o = flash_attention_hsd(q, k, v, causal=causal, window=window)
    r = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_dtypes(dtype, rng):
    dt = jnp.dtype(dtype)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64)).astype(dt)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dt)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dt)
    o = flash_attention_hsd(q, k, v, causal=True)
    r = attention_ref(q, k, v, causal=True)
    atol = 3e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=atol)


def test_flash_wrapper_layout(rng):
    """(B,S,KVH,G,D) wrapper layout matches the model-side jnp path."""
    from repro.models.attention import causal_attention
    B, S, KVH, G, D = 1, 200, 2, 2, 32
    ks = jax.random.split(rng, 3)
    qg = jax.random.normal(ks[0], (B, S, KVH, G, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    o1 = flash_attention(qg, k, v, causal=True, bq=128, bk=128)
    o2 = causal_attention(qg, k, v, jnp.int32(0), n_q_chunks=4,
                          block_k=64)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


@pytest.mark.parametrize("S,causal,window", [
    (4, True, 0),                      # trunk feature mode (padded S)
    (128, True, 32),                   # sliding window
    (96, False, 0),                    # non-causal, padded S
])
def test_flash_custom_vjp_matches_ref_grad(S, causal, window, rng):
    """flash_attention's custom VJP (Pallas forward, backward through
    the oracle) gives the value and the gradients of `attention_ref` in
    the grouped layout, for every input."""
    from repro.kernels.flash_attention.ref import attention_ref_grouped
    B, KVH, G, D = 2, 2, 2, 32
    ks = jax.random.split(rng, 4)
    qg = jax.random.normal(ks[0], (B, S, KVH, G, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    ct = jax.random.normal(ks[3], (B, S, KVH, G, D))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, causal=causal, window=window)
                                  * ct)

    l1, g1 = jax.value_and_grad(loss(flash_attention), (0, 1, 2))(qg, k, v)
    l2, g2 = jax.value_and_grad(loss(attention_ref_grouped),
                                (0, 1, 2))(qg, k, v)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def _grouped_inputs(key, B, S, KVH=2, G=2, D=64):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, S, KVH, G, D)),
            jax.random.normal(ks[1], (B, S, KVH, D)),
            jax.random.normal(ks[2], (B, S, KVH, D)))


@pytest.mark.parametrize("S", [4, 8])
@pytest.mark.parametrize("B", [1, 4, 16, 256, 300])
def test_flash_short_sequence_parity(B, S, rng):
    """The short grid (a step per block of samples, all heads) matches
    the oracle at the trunk's widths with GQA G=2, causal, including a
    B (300) that the block of samples does not divide."""
    from repro.kernels.flash_attention.ref import attention_ref_grouped
    qg, k, v = _grouped_inputs(rng, B, S)
    o = flash_attention(qg, k, v, causal=True)
    r = attention_ref_grouped(qg, k, v, causal=True)
    np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,causal,window", [
    (7, 5, False, 0),                  # non-causal, S no sublane multiple
    (9, 8, True, 3),                   # sliding window inside a sample
    (40, 3, True, 0),
])
def test_flash_short_sequence_masks(B, S, causal, window, rng):
    """The short grid's causal, window and non-causal masks at odd S."""
    from repro.kernels.flash_attention.ref import attention_ref_grouped
    qg, k, v = _grouped_inputs(rng, B, S, D=32)
    o = flash_attention(qg, k, v, causal=causal, window=window)
    r = attention_ref_grouped(qg, k, v, causal=causal, window=window)
    np.testing.assert_allclose(o, r, atol=2e-5, rtol=2e-5)


def test_flash_short_grid_masks_padded_keys(rng):
    """`valid_len` on the short grid: keys past it, zero-padded by the
    caller, are never attended."""
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (3, 4, 5, 32))
    k = jax.random.normal(ks[1], (3, 2, 5, 32))
    v = jax.random.normal(ks[2], (3, 2, 5, 32))
    pad = ((0, 0), (0, 0), (0, 3), (0, 0))
    o = flash_attention_hsd(jnp.pad(q, pad), jnp.pad(k, pad),
                            jnp.pad(v, pad), causal=False, valid_len=5)
    r = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(o[:, :, :5], r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S", [(256, 4)])
def test_flash_short_custom_vjp(B, S, rng):
    """Value and gradients through the custom VJP on the short grid."""
    from repro.kernels.flash_attention.ref import attention_ref_grouped
    qg, k, v = _grouped_inputs(rng, B, S)
    ct = jax.random.normal(jax.random.fold_in(rng, 1), qg.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, causal=True) * ct)

    l1, g1 = jax.value_and_grad(loss(flash_attention), (0, 1, 2))(qg, k, v)
    l2, g2 = jax.value_and_grad(loss(attention_ref_grouped),
                                (0, 1, 2))(qg, k, v)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,grid,bb", [
    (1, 4, (1,), 1),                   # serving buckets: one step
    (16, 4, (1,), 16),
    (256, 4, (2,), 128),               # rollout
    (8192, 4, (64,), 128),             # learner forward
    (8, 512, (8, 4, 4, 4), None),      # long S keeps the tiled grid
])
def test_flash_plan_picks_grid(B, S, grid, bb):
    """The grid follows the shapes alone: trunk shapes (S=4, H=4, KVH=2,
    D=64, float32) step over blocks of samples, long S over (B, H, nq,
    nk)."""
    from repro.kernels.flash_attention.kernel import plan
    assert plan(B, 4, 2, S, 64) == (grid, bb)


@pytest.mark.parametrize("B,bb", [(32, 16), (1024, 16), (300, 15),
                                  (19, 19)])
def test_flash_plan_blocks_divide_batch(B, bb):
    """LFM2's heads (H=32, KVH=8) fit 19 samples a step; the step takes
    the most that divide B, so no block runs past the batch."""
    from repro.kernels.flash_attention.kernel import plan
    assert plan(B, 32, 8, 4, 64) == ((B // bb,), bb)


@pytest.mark.parametrize("B,S,KVH,G,D,causal,window", [
    (2, 128, 2, 2, 32, True, 0),
    (1, 256, 1, 4, 64, True, 64),      # sliding window, MQA kv=1
    (2, 96, 2, 1, 32, False, 0),       # non-causal, non-multiple S
])
def test_attention_dispatcher_parity(B, S, KVH, G, D, causal, window,
                                     rng):
    """core/attention.py dispatcher: ref path == Pallas kernel path in
    the trunk's (B, S, KVH, G, D) grouped-query layout."""
    from repro.core.attention import attention
    ks = jax.random.split(rng, 3)
    qg = jax.random.normal(ks[0], (B, S, KVH, G, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    o_ref = attention(qg, k, v, causal=causal, window=window,
                      use_kernel=False)
    o_ops = flash_attention(qg, k, v, causal=causal, window=window)
    assert o_ref.shape == (B, S, KVH, G, D)
    np.testing.assert_allclose(o_ref, o_ops, atol=2e-5, rtol=2e-5)


def test_attention_dispatcher_kernel_flag_off_tpu(rng):
    """use_kernel=True falls back to the ref path bitwise off-TPU
    (interpret-mode guard) — same convention as core/vtrace.py."""
    from repro.core.attention import attention
    from repro.kernels.common import interpret_mode
    assert interpret_mode()  # this suite never runs on TPU
    ks = jax.random.split(rng, 3)
    qg = jax.random.normal(ks[0], (1, 64, 2, 2, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    a = attention(qg, k, v, causal=True, use_kernel=True)
    b = attention(qg, k, v, causal=True, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- wkv6
@pytest.mark.parametrize("B,T,H,N,chunk", [
    (2, 100, 3, 16, 32),
    (1, 64, 2, 64, 64),
    (1, 37, 1, 8, 16),                 # padding path
])
def test_wkv6_sweep(B, T, H, N, chunk, rng):
    ks = jax.random.split(rng, 4)
    r = jax.random.normal(ks[0], (B, T, H, N))
    k = jax.random.normal(ks[1], (B, T, H, N))
    v = jax.random.normal(ks[2], (B, T, H, N))
    logw = -jnp.exp(0.5 * jax.random.normal(ks[3], (B, T, H, N)))
    u = 0.3 * jnp.ones((H, N))
    y_ref, _ = wkv6_ref(r, k, v, logw, u)
    y_k = wkv6(r, k, v, logw, u, chunk=chunk)
    np.testing.assert_allclose(y_k, y_ref, atol=2e-4, rtol=1e-3)


def test_wkv6_model_chunked_matches_ref(rng):
    from repro.models.rwkv6 import wkv_chunked
    B, T, H, N = 2, 50, 2, 16
    ks = jax.random.split(rng, 4)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) for i in range(3))
    logw = -jnp.exp(0.5 * jax.random.normal(ks[3], (B, T, H, N)))
    u = 0.1 * jnp.ones((H, N))
    state0 = 0.2 * jax.random.normal(rng, (B, H, N, N))
    y_ref, s_ref = wkv6_ref(r, k, v, logw, u, state0)
    y_c, s_c = wkv_chunked(r, k, v, logw, u, state0, chunk=16)
    np.testing.assert_allclose(y_c, y_ref, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(s_c, s_ref, atol=2e-4, rtol=1e-3)


# ----------------------------------------------------------------- gmm
def _gmm_loop(x, w, sizes):
    """Plain per-group products: rows of group g times w[g], then zeros."""
    out, r = np.zeros((x.shape[0], w.shape[-1]), np.float64), 0
    for g, n in enumerate(sizes):
        out[r:r + n] = np.asarray(x[r:r + n], np.float64) @ np.asarray(
            w[g], np.float64)
        r += n
    return out


@pytest.mark.parametrize("sizes,d,f", [
    ((3, 0, 20, 5, 12), 96, 130),      # an empty group, odd widths
    ((128, 128), 128, 128),
    ((1, 2, 3, 4, 5, 6, 7, 8), 512, 64),
])
def test_gmm_sweep(sizes, d, f, rng):
    """The experts' grouped matmul over ragged groups (and rows past
    them, which give zeros) against plain per-group products."""
    M = sum(sizes) + 7
    x = jax.random.normal(rng, (M, d))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (len(sizes), d, f))
    o = moe_gmm(x, w, jnp.asarray(sizes, jnp.int32))
    np.testing.assert_allclose(o, _gmm_loop(x, w, sizes), atol=3e-4,
                               rtol=1e-4)
    assert not np.any(np.asarray(o[sum(sizes):]))


def test_gmm_bf16(rng):
    x = jax.random.normal(rng, (64, 64)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(rng, 1), (2, 64, 64))
    o = moe_gmm(x, w, jnp.asarray([40, 24], jnp.int32))
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               _gmm_loop(x.astype(jnp.float32), w,
                                         (40, 24)), atol=0.2, rtol=0.05)


@pytest.mark.parametrize("sizes", [(64, 0, 0, 0), (0, 0, 0, 0),
                                   (16, 16, 16, 16)])
def test_gmm_skewed_groups(sizes, rng):
    """Every row in one group, no row in any, or an even split."""
    x = jax.random.normal(rng, (64, 128))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (4, 128, 128))
    np.testing.assert_allclose(moe_gmm(x, w, jnp.asarray(sizes, jnp.int32)),
                               _gmm_loop(x, w, sizes), atol=3e-4, rtol=1e-4)


def test_gmm_grad(rng):
    """Gradients of rows and weights against the per-group products'."""
    x = jax.random.normal(rng, (40, 64))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (3, 64, 128))
    sizes = (10, 0, 25)
    gs = jnp.asarray(sizes, jnp.int32)

    def dense(x, w):   # the same products as one masked einsum per group
        g = jnp.repeat(jnp.arange(3), jnp.asarray(sizes),
                       total_repeat_length=35)
        y = jnp.einsum("md,mdf->mf", x[:35], w[g])
        return jnp.concatenate([y, jnp.zeros((5, 128))])

    got = jax.grad(lambda x, w: jnp.sum(jnp.sin(moe_gmm(x, w, gs))),
                   (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(jnp.sin(dense(x, w))), (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------- vtrace
@pytest.mark.parametrize("T,B", [(37, 9), (64, 128), (128, 1)])
def test_vtrace_kernel_sweep(T, B, rng):
    ks = jax.random.split(rng, 4)
    lr = 0.3 * jax.random.normal(ks[0], (T, B))
    disc = 0.99 * (jax.random.uniform(ks[1], (T, B)) > 0.05)
    rew = jax.random.normal(ks[2], (T, B))
    val = jax.random.normal(ks[3], (T, B))
    boot = jax.random.normal(ks[0], (B,))
    vs1, a1 = vtrace_ref(lr, disc, rew, val, boot)
    vs2, a2 = vtrace_k(lr, disc, rew, val, boot)
    np.testing.assert_allclose(vs1, vs2, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(a1, a2, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------- advantages
def _adv_inputs(T, B, rng):
    ks = jax.random.split(rng, 4)
    rew = jax.random.normal(ks[0], (T, B))
    val = jax.random.normal(ks[1], (T, B))
    dones = jax.random.uniform(ks[2], (T, B)) < 0.1
    boot = jax.random.normal(ks[3], (B,))
    return rew, val, dones, boot


@pytest.mark.parametrize("T,B", [(37, 9), (64, 128), (128, 1)])
def test_advantages_kernel_sweep(T, B, rng):
    """The single reverse-scan kernel reproduces BOTH estimators built
    on it (GAE and n-step returns) against the scan oracle, including
    the non-multiple-of-bb padding path."""
    rew, val, dones, boot = _adv_inputs(T, B, rng)
    a1, r1 = gae_ref(rew, val, dones, boot, 0.99, 0.95)
    a2, r2 = adv_ops.gae(rew, val, dones, boot, 0.99, 0.95)
    np.testing.assert_allclose(a1, a2, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(r1, r2, atol=1e-5, rtol=1e-5)
    n1 = nstep_return_ref(rew, dones, boot, 0.99)
    n2 = adv_ops.nstep_return(rew, dones, boot, 0.99)
    np.testing.assert_allclose(n1, n2, atol=1e-5, rtol=1e-5)


def test_scan_kernels_differentiate_like_refs(rng):
    """Gradients through the kernel wrappers (interpret mode) equal the
    refs': n-step returns carry the bootstrap value's gradient (A3C's
    value loss), V-trace targets carry none (both stop-gradient)."""
    T, B = 16, 9
    rew, val, dones, boot = _adv_inputs(T, B, rng)
    ct = jax.random.normal(jax.random.fold_in(rng, 7), (T, B))

    def nstep_loss(fn):
        return lambda r, b: jnp.sum(fn(r, dones, b, 0.99) * ct)

    g1 = jax.grad(nstep_loss(adv_ops.nstep_return), (0, 1))(rew, boot)
    g2 = jax.grad(nstep_loss(nstep_return_ref), (0, 1))(rew, boot)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(g1[1]).sum()) > 0.0

    disc = 0.99 * (1.0 - dones.astype(jnp.float32))

    def vtrace_loss(fn):
        return lambda v, b: sum(jnp.sum(o * ct) for o in
                                fn(0.1 * rew, disc, rew, v, b))

    g1 = jax.grad(vtrace_loss(vtrace_k), (0, 1))(val, boot)
    g2 = jax.grad(vtrace_loss(vtrace_ref), (0, 1))(val, boot)
    for a, b in zip(g1, g2):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_advantages_generic_recurrence(rng):
    T, B = 50, 40
    ks = jax.random.split(rng, 3)
    base = jax.random.normal(ks[0], (T, B))
    coef = jax.random.uniform(ks[1], (T, B))
    init = jax.random.normal(ks[2], (B,))
    np.testing.assert_allclose(
        discounted_return_ref(base, coef, init),
        adv_ops.discounted_return(base, coef, init),
        atol=1e-5, rtol=1e-5)


def test_advantages_ref_pins_legacy_inline_scans(rng):
    """The oracle is BITWISE the scans that used to live inline in
    algos/ppo.py (GAE) and algos/a3c.py (n-step) — guards the
    'numerically unchanged training' acceptance criterion."""
    gamma, lam = 0.99, 0.95
    rew, val, dones, boot = _adv_inputs(33, 7, rng)
    values_tp1 = jnp.concatenate([val[1:], boot[None]], axis=0)
    nonterm = 1.0 - dones.astype(jnp.float32)
    deltas = rew + gamma * nonterm * values_tp1 - val

    def show(acc, xs):
        delta, nt = xs
        acc = delta + gamma * lam * nt * acc
        return acc, acc

    _, adv_legacy = jax.lax.scan(show, jnp.zeros_like(boot),
                                 (deltas, nonterm), reverse=True)
    adv, ret = gae_ref(rew, val, dones, boot, gamma, lam)
    assert np.array_equal(np.asarray(adv), np.asarray(adv_legacy))
    assert np.array_equal(np.asarray(ret), np.asarray(adv_legacy + val))

    disc = gamma * (1.0 - dones.astype(jnp.float32))

    def nstep_body(acc, xs):
        r, d = xs
        acc = r + d * acc
        return acc, acc

    _, ret_legacy = jax.lax.scan(nstep_body, boot, (rew, disc),
                                 reverse=True)
    assert np.array_equal(
        np.asarray(nstep_return_ref(rew, dones, boot, gamma)),
        np.asarray(ret_legacy))


# --------------------------------------------------------- replay_sample
_BLK = TB * 128      # slots in one block of the draw's tournament


def _shape_scores(kind, prio, gumbel):
    """Lay the top of the scores out over the tournament's blocks. Ties
    are exact: equal priority and equal Gumbel value."""
    if kind == "ties_across_blocks":        # lowest index first, and
        hot = jnp.array([3 * _BLK, 3 * _BLK - 1, 5 * _BLK + 7, _BLK,
                         _BLK - 1])         # a block's last before the
        return prio.at[hot].set(2.0), gumbel.at[hot].set(30.0)  # next's
    if kind == "ties_in_block":
        hot = 2 * _BLK + jnp.array([1000, 5, 300, 6, 1023])
        return prio.at[hot].set(2.0), gumbel.at[hot].set(30.0)
    if kind == "many_in_block":             # 41 picks re-scan block 3
        return prio, gumbel.at[3 * _BLK + jnp.arange(0, _BLK, 25)].add(12.0)
    if kind == "all_in_block":              # every pick from block 5
        return prio, gumbel.at[5 * _BLK:6 * _BLK].add(15.0)
    if kind == "surplus":   # the order kept, every score far from 0: a
        # surplus draw compares every filled slot's score, and near 0
        # the relative check would read the log's rounding, not the draw
        return _shape_scores("ties_across_blocks", prio, gumbel + 40.0)
    return prio, gumbel


_DRAW_PATTERNS = ("ties_across_blocks", "ties_in_block", "many_in_block",
                  "all_in_block")


@pytest.mark.parametrize("C,size,n,kind", [
    pytest.param(512, 300, 64, None, id="512-300-64"),
    pytest.param(2048, 2048, 128, None, id="2048-2048-128"),  # full buffer
    pytest.param(256, 17, 16, None, id="256-17-16"),  # nearly-empty
    pytest.param(131, 100, 1, None, id="131-100-1"),  # odd C, one draw
    pytest.param(64, 10, 32, None, id="64-10-32"),    # n > size fallback
    pytest.param(16384, 12000, 16, None,
                 id="16384-12000-16"),        # several row blocks
    # 16 tournament blocks: ties, and picks crowded into one block
    *(pytest.param(16 * _BLK, 12 * _BLK, 128, kind, id=kind)
      for kind in _DRAW_PATTERNS),
    pytest.param(16 * _BLK, _BLK + 6, _BLK + 16, "surplus",
                 id="surplus"),               # n > size over two blocks
])
def test_replay_sample_kernel_matches_ref(C, size, n, kind, rng):
    ks = jax.random.split(rng, 2)
    prio = jnp.abs(jax.random.normal(ks[0], (C,))) + 0.01
    gumbel = jax.random.gumbel(ks[1], (C,))
    prio, gumbel = _shape_scores(kind, prio, gumbel)
    i1, w1 = prioritized_sample_ref(prio, size, gumbel, n)
    i2, w2 = prioritized_sample(prio, jnp.int32(size), gumbel, n)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(w1, w2, atol=1e-5, rtol=1e-5)
    assert bool((i1 < size).all()), "never returns an unfilled slot"


def test_replay_sample_without_replacement_and_valid(rng):
    C, size, n = 512, 400, 64
    ks = jax.random.split(rng, 2)
    prio = jnp.abs(jax.random.normal(ks[0], (C,))) + 0.01
    idx, w = prioritized_sample(
        prio, jnp.int32(size), jax.random.gumbel(ks[1], (C,)), n)
    idx = np.asarray(idx)
    assert len(set(idx.tolist())) == n, "Gumbel-top-k: no replacement"
    assert (idx < size).all(), "must never sample unfilled slots"
    w = np.asarray(w)
    assert ((w > 0) & (w <= 1.0 + 1e-6)).all() and w.max() == \
        pytest.approx(1.0)


# ------------------------------------- sharded replay merge (PR 9 seam)
@pytest.mark.parametrize("size", [64, 33, 16, 7, 5, 1, 0])
def test_shard_topk_merge_matches_flat_sample(size, rng):
    """Per-shard top-k (shard_gumbel_topk_ref) -> shard-major concat ->
    global top-n -> degenerate rule -> prioritized_weights_ref is
    BITWISE the flat prioritized_sample_ref at every fill level —
    top_k's stable tie-break (lower input position wins) survives the
    merge because shard-major concat preserves global index order. Ties
    are forced in both priorities and Gumbel noise to exercise it."""
    C, R, n = 64, 4, 16
    chunk = C // R
    ks = jax.random.split(rng, 2)
    prio = jnp.abs(jax.random.normal(ks[0], (C,))) + 0.01
    prio = prio.at[1::7].set(prio[0])          # cross-shard prio ties
    gumbel = jax.random.gumbel(ks[1], (C,))
    gumbel = gumbel.at[1::7].set(gumbel[0])    # -> exact score ties
    fi, fw = prioritized_sample_ref(prio, size, gumbel, n)

    nvalid = max(size, 1)  # GLOBAL guard only: slot 0 of shard 0
    k = min(n, chunk)
    cand_s, cand_i = [], []
    for r in range(R):
        lv = int(np.clip(nvalid - r * chunk, 0, chunk))  # NO local guard
        s, li = shard_gumbel_topk_ref(prio[r * chunk:(r + 1) * chunk], lv,
                                      gumbel[r * chunk:(r + 1) * chunk],
                                      k)
        cand_s.append(s)
        cand_i.append(li + r * chunk)
    _, pos = jax.lax.top_k(jnp.concatenate(cand_s), n)
    idx = jnp.concatenate(cand_i)[pos]
    idx = jnp.where(jnp.arange(n) < nvalid, idx, idx[0]).astype(jnp.int32)
    w = prioritized_weights_ref(prio, size, idx)
    assert np.array_equal(np.asarray(fi), np.asarray(idx))
    assert np.array_equal(np.asarray(fw), np.asarray(w))


@pytest.mark.parametrize("C,nvalid,k,kind", [
    pytest.param(64, 40, 16, None, id="64-40-16"),  # one padded row block
    pytest.param(300, 300, 32, None, id="300-300-32"),  # full, odd C
    pytest.param(16384, 9000, 8, None,
                 id="16384-9000-8"),          # several row blocks
    pytest.param(64, 5, 16, None, id="64-5-16"),  # surplus: k > nvalid
    pytest.param(64, 0, 4, None, id="64-0-4"),    # empty shard: only -inf
    *(pytest.param(16 * _BLK, 12 * _BLK, 64, kind, id=kind)
      for kind in _DRAW_PATTERNS),
    pytest.param(16 * _BLK, _BLK + 6, _BLK + 16, "surplus",
                 id="surplus"),               # k > nvalid over two blocks
    pytest.param(16 * _BLK, 0, 16, "all_in_block", id="empty"),
])
def test_shard_topk_kernel_matches_ref(C, nvalid, k, kind, rng):
    """The per-shard Pallas candidate draw (interpret mode) picks the
    ref's indices bitwise, ties included, with the ref's scores to f32
    rounding (the kernel evaluates log on (R, 128) tiles)."""
    from repro.kernels.replay_sample.ops import shard_topk
    ks = jax.random.split(rng, 2)
    prio = jnp.abs(jax.random.normal(ks[0], (C,))) + 0.01
    prio = prio.at[1::7].set(prio[0])          # priority ties
    gumbel = jax.random.gumbel(ks[1], (C,))
    gumbel = gumbel.at[1::7].set(gumbel[0])    # -> exact score ties
    prio, gumbel = _shape_scores(kind, prio, gumbel)
    s1, i1 = shard_gumbel_topk_ref(prio, nvalid, gumbel, k)
    s2, i2 = shard_topk(prio, jnp.int32(nvalid), gumbel, k)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(s1, s2, rtol=1e-6)


def test_shard_topk_dispatcher_kernel_flag_off_tpu(rng):
    """core/replay_sample.py's shard_gumbel_topk dispatcher:
    use_kernel=True falls back to the ref bitwise off-TPU (interpret-
    mode guard), same convention as fused_prioritized_sample."""
    from repro.core.replay_sample import shard_gumbel_topk
    from repro.kernels.common import interpret_mode
    assert interpret_mode()  # this suite never runs on TPU
    ks = jax.random.split(rng, 2)
    prio = jnp.abs(jax.random.normal(ks[0], (128,))) + 0.01
    gumbel = jax.random.gumbel(ks[1], (128,))
    a = shard_gumbel_topk(prio, jnp.int32(70), gumbel, 16,
                          use_kernel=True)
    b = shard_gumbel_topk(prio, jnp.int32(70), gumbel, 16,
                          use_kernel=False)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ------------------------------- priority write-back round-trips (PR 9)
@pytest.mark.parametrize("fused", [False, True])
def test_replay_priority_writeback_round_trip(fused, rng):
    """sample -> TD errors -> update_priorities -> resample on both flat
    paths (legacy categorical, fused Gumbel-top-k): the write-back lands
    |td|+eps exactly on the sampled slots, leaves every other slot
    untouched, and the resample is deterministic and draws from the
    updated mass (a slot boosted to dominance must be drawn). TD values
    are a function of the index so categorical's with-replacement
    duplicates scatter identical values (deterministic on both paths)."""
    from repro.core.replay import PrioritizedReplay
    C, size, n = 128, 100, 32
    buf = PrioritizedReplay(C, fused=fused)
    ks = jax.random.split(rng, 3)
    state = buf.init({"obs": jnp.zeros((3,))})
    state = buf.add_batch(
        state, {"obs": jax.random.normal(ks[0], (size, 3))},
        jnp.abs(jax.random.normal(ks[1], (size,))) + 0.1)

    _, idx, _ = buf.sample(state, ks[2], n)
    td = (idx.astype(jnp.float32) + 1.0) * 0.1  # duplicate-safe
    state2 = buf.update_priorities(state, idx, td)
    prio = np.asarray(state2["prio"])
    np.testing.assert_allclose(prio[np.asarray(idx)],
                               np.abs(np.asarray(td)) + buf.eps,
                               rtol=1e-6)
    untouched = np.setdiff1d(np.arange(C), np.asarray(idx))
    np.testing.assert_array_equal(prio[untouched],
                                  np.asarray(state["prio"])[untouched])

    k2 = jax.random.fold_in(ks[2], 1)
    b1, i1, w1 = buf.sample(state2, k2, n)
    b2, i2, w2 = buf.sample(state2, k2, n)
    for a, b in zip(jax.tree_util.tree_leaves((b1, i1, w1)),
                    jax.tree_util.tree_leaves((b2, i2, w2))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    boosted = int(np.asarray(idx)[0])
    state3 = buf.update_priorities(
        state2, jnp.asarray([boosted]), jnp.asarray([1e6]))
    _, i3, _ = buf.sample(state3, jax.random.fold_in(k2, 2), n)
    assert boosted in np.asarray(i3).tolist()


def test_replay_writeback_state_identical_across_paths(rng):
    """Given the SAME sampled indices and TD errors, the categorical and
    fused buffers and the sharded service write bitwise-identical
    priority state — update_priorities is path-independent, so a
    checkpoint taken after write-back is portable across sampling paths
    and plans."""
    from repro.core.replay import PrioritizedReplay
    from repro.core.replay_service import ShardedPrioritizedReplay
    C, size, n = 64, 50, 16
    ks = jax.random.split(rng, 3)
    batch = {"obs": jax.random.normal(ks[0], (size, 3))}
    prio0 = jnp.abs(jax.random.normal(ks[1], (size,))) + 0.1
    cat = PrioritizedReplay(C, fused=False)
    fus = PrioritizedReplay(C, fused=True)
    svc = ShardedPrioritizedReplay(C, "rp", 4)
    cstate = cat.add_batch(cat.init({"obs": jnp.zeros((3,))}), batch,
                           prio0)
    fstate = fus.add_batch(fus.init({"obs": jnp.zeros((3,))}), batch,
                           prio0)
    _, idx, _ = fus.sample(fstate, ks[2], n)
    td = jax.random.normal(jax.random.fold_in(ks[2], 1), (n,))
    c2 = cat.update_priorities(cstate, idx, td)
    f2 = fus.update_priorities(fstate, idx, td)
    s2 = jax.vmap(svc.update_priorities, in_axes=(0, None, None),
                  axis_name="rp")(svc.shard_state(fstate), idx, td)
    np.testing.assert_array_equal(np.asarray(c2["prio"]),
                                  np.asarray(f2["prio"]))
    np.testing.assert_array_equal(
        np.asarray(f2["prio"]),
        np.asarray(svc.unshard_state(s2)["prio"]))
