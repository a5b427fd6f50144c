"""lfm2-8b-a1b: IMPALA with V-trace training LFM2-8B-A1B as the policy
trunk, at its published widths, cut to one chip's share of a four-chip
expert-parallel deployment: layers 0-5 (conv + dense twice, then attention
and three convs, each with experts), experts 0-7 of each layer's 32, and
8,192 rows of the vocabulary; float32 on on-device CartPole, AdamW at lr
1e-6 behind a global-norm clip of 1.0. Each expert layer's experts are
placed so that the 8 this chip holds take a quarter of the load.

The sizes are in `lfm2-8b-a1b.json` under the published config's keys;
this file builds the program's side from them, counts the work, and
runs the plain reference (`bench/reference/impala_lfm2.py` over
`bench/reference/lfm2.py`) that decides `correct`.
"""
import functools
import sys

import jax
import jax.numpy as jnp

from bench import check, harness
from bench import roofline
from bench.reference import impala_lfm2 as ref_impala
from bench.reference import lfm2 as ref_lfm2

# the cell's kernels, found in the trace by name: the Pallas ones by the
# jitted function whose root they are (under the learner's value_and_grad
# the name gains a prefix, `%jvp_jit_flash_attention_hsd__.N`), the
# experts' grouped matmul by the name XLA gives its ragged dot, forward
# and backward
KERNEL_PATTERNS = {"flash_attention": r"^%\w*flash_attention_hsd\w*",
                   "vtrace": r"^%\w*vtrace_tb\w*",
                   "moe_gmm": r"^%ragged-dot-none\b"}
WEIGHT_SALT = 0x2545F491   # the weights' key: PRNGKey(seed ^ salt)
# the placement's states: PLACE_ENVS envs stepped PLACE_STEPS times under
# random actions, from fold_in(weights' key, PLACE_SALT)
PLACE_SALT, PLACE_ENVS, PLACE_STEPS = 0x91ACE, 64, 32
FEAT_SALT = 0xFEA7         # the feature lift's bias: fold_in(weights' key, salt)
FAULTS = ("all_experts", "no_expert_bias")


def _szt(sizes):
    """The reference's sizes, from the published config's keys."""
    sz = {"n_layers": sizes["num_hidden_layers"],
          "d_model": sizes["hidden_size"],
          "n_heads": sizes["num_attention_heads"],
          "n_kv_heads": sizes["num_key_value_heads"],
          "head_dim": sizes["head_dim"],
          "d_ff": sizes["intermediate_size"],
          "expert_d_ff": sizes["moe_intermediate_size"],
          "n_experts": sizes["num_experts_published"],
          "held": tuple(sizes["experts_held"]),
          "top_k": sizes["num_experts_per_tok"],
          "first_dense": sizes["num_dense_layers"],
          "layer_types": tuple("conv" if t == "conv" else "attn"
                               for t in sizes["layer_types"]),
          "conv_L": sizes["conv_L_cache"],
          "eps": sizes["norm_eps"],
          "rope_theta": float(sizes["rope_theta"]),
          "routed_scale": float(sizes["routed_scaling_factor"]),
          "vocab": sizes["vocab_size"],
          "bias_scale": sizes["bias_scale"],
          "feature_bias_scale": sizes["feature_bias_scale"],
          "obs_dim": sizes["obs_dim"],
          "n_actions": sizes["n_actions"]}
    return tuple(sz.items())


def _hpt(sizes):
    keys = ("gamma", "vf_coef", "ent_coef", "lr", "max_grad_norm")
    return tuple((k, sizes[k]) for k in keys)


def algorithm(sizes, traffic):
    return sizes["algorithm"], {
        "policy": "trunk",
        "trunk_kwargs": {"arch": sizes["arch"],
                         "reduced": sizes["arch_reduced"],
                         "n_layers": sizes["num_hidden_layers"],
                         "vocab": sizes["vocab_size"],
                         "experts_held": tuple(sizes["experts_held"])},
        "lr": sizes["lr"], "max_grad_norm": sizes["max_grad_norm"],
        "gamma": sizes["gamma"], "vf_coef": sizes["vf_coef"],
        "ent_coef": sizes["ent_coef"], "clip_rho": sizes["clip_rho"],
        "clip_c": sizes["clip_c"]}


def _wkey(seed):
    return jax.random.PRNGKey(seed ^ WEIGHT_SALT)


@functools.lru_cache(maxsize=None)
def _make_weights(szt, precision):
    sz = dict(szt)

    def make(key):
        p = ref_lfm2.init(key, sz)
        b = p["feat"]["b"]
        p = {**p, "pi": {**p["pi"], "w": jnp.zeros_like(p["pi"]["w"])},
             "feat": {**p["feat"], "b": sz["feature_bias_scale"]
                      * jax.random.normal(jax.random.fold_in(key, FEAT_SALT),
                                          b.shape)}}
        states = ref_impala.random_states(jax.random.fold_in(key, PLACE_SALT),
                                          PLACE_ENVS, PLACE_STEPS)
        return ref_lfm2.place(p, states, sz, precision)

    return jax.jit(make)


def weights(sizes, seed):
    """The benchmark's weights, made on the device in one jitted call:
    drawn from the seed (`lfm2.init`), but with the policy head at zero
    and the feature lift's bias normal at `feature_bias_scale`; then each
    expert layer's router columns and bias placed (`lfm2.place`) on the
    tokens of CartPole states that a random policy visits, so that the
    experts this chip holds take its balanced share of the load.

    A zero policy head makes the first rollout's actions the keys' alone,
    so a routing tie that the program and the reference break apart
    changes values, not which episodes are run. A zero lift bias would
    leave RMSNorm only each feature's sign: some thirty distinct tokens,
    which reach a number of held experts a rollout step that swings with
    the seed."""
    return _make_weights(_szt(sizes), sizes["matmul_precision"])(
        _wkey(seed))


def _program_config_matches(cfg, sizes):
    """Raise if the program's config is not the one the JSON states."""
    m = cfg.moe
    sz = dict(_szt(sizes))
    pairs = {"n_layers": (cfg.n_layers, sz["n_layers"]),
             "d_model": (cfg.d_model, sz["d_model"]),
             "n_heads": (cfg.n_heads, sz["n_heads"]),
             "n_kv_heads": (cfg.n_kv_heads, sz["n_kv_heads"]),
             "head_dim": (cfg.head_dim, sz["head_dim"]),
             "d_ff": (cfg.d_ff, sz["d_ff"]),
             "vocab": (cfg.vocab, sz["vocab"]),
             "rope_theta": (cfg.rope_theta, sz["rope_theta"]),
             "norm_eps": (cfg.norm_eps, sz["eps"]),
             "conv_cache": (cfg.conv_cache, sz["conv_L"]),
             "qk_norm": (cfg.qk_norm, True),
             "tie_embeddings": (cfg.tie_embeddings, sizes["tie_embedding"]),
             "layer kinds": (tuple("conv" if k == "conv" else "attn"
                                   for k in cfg.pattern()),
                             sz["layer_types"]),
             "moe.n_experts": (m.n_experts, sz["n_experts"]),
             "moe.held": (m.held_range(), sz["held"]),
             "moe.n_held": (m.n_held, sizes["num_experts"]),
             "moe.top_k": (m.top_k, sz["top_k"]),
             "moe.d_ff": (m.d_ff, sz["expert_d_ff"]),
             "moe.first_dense": (m.first_dense, sz["first_dense"]),
             "moe.score": (m.score, sizes["score"]),
             "moe.expert_bias": (m.expert_bias, sizes["use_expert_bias"]),
             "moe.routed_scale": (m.routed_scale, sz["routed_scale"]),
             "moe.n_shared": (m.n_shared, 0)}
    for k, (got, want) in pairs.items():
        if got != want:
            raise ValueError(f"program's {cfg.name} has {k}={got}, the "
                             f"configuration states {want}")


def make_state(agent, sizes, traffic, seed):
    """The carried TrainState around the benchmark's weights: the
    program's own optimizer state and actor ring, built from them."""
    from repro.core.agent import TrainState
    _program_config_matches(agent.policy.lm.cfg, sizes)
    params = weights(sizes, seed)
    want = jax.eval_shape(agent.policy.init, jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want) != got:
        raise ValueError("the benchmark's weights do not have the "
                         "program's parameter tree")
    return TrainState(params, agent.opt.init(params), {},
                      agent._ring_init(params), jnp.zeros((), jnp.int32))


def observe(first, state, sizes, traffic, seed):
    """The program's readings after its first call: the first step's
    loss, and per leaf the norm of the parameters' change."""
    return {"loss": [float(x) for x in first["loss"][:1]],
            "change": check.change_norms(state.params,
                                         weights(sizes, seed))}


def reference(sizes, traffic, seed, dtype=jnp.float32, fault="",
              precision=None):
    """The plain reference's readings after the same K iterations. The
    sound float32 reference also reports, on stderr, how the first
    rollout's tokens were routed (`lfm2.routing`)."""
    params0 = weights(sizes, seed)
    szt = _szt(sizes)
    precision = precision or sizes["matmul_precision"]
    losses, params, opt, obs = ref_impala.follow(
        params0, jnp.int32(seed), _wkey(seed),
        key_seed=harness.PROGRAM_KEY_SEED, n=traffic["n_envs"],
        T=traffic["unroll"], iters=traffic["superstep"], szt=szt,
        hpt=_hpt(sizes), dtype=dtype, precision=precision, fault=fault)
    out = {"loss": [float(x) for x in jax.device_get(losses)[:1]],
           "change": check.change_norms(params, params0),
           "moment": check.leaf_norms(opt["m"])}
    if not fault and dtype == jnp.float32:
        stats = jax.device_get(jax.jit(
            lambda p, o: ref_lfm2.routing(p, o, dict(szt), precision))(
                params0, obs.reshape(-1, obs.shape[-1])))
        out["routing"] = [[float(a), float(b)] for a, b in stats]
        sys.stderr.write("routing (held share, share changed without the "
                         f"bias) per expert layer: {out['routing']}\n")
    return out


def compare(prog, ref, limits):
    """loss1_gap: the first step's loss against the reference's, relative:
    the rollout (policy forward with flash attention and the expert
    layers, the draw, the env step) and the learner's loss with V-trace
    on the same weights; change_gap: the worst leaf's gap between the
    norms of the two parameter changes after the first call. Leaves whose
    reference gradient is nought to rounding (under a thousandth of the
    median leaf's, by the optimizer's first moment: the expert bias, the
    token table) are left out."""
    keep = check.moving_leaves(ref["moment"])
    values = {"loss1_gap": check.worst_rel_gap(prog["loss"], ref["loss"]),
              "change_gap": check.worst_leaf_gap(prog["change"],
                                                 ref["change"], keep)}
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in values.items()]


def control_run(cell, seed, fault=""):
    """The control: the reference in bfloat16 in the program's place,
    compared as the program is. With `fault` ("all_experts",
    "no_expert_bias"), the float32 reference with that fault planted in
    its place instead."""
    sizes, traffic = cell["sizes"], cell["traffic"]
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    ref = reference(sizes, traffic, seed)
    ctl = (reference(sizes, traffic, seed, fault=fault) if fault else
           reference(sizes, traffic, seed, dtype=jnp.bfloat16))
    checks = compare(ctl, ref, traffic["limits"])
    return {"control": fault or "bfloat16", "seed": seed,
            "routing": ref["routing"],
            "checks": {c["name"]: c["value"] for c in checks}}


# ------------------------------------------------------------- work
def flops_per_iter(sizes, traffic):
    """Model FLOPs of one iteration: a forward pass per env step in the
    rollout, forward and backward (3x) per sample in the learner, and
    the bootstrap forward per env; the experts at the held share of the
    top-k. No recompute is counted."""
    n, T = traffic["n_envs"], traffic["unroll"]
    return ref_lfm2.forward_flops(dict(_szt(sizes))) * (n * T + 3 * n * T
                                                        + n)


def gmm_work(rows, d, f, groups, itemsize=4):
    """A grouped matmul of `rows` rows against `groups` (d, f) weight
    blocks: 2 rows d f FLOPs; the rows read, each weight block read and
    the output written once."""
    return {"flops": 2 * rows * d * f,
            "bytes": itemsize * (rows * d + groups * d * f + rows * f)}


def kernels(sizes, traffic, iters):
    """Work of the cell's kernels over `iters` iterations, per kernel:
    the least FLOPs and bytes the operation needs, whatever implements it
    (`bench/roofline.py`, `gmm_work`), and how many calls make it.
    Attention: forward calls only (its backward runs the oracle in XLA).
    The grouped matmul: every forward call, and the learner's backward,
    whose two ragged dots a product (the rows' gradient and the
    weights') each take the forward's work. The experts' rows are the
    tokens x top-k x held / published experts."""
    sz = dict(_szt(sizes))
    n, T = traffic["n_envs"], traffic["unroll"]
    H, KVH, D, S = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"], \
        sz["obs_dim"]
    d, f, (lo, hi) = sz["d_model"], sz["expert_d_ff"], sz["held"]
    n_attn = sum(k == "attn" for k in sz["layer_types"])
    n_moe = sz["n_layers"] - sz["first_dense"]
    share = sz["top_k"] * (hi - lo) / sz["n_experts"]
    # every rollout step, the learner's forward over all samples and its
    # bootstrap forward
    batches = [n] * T + [n * T, n]
    fa = [roofline.flash_attention_fwd(b, H, KVH, S, D) for b in batches]
    products = lambda b: (gmm_work(b * S * share, d, f, hi - lo),
                          gmm_work(b * S * share, d, f, hi - lo),
                          gmm_work(b * S * share, f, d, hi - lo))
    mm = [w for b in batches for w in products(b)]
    mm += [w for w in products(n * T) for _ in range(2)]
    vt = roofline.vtrace(T, n)
    per_iter = {
        "flash_attention": {"flops": n_attn * sum(w["flops"] for w in fa),
                            "bytes": n_attn * sum(w["bytes"] for w in fa),
                            "calls": n_attn * len(fa)},
        "moe_gmm": {"flops": n_moe * sum(w["flops"] for w in mm),
                    "bytes": n_moe * sum(w["bytes"] for w in mm),
                    "calls": n_moe * len(mm)},
        "vtrace": {"flops": vt["flops"], "bytes": vt["bytes"], "calls": 1}}
    return {k: {f_: v * iters for f_, v in w.items()}
            for k, w in per_iter.items()}
