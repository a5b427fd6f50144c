"""impala-drltrunk: IMPALA with V-trace training the paper-drl-trunk
transformer policy (d_model 256, 4 layers, 4 heads over 2 KV heads,
head_dim 64, SwiGLU d_ff 512, float32) on on-device CartPole, AdamW at
lr 1e-3 behind a global-norm clip of 1.0.

The sizes are in `impala-drltrunk.json`; this file builds the program's
side from them, counts the work, and runs the plain reference
(`bench/reference/impala.py`) that decides `correct`.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import check, harness
from bench.reference import impala as ref_impala
from bench.reference import trunk as ref_trunk

# the cell's Pallas kernels, found in the trace by name or name stack
KERNEL_PATTERNS = {"flash_attention": r"^%flash_attention_hsd\b",
                   "vtrace": r"^%\w*vtrace_tb\w*"}
WEIGHT_SALT = 0x2545F491   # the weights' key: PRNGKey(seed ^ salt)


def _szt(sizes):
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "rope_theta", "obs_dim", "n_actions")
    return tuple((k, sizes[k]) for k in keys)


def _hpt(sizes):
    keys = ("gamma", "vf_coef", "ent_coef", "lr", "max_grad_norm")
    return tuple((k, sizes[k]) for k in keys)


def algorithm(sizes, traffic):
    return sizes["algorithm"], {
        "policy": "trunk", "trunk_kwargs": {"reduced": False},
        "lr": sizes["lr"], "max_grad_norm": sizes["max_grad_norm"],
        "gamma": sizes["gamma"], "vf_coef": sizes["vf_coef"],
        "ent_coef": sizes["ent_coef"], "clip_rho": sizes["clip_rho"],
        "clip_c": sizes["clip_c"]}


def weights(sizes, seed):
    """The benchmark's weights, made on the device in one jitted call."""
    key = jax.random.PRNGKey(seed ^ WEIGHT_SALT)
    return jax.jit(lambda k: ref_trunk.init(k, dict(_szt(sizes))))(key)


def make_state(agent, sizes, traffic, seed):
    """The carried TrainState around the benchmark's weights: the
    program's own optimizer state and actor ring, built from them."""
    from repro.core.agent import TrainState
    cfg = agent.policy.lm.cfg
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "rope_theta"):
        if getattr(cfg, k) != sizes[k]:
            raise ValueError(f"program's {cfg.name} has {k}="
                             f"{getattr(cfg, k)}, the configuration "
                             f"states {sizes[k]}")
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
    """The plain reference's readings after the same K iterations."""
    params0 = weights(sizes, seed)
    losses, params, opt, _ = ref_impala.follow(
        params0, jnp.int32(seed), key_seed=harness.PROGRAM_KEY_SEED,
        n=traffic["n_envs"], T=traffic["unroll"],
        iters=traffic["superstep"], szt=_szt(sizes), hpt=_hpt(sizes),
        dtype=dtype, precision=precision or sizes["matmul_precision"],
        fault=fault)
    return {"loss": [float(x) for x in jax.device_get(losses)[:1]],
            "change": check.change_norms(params, params0),
            "moment": check.leaf_norms(opt["m"])}


def compare(prog, ref, limits):
    """loss1_gap: the first step's loss against the reference's, relative:
    the rollout (policy forward with flash attention, the draw, the env
    step) and the learner's loss with V-trace on the same weights;
    change_gap: the worst leaf's gap between the norms of the two
    parameter changes after the first call. Leaves whose reference
    gradient is nought to rounding (under a thousandth of the median
    leaf's, by the optimizer's first moment) are left out."""
    keep = check.moving_leaves(ref["moment"])
    values = {"loss1_gap": check.worst_rel_gap(prog["loss"], ref["loss"]),
              "change_gap": check.worst_leaf_gap(prog["change"],
                                                 ref["change"], keep)}
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in values.items()]


def control_run(cell, seed, fault=""):
    """The control: the reference in bfloat16 in the program's place,
    compared as the program is. With `fault`, the reference with that
    fault planted ("half_batch") in its place instead."""
    sizes, traffic = cell["sizes"], cell["traffic"]
    if traffic["driver"] == "serve":
        return serve_control(cell, seed, fault)
    ref = reference(sizes, traffic, seed)
    ctl = (reference(sizes, traffic, seed, fault=fault) if fault else
           reference(sizes, traffic, seed, dtype=jnp.bfloat16))
    checks = compare(ctl, ref, traffic["limits"])
    return {"control": fault or "bfloat16", "seed": seed,
            "checks": {c["name"]: c["value"] for c in checks}}


# ------------------------------------------------------------- work
def flops_per_iter(sizes, traffic):
    """Model FLOPs of one iteration: a forward pass per env step in the
    rollout, forward and backward (3x) per sample in the learner, and
    the bootstrap forward per env. No recompute is counted."""
    n, T = traffic["n_envs"], traffic["unroll"]
    return ref_trunk.forward_flops(sizes) * (n * T + 3 * n * T + n)


def kernels(sizes, traffic, iters):
    """Work of the cell's Pallas kernels over `iters` iterations, per
    kernel: the least FLOPs and bytes the operation needs, whatever
    implements it (bench/roofline.py), and how many calls make it."""
    from bench import roofline
    n, T, L = traffic["n_envs"], traffic["unroll"], sizes["n_layers"]
    H, KVH, D, S = (sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
                    sizes["obs_dim"])
    # forward attention: every rollout step, the learner's forward over
    # all samples and its bootstrap forward, in each layer
    batches = [n] * T + [n * T, n]
    fa = [roofline.flash_attention_fwd(b, H, KVH, S, D) for b in batches]
    vt = roofline.vtrace(T, n)
    per_iter = {
        "flash_attention": {"flops": L * sum(w["flops"] for w in fa),
                            "bytes": L * sum(w["bytes"] for w in fa),
                            "calls": L * len(fa)},
        "vtrace": {"flops": vt["flops"], "bytes": vt["bytes"], "calls": 1}}
    return {k: {f: v * iters for f, v in w.items()}
            for k, w in per_iter.items()}


# ----------------------------------------------------------- serving
def serve_policy(sizes):
    """The policy the trained agent serves, and its env (for the
    observation spec)."""
    import repro.envs as envs
    from repro.core.agent import make
    env = envs.make(sizes["env"])
    agent = make(sizes["algorithm"], env=env,
                 **algorithm(sizes, {})[1])
    return agent.policy, env


def serve_flops(sizes):
    """Forward FLOPs of one served observation."""
    return ref_trunk.forward_flops(sizes)


def serve_reference(sizes, seed, rows, rids, dtype=jnp.float32,
                    precision=None):
    """The plain forward on each sampled request, its action drawn as
    the engine states it: categorical over the logits with the key
    fold_in(PRNGKey(seed), request id), that is argmax(logits + g).
    Returns per request the logits, the Gumbel noise, log-probs of
    every action and the value."""
    params = weights(sizes, seed)
    szt = _szt(sizes)

    @jax.jit
    def fwd(params, obs, ids):
        logits, value = ref_trunk.forward(
            params, obs, dict(szt), dtype,
            precision or sizes["matmul_precision"])
        base = jax.random.PRNGKey(seed)
        g = jax.vmap(lambda i: jax.random.gumbel(
            jax.random.fold_in(base, i), (logits.shape[-1],)))(ids)
        return logits, g, jax.nn.log_softmax(logits), value

    out = jax.device_get(fwd(params, jnp.asarray(rows),
                             jnp.asarray(rids, jnp.int32)))
    return dict(zip(("logits", "gumbel", "logp", "value"), out))


def serve_compare(got, ref, limits):
    """action_gap: the widest gap by which a served action's perturbed
    reference logit (logit + its Gumbel draw) lies below the reference's
    best, nought where every served action is the reference's draw;
    logp_rms: the root mean square of served log-prob - the reference's
    log-prob of that action; value_rms: the root mean square of served
    value - the reference's value, over the root mean square of the
    reference's values."""
    score = ref["logits"].astype(np.float64) + ref["gumbel"]
    a = np.asarray(got["action"]).astype(int)
    rows = np.arange(len(a))
    rms = lambda x: float(np.sqrt(np.mean(np.square(
        np.asarray(x, np.float64)))))
    values = {
        "action_gap": float(np.max(score.max(-1) - score[rows, a])),
        "logp_rms": rms(got["logp"] - ref["logp"][rows, a]),
        "value_rms": rms(got["value"] - ref["value"])
        / max(rms(ref["value"]), 1e-30)}
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in values.items()]


def serve_control(cell, seed, fault=""):
    """The reference in bfloat16 serving the first requests of the
    seed's traffic: its own draw, log-prob and value, compared as the
    program's are. With `fault` "value_zero", the float32 reference with
    its value head's answer zeroed, in the program's place instead."""
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py")
    sizes, traffic = cell["sizes"], cell["traffic"]
    n = traffic["check_requests"]
    rows = drv.observations(n, traffic, seed)
    rids = np.arange(n)
    ref = serve_reference(sizes, seed, rows, rids)
    low = (ref if fault else
           serve_reference(sizes, seed, rows, rids, dtype=jnp.bfloat16))
    a = np.argmax(low["logits"].astype(np.float64) + low["gumbel"], -1)
    got = {"action": a, "logp": low["logp"][np.arange(n), a],
           "value": low["value"] * (0.0 if fault == "value_zero" else 1.0)}
    checks = serve_compare(got, ref, traffic["limits"])
    return {"control": fault or "bfloat16", "seed": seed,
            "checks": {c["name"]: c["value"] for c in checks}}
