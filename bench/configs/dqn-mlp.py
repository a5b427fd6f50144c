"""dqn-mlp: prioritized double DQN on the fused Gumbel-top-k draw with
Ape-X max-priority inserts, an MLP Q-net (64, 64) on on-device
CartPole, a 2^20-transition replay in HBM and a learner batch of 512.

The sizes are in `dqn-mlp.json`; this file builds the program's side
from them (weights and a full replay made by the benchmark from the
seed), counts the work, and runs the plain reference
(`bench/reference/dqn.py`) that decides `correct`.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import check, harness
from bench.reference import dqn as ref_dqn

KERNEL_PATTERNS = {"replay_sample": r"^%prioritized_sample_c\b"}
WEIGHT_SALT = 0x2545F491   # the weights' key: PRNGKey(seed ^ salt)
FILL_SALT = 0x51ED270B     # the replay's key: PRNGKey(seed ^ salt)
N_LOSSES = 3


def _hpt(sizes, traffic):
    return (("eps_start", sizes["eps_start"]), ("eps_end", sizes["eps_end"]),
            ("eps_decay_steps", _eps_decay(traffic)),
            ("batch", sizes["batch_size"]), ("alpha", sizes["alpha"]),
            ("beta", sizes["beta"]), ("prio_eps", sizes["prio_eps"]),
            ("gamma", sizes["gamma"]), ("lr", sizes["lr"]),
            ("target_update", sizes["target_update"]), ("qsteps0", 0))


def _eps_decay(traffic):
    """The agent's default: epsilon anneals over 60% of the horizon."""
    return max(1, int(0.6 * traffic["horizon_iters"]))


def algorithm(sizes, traffic):
    return sizes["algorithm"], {
        "hidden": tuple(sizes["hidden"]), "prioritized": True,
        "replay_capacity": sizes["replay_capacity"],
        "batch_size": sizes["batch_size"], "warmup": sizes["warmup"],
        "eps_start": sizes["eps_start"], "eps_end": sizes["eps_end"],
        "lr": sizes["lr"], "gamma": sizes["gamma"],
        "target_update": sizes["target_update"], "double": sizes["double"],
        "fused_sampling": sizes["fused_sampling"]}


def weights(sizes, seed):
    key = jax.random.PRNGKey(seed ^ WEIGHT_SALT)
    return jax.jit(lambda k: ref_dqn.init(k, sizes))(key)


def replay(sizes, seed):
    key = jax.random.PRNGKey(seed ^ FILL_SALT)
    return jax.jit(lambda k: ref_dqn.fill(k, sizes["replay_capacity"]))(key)


def make_state(agent, sizes, traffic, seed):
    """The carried TrainState: the benchmark's weights (online and
    target), the program's optimizer state and actor ring, and a full
    replay; the learner counter starts at `warmup`, so every iteration
    of the run learns."""
    from repro.core.agent import TrainState
    rp = agent.replay
    if (rp.capacity, rp.alpha, rp.beta, rp.eps, rp.fused) != (
            sizes["replay_capacity"], sizes["alpha"], sizes["beta"],
            sizes["prio_eps"], sizes["fused_sampling"]):
        raise ValueError("the program's replay differs from the "
                         "configuration")
    online = weights(sizes, seed)
    want = jax.eval_shape(agent.dqn.init, jax.random.PRNGKey(0))["online"]
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    if shape(want) != shape(online):
        raise ValueError("the benchmark's weights do not have the "
                         "program's parameter tree")
    params = {"online": online,
              "target": jax.tree_util.tree_map(jnp.copy, online),
              "steps": jnp.zeros((), jnp.int32)}
    return TrainState(params, agent.opt.init(online),
                      {"replay": replay(sizes, seed)},
                      agent._ring_init(online),
                      jnp.full((), sizes["warmup"], jnp.int32))


def _inserted(store, traffic):
    n = traffic["superstep"] * traffic["n_envs"] * traffic["unroll"]
    return jax.device_get(jax.tree_util.tree_map(lambda a: a[:n], store))


def observe(first, state, sizes, traffic, seed):
    rp = state.extra["replay"]
    return {"loss": [float(x) for x in first["loss"][:N_LOSSES]],
            "change": check.change_norms(state.params["online"],
                                         weights(sizes, seed)),
            "moment": check.leaf_norms(state.opt_state["m"]),
            "prio": np.asarray(jax.device_get(rp["prio"])),
            "inserted": _inserted(rp["store"], traffic)}


def reference(sizes, traffic, seed, dtype=jnp.float32, fault="",
              precision=None):
    online0 = weights(sizes, seed)
    losses, online, opt, rp = ref_dqn.follow(
        online0, replay(sizes, seed), jnp.int32(seed),
        key_seed=harness.PROGRAM_KEY_SEED, n=traffic["n_envs"],
        T=traffic["unroll"], iters=traffic["superstep"],
        step0=sizes["warmup"], hpt=_hpt(sizes, traffic), dtype=dtype,
        precision=precision or sizes["matmul_precision"], fault=fault)
    return {"loss": [float(x) for x in jax.device_get(losses)[:N_LOSSES]],
            "change": check.change_norms(online, online0),
            "moment": check.leaf_norms(opt["m"]),
            "prio": np.asarray(jax.device_get(rp["prio"])),
            "inserted": _inserted(rp["store"], traffic)}


def _insert_gap(p, r):
    """Worst field of the inserted transitions: largest difference over
    the field's largest magnitude; for actions and ends, the share of
    rows that differ."""
    gaps = []
    for k in ("obs", "next_obs", "reward"):
        a, b = np.asarray(p[k], np.float64), np.asarray(r[k], np.float64)
        gaps.append(float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)))
    for k in ("action", "done"):
        gaps.append(float(np.mean(np.asarray(p[k]) != np.asarray(r[k]))))
    return max(gaps)


def compare(prog, ref, limits):
    keep = check.moving_leaves(ref["moment"])
    pp, pr = prog["prio"].astype(np.float64), ref["prio"].astype(np.float64)
    values = {"loss_gap": check.worst_rel_gap(prog["loss"], ref["loss"]),
              "moment_gap": check.worst_leaf_gap(prog["moment"],
                                                 ref["moment"], keep),
              "change_gap": check.worst_leaf_gap(prog["change"],
                                                 ref["change"], keep),
              "prio_gap": float(np.max(np.abs(pp - pr)) / np.max(pr)),
              "insert_gap": _insert_gap(prog["inserted"], ref["inserted"])}
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in values.items()]


def control_run(cell, seed, fault=""):
    """The control: the reference in bfloat16 in the program's place,
    compared as the program is. With `fault` ("half_batch"), the
    reference with that fault planted in its place instead."""
    sizes, traffic = cell["sizes"], cell["traffic"]
    ref = reference(sizes, traffic, seed)
    ctl = (reference(sizes, traffic, seed, fault=fault) if fault else
           reference(sizes, traffic, seed, dtype=jnp.bfloat16))
    checks = compare(ctl, ref, traffic["limits"])
    return {"control": fault or "bfloat16", "seed": seed,
            "checks": {c["name"]: c["value"] for c in checks}}


# ------------------------------------------------------------- work
def _mlp_flops(sizes):
    dims = [sizes["obs_dim"]] + list(sizes["hidden"]) + [sizes["n_actions"]]
    return sum(2 * a * b for a, b in zip(dims, dims[1:]))


def flops_per_iter(sizes, traffic):
    """Model FLOPs of one iteration: a Q forward per env step in the
    rollout; in the learner, per drawn sample the online forward and
    backward (3x) on s and one forward each of the online and target
    nets on s'."""
    f = _mlp_flops(sizes)
    return f * (traffic["n_envs"] * traffic["unroll"]
                + 5 * sizes["batch_size"])


def kernels(sizes, traffic, iters):
    from bench import roofline
    w = roofline.prioritized_sample(sizes["replay_capacity"],
                                    sizes["batch_size"])
    return {"replay_sample": {"flops": w["flops"] * iters,
                              "bytes": w["bytes"] * iters, "calls": iters}}
