#!/usr/bin/env python3
"""Prove on a TPU that the training and serving path runs end to end.

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # four chips: the sharded plans only

Everything runs in this one process (a chip belongs to one process at a
time) through the entry points a user calls: `Trainer.fit` under a
`DistPlan`, `ParamStore` and `ServeEngine`. Weights are random, made
from a fixed seed.

One chip:
  a. IMPALA with the full-width paper-drl-trunk (d_model 256, 4 layers)
     on cartpole, 256 envs x unroll 32: finite losses, and the compiled
     superstep holds Pallas kernels (`tpu_custom_call`), so the flash
     attention and V-trace kernels ran rather than their references.
  b. Serving (a)'s trained policy through ServeEngine: 64 requests over
     every bucket with a hot-swap halfway, finite actions, version tags,
     no compile after warmup.
  c. PPO (MLP, 4096 envs; the GAE kernel), A3C (the n-step kernel) and
     prioritized DQN with a 2^20-slot replay (the fused sampling
     kernel), each a few supersteps with the same checks as (a).
  d. Each kernel against its ref.py oracle on seeded inputs: the max
     absolute difference must stay under a stated tolerance.

Four chips (--chips 4): the trunk under IMPALA with ZeRO-3 sharding
against plain data parallelism over four chips, and DQN with the
sharded replay service against two data-parallel chips; prints the
largest loss difference of each pair and every device's bytes in use.

Prints one line per phase; set-up and compile seconds are not speed
measurements. The last line is a JSON object with "ok" and the device.
Exits non-zero, printing no such line, when JAX finds no TPU.
"""
import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRUNK = {"policy": "trunk", "trunk_kwargs": {"reduced": False}}
# Ape-X-sized replay and learner batch; 2 collection-only iterations so
# the learner updates (and reports a loss) in the remaining ones
DQN = {"replay_capacity": 2 ** 20, "batch_size": 512, "warmup": 2}
ITERS, SUPERSTEP = 6, 2          # three dispatches; the first compiles
# tolerances of phase (d), max |kernel - ref|. Attention: the kernel's
# f32 matmuls may run as bf16 MXU passes; the ref runs at "highest".
ATTN_TOL, SCAN_TOL, WEIGHT_TOL = 2e-2, 1e-4, 1e-4
# --chips 4: a paired plan must reproduce the other's losses this
# closely (relative); they differ only in reduction order
PAIR_RTOL = 5e-2
SEED = 0                         # weights, envs and kernel inputs


def say(msg):
    print(msg, flush=True)


def losses(history):
    return [h["loss"] for h in history]


def train(env_name, algo, n_envs, unroll, algo_kwargs, plan=None):
    """Fit through the Trainer. Returns (trainer, env, state, history,
    compiled superstep HLO text, timings)."""
    import repro.envs as envs
    from repro.core.trainer import Trainer, TrainerConfig
    t0 = time.perf_counter()
    cfg = TrainerConfig(algo=algo, iters=ITERS, superstep=SUPERSTEP,
                        n_envs=n_envs, unroll=unroll, plan=plan, seed=SEED,
                        log_every=1, algo_kwargs=algo_kwargs)
    env = envs.make(env_name)
    trainer = Trainer(env, cfg)
    t1 = time.perf_counter()
    hlo = trainer.lower().compile().as_text()
    t2 = time.perf_counter()
    state, history = trainer.fit()
    t3 = time.perf_counter()
    ls = losses(history)
    if not ls or not all(math.isfinite(x) for x in ls):
        raise RuntimeError(f"{algo} on {env_name}: non-finite losses {ls}")
    times = {"build_s": t1 - t0, "compile_s": t2 - t1, "fit_s": t3 - t2}
    return trainer, env, state, history, hlo, times


def count_kernels(tag, label, hlo):
    """Pallas kernels in a compiled superstep; none means the step ran
    the references (or interpret mode) and fails the phase."""
    n_kernels = hlo.count("tpu_custom_call")
    if not n_kernels:
        raise RuntimeError(f"[{tag}] {label}: no Pallas kernel "
                           f"(tpu_custom_call) in the compiled superstep")
    return n_kernels


def phase_train(tag, label, *args):
    trainer, env, state, history, hlo, times = train(*args)
    say(f"[{tag}] {label}: losses {losses(history)} "
        f"tpu_custom_call x{count_kernels(tag, label, hlo)}")
    say(f"[{tag}] setup: build {times['build_s']:.2f} s, compile "
        f"{times['compile_s']:.2f} s, fit {times['fit_s']:.2f} s "
        f"(fit includes its own compile or cache read)")
    return trainer, env, state


def phase_serve(trainer, env, state):
    """(b) serve the trained policy across every bucket with a
    hot-swap halfway; compile_count must stay flat after warmup."""
    import jax
    import numpy as np
    from repro.core.serving import ParamStore, ServeEngine
    t0 = time.perf_counter()
    store = ParamStore()
    v1 = store.publish_from_state(trainer.agent, state)
    engine = ServeEngine.for_agent(trainer.agent, env, buckets=(1, 4, 16),
                                   store=store, seed=SEED)
    warm = engine.warmup()
    t1 = time.perf_counter()
    obs = np.asarray(jax.vmap(env.obs)(
        env.reset_batch(jax.random.PRNGKey(SEED), 64)))
    groups = [1, 3, 4, 9, 16, 2, 13, 16]            # 64 requests
    responses, start = [], 0
    v2 = None
    for g_i, g in enumerate(groups):
        if g_i == len(groups) // 2:
            v2 = store.publish_from_state(trainer.agent, state)
        for row in obs[start:start + g]:
            engine.submit(row)
        start += g
        responses.extend(engine.drain())
    t2 = time.perf_counter()
    if len(responses) != 64:
        raise RuntimeError(f"[b] answered {len(responses)} of 64 requests")
    bad = [r["id"] for r in responses
           if not (np.all(np.isfinite(np.asarray(r["action"], np.float64)))
                   and math.isfinite(r["logp"])
                   and math.isfinite(r["value"]))]
    if bad:
        raise RuntimeError(f"[b] non-finite responses for ids {bad}")
    versions = sorted({r["version"] for r in responses})
    if versions != [v1, v2]:
        raise RuntimeError(f"[b] version tags {versions}, expected "
                           f"{[v1, v2]}")
    if engine.compile_count != warm:
        raise RuntimeError(f"[b] {engine.compile_count - warm} compiles "
                           f"after warmup")
    say(f"[b] serve: 64 requests over buckets {engine.buckets}, versions "
        f"{versions}, compile_count {engine.compile_count} flat after "
        f"warmup, all actions finite")
    say(f"[b] setup: build+warmup {t1 - t0:.2f} s, serve {t2 - t1:.2f} s")


def _maxdiff(a, b):
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                                - np.asarray(b, np.float64))))


def _check(name, diff, tol):
    say(f"[d] {name}: max|kernel-ref| = {diff!r} (tol {tol})")
    if not diff <= tol:
        raise RuntimeError(f"[d] {name}: {diff} > {tol}")


def phase_kernels():
    """(d) each Pallas kernel against its ref.py oracle, same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.advantages import ops as adv
    from repro.kernels.advantages.ref import gae_ref, nstep_return_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref_grouped
    from repro.kernels.replay_sample.ops import (prioritized_sample,
                                                 shard_topk)
    from repro.kernels.replay_sample.ref import (prioritized_sample_ref,
                                                 shard_gumbel_topk_ref)
    from repro.kernels.vtrace.ops import vtrace
    from repro.kernels.vtrace.ref import vtrace_ref
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(SEED)
    ks = iter(jax.random.split(key, 32))
    nrm = lambda shape: jax.random.normal(next(ks), shape)
    highest = jax.default_matmul_precision("highest")

    # flash attention at paper-drl-trunk widths: trunk S=4 and long S
    for B, S in ((256, 4), (4, 512)):
        qg, k, v = nrm((B, S, 2, 2, 64)), nrm((B, S, 2, 64)), nrm((B, S, 2,
                                                                    64))
        ct = nrm((B, S, 2, 2, 64))
        loss = lambda f: (lambda *a: jnp.sum(f(*a) * ct))
        o = jax.jit(flash_attention)(qg, k, v)
        g = jax.jit(jax.grad(loss(flash_attention), (0, 1, 2)))(qg, k, v)
        with highest:
            o_r = jax.jit(attention_ref_grouped)(qg, k, v)
            g_r = jax.jit(jax.grad(loss(attention_ref_grouped),
                                   (0, 1, 2)))(qg, k, v)
        _check(f"flash_attention fwd B={B} S={S}", _maxdiff(o, o_r),
               ATTN_TOL)
        _check(f"flash_attention grad B={B} S={S}",
               max(_maxdiff(a, b) for a, b in zip(g, g_r)), ATTN_TOL)

    T, B = 32, 1024
    lr, rew, val = 0.3 * nrm((T, B)), nrm((T, B)), nrm((T, B))
    disc = 0.99 * (jax.random.uniform(next(ks), (T, B)) > 0.05)
    boot = nrm((B,))
    out, ref = jax.jit(vtrace)(lr, disc, rew, val, boot), \
        jax.jit(vtrace_ref)(lr, disc, rew, val, boot)
    _check(f"vtrace T={T} B={B}",
           max(_maxdiff(a, b) for a, b in zip(out, ref)), SCAN_TOL)

    T, B = 32, 4096
    rew, val, boot = nrm((T, B)), nrm((T, B)), nrm((B,))
    dones = jax.random.uniform(next(ks), (T, B)) < 0.05
    out = jax.jit(adv.gae)(rew, val, dones, boot)
    ref = jax.jit(gae_ref)(rew, val, dones, boot)
    _check(f"gae T={T} B={B}",
           max(_maxdiff(a, b) for a, b in zip(out, ref)), SCAN_TOL)
    _check(f"nstep_return T={T} B={B}",
           _maxdiff(jax.jit(adv.nstep_return)(rew, dones, boot),
                    jax.jit(nstep_return_ref)(rew, dones, boot)), SCAN_TOL)

    # fused prioritized sampling at the Nature-DQN replay size; the
    # draw must pick the ref's slots (compared as sets: the kernel's
    # log may differ from XLA's in the last ulp, which can swap the
    # order of two near-tied picks) with the ref's weights
    C, n = 2 ** 20, 512
    prio = jnp.abs(nrm((C,))) + 0.01
    gumbel = jax.random.gumbel(next(ks), (C,))
    size = jnp.int32(700_000)
    i_k, w_k = jax.jit(prioritized_sample, static_argnums=3)(
        prio, size, gumbel, n)
    i_r, w_r = jax.jit(prioritized_sample_ref, static_argnums=3)(
        prio, size, gumbel, n)
    i_k, w_k, i_r, w_r = map(np.asarray, (i_k, w_k, i_r, w_r))
    if set(i_k.tolist()) != set(i_r.tolist()):
        raise RuntimeError("[d] prioritized_sample drew other slots than "
                           "the ref")
    ok, orr = np.argsort(i_k), np.argsort(i_r)
    say(f"[d] prioritized_sample C=2^20 n={n}: same {n} slots, "
        f"{int(np.sum(i_k == i_r))}/{n} in the ref's order")
    _check(f"prioritized_sample weights C=2^20 n={n}",
           _maxdiff(w_k[ok], w_r[orr]), WEIGHT_TOL)

    chunk = C // 2
    s_k, j_k = jax.jit(shard_topk, static_argnums=3)(
        prio[:chunk], jnp.int32(300_000), gumbel[:chunk], n)
    s_r, j_r = jax.jit(shard_gumbel_topk_ref, static_argnums=3)(
        prio[:chunk], jnp.int32(300_000), gumbel[:chunk], n)
    if set(np.asarray(j_k).tolist()) != set(np.asarray(j_r).tolist()):
        raise RuntimeError("[d] shard_topk drew other slots than the ref")
    _check(f"shard_topk scores chunk=2^19 k={n}", _maxdiff(s_k, s_r),
           WEIGHT_TOL)
    say(f"[d] setup: compile+run {time.perf_counter() - t0:.2f} s")


def memory_line(tag, label, trainer):
    """Bytes each device gains when the plan's initial train state and
    env state are placed over its mesh as the superstep takes them
    (sharded roles spread them; replicated leaves are copied)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    devices = jax.devices()
    in_use = lambda: [d.memory_stats()["bytes_in_use"] for d in devices]
    before = in_use()
    state, sim, _ = trainer._init_all()
    per_device = NamedSharding(trainer.mesh, P(*trainer.plan.axis_names))
    placed = jax.device_put((state, sim), per_device)
    del state, sim
    jax.block_until_ready(placed)
    used = [a - b for a, b in zip(in_use(), before)]
    say(f"[{tag}] {label} bytes_in_use gained per device with the state "
        f"placed: {used}")
    del placed


def run_one_chip():
    trainer, env, state = phase_train(
        "a", "impala trunk d_model=256 L=4 cartpole n_envs=256 unroll=32",
        "cartpole", "impala", 256, 32, TRUNK)
    phase_serve(trainer, env, state)
    del trainer, state
    phase_train("c", "ppo mlp cartpole n_envs=4096 unroll=32",
                "cartpole", "ppo", 4096, 32, {})
    phase_train("c", "a3c mlp cartpole n_envs=256 unroll=32",
                "cartpole", "a3c", 256, 32, {})
    phase_train("c", "dqn mlp cartpole replay_capacity=2^20 batch=512 "
                "n_envs=256 unroll=32", "cartpole", "dqn", 256, 32, DQN)
    phase_kernels()


def run_four_chips():
    """Only what exists across chips: sharded learner state and the
    sharded replay service, each beside the plan it must reproduce."""
    from repro.core.distribution import DistPlan
    pairs = [
        ("trunk zero3", "impala", 256, 32, TRUNK,
         "workers=2:allreduce:bsp,shard=2:allreduce:bsp:zero3",
         "workers=4:allreduce:bsp"),
        ("dqn replay", "dqn", 256, 32, DQN,
         "workers=2:allreduce:bsp,replay=2:allreduce:bsp:replay",
         "workers=2:allreduce:bsp"),
    ]
    for label, algo, n_envs, unroll, kw, plan_a, plan_b in pairs:
        runs = []
        for plan in (plan_a, plan_b):
            trainer, _, _, history, hlo, times = train(
                "cartpole", algo, n_envs, unroll, kw,
                plan=DistPlan.parse(plan))
            say(f"[4] {label} {plan}: losses {losses(history)} "
                f"tpu_custom_call x{count_kernels('4', plan, hlo)}")
            say(f"[4] setup: build {times['build_s']:.2f} s, compile "
                f"{times['compile_s']:.2f} s, fit {times['fit_s']:.2f} s")
            memory_line("4", plan, trainer)
            runs.append(losses(history))
            del trainer
        diff = max(abs(a - b) for a, b in zip(*runs))
        scale = max(1.0, max(abs(x) for x in runs[1]))
        say(f"[4] {label}: max |loss({plan_a}) - loss({plan_b})| = "
            f"{diff!r}")
        if not diff <= PAIR_RTOL * scale:
            raise RuntimeError(f"[4] {label}: loss difference {diff} over "
                               f"{PAIR_RTOL} x {scale}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip plans")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU devices, found {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    n_cached = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    say(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}: "
        f"{'warm, ' + str(n_cached) + ' entries' if n_cached else 'cold'}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    say(f"total {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
