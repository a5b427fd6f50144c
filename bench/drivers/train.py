"""Window driver of the training cells.

The window drives the program `Trainer.fit` dispatches: the jitted
superstep of K iterations (`Trainer._superstep(K)`), the state carried
from call to call and the metrics read back once per call, as `fit`
does. No public method of the Trainer runs supersteps for a time
window, so this loop mirrors `fit`'s.

Set-up builds the Trainer, the benchmark's own weights and env state
from the seed, and the carried state; its first call, which compiles,
also gives the readings that `correct` is decided on. The window then
dispatches supersteps until `--seconds` have passed; its rate is the
env steps of every iteration the window completed over all the time
the window took.
"""
import gc
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness


def build(cell, seed):
    from repro.core.trainer import Trainer, TrainerConfig
    import repro.envs as envs

    code, sizes, traffic = cell["code"], cell["sizes"], cell["traffic"]
    algo, algo_kwargs = code.algorithm(sizes, traffic)
    cfg = TrainerConfig(algo=algo, iters=traffic["horizon_iters"],
                        superstep=traffic["superstep"],
                        n_envs=traffic["n_envs"], unroll=traffic["unroll"],
                        seed=harness.PROGRAM_KEY_SEED,
                        algo_kwargs=algo_kwargs)
    env = envs.make(sizes["env"])
    trainer = Trainer(env, cfg)
    state = code.make_state(trainer.agent, sizes, traffic, seed)
    _, k_env, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    sim = {"env": env.reset_batch(k_env, cfg.n_envs),
           "ep_run": jnp.zeros((cfg.n_envs,)),
           # float32 as the superstep returns it: fit's weak-typed NaN
           # makes the second call compile the superstep again
           "ep_last": jnp.full((), jnp.nan, jnp.float32)}
    # every iteration acts with the newest parameters (delay 0)
    delays = jnp.zeros((cfg.superstep,), jnp.int32)
    return trainer, state, sim, delays


def run(cell, args, seed, devs, spans, t_start, trace_body):
    code, sizes, traffic = cell["code"], cell["sizes"], cell["traffic"]
    t_build = time.perf_counter()
    trainer, state, sim, delays = build(cell, seed)
    t_built = time.perf_counter()
    K = traffic["superstep"]
    step = trainer._superstep(K)
    steps_per_iter = traffic["n_envs"] * traffic["unroll"]
    it0 = [0]

    def dispatch():
        nonlocal state, sim
        # numpy: a jnp.arange from a nonzero start compiles an add
        its = np.arange(it0[0], it0[0] + K, dtype=np.int32)
        with spans("bench.dispatch"):
            state, sim, metrics = step(state, sim, its, delays)
        with spans("bench.device_get"):
            metrics = jax.device_get(metrics)
        it0[0] += K
        return metrics

    # set-up: the first call compiles and runs iterations 0..K-1
    first = dispatch()
    t_first = time.perf_counter()
    prog = code.observe(first, state, sizes, traffic, seed)
    t_setup = time.perf_counter()
    print(f"setup: imports and devices {t_build - t_start:.3f} s, build "
          f"{t_built - t_build:.3f} s, first call {t_first - t_built:.3f} s,"
          f" readings {t_setup - t_first:.3f} s", file=sys.stderr)

    def window(seconds):
        done, bad, per_call = 0, 0, []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            a = time.perf_counter()
            m = dispatch()
            per_call.append(time.perf_counter() - a)
            done += K
            bad += sum(1 for x in m["loss"] if not math.isfinite(float(x)))
        return done, bad, time.perf_counter() - t0, per_call

    out = {"setup_s": t_setup - t_start}
    if args.trace:
        (done, bad, elapsed, per_call), reduced = trace_body(
            lambda: window(traffic["trace_seconds"]))
        out.update(reduced=reduced, iters=done,
                   flops=done * code.flops_per_iter(sizes, traffic),
                   kernels=code.kernels(sizes, traffic, done))
    else:
        done, bad, elapsed, per_call = window(args.seconds)
    out.update(attempted=done, failed=bad, window_s=elapsed,
               per_call_s=per_call,
               metrics={"env_steps_per_s": done * steps_per_iter / elapsed})
    calls = sorted(per_call)
    print(f"window: {len(per_call)} calls, first {per_call[0]:.4f} s, "
          f"median {calls[len(calls) // 2]:.4f} s, max {calls[-1]:.4f} s",
          file=sys.stderr)
    out["device"] = harness.device_info(devs)
    # free the program's state before the reference runs on the chip
    del state, sim, step, trainer
    gc.collect()
    ref = code.reference(sizes, traffic, seed)
    out["checks"] = code.compare(prog, ref, traffic["limits"])
    return out
