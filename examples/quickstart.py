"""Quickstart: the framework in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py

1. Train a reduced assigned-architecture LM for a few steps.
2. Serve it with a KV cache.
3. Run distributed DRL (IMPALA + V-trace) on the zero-copy CartPole,
   resolved through the env registry (`envs.make("cartpole")`) — then
   the same run pipelined: rollout producer and learner consumer
   decoupled by a device-resident trajectory queue.
4. Serve the trained policy: bucketed micro-batching + versioned
   zero-recompile hot-swap through repro.core.serving (see
   examples/serve_policy_cartpole.py for the checkpoint-restore and
   offered-load version).
5. Run an ES generation (evolution-based training, survey §7) with the
   policy built from the env's spec (`MLPPolicy.for_spec`).
"""
import jax
import jax.numpy as jnp

# ---- 1. LM training (learner path) ---------------------------------------
from repro.launch.train import train

out = train("gemma3-1b", reduced=True, steps=30, batch=8, seq=64,
            lr=1e-3, log_every=10)
print("train:", out["history"][-1], "optimal_ce:", out["optimal_ce"])

# ---- 2. Serving (actor path) ----------------------------------------------
from repro.launch.serve import serve

print("serve:", serve("gemma3-1b", reduced=True, batch=2,
                      prompt_len=16, gen_len=8))

# ---- 3. Distributed DRL: IMPALA through the unified Trainer ----------------
import repro.envs as envs
from repro.core.distribution import DistPlan
from repro.core.trainer import Trainer, TrainerConfig

env = envs.make("cartpole")          # name registry, parallel to agent.make
# The distribution is declared, not hard-coded: a DistPlan names the
# mesh axes (1-D here; try DistPlan.grid(2, 2) on 4 devices), the
# per-axis collective + sync discipline, and an elastic actor-shard
# schedule — env shards cycle 16 -> 32 between supersteps while the
# agent only ever sees `traj`.
plan = DistPlan.flat(1, collective="allreduce", sync="bsp",
                     actors=(16, 32))
cfg = TrainerConfig(algo="impala", iters=40, superstep=10, n_envs=16,
                    unroll=16, plan=plan, policy_lag=2, log_every=10)
trainer = Trainer(env, cfg)
state, hist = trainer.fit()
print("impala:", hist[-1], "plan:", plan.describe(),
      "actor_shards:", trainer.actor_shards)

# ---- 3b. The same run, pipelined ------------------------------------------
# pipeline=True decouples each iteration into a rollout producer and a
# learner consumer joined by a device-resident trajectory queue
# (repro.core.pipeline). The queue depth is whatever staleness the
# plan's sync discipline admits: this ssp plan allows the producer to
# run 1 iteration ahead of the learner, so the learner consumes a
# trajectory one iteration old; a bsp plan would pin depth 0 (no queue:
# the lockstep run above).
pplan = DistPlan.flat(1, collective="allreduce", sync="ssp",
                      staleness_bound=1, max_delay=1)
pcfg = TrainerConfig(algo="impala", iters=40, superstep=10, n_envs=16,
                     unroll=16, plan=pplan, log_every=10, pipeline=True)
ptrainer = Trainer(env, pcfg)
_, phist = ptrainer.fit()
print("impala/pipelined:", phist[-1],
      f"depth={ptrainer.pipeline_depth}",
      f"queue_capacity={ptrainer.pipeline_capacity}")

# ---- 4. Serve the trained policy ------------------------------------------
# The traffic-facing mirror of the Trainer: publish the live
# actor-policy view into a versioned ParamStore, warm up one compiled
# program per bucket size, and serve micro-batches padded to the
# smallest fitting bucket. Hot-swapping fresh params is zero-recompile
# by construction (params are traced inputs), pinned by compile_count.
from repro.core.serving import ServeEngine

engine = ServeEngine.for_agent(trainer.agent, env, buckets=(1, 4, 16))
engine.store.publish_from_state(trainer.agent, state)
engine.warmup()                      # one compile per bucket, up front
obs = jax.vmap(env.spec.observation.sample)(
    jax.random.split(jax.random.PRNGKey(2), 7))
actions = engine.serve(obs)          # 7 requests -> buckets 16 (or 4+4...)
c0 = engine.compile_count
engine.store.publish_from_state(trainer.agent, state)   # hot-swap
engine.serve(obs)
print("serve_policy:", f"actions={actions.tolist()}",
      f"version={engine.store.version}",
      f"compiles={engine.compile_count} (was {c0} before hot-swap)",
      f"stats={engine.stats}")
assert engine.compile_count == c0    # the zero-recompile pin, live

# ---- 5. Evolution strategies (survey §7) -----------------------------------
from repro.core.networks import MLPPolicy
from repro.core.evo import ES

penv = envs.make("pendulum")
ppol = MLPPolicy.for_spec(penv.spec, hidden=(16,))
es = ES(ppol, penv, pop_size=16, max_steps=100)
theta = es.init(jax.random.PRNGKey(0))
theta, fitness, comm = jax.jit(es.step)(theta, jax.random.PRNGKey(1))
print(f"es: mean_fitness={float(fitness):.1f} comm_bytes={comm} "
      f"(vs {4 * theta.size} for a gradient exchange)")
