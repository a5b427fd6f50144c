"""Unified distributed-DRL launcher: config parsing + ``Trainer.fit``.

  PYTHONPATH=src python -m repro.launch.rl_train --algo impala \
      --env cartpole --plan "hosts=2:allreduce:bsp,workers=2:gossip:asp"

Every axis of the survey's taxonomy is one orthogonal flag, resolved by
the unified Agent/Trainer API (repro.core.agent / repro.core.trainer):

  --algo      a3c | dqn | impala | ppo    (Agent registry)
  --env       any registered env name     (env registry, `envs.make` —
                                           incl. scenario families like
                                           cartpole-rand and wrapped
                                           variants like pendulum-norm)
  --plan      hierarchical DistPlan: comma-separated mesh axes,
              outermost first, each
              ``name=size[:collective[:sync[:role]]]`` with collective
              in {ps, allreduce, gossip} (§3), sync in {bsp, asp, ssp}
              (§6) and role in {data, shard, zero3, replay} — ``shard``
              marks the ZeRO-2 learner-state sharding axis (optimizer
              state partitioned 1/size per device, gradients reduce-
              scattered, params all-gathered; allreduce only), ``zero3``
              full ZeRO-3 (params stored sharded too, all-gathered per
              use; allreduce + bsp only), ``replay`` the sharded replay
              service (ONE logical prioritized buffer over the axis,
              1/size capacity per member; allreduce + bsp only), e.g.
              ``hosts=2:allreduce:bsp,workers=4:gossip:asp``,
              ``workers=4:allreduce:bsp,shard=2:allreduce:bsp:zero3`` or
              ``workers=2:allreduce:bsp,replay=2:allreduce:bsp:replay``
  --policy    mlp | trunk — the policy network every algorithm trains:
              the house actor-critic MLP or the transformer trunk
              (networks.TrunkPolicy over configs/paper_drl.py's
              paper-drl-trunk, attention via core/attention.py's
              flash-attention dispatcher)
  --actors    elastic env-shard schedule, e.g. ``32,64,32`` — the total
              env count cycles through these values per superstep
              (ElegantRL-Podracer-style elastic actor shards)

Legacy single-axis flags remain and lower onto a 1-D plan (the two
spellings are bitwise-identical):

  --topology  ps | allreduce | gossip     == --plan "workers=N:<topo>:<sync>"
  --sync      bsp | asp | ssp
  --n-workers N

The launcher forces enough fake host devices for the plan's mesh before
jax loads. Training runs as fused supersteps: ``--superstep K``
iterations of rollout -> learner_step -> lag-ring rotate execute inside
one jitted ``lax.scan`` with a single host round-trip per dispatch;
``--unfused`` falls back to per-iteration dispatch (same numerics, for
debugging and the benchmarks/fused_superstep.py comparison).
``--pipeline`` decouples each iteration into a rollout producer and a
learner consumer joined by a device-resident trajectory queue
(repro.core.pipeline) whose depth is the staleness the plan's sync
discipline admits — the output JSON reports the resolved depth and
queue capacity.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# static mirrors of the library tuples so the parser builds without
# importing jax (XLA_FLAGS must be set first); cross-checked in main()
ALGOS = ("a3c", "dqn", "impala", "ppo")
ENV_NAMES = ("cartpole", "cartpole-rand", "cartpole-repeat", "gridworld",
             "gridworld-rand", "pendulum", "pendulum-norm",
             "pendulum-rand")
TOPOLOGY_CHOICES = ("allreduce", "ps", "gossip")
SYNC_CHOICES = ("bsp", "asp", "ssp")


def _plan_n_devices(spec: str) -> int:
    """Device count a --plan string needs — pure string math so it runs
    before jax is imported (full validation happens in DistPlan.parse).
    Rejects empty specs, duplicate axis names and non-integer sizes
    here too, naming the offending input, so the CLI errors cleanly
    without ever paying the jax import."""
    if not spec or not spec.strip():
        raise ValueError("empty --plan: expected comma-separated axes "
                         "name=size[:collective[:sync[:role]]]")
    n = 1
    seen = []
    for seg in spec.split(","):
        head = seg.strip().split(":")[0]
        if "=" not in head:
            raise ValueError(f"bad plan axis {seg!r}: expected "
                             f"name=size[:collective[:sync[:role]]]")
        name, size = head.split("=", 1)
        name = name.strip()
        if name in seen:
            raise ValueError(f"duplicate plan axis name {name!r} "
                             f"in {spec!r}")
        seen.append(name)
        try:
            n *= int(size)
        except ValueError:
            raise ValueError(f"bad plan axis {seg!r}: size {size!r} "
                             f"is not an integer") from None
    return n


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.rl_train",
        description="Unified distributed-DRL launcher (survey taxonomy "
                    "as orthogonal flags).")
    ap.add_argument("--algo", default="impala", choices=ALGOS)
    ap.add_argument("--env", default="cartpole", metavar="ENV",
                    help="registered environment, validated against the "
                         "repro.envs registry (built-ins: "
                         + ", ".join(ENV_NAMES) + "; third-party "
                         "`envs.register` entries work too)")
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--superstep", type=int, default=10,
                    help="iterations fused per jitted dispatch")
    ap.add_argument("--n-envs", type=int, default=32)
    ap.add_argument("--unroll", type=int, default=32)
    ap.add_argument("--plan", default=None, metavar="PLAN",
                    help="hierarchical DistPlan, comma-separated axes "
                         "outermost first, each name=size[:collective"
                         "[:sync[:role]]] — role `shard` marks the "
                         "ZeRO-2 learner-state sharding axis (optimizer "
                         "state lives 1/size per device; must use "
                         "allreduce), `zero3` full ZeRO-3 (params "
                         "stored sharded too, all-gathered per use; "
                         "allreduce + bsp), `replay` the sharded replay "
                         "service (one logical prioritized buffer, "
                         "1/size capacity per member; allreduce + bsp), "
                         "e.g. 'workers=4:allreduce:bsp,shard=2:"
                         "allreduce:bsp:zero3' or 'workers=2:allreduce:"
                         "bsp,replay=2:allreduce:bsp:replay'; overrides "
                         "--n-workers/--topology/--sync (which lower "
                         "onto a 1-D plan)")
    ap.add_argument("--actors", default=None, metavar="N,N,...",
                    help="elastic env-shard schedule: total env counts "
                         "cycled per superstep (each must divide across "
                         "the plan's devices)")
    ap.add_argument("--policy", default="mlp", choices=("mlp", "trunk"),
                    help="policy network: the house actor-critic MLP or "
                         "the transformer trunk (paper-drl-trunk config, "
                         "flash-attention dispatcher)")
    ap.add_argument("--n-workers", type=int, default=1)
    ap.add_argument("--topology", default="allreduce",
                    choices=TOPOLOGY_CHOICES)
    ap.add_argument("--sync", default="bsp", choices=SYNC_CHOICES)
    ap.add_argument("--policy-lag", type=int, default=0)
    ap.add_argument("--max-delay", type=int, default=4)
    ap.add_argument("--staleness-bound", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-vtrace", action="store_true",
                    help="impala only: naive targets instead of V-trace")
    ap.add_argument("--unfused", action="store_true",
                    help="per-iteration dispatch instead of fused scan")
    ap.add_argument("--pipeline", action="store_true",
                    help="decoupled actor-learner pipeline: split each "
                         "iteration into a rollout producer and learner "
                         "consumer joined by a device-resident "
                         "trajectory queue; the queue depth is what the "
                         "plan's per-axis sync discipline admits (bsp 0 "
                         "= lockstep/bitwise-fused, ssp its bound, asp "
                         "its max delay, summed over axes)")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    try:
        # `is not None`, not truthiness: --plan "" must be rejected as
        # an empty axis list, never silently fall back to legacy flags
        n_devices = (_plan_n_devices(args.plan) if args.plan is not None
                     else args.n_workers)
    except ValueError as e:
        ap.error(str(e))
    if n_devices > 1 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_devices}").strip()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import repro.envs as envs
    from repro.core import agent as agent_api
    from repro.core.distribution import DistPlan
    from repro.core.sync import MECHANISMS
    from repro.core.topology import TOPOLOGIES
    from repro.core.trainer import Trainer, TrainerConfig

    # the CLI tuples are static so the parser stays jax-free; fail loudly
    # if they ever drift from the library registries
    assert set(TOPOLOGY_CHOICES) == set(TOPOLOGIES)
    assert set(SYNC_CHOICES) == set(MECHANISMS)
    # built-in list may lag third-party registrations, never the reverse
    assert set(ENV_NAMES) <= set(envs.available()), envs.available()
    if args.algo not in agent_api.available():
        ap.error(f"--algo {args.algo} not registered; available: "
                 f"{agent_api.available()}")
    if args.env not in envs.available():
        ap.error(f"--env {args.env} not registered; available: "
                 f"{envs.available()}")

    try:
        actors = (tuple(int(n) for n in args.actors.split(","))
                  if args.actors else None)
        if args.plan is not None:
            plan = DistPlan.parse(args.plan, max_delay=args.max_delay,
                                  staleness_bound=args.staleness_bound,
                                  actors=actors)
        else:  # legacy flags lower onto the bitwise-identical 1-D plan
            plan = DistPlan.flat(args.n_workers, args.topology,
                                 args.sync, args.max_delay,
                                 args.staleness_bound, actors=actors)
    except ValueError as e:
        ap.error(str(e))

    algo_kwargs = {"policy": args.policy}
    if args.algo == "impala":
        algo_kwargs["use_vtrace"] = not args.no_vtrace
    cfg = TrainerConfig(
        algo=args.algo, iters=args.iters, superstep=args.superstep,
        n_envs=args.n_envs, unroll=args.unroll, plan=plan,
        policy_lag=args.policy_lag, seed=args.seed,
        log_every=args.log_every, pipeline=args.pipeline,
        algo_kwargs=algo_kwargs)
    env = envs.make(args.env)
    t0 = time.time()
    trainer = Trainer(env, cfg)
    _, history = trainer.fit(fused=not args.unfused)
    print(json.dumps({
        "algo": args.algo, "env": args.env, "policy": args.policy,
        "plan": plan.describe(),
        "n_devices": plan.n_devices, "fused": not args.unfused,
        # actor-learner pipeline: queue depth the plan's sync admits
        # (0 = lockstep) and the ring capacity actually allocated
        "pipeline": args.pipeline,
        "pipeline_depth": trainer.pipeline_depth,
        "pipeline_capacity": trainer.pipeline_capacity,
        "actor_shards": trainer.actor_shards[-5:],
        # ZeRO partition of the learner state (shard-role axis): axis
        # name, shard count and flat/padded/chunk element counts; None
        # on unsharded (or size-1 shard) plans
        "partition": trainer.partition,
        # sharded replay service (replay-role axis): axis name, shard
        # count and global/chunk slot counts; None when no active
        # replay axis
        "partition_replay": trainer.partition_replay,
        "wall_s": round(time.time() - t0, 1), "history": history[-5:]}))


if __name__ == "__main__":
    main()
