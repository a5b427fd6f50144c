"""Policy-serving launcher: offered-load benchmark over the
repro.core.serving engine (survey §3.3 centralized inference — the
traffic-facing mirror of repro.launch.rl_train).

  PYTHONPATH=src python -m repro.launch.serve_policy --algo ppo \
      --env cartpole --load 500,2000 --buckets "1,4,16;16" --quick

Trains a policy briefly (or restores one with --ckpt), publishes it
into a versioned ParamStore, then replays an open-loop arrival process
at each offered load (requests/second) against each bucket
configuration: requests are admitted FIFO, padded to the smallest
fitting bucket (one compile per bucket, pinned flat), and hot-swapped
onto fresh params halfway through every cell (zero recompiles, by
construction — params are traced inputs). Per-request latency is
charged from the *scheduled* arrival, so queueing delay under
overload shows up in the percentiles, exactly like a production load
generator.

Always writes BENCH_serve.json (repo root unless --out redirects it,
repro-bench/v1): one row per
(load x bucket-config) cell with p50/p99 latency and delivered
throughput, plus the serve/compile_flat row pinning
recompiles_after_warmup=0 across all cells and hot-swaps
(tests/test_bench_schema.py validates both, --quick output included).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# repo-root shim so `python -m repro.launch.serve_policy` can reach the
# benchmarks package (the BENCH_*.json writer) from any cwd
_REPO_ROOT = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".."))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

ALGOS = ("a3c", "dqn", "impala", "ppo")


def parse_buckets(spec: str):
    """Bucket grammar: semicolon-separated configurations, each a
    comma-separated strictly increasing list of positive micro-batch
    sizes — e.g. "1,4,16;8,32" is two configurations. Validated here
    (jax-free, so bad flags fail before anything trains); the engine
    re-validates through serving.validate_buckets."""
    configs = []
    for part in spec.split(";"):
        if not part.strip():
            raise ValueError(f"empty bucket configuration in {spec!r}")
        try:
            cfg_b = tuple(int(b) for b in part.split(","))
        except ValueError:
            raise ValueError(f"bad bucket configuration {part!r}: "
                             f"expected comma-separated integers") \
                from None
        if any(b <= 0 for b in cfg_b) or \
                any(b <= a for a, b in zip(cfg_b, cfg_b[1:])):
            raise ValueError(
                f"bad bucket configuration {part!r}: sizes must be "
                f"positive and strictly increasing")
        configs.append(cfg_b)
    return configs


def parse_loads(spec: str):
    try:
        loads = tuple(float(x) for x in spec.split(","))
    except ValueError:
        raise ValueError(f"bad --load {spec!r}: expected "
                         f"comma-separated requests/second") from None
    if not loads or any(x <= 0 for x in loads):
        raise ValueError(f"offered loads must be positive, got {spec!r}")
    return loads


def run_offered_load(engine, obs_rows, load_rps, n, swap_params=None):
    """Open-loop load replay: request i arrives at start + i/load_rps
    (virtual schedule mapped onto the real clock); the engine serves as
    fast as it can, sleeping only when the queue is empty and the next
    arrival is in the future. Latency = completion - scheduled arrival,
    so a too-slow engine accumulates queueing delay instead of secretly
    throttling the load. Halfway through, `swap_params` (if given) is
    hot-swapped in — live traffic, zero recompiles."""
    start = time.perf_counter() + 0.002
    arrivals = [start + i / load_rps for i in range(n)]
    submitted, swapped = 0, False
    lats, versions = [], set()
    last_done = start
    while len(lats) < n:
        now = time.perf_counter()
        while submitted < n and arrivals[submitted] <= now:
            engine.submit(obs_rows[submitted % len(obs_rows)],
                          arrival=arrivals[submitted])
            submitted += 1
        if not len(engine.batcher):
            time.sleep(max(0.0,
                           arrivals[submitted] - time.perf_counter()))
            continue
        if swap_params is not None and not swapped and len(lats) >= n // 2:
            engine.store.publish(swap_params)
            swapped = True
        for r in engine.step():
            lats.append(r["latency_s"])
            versions.add(r["version"])
        last_done = time.perf_counter()
    lat_ms = np.asarray(lats) * 1e3
    return {"p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99)),
            "throughput_rps": n / (last_done - start),
            "offered_rps": load_rps, "n": n,
            "hot_swaps": int(swapped), "versions": len(versions)}


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve_policy",
        description="Batched low-latency policy serving: offered-load "
                    "p50/p99 benchmark over repro.core.serving.")
    ap.add_argument("--algo", default="ppo", choices=ALGOS)
    ap.add_argument("--env", default="cartpole", metavar="ENV",
                    help="registered environment (repro.envs registry)")
    ap.add_argument("--load", default="300,1200", metavar="RPS,RPS,...",
                    help="offered loads in requests/second; one bench "
                         "row per load x bucket-config cell")
    ap.add_argument("--buckets", default="1,4,16;8,32",
                    metavar="B,B;B,...",
                    help="bucket configurations: semicolon-separated, "
                         "each an ascending comma list of micro-batch "
                         "sizes a request batch is padded to (one "
                         "compile per bucket, flat under traffic)")
    ap.add_argument("--requests", type=int, default=600,
                    help="requests replayed per cell")
    ap.add_argument("--train-iters", type=int, default=20,
                    help="Trainer iterations before serving (0 = serve "
                         "the freshly initialized policy)")
    ap.add_argument("--ckpt", default=None, metavar="PATH",
                    help="serve params restored from a repro.checkpoint "
                         "archive instead of training here")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, metavar="DIR",
                    help="directory for BENCH_serve.json (default: "
                         "repo root — the committed trajectory; tests "
                         "pass a temp dir so suite runs never dirty "
                         "the committed full-run file)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fewer requests/iterations, "
                         "default loads 500,2000 and buckets 4,16;16")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.quick:
        if args.load == ap.get_default("load"):
            args.load = "500,2000"
        if args.buckets == ap.get_default("buckets"):
            args.buckets = "4,16;16"
        if args.requests == ap.get_default("requests"):
            args.requests = 160
        if args.train_iters == ap.get_default("train_iters"):
            args.train_iters = 4
    try:
        loads = parse_loads(args.load)
        configs = parse_buckets(args.buckets)
    except ValueError as e:
        ap.error(str(e))

    import jax
    import repro.envs as envs
    from benchmarks.common import write_bench_json
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core.serving import ParamStore, ServeEngine
    from repro.core.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    if args.env not in envs.available():
        ap.error(f"--env {args.env} not registered; available: "
                 f"{envs.available()}")
    env = envs.make(args.env)
    cfg = TrainerConfig(algo=args.algo, iters=max(args.train_iters, 1),
                        superstep=min(4, max(args.train_iters, 1)),
                        n_envs=8, unroll=16, seed=args.seed,
                        log_every=max(args.train_iters, 1))
    trainer = Trainer(env, cfg)
    t0 = time.time()
    store = ParamStore()
    if args.ckpt is not None:
        store.load_checkpoint(args.ckpt, trainer.agent)
        train_s = 0.0
        source = "checkpoint"
    else:
        state, _ = trainer.fit() if args.train_iters > 0 else \
            (trainer.agent.init(jax.random.PRNGKey(args.seed)), None)
        store.publish_from_state(trainer.agent, state)
        train_s = time.time() - t0
        source = "trained-in-process" if args.train_iters > 0 \
            else "fresh-init"
    # the hot-swap payload: same shapes (template-validated), fresh
    # values — published mid-cell to prove live traffic never recompiles
    _, base_params = store.get()
    swap_params = jax.tree_util.tree_map(
        lambda a: a * (1 + 1e-3) if jax.numpy.issubdtype(
            a.dtype, jax.numpy.floating) else a, base_params)

    spec = env.spec
    obs_rows = np.asarray(jax.vmap(spec.observation.sample)(
        jax.random.split(jax.random.PRNGKey(args.seed + 1),
                         min(args.requests, 256))))

    rows, cells = [], []
    warmup_compiles = total_compiles = hot_swaps = 0
    for cfg_b in configs:
        engine = ServeEngine(trainer.agent.policy, spec.observation,
                             buckets=cfg_b, store=store, seed=args.seed)
        warmup_compiles += engine.warmup()
        tag = "-".join(str(b) for b in cfg_b)
        for load in loads:
            cell = run_offered_load(engine, obs_rows, load,
                                    args.requests,
                                    swap_params=swap_params)
            hot_swaps += cell["hot_swaps"]
            cells.append(dict(cell, buckets=tag))
            rows.append((
                f"serve/{args.algo}/b{tag}/load{load:g}",
                cell["p50_ms"] * 1e3,
                f"p50_ms={cell['p50_ms']:.3f};"
                f"p99_ms={cell['p99_ms']:.3f};"
                f"throughput_rps={cell['throughput_rps']:.1f};"
                f"offered_rps={load:g};n={cell['n']};"
                f"hot_swaps={cell['hot_swaps']};"
                f"versions={cell['versions']}"))
        total_compiles += engine.compile_count
    recompiles = total_compiles - warmup_compiles
    rows.append((
        "serve/compile_flat", None,
        f"warmup_compiles={warmup_compiles};"
        f"recompiles_after_warmup={recompiles};"
        f"hot_swaps={hot_swaps};bucket_configs={len(configs)};"
        f"loads={len(loads)}"))
    path = write_bench_json(
        "serve", rows, out_dir=args.out, algo=args.algo, env=args.env,
        loads=list(loads),
        bucket_configs=[list(c) for c in configs],
        requests_per_cell=args.requests, quick=args.quick,
        train_iters=args.train_iters, source=source)
    print(json.dumps({
        "algo": args.algo, "env": args.env, "loads": list(loads),
        "bucket_configs": [list(c) for c in configs],
        "requests_per_cell": args.requests,
        "param_version": store.version,
        "warmup_compiles": warmup_compiles,
        "recompiles_after_warmup": recompiles,
        "hot_swaps": hot_swaps, "train_s": round(train_s, 1),
        "bench": os.path.basename(path), "cells": cells}))


if __name__ == "__main__":
    main()
