"""Distributed synchronization mechanisms (survey §6, Fig. 6):
BSP / ASP / SSP as a *deterministic staleness engine*.

SPMD adaptation (DESIGN.md §4.3): true asynchrony has no reproducible
JAX analogue, but what the survey says matters — *stale updates* (workers
computing gradients against old params) — is modeled exactly: a history
buffer of the last D+1 param versions is carried through lax.scan and
worker w at step t reads version `delay[t, w]`:

    BSP: delay ≡ 0 (bulk-synchronous, consistent)
    ASP: delay ~ U[0, max_delay]       (unbounded staleness)
    SSP: delay ~ min(U[0, max_delay], bound)  (stale-synchronous)

benchmarks/fig6_sync.py reproduces the survey's qualitative claim:
ASP degrades convergence vs BSP; SSP recovers most of it at a fraction
of the synchronization cost.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

MECHANISMS = ("bsp", "asp", "ssp")


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    mechanism: str = "bsp"        # bsp | asp | ssp
    n_workers: int = 4
    max_delay: int = 4            # ASP worst case
    staleness_bound: int = 1      # SSP bound


def pipeline_depth(cfg: SyncConfig) -> int:
    """How far a decoupled rollout producer may run AHEAD of the
    learner consumer under this sync discipline — the trajectory-queue
    depth of the Trainer's ``pipeline=`` mode (repro.core.pipeline).

    The mapping is the same staleness budget `make_delays` spends as
    random policy-lag: BSP admits none (depth 0 = lockstep, no queue),
    SSP admits its bound, ASP its worst case. The lockstep path
    *models* that staleness by reading lagged params out of the
    actor ring; the pipelined path *realizes* it — a trajectory
    consumed at iteration t was produced `depth` iterations earlier
    with the params then newest, so the actor-param lag is structural
    (exactly `depth`), not sampled."""
    if cfg.mechanism == "bsp":
        return 0
    if cfg.mechanism == "asp":
        return cfg.max_delay
    if cfg.mechanism == "ssp":
        return min(cfg.max_delay, cfg.staleness_bound)
    raise ValueError(cfg.mechanism)


def make_delays(cfg: SyncConfig, n_steps: int, key):
    if cfg.mechanism == "bsp":
        return jnp.zeros((n_steps, cfg.n_workers), jnp.int32)
    d = jax.random.randint(key, (n_steps, cfg.n_workers), 0,
                           cfg.max_delay + 1)
    if cfg.mechanism == "ssp":
        d = jnp.minimum(d, cfg.staleness_bound)
    elif cfg.mechanism != "asp":
        raise ValueError(cfg.mechanism)
    return d


def train_with_staleness(loss_fn, params0, optimizer, batches, delays):
    """Run data-parallel training under a staleness schedule.

    loss_fn(params, batch) -> scalar;
    batches: pytree with leading dims (T, W, ...);
    delays:  (T, W) int32, delay d => grads from params d steps old.
    Returns (final params, losses (T,))."""
    D = int(jax.device_get(delays.max())) if delays.size else 0
    hist0 = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (D + 1,) + p.shape), params0)
    opt_state0 = optimizer.init(params0)

    def step(carry, xs):
        params, opt_state, hist = carry
        batch_w, delay_w = xs

        def worker(b, d):
            stale = jax.tree_util.tree_map(
                lambda h: jnp.take(h, jnp.minimum(d, D), axis=0), hist)
            return jax.value_and_grad(loss_fn)(stale, b)

        losses, grads = jax.vmap(worker)(batch_w, delay_w)
        g = jax.tree_util.tree_map(lambda x: x.mean(0), grads)
        params, opt_state = optimizer.apply(params, opt_state, g)
        hist = jax.tree_util.tree_map(
            lambda h, p: jnp.roll(h, 1, axis=0).at[0].set(p), hist, params)
        return (params, opt_state, hist), losses.mean()

    (params, _, _), losses = jax.lax.scan(
        step, (params0, opt_state0, hist0), (batches, delays))
    return params, losses


def sync_cost_model(cfg: SyncConfig, t_compute_mean, t_compute_std,
                    n_steps, key):
    """Analytic throughput model (survey §6.2 synchronization barrier):
    per-step wall time under worker-speed heterogeneity ~N(mean, std).
    BSP waits for the max; ASP takes the mean; SSP waits only when the
    bound trips (approximated as a max over a `bound`-step window)."""
    t = jnp.maximum(t_compute_mean + t_compute_std * jax.random.normal(
        key, (n_steps, cfg.n_workers)), 1e-3)
    if cfg.mechanism == "bsp":
        return t.max(axis=1).sum()
    if cfg.mechanism == "asp":
        return t.mean(axis=1).sum()
    # ssp: amortized barrier every `bound` steps
    b = max(cfg.staleness_bound, 1)
    pad = (-n_steps) % b
    tw = jnp.pad(t, ((0, pad), (0, 0))).reshape(-1, b, cfg.n_workers)
    # per b-step window: (b-1) free-running steps + one barrier step
    return (tw.mean(axis=(1, 2)) * (b - 1) + tw.max(axis=(1, 2))).sum()
