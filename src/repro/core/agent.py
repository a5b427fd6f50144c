"""Unified Agent protocol + registry — the survey's actor/learner seam.

Every algorithm — on-policy (PPO/A3C), off-policy-corrected (IMPALA) and
replay-based (DQN) — trains behind the same three methods, so one driver
(`repro.core.trainer.Trainer`) can compose any algorithm with any system
topology (§3) and synchronization mechanism (§6) instead of hard-coding
one composition per algorithm:

    init(key)                  -> TrainState   (registered pytree)
    actor_policy(state, delay) -> behavior params for the rollout engine,
                                  `delay` learner-updates old (policy lag)
    learner_step(state, traj, boot_obs, key, grad_tx, param_tx)
                               -> (TrainState, metrics)

`grad_tx` / `param_tx` are the topology hooks: the Trainer injects
`topology.exchange_grads` (ps/allreduce) and `topology.gossip_mix`
(gossip) there, so agents stay topology-agnostic. Policy lag is carried
as a ring of stacked actor params inside TrainState; §6's bsp/asp/ssp
become schedules over the `delay` argument.

Algorithms self-register by name when `repro.core.algos` is imported;
`make("impala", env=env, ...)` constructs one from config.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


class PartitionList(list):
    """Marker type for a *per-block* partition: the optimizer target
    split into independently shardable entries (layer-wise ZeRO-3).
    Entry order is transformer blocks first, the non-block remainder
    last. Each entry runs through `flatten_and_pad` on its own, so a
    ZeRO-3 wrapper can gather → use → drop one block at a time instead
    of materializing the whole flattened vector per use."""


def flatten_and_pad(tree, n_shards: int):
    """Flatten a pytree to ONE 1-D vector zero-padded to a multiple of
    `n_shards` — the default partitioning for ZeRO-style learner-state
    sharding: any params pytree becomes `n_shards` equal contiguous
    chunks with no per-algorithm partitioning code.

    Returns ``(vec, size, unravel)``: `vec` the padded vector (its
    length divides evenly by `n_shards` by construction), `size` the
    true unpadded length, and ``unravel(vec[:size])`` restores the
    pytree. Mixed-dtype trees follow ravel_pytree's promotion; all
    agents here carry uniform f32 learner params."""
    vec, unravel = ravel_pytree(tree)
    if vec.size == 0:
        raise ValueError("cannot shard an empty parameter pytree")
    pad = (-vec.size) % n_shards
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec, vec.size - pad, unravel


@dataclasses.dataclass
class TrainState:
    """The unified train-state pytree every algorithm flows through."""
    params: Any      # learner params (whole algorithm-specific pytree)
    opt_state: Any
    extra: Any       # algorithm-private state (replay buffer, ...)
    ring: Any        # (D+1, ...) stacked actor-param history, [0]=newest
    steps: Any       # int32 learner-update counter


jax.tree_util.register_dataclass(
    TrainState,
    data_fields=("params", "opt_state", "extra", "ring", "steps"),
    meta_fields=())


class Agent:
    """Base class: the lag-ring plumbing shared by all agents.

    Subclasses set `self.policy` (an object with `sample`/`apply` for the
    rollout engine) and `self.ring_size`, and implement `init` and
    `learner_step`. `behavior_params` picks the sub-tree actors need
    (default: the whole params pytree)."""

    policy: Any
    ring_size: int = 1

    # -- protocol ------------------------------------------------------
    def init(self, key) -> TrainState:
        raise NotImplementedError

    def learner_step(self, state, traj, boot_obs, key,
                     grad_tx=None, param_tx=None):
        raise NotImplementedError

    def actor_policy(self, state: TrainState, delay=0):
        """Behavior params `delay` learner-updates old (clipped to the
        ring depth) — §6 sync mechanisms are schedules over `delay`."""
        return self._ring_read(state.ring, delay)

    def partition_spec(self, state: TrainState):
        """The sub-pytree of `state` the optimizer updates — what
        `opt_state` mirrors and what a ZeRO `shard`-role mesh axis
        partitions (`flatten_and_pad` turns it into equal chunks, so
        any pytree shards without per-algorithm partitioning code).
        Default: the whole params pytree; override when the optimizer
        targets a subtree (see DQNAgent: only the online net)."""
        return state.params

    def replace_partition(self, params, sub):
        """Inverse of `partition_spec` on the params pytree: return
        `params` with the optimizer-target subtree replaced by `sub`.
        ZeRO-3 uses this pair to split params into a sharded chunk
        (the partition) plus an unsharded rest, and to graft a gathered
        partition back in per use. Default (partition == whole tree):
        the rest is empty, so the grafted tree IS `sub`."""
        return sub

    def partition_list(self, part):
        """Optionally split the optimizer-target pytree `part` (the
        value `partition_spec` returns, or any congruent tree such as
        one actor-ring slot) into per-block entries for layer-wise
        ZeRO-3: a `PartitionList` of [block_0, ..., block_{R-1},
        remainder]. Default consults the policy's `partition_list` hook
        (TrunkPolicy: one entry per superblock of the scan stack plus
        the non-block remainder). Returns None when the policy exposes
        no block structure — list-free agents (MLP policies, DQN's
        q-net adapter) then fall back to the single-partition path
        bitwise-unchanged."""
        split = getattr(self.policy, "partition_list", None)
        if split is None:
            return None
        parts = split(part)
        return None if parts is None else PartitionList(parts)

    def merge_partition_list(self, entries, materialize=False):
        """Inverse of `partition_list` (policy hook). With
        `materialize=False` the block entries stay a Python list — the
        lazy form the trunk's `_run_seq` consumes one block at a time
        (gather → run → drop); `materialize=True` restacks them into
        the canonical stacked layout for host/checkpoint forms."""
        return self.policy.merge_partition_list(entries,
                                                materialize=materialize)

    # -- lag-ring helpers ----------------------------------------------
    def _ring_init(self, behavior_params):
        return jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p, (self.ring_size,) + p.shape),
            behavior_params)

    def _ring_read(self, ring, delay):
        d = jnp.minimum(jnp.asarray(delay, jnp.int32), self.ring_size - 1)
        return jax.tree_util.tree_map(
            lambda r: jnp.take(r, d, axis=0), ring)

    def _ring_push(self, ring, behavior_params):
        return jax.tree_util.tree_map(
            lambda h, p: jnp.roll(h, 1, axis=0).at[0].set(p),
            ring, behavior_params)


class PolicyGradientAgent(Agent):
    """Shared init/learner_step for agents whose learner is one
    `value_and_grad` over ``self.algo.loss(params, traj, boot_obs)``
    (A3C, IMPALA; PPO reuses `init` and overrides `learner_step`).
    Subclasses' __init__ must set `policy`, `algo`, `opt`, `ring_size`."""

    def init(self, key):
        params = self.policy.init(key)
        return TrainState(params, self.opt.init(params), {},
                          self._ring_init(params), jnp.zeros((), jnp.int32))

    def learner_step(self, state, traj, boot_obs, key,
                     grad_tx=None, param_tx=None):
        loss, grads = jax.value_and_grad(self.algo.loss)(
            state.params, traj, boot_obs)
        if grad_tx is not None:
            grads = grad_tx(grads)
        with jax.named_scope("optimizer"):
            params, opt_state = self.opt.apply(state.params,
                                               state.opt_state, grads)
        if param_tx is not None:
            params = param_tx(params)
        return TrainState(params, opt_state, state.extra,
                          self._ring_push(state.ring, params),
                          state.steps + 1), {"loss": loss}


# ------------------------------------------------------------ registry
_REGISTRY: Dict[str, Callable[..., Agent]] = {}


def register(name: str, factory: Callable[..., Agent]) -> None:
    """Register an Agent factory under `name` (called with env=..., **kw)."""
    _REGISTRY[name] = factory


def available():
    """Names of all registered algorithms."""
    import repro.core.algos  # noqa: F401 — triggers self-registration
    return tuple(sorted(_REGISTRY))


def make(name: str, env, **kwargs) -> Agent:
    """Construct a registered algorithm by name from config. The Trainer
    passes `ring_size` (actor-param history depth) and `total_iters`
    (training horizon, for schedules like DQN's ε-anneal) alongside any
    user algo_kwargs; factories accept and may ignore them."""
    import repro.core.algos  # noqa: F401 — triggers self-registration
    if name not in _REGISTRY:
        raise KeyError(f"unknown algorithm {name!r}; available: "
                       f"{', '.join(sorted(_REGISTRY))}")
    return _REGISTRY[name](env=env, **kwargs)
