"""Experience replay (survey §3: Gorila/Ape-X Replay Memory component).

Pure-functional fixed-capacity buffers living on device:
  * `UniformReplay` — Gorila-style uniform sampling.
  * `PrioritizedReplay` — Ape-X style proportional prioritization
    p_i ∝ |TD_i|^α with importance-sampling weights w_i ∝ (N p_i)^{-β}.
    Two sampling paths (TPU-friendly either way — no host-side
    sum-tree):
      - legacy (`fused=False`, default): n independent categorical
        draws over log-priorities (WITH replacement); the IS weights
        gather the chosen logits and normalize by the scalar partition
        function — bitwise what the old full-capacity
        `jax.nn.softmax` materialization computed, without it.
      - fused (`fused=True`): one Gumbel-top-k pass (WITHOUT
        replacement) through `core.replay_sample` — the Pallas kernel
        on TPU, its jnp oracle elsewhere.

Edge cases (both buffers):
  * Sampling from an EMPTY buffer (size == 0) is well-defined but
    degenerate: every draw returns slot 0 — the zeros `init` wrote —
    with finite weights. Callers must gate on warmup/size (see
    algos/dqn.py); there is no in-graph error because `size` is traced.
  * `add_batch` with n > capacity used to self-overwrite through
    duplicate ring indices (unspecified scatter order); since n is
    static it is now guarded explicitly — only the LAST `capacity`
    items are written (ring semantics), deterministically.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.replay_sample import fused_prioritized_sample


def _ring_fit(state, batch, capacity, priorities=None):
    """Ring-write plan for n items: with n > capacity, drop all but the
    last `capacity` (they would be overwritten within this very batch —
    the old duplicate-index scatter relied on unspecified ordering to
    do the same). Returns (idx, batch, priorities, new_ptr)."""
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    drop = max(n - capacity, 0)
    if drop:
        batch = jax.tree_util.tree_map(lambda b: b[drop:], batch)
        if priorities is not None:
            priorities = priorities[drop:]
    idx = (state["ptr"] + drop + jnp.arange(n - drop)) % capacity
    return idx, batch, priorities, (state["ptr"] + n) % capacity


@dataclasses.dataclass
class UniformReplay:
    capacity: int

    def init(self, example: Any):
        store = jax.tree_util.tree_map(
            lambda a: jnp.zeros((self.capacity,) + jnp.shape(a),
                                jnp.asarray(a).dtype), example)
        return {"store": store, "ptr": jnp.zeros((), jnp.int32),
                "size": jnp.zeros((), jnp.int32)}

    def add_batch(self, state, batch):
        """batch: pytree with leading dim n (n > capacity keeps only the
        last `capacity` items — see module docstring)."""
        with jax.named_scope("replay.insert"):
            n = jax.tree_util.tree_leaves(batch)[0].shape[0]
            idx, batch, _, ptr = _ring_fit(state, batch, self.capacity)
            store = jax.tree_util.tree_map(
                lambda s, b: s.at[idx].set(b), state["store"], batch)
            return {"store": store, "ptr": ptr,
                    "size": jnp.minimum(state["size"] + n, self.capacity)}

    def sample(self, state, key, n):
        """Uniform over filled slots. Empty buffer -> slot-0 zeros (see
        module docstring)."""
        with jax.named_scope("replay.sample"):
            idx = jax.random.randint(key, (n,), 0,
                                     jnp.maximum(state["size"], 1))
            return (jax.tree_util.tree_map(lambda s: s[idx],
                                           state["store"]), idx)


@dataclasses.dataclass
class PrioritizedReplay:
    capacity: int
    alpha: float = 0.6
    beta: float = 0.4
    eps: float = 1e-6
    fused: bool = False   # Gumbel-top-k kernel path (see module doc)

    def init(self, example: Any):
        store = jax.tree_util.tree_map(
            lambda a: jnp.zeros((self.capacity,) + jnp.shape(a),
                                jnp.asarray(a).dtype), example)
        return {"store": store, "prio": jnp.zeros((self.capacity,)),
                "ptr": jnp.zeros((), jnp.int32),
                "size": jnp.zeros((), jnp.int32)}

    def add_batch(self, state, batch, priorities=None):
        with jax.named_scope("replay.insert"):
            n = jax.tree_util.tree_leaves(batch)[0].shape[0]
            idx, batch, priorities, ptr = _ring_fit(
                state, batch, self.capacity, priorities)
            store = jax.tree_util.tree_map(
                lambda s, b: s.at[idx].set(b), state["store"], batch)
            if priorities is None:  # new samples get max priority (Ape-X)
                priorities = jnp.full((idx.shape[0],), jnp.maximum(
                    state["prio"].max(), 1.0))
            prio = state["prio"].at[idx].set(priorities)
            return {"store": store, "prio": prio, "ptr": ptr,
                    "size": jnp.minimum(state["size"] + n, self.capacity)}

    def sample(self, state, key, n):
        """-> (batch, idx, is_weights). Proportional to p_i^α; WITH
        replacement on the legacy path, WITHOUT (Gumbel-top-k) on the
        fused path. Empty buffer -> finite-weight slot-0 draws."""
        with jax.named_scope("replay.sample"):
            if self.fused:
                gumbel = jax.random.gumbel(key, (self.capacity,))
                idx, w = fused_prioritized_sample(
                    state["prio"], state["size"], gumbel, n,
                    self.alpha, self.beta, self.eps, use_kernel=True)
            else:
                # the arange guard keeps slot 0 "valid" when empty so
                # the normalization below stays NaN-free (bitwise
                # unchanged whenever size >= 1)
                valid = (jnp.arange(self.capacity)
                         < jnp.maximum(state["size"], 1))
                logits = self.alpha * jnp.log(state["prio"] + self.eps)
                logits = jnp.where(valid, logits, -jnp.inf)
                idx = jax.random.categorical(key, logits, shape=(n,))
                # π_idx gathered from the chosen logits + scalar
                # partition function — no capacity-sized softmax
                # materialization
                unnorm = jnp.exp(logits - jnp.max(logits))
                N = jnp.maximum(state["size"], 1)
                w = ((N * (unnorm[idx] / unnorm.sum()) + 1e-12)
                     ** (-self.beta))
                w = w / jnp.maximum(w.max(), 1e-12)
            batch = jax.tree_util.tree_map(lambda s: s[idx],
                                           state["store"])
            return batch, idx, w

    def update_priorities(self, state, idx, td_errors):
        with jax.named_scope("replay.update_priorities"):
            prio = state["prio"].at[idx].set(jnp.abs(td_errors) + self.eps)
            return dict(state, prio=prio)
