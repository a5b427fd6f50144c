"""Decoupled actor–learner pipeline: the device-resident trajectory
queue (survey §2 learning-system architectures).

Every production architecture the survey describes — Gorila's and
Ape-X's actor/learner separation, SRL's description/execution split —
decouples experience *generation* from *learning* so simulation latency
hides behind the learner update. This module is that seam rendered in
pure SPMD: a fixed-capacity ring of trajectory pytrees plus head/tail
counters, living in the training carry, connecting a rollout *producer*
to a learner *consumer* (repro.core.trainer's ``pipeline=`` mode).

Design points:

  * **Device-resident.** The buffer is an ordinary pytree of jnp
    arrays with a leading ``(capacity,)`` dim per leaf — it rides in
    the superstep carry, is donated along with it (zero-copy, PR 3's
    aliasing machinery applies unchanged), and under a multi-device
    DistPlan each device holds its *own* queue of its local
    trajectories inside ``shard_map`` (no cross-device traffic beyond
    the plan's collectives).

  * **Total functions.** ``queue_push`` on a full queue is a guarded
    no-op returning ``ok=False`` (backpressure: the element is
    *refused*, never silently dropped or overwritten);
    ``queue_pop`` on an empty queue is a guarded no-op returning the
    (stale) head-slot contents and ``ok=False``. The Trainer's
    static schedule never trips either guard — steady state holds
    exactly ``depth`` items — but the ops stay safe under jit/scan
    where Python-level control flow is unavailable.

  * **Staleness-bounded.** Capacity is the pipeline depth the
    DistPlan's per-axis sync discipline admits
    (``DistPlan.pipeline_depth``): bsp admits none (depth 0 is
    lockstep, with no queue), ssp admits ``staleness_bound``, asp
    ``max_delay``. A producer can therefore never run further ahead
    than the sync discipline already allowed as policy lag — the queue
    *realizes* structurally the staleness the lockstep path only
    models with delay schedules.

Counters are monotonically increasing int32 (slot = counter %
capacity), so ``size = tail - head`` needs no emptiness flag and
wraparound is exact until 2**31 pushes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def queue_capacity(q) -> int:
    """Static ring capacity (leading dim of every buffer leaf)."""
    return jax.tree_util.tree_leaves(q["buf"])[0].shape[0]


def queue_size(q):
    """Traced number of items currently queued (0 <= size <= cap)."""
    return q["tail"] - q["head"]


def queue_init(item, capacity: int):
    """Fresh empty queue for items shaped like `item` (arrays or
    ShapeDtypeStructs): every buffer leaf gets a leading ``(capacity,)``
    dim of zeros; head/tail counters start at 0."""
    if capacity < 1:
        raise ValueError(f"queue capacity must be >= 1, got {capacity}")
    buf = jax.tree_util.tree_map(
        lambda s: jnp.zeros((capacity,) + tuple(s.shape), s.dtype), item)
    return {"buf": buf, "head": jnp.zeros((), jnp.int32),
            "tail": jnp.zeros((), jnp.int32)}


def queue_push(q, item):
    """Append `item` at the tail. Full queue => guarded no-op
    (backpressure), returns ``(queue, ok)`` with ``ok=False`` and the
    queue unchanged — an element is never overwritten."""
    cap = queue_capacity(q)
    full = queue_size(q) >= cap
    slot = jax.lax.rem(q["tail"], jnp.int32(cap))

    def write(b, x):
        cur = jax.lax.dynamic_index_in_dim(b, slot, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            b, jnp.where(full, cur, x), slot, 0)

    buf = jax.tree_util.tree_map(write, q["buf"], item)
    tail = q["tail"] + jnp.where(full, 0, 1).astype(jnp.int32)
    return {"buf": buf, "head": q["head"], "tail": tail}, ~full


def queue_pop(q):
    """Remove and return the oldest item. Empty queue => guarded no-op:
    returns ``(queue, item, ok)`` with ``ok=False``, the queue
    unchanged, and `item` the stale head-slot contents (well-defined —
    zeros before any push reached that slot)."""
    cap = queue_capacity(q)
    empty = queue_size(q) <= 0
    slot = jax.lax.rem(q["head"], jnp.int32(cap))
    item = jax.tree_util.tree_map(
        lambda b: jax.lax.dynamic_index_in_dim(b, slot, 0, keepdims=False),
        q["buf"])
    head = q["head"] + jnp.where(empty, 0, 1).astype(jnp.int32)
    return {"buf": q["buf"], "head": head, "tail": q["tail"]}, item, ~empty
