"""Distribution Plan API — the declarative description of how training
is spread over devices (survey §3 architectures x §6 synchronization,
composed hierarchically).

Real DRL systems compose *hierarchies* of parallelism — intra-node
allreduce under inter-node parameter-server or gossip, with per-level
sync disciplines (SRL separates the dataflow description from its
execution; ElegantRL-Podracer makes actor counts a scheduling knob).
A `DistPlan` is that description as a static pytree-of-config:

  * a device mesh of named axes (`AxisSpec`), outermost first —
    default 1-D ``(workers,)``, first-class 2-D ``(hosts, workers)``;
  * a per-axis collective — ``allreduce`` / ``ps`` / ``gossip`` —
    compiled into the Trainer's `grad_tx`/`param_tx` hooks. Consecutive
    allreduce axes fuse into ONE collective over the axis-name tuple,
    so a (1, N) or (2, N/2) nesting of pure allreduce lowers to the
    same all-reduce over the same device group as the flat plan and
    stays bitwise-identical (pinned in tests/test_trainer.py);
  * a per-axis sync schedule — ``bsp``/``asp``/``ssp`` rendered as
    policy-lag delays (repro.core.sync) which ADD across levels: a
    device at mesh coordinates (i0, i1, ...) acts with params
    ``sum_a delay_a[t, i_a]`` learner-updates old;
  * a per-axis ``role`` — ``data`` (plain data-parallel workers),
    ``shard`` (ZeRO-2 learner-state sharding, §5 memory ceiling): over
    a shard axis the Trainer reduce-scatters gradients, applies the
    optimizer update on the local 1/N slice of the flattened
    params/opt_state, and all-gathers params before the next rollout.
    A shard axis must use ``allreduce`` (its gradient mean fuses into
    the data-parallel pmean, making pmean + local slice the
    reduce-scatter), so a sharded plan trains f32-bitwise-identically
    to its replicated counterpart and a shard axis of size 1 is a
    bitwise no-op (pinned in tests/test_trainer.py);
    ``zero3`` (full ZeRO-3: params additionally stored as 1/N chunks,
    gathered per use); or ``replay`` (sharded replay service, Gorila's
    distributed replay memory): the replay group holds ONE logical
    replay buffer, each member owning a contiguous 1/N capacity slice.
    Members replicate the data-position rollout/learner compute (the
    axis adds replay capacity, not sample throughput), insertion
    routes transitions to the owning shard, sampling merges per-shard
    Gumbel-top-k candidates over the axis, and priority write-back
    routes to the owner — draw-for-draw the single-buffer
    PrioritizedReplay, so the fit stays bitwise the flat data plan
    (pinned in tests/test_replay_service.py);
  * an optional elastic ``actors=`` schedule: total env-shard counts
    cycled per superstep dispatch. Agents only consume ``traj``, so
    resharding between supersteps is invisible to them.

The legacy single-axis path (`n_workers`/`topology`/`sync` flags) lowers
onto `DistPlan.flat(...)` and stays bitwise-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sync import (MECHANISMS, SyncConfig, make_delays,
                             pipeline_depth as _sync_pipeline_depth)
from repro.core.topology import TOPOLOGIES, exchange_grads, gossip_mix

_SYNC_EXTRA = {"bsp": lambda ax: 0,
               "asp": lambda ax: ax.max_delay,
               "ssp": lambda ax: min(ax.max_delay, ax.staleness_bound)}

ROLES = ("data", "shard", "zero3", "replay")


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """One named mesh axis: its size, how gradients/params are exchanged
    across it (§3), how stale its members may act (§6), and its role —
    `data` (plain data-parallel workers), `shard` (ZeRO-2 learner-
    state sharding: gradients are reduce-scattered over the axis, the
    optimizer update runs on the local 1/size slice of the flattened
    params/opt_state, and params are all-gathered before the next
    rollout), `zero3` (full ZeRO-3: params are additionally STORED
    as 1/size chunks in TrainState and all-gathered per use inside
    learner_step/actor_policy — gather, compute, drop), or `replay`
    (sharded replay service: the group holds ONE logical replay buffer,
    1/size of its capacity per member, while replicating the
    data-position compute)."""
    name: str
    size: int
    collective: str = "allreduce"   # §3: allreduce | ps | gossip
    sync: str = "bsp"               # §6: bsp | asp | ssp
    max_delay: int = 4              # asp worst-case extra staleness
    staleness_bound: int = 1        # ssp bound on extra staleness
    role: str = "data"              # data | shard | zero3 | replay

    def __post_init__(self):
        if not self.name:
            raise ValueError("axis name must be non-empty")
        if self.size < 1:
            raise ValueError(f"axis {self.name!r}: size {self.size} < 1")
        if self.collective not in TOPOLOGIES:
            raise ValueError(f"axis {self.name!r}: collective "
                             f"{self.collective!r} not in {TOPOLOGIES}")
        if self.sync not in MECHANISMS:
            raise ValueError(f"axis {self.name!r}: sync {self.sync!r} "
                             f"not in {MECHANISMS}")
        if self.role not in ROLES:
            raise ValueError(f"axis {self.name!r}: role {self.role!r} "
                             f"not in {ROLES}")
        if self.role in ("shard", "zero3") and self.collective != "allreduce":
            raise ValueError(
                f"axis {self.name!r}: a {self.role}-role axis must use "
                f"the 'allreduce' collective (got {self.collective!r}) — "
                f"its gradient mean fuses into the data-parallel "
                f"reduction so that pmean + local slice IS the "
                f"reduce-scatter (bitwise the replicated plan)")
        if self.role == "replay" and self.collective != "allreduce":
            raise ValueError(
                f"axis {self.name!r}: a replay-role axis must use the "
                f"'allreduce' collective (got {self.collective!r}) — "
                f"the sharded replay service merges per-shard top-k "
                f"candidates and assembles batches with all-gather/psum "
                f"over the axis, which presumes the synchronous "
                f"allreduce domain")
        if self.role == "zero3" and self.sync != "bsp":
            raise ValueError(
                f"axis {self.name!r}: a zero3-role axis must use 'bsp' "
                f"sync (got {self.sync!r}) — the gather-per-use params "
                f"are assembled from one ring slot per shard member, so "
                f"shard-group members must act in lockstep; spend the "
                f"staleness budget on the data axes instead")
        if self.role == "replay" and self.sync != "bsp":
            raise ValueError(
                f"axis {self.name!r}: a replay-role axis must use 'bsp' "
                f"sync (got {self.sync!r}) — replay-group members hold "
                f"slices of ONE logical buffer, so they must act in "
                f"lockstep for its contents to stay coherent; spend the "
                f"staleness budget on the data axes instead")

    @property
    def ring_extra(self) -> int:
        """Actor-ring depth this axis's sync discipline can reach into."""
        return _SYNC_EXTRA[self.sync](self)

    @property
    def pipeline_depth(self) -> int:
        """Trajectory-queue depth this axis's sync discipline admits in
        the Trainer's ``pipeline=`` mode (repro.core.sync.pipeline_depth):
        bsp -> 0 (lockstep), ssp -> staleness_bound, asp -> max_delay.
        Numerically the same staleness budget as `ring_extra` — the
        lockstep path spends it as sampled policy lag, the pipelined
        path as producer run-ahead."""
        return _sync_pipeline_depth(SyncConfig(
            self.sync, self.size, self.max_delay, self.staleness_bound))


@dataclasses.dataclass(frozen=True)
class DistPlan:
    """Hierarchical distribution plan: mesh axes (outermost first) plus
    an optional elastic actor-shard schedule. Static / hashable — safe
    to close over in jitted code."""
    axes: Tuple[AxisSpec, ...] = (AxisSpec("workers", 1),)
    actors: Optional[Tuple[int, ...]] = None  # env shards per superstep

    def __post_init__(self):
        if not self.axes:
            raise ValueError("DistPlan needs at least one mesh axis "
                             "(empty axis list)")
        names = [a.name for a in self.axes]
        if len(set(names)) != len(names):
            dups = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(f"duplicate mesh axis name(s) {dups} "
                             f"in {names}")
        shards = [a.name for a in self.axes if a.role in ("shard", "zero3")]
        if len(shards) > 1:
            raise ValueError(f"at most one shard-role axis is supported "
                             f"(got {shards}); compose a bigger shard "
                             f"group as one axis instead")
        replays = [a.name for a in self.axes if a.role == "replay"]
        if len(replays) > 1:
            raise ValueError(f"at most one replay-role axis is supported "
                             f"(got {replays}); compose a bigger replay "
                             f"group as one axis instead")
        if self.actors is not None:
            if not self.actors:
                raise ValueError("actors= schedule must be non-empty")
            bad = [n for n in self.actors if n < 1]
            if bad:
                raise ValueError(f"actors= entries must be >= 1: {bad}")
            object.__setattr__(self, "actors", tuple(self.actors))
        object.__setattr__(self, "axes", tuple(self.axes))

    # ---- constructors -------------------------------------------------
    @classmethod
    def flat(cls, n_workers: int = 1, collective: str = "allreduce",
             sync: str = "bsp", max_delay: int = 4,
             staleness_bound: int = 1, actors=None,
             axis: str = "workers") -> "DistPlan":
        """The legacy single-axis path as a plan: 1-D (workers,) mesh.
        `Trainer(env, TrainerConfig(plan=DistPlan.flat(4)))` is bitwise
        what `n_workers=4, topology="allreduce", sync="bsp"` was."""
        return cls(axes=(AxisSpec(axis, n_workers, collective, sync,
                                  max_delay, staleness_bound),),
                   actors=None if actors is None else tuple(actors))

    @classmethod
    def grid(cls, hosts: int, workers: int,
             inter: str = "allreduce", intra: str = "allreduce",
             inter_sync: str = "bsp", intra_sync: str = "bsp",
             max_delay: int = 4, staleness_bound: int = 1,
             actors=None) -> "DistPlan":
        """First-class 2-D (hosts, workers) plan: `intra` is the
        collective/sync within a host (the inner axis), `inter` across
        hosts (the outer axis) — e.g. intra-host allreduce + inter-host
        gossip."""
        return cls(axes=(AxisSpec("hosts", hosts, inter, inter_sync,
                                  max_delay, staleness_bound),
                         AxisSpec("workers", workers, intra, intra_sync,
                                  max_delay, staleness_bound)),
                   actors=None if actors is None else tuple(actors))

    @classmethod
    def zero(cls, n_workers: int, n_shards: int,
             collective: str = "allreduce", sync: str = "bsp",
             max_delay: int = 4, staleness_bound: int = 1,
             actors=None) -> "DistPlan":
        """Data-parallel workers + a ZeRO-2 shard axis (innermost, so
        the shard group sits on the fastest fabric): gradients reduce-
        scatter over `shard`, the optimizer updates the local 1/n slice,
        params all-gather before the next rollout."""
        return cls(axes=(AxisSpec("workers", n_workers, collective, sync,
                                  max_delay, staleness_bound),
                         AxisSpec("shard", n_shards, "allreduce", "bsp",
                                  max_delay, staleness_bound,
                                  role="shard")),
                   actors=None if actors is None else tuple(actors))

    @classmethod
    def zero3(cls, n_workers: int, n_shards: int,
              collective: str = "allreduce", sync: str = "bsp",
              max_delay: int = 4, staleness_bound: int = 1,
              actors=None) -> "DistPlan":
        """Data-parallel workers + a full ZeRO-3 shard axis (innermost):
        like `zero()` but params are also stored as 1/n chunks and all-
        gathered per use inside learner_step/actor_policy — gather,
        compute, drop — so per-device params+opt_state bytes shrink
        toward 1/n instead of only the opt_state."""
        return cls(axes=(AxisSpec("workers", n_workers, collective, sync,
                                  max_delay, staleness_bound),
                         AxisSpec("shard", n_shards, "allreduce", "bsp",
                                  max_delay, staleness_bound,
                                  role="zero3")),
                   actors=None if actors is None else tuple(actors))

    @classmethod
    def replay(cls, n_workers: int, n_shards: int,
               collective: str = "allreduce", sync: str = "bsp",
               max_delay: int = 4, staleness_bound: int = 1,
               actors=None) -> "DistPlan":
        """Data-parallel workers + a sharded-replay axis (innermost):
        the replay group holds ONE logical replay buffer, each member
        owning a contiguous 1/n slice of its capacity (Gorila's
        distributed replay memory as collectives over the mesh).
        Members replicate the data-axis rollout/learner compute — the
        axis adds replay capacity, not sample throughput — so the fit
        is bitwise the flat `n_workers` plan (tests/test_replay_service
        pins it)."""
        return cls(axes=(AxisSpec("workers", n_workers, collective, sync,
                                  max_delay, staleness_bound),
                         AxisSpec("replay", n_shards, "allreduce", "bsp",
                                  max_delay, staleness_bound,
                                  role="replay")),
                   actors=None if actors is None else tuple(actors))

    @classmethod
    def parse(cls, spec: str, max_delay: int = 4,
              staleness_bound: int = 1, actors=None) -> "DistPlan":
        """Parse the CLI grammar: comma-separated axes, outermost first,
        each ``name=size[:collective[:sync[:role]]]``, e.g.

            hosts=2:allreduce:bsp,workers=2:gossip:asp
            workers=4:allreduce:bsp,shard=2:allreduce:bsp:shard
            workers=4:allreduce:bsp,shard=2:allreduce:bsp:zero3
            workers=2:allreduce:bsp,replay=2:allreduce:bsp:replay

        Role ``shard`` marks the ZeRO-2 learner-state sharding axis,
        ``zero3`` the full ZeRO-3 axis (params stored sharded too,
        gathered per use), ``replay`` the sharded replay-service axis
        (the group holds ONE logical replay buffer, 1/size per member;
        allreduce + bsp only); default ``data``. Empty specs, empty
        segments and duplicate axis names raise errors naming the
        offending input."""
        if not spec or not spec.strip():
            raise ValueError(
                "empty plan: expected comma-separated axes "
                "name=size[:collective[:sync[:role]]], e.g. "
                "'workers=4:allreduce:bsp'")
        axes = []
        for seg in spec.split(","):
            parts = seg.strip().split(":")
            if "=" not in parts[0]:
                raise ValueError(f"bad plan axis {seg!r}: expected "
                                 f"name=size[:collective[:sync[:role]]]")
            name, size = parts[0].split("=", 1)
            try:
                size = int(size)
            except ValueError:
                raise ValueError(f"bad plan axis {seg!r}: size "
                                 f"{size!r} is not an integer") from None
            collective = parts[1] if len(parts) > 1 else "allreduce"
            sync = parts[2] if len(parts) > 2 else "bsp"
            role = parts[3] if len(parts) > 3 else "data"
            if len(parts) > 4:
                raise ValueError(f"bad plan axis {seg!r}: too many ':' "
                                 f"(grammar is name=size[:collective"
                                 f"[:sync[:role]]])")
            axes.append(AxisSpec(name.strip(), size, collective,
                                 sync, max_delay, staleness_bound, role))
        return cls(axes=tuple(axes),
                   actors=None if actors is None else tuple(actors))

    # ---- derived shape ------------------------------------------------
    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a.name for a in self.axes)

    @property
    def mesh_shape(self) -> Tuple[int, ...]:
        return tuple(a.size for a in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for a in self.axes:
            n *= a.size
        return n

    @property
    def ring_extra(self) -> int:
        """Worst-case total extra staleness: per-axis delays add."""
        return sum(a.ring_extra for a in self.axes)

    @property
    def pipeline_depth(self) -> int:
        """Trajectory-queue depth of the plan in the Trainer's
        ``pipeline=`` mode: per-axis staleness budgets add, exactly as
        the per-axis delay schedules add in the lockstep rendering. A
        pure bsp plan has depth 0: no queue, and the superstep is the
        lockstep one."""
        return sum(a.pipeline_depth for a in self.axes)

    @property
    def shard_axis(self) -> Optional[AxisSpec]:
        """The (single, validated) ZeRO shard-role axis — role `shard`
        (ZeRO-2) or `zero3` — or None."""
        for a in self.axes:
            if a.role in ("shard", "zero3"):
                return a
        return None

    @property
    def data_axes(self) -> Tuple[AxisSpec, ...]:
        return tuple(a for a in self.axes if a.role == "data")

    @property
    def shard_size(self) -> int:
        """Learner-state shard count (1 when no shard axis)."""
        ax = self.shard_axis
        return 1 if ax is None else ax.size

    @property
    def replay_axis(self) -> Optional[AxisSpec]:
        """The (single, validated) replay-role axis, or None."""
        for a in self.axes:
            if a.role == "replay":
                return a
        return None

    @property
    def replay_size(self) -> int:
        """Replay shard count (1 when no replay axis)."""
        ax = self.replay_axis
        return 1 if ax is None else ax.size

    @property
    def sim_shape(self) -> Tuple[int, ...]:
        """Mesh shape with the ACTIVE replay axis (size > 1) collapsed
        to 1 — the env grid: replay-group members replicate the rollout
        of their data position (the axis adds replay capacity, not
        sample throughput), so envs shard over the non-replay axes
        only. A size-1 replay axis stays a plain data axis (the no-op
        guarantee holds by construction)."""
        return tuple(1 if (a.role == "replay" and a.size > 1) else a.size
                     for a in self.axes)

    @property
    def sim_devices(self) -> int:
        """Device count of the env grid (`sim_shape`); equals
        `n_devices` on plans without an active replay axis."""
        n = 1
        for s in self.sim_shape:
            n *= s
        return n

    def describe(self) -> str:
        s = ",".join(f"{a.name}={a.size}:{a.collective}:{a.sync}"
                     + (f":{a.role}" if a.role != "data" else "")
                     for a in self.axes)
        if self.actors is not None:
            s += ";actors=" + ",".join(map(str, self.actors))
        return s

    # ---- mesh construction --------------------------------------------
    def validate_devices(self, devices) -> None:
        """Clear error instead of silently slicing/wrapping devices."""
        n_available = len(devices)
        if self.n_devices > n_available:
            shape = "x".join(f"{a.name}={a.size}" for a in self.axes)
            msg = (f"DistPlan mesh ({shape}) needs {self.n_devices} devices "
                   f"but only {n_available} "
                   f"{'is' if n_available == 1 else 'are'} visible")
            platform = devices[0].platform
            if platform == "cpu":
                msg += (f"; set XLA_FLAGS=--xla_force_host_platform_"
                        f"device_count={self.n_devices} before importing "
                        f"jax (the rl_train CLI does this automatically)")
            else:
                msg += (f"; found {n_available} {platform} device(s) "
                        f"({devices[0].device_kind})")
            raise RuntimeError(msg)

    def build_mesh(self, devices=None):
        """Mesh over the first `n_devices` visible devices, row-major:
        the device at mesh coordinates (i0, i1, ...) is flat device
        ``sum_a i_a * stride_a`` — the same order the flat plan uses, so
        nesting never permutes which envs/RNG streams a device owns."""
        from jax.sharding import Mesh
        devices = jax.devices() if devices is None else devices
        self.validate_devices(devices)
        devs = np.asarray(devices[:self.n_devices]).reshape(
            self.mesh_shape)
        return Mesh(devs, self.axis_names)

    # ---- compiled pieces consumed by the Trainer ----------------------
    def linear_index(self):
        """Traced flat device index inside shard_map (RNG stream id) —
        identical to the flat plan's `axis_index("workers")`."""
        idx = jax.lax.axis_index(self.axes[0].name)
        for a in self.axes[1:]:
            idx = idx * a.size + jax.lax.axis_index(a.name)
        return idx

    def sim_index(self):
        """Traced device index over the env grid (`sim_shape`) — the
        RNG stream id. Like `linear_index` but the ACTIVE replay axis
        contributes nothing, so every member of a replay group draws
        exactly the stream of its data position in the flat plan (the
        group replicates rollouts; only replay STORAGE is sharded). On
        plans without an active replay axis this is `linear_index`
        term-for-term — a size-1 replay axis contributes idx*1 + 0."""
        idx = None
        for a in self.axes:
            if a.role == "replay" and a.size > 1:
                continue
            i = jax.lax.axis_index(a.name)
            idx = i if idx is None else idx * a.size + i
        return jnp.zeros((), jnp.int32) if idx is None else idx

    def compile_collectives(self):
        """(grad_tx, param_tx) hooks: per-axis collectives applied
        innermost -> outermost. Consecutive allreduce axes fuse into one
        pmean over the axis-name tuple (bitwise the flat all-reduce);
        ps star-gathers per axis; gossip skips the grad exchange and
        ring-mixes params on its axis instead."""
        steps = []  # innermost -> outermost: ("allreduce"|"ps", names)
        for ax in reversed(self.axes):
            if ax.role == "replay" and ax.size > 1:
                # replay-group members compute identical gradients by
                # construction (same envs, same RNG streams, same
                # sampled batch — only replay STORAGE differs), so
                # there is nothing to exchange; skipping the axis keeps
                # the reduction association bitwise the flat plan's. A
                # size-1 replay axis participates like a data axis.
                continue
            if ax.collective == "allreduce":
                if steps and steps[-1][0] == "allreduce":
                    # fuse, keeping names outermost-first: the device
                    # iteration order of the fused all-reduce then
                    # matches the flat plan's, bitwise
                    steps[-1] = ("allreduce", (ax.name,) + steps[-1][1])
                else:
                    steps.append(("allreduce", (ax.name,)))
            elif ax.collective == "ps":
                steps.append(("ps", ax.name))
        gossip_axes = tuple(ax.name for ax in reversed(self.axes)
                            if ax.collective == "gossip")

        def grad_tx(grads):
            for kind, names in steps:
                grads = exchange_grads(grads, names, kind)
            return grads

        def param_tx(params):
            for name in gossip_axes:
                params = gossip_mix(params, name)
            return params

        return grad_tx, (param_tx if gossip_axes else None)

    def make_delay_schedule(self, n_steps: int, key):
        """(n_steps,) + mesh_shape int32 delays: per-axis §6 schedules
        broadcast over the other axes and summed. A single-axis plan
        consumes `key` exactly as the legacy path did (bitwise-identical
        schedules); multi-axis plans split it per axis."""
        total = jnp.zeros((n_steps,) + self.mesh_shape, jnp.int32)
        keys = ([key] if len(self.axes) == 1
                else list(jax.random.split(key, len(self.axes))))
        for i, ax in enumerate(self.axes):
            d = make_delays(SyncConfig(ax.sync, ax.size, ax.max_delay,
                                       ax.staleness_bound),
                            n_steps, keys[i])         # (n_steps, size)
            shape = [n_steps] + [1] * len(self.axes)
            shape[1 + i] = ax.size
            total = total + d.reshape(shape)
        return total

    def actor_schedule(self, superstep_idx: int, default: int) -> int:
        """Total env-shard count for superstep `superstep_idx` (the
        schedule cycles); `default` when the plan is not elastic."""
        if self.actors is None:
            return default
        return self.actors[superstep_idx % len(self.actors)]


# all-meta pytrees: plans flow through jit/closure boundaries as static
# config, never as traced leaves
jax.tree_util.register_static(AxisSpec)
jax.tree_util.register_static(DistPlan)
