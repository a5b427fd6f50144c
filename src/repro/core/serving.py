"""Policy serving subsystem: batched low-latency inference for live
traffic (survey §3.3 learner-side/centralized inference; SRL's
dedicated inference-worker class; Gorila's separation of acting from
learning).

Training (repro.core.trainer) owns throughput; this module owns
*latency under load*. It mirrors the Trainer seam on the traffic side:

  * **`serve_step`** — a jitted micro-batch program per bucket
    size. One program evaluates `agent.actor_policy`-compatible
    behavior params on a `(bucket, *obs_shape)` request batch: each
    request's action/log-prob/value comes from ONE
    `policy.sample_value` evaluation keyed by `fold_in(base_key,
    request_id)`, so a response depends only on (engine seed, request
    id, params) — never on which other requests happened to share the
    micro-batch. Nothing is donated: params are shared by every
    in-flight batch and across `ParamStore` versions.

  * **`RequestBatcher`** — host-side FIFO admission queue. Requests
    are never dropped and never reordered: `take` returns the oldest
    admissible requests up to the micro-batch cap, and anything beyond
    the cap simply waits for the next dispatch (backpressure, exactly
    like `queue_push` refusing on full in repro.core.pipeline).

  * **Bucketed micro-batching** — a batch of B live requests is padded
    to the smallest registered bucket >= B (`bucket_for`), exactly the
    pad-to-bucket discipline of the kernels ops layer
    (kernels/advantages/ops.py pads B to a block multiple), so each
    bucket size compiles ONCE and `ServeEngine.compile_count` stays
    flat under live traffic whatever batch sizes the load produces.
    Within a fixed bucket the padded rows are bitwise-inert: row i of a
    bucket-of-B dispatch equals row i of a per-request (single-request,
    same-bucket) dispatch bit for bit — pinned per registered env spec
    in tests/test_serving.py. (Across *different* bucket sizes XLA may
    pick different matmul tilings, so cross-bucket equality is
    numerical, not bitwise — one more reason the bucket set is a small
    static grammar and not per-batch shapes.)

  * **`ParamStore`** — versioned zero-recompile param hot-swap. Params
    enter `serve_step` as traced inputs, so publishing new weights —
    from a Trainer fit, a `repro.checkpoint` archive, or the live
    actor-param ring via `agent.actor_policy` — never triggers
    recompilation; `publish` validates the new pytree against the
    first-published template (same treedef/shapes/dtypes) and raises
    before a silently recompiling swap can happen. Versions are
    monotonic; a dispatch reads `(version, params)` once at admission,
    so in-flight batches finish on the version they started with and
    every response is tagged with the version that produced it.

While a profiler session runs, `ServeEngine.step` records a
`serve.step` span with four children that cover it (`serve.admit`,
`serve.dispatch`, `serve.read_back`, `serve.respond`) and counts
`serve.rows`, `serve.bucket_rows` and `serve.queue_wait_s` at admission
(repro.core.spans); otherwise it records nothing.

Offered-load latency/throughput is measured by
`repro.launch.serve_policy` -> repo-root BENCH_serve.json (p50/p99 at
varying offered load and bucket configurations), schema-guarded by
tests/test_bench_schema.py.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans


# --------------------------------------------------------- param store
class ParamStore:
    """Versioned behavior-param store for zero-recompile hot-swap.

    The first `publish` fixes the template (treedef + leaf
    shapes/dtypes); every later publish must match it exactly, which is
    what makes hot-swap recompile-free BY CONSTRUCTION — `serve_step`
    is traced once per bucket against the template's shapes and new
    versions only ever change buffer *contents*. `get()` hands out
    `(version, params)` as an immutable snapshot: publishing never
    mutates previously handed-out arrays, so in-flight batches finish
    on the version they started with.
    """

    def __init__(self):
        self._version = 0
        self._params = None
        self._template = None   # [(keypath, shape, dtype), ...]

    @property
    def version(self) -> int:
        """Monotonic version of the latest published params (0 = none)."""
        return self._version

    @staticmethod
    def _signature(params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [("/".join(str(p) for p in path), leaf.shape,
                   jnp.dtype(leaf.dtype)) for path, leaf in flat]
        return treedef, leaves

    def publish(self, params) -> int:
        """Swap in new behavior params; returns the new version.

        Raises ValueError naming the offending leaf if the pytree does
        not match the first-published template — shape drift would mean
        a recompile, which serving never allows."""
        params = jax.tree_util.tree_map(jnp.asarray, params)
        treedef, leaves = self._signature(params)
        if self._template is None:
            self._template = (treedef, leaves)
        else:
            t_def, t_leaves = self._template
            if treedef != t_def:
                raise ValueError(
                    f"hot-swap rejected: params treedef {treedef} does "
                    f"not match the published template {t_def}")
            for (path, shape, dtype), (tp, ts, td) in zip(leaves,
                                                          t_leaves):
                if (shape, dtype) != (ts, td):
                    raise ValueError(
                        f"hot-swap rejected: leaf {path!r} is "
                        f"{shape}/{dtype}, template has {ts}/{td} — "
                        f"shape/dtype drift would force a recompile")
        self._version += 1
        self._params = params
        return self._version

    def publish_from_state(self, agent, state, delay: int = 0) -> int:
        """Publish the live actor-param ring view: whatever
        `agent.actor_policy(state, delay)` serves the rollout engine
        (for DQN that includes the annealed exploration rate, so served
        actions match the live actors bitwise). A ZeRO-3 sharded
        TrainState (topology.ZeRO3Agent wrapper form) is reassembled to
        the replicated tree shape first, so the published pytree always
        matches the plan-independent template."""
        state = getattr(agent, "host_state", lambda s: s)(state)
        return self.publish(agent.actor_policy(state, delay))

    def load_checkpoint(self, path, agent, example_state=None,
                        delay: int = 0) -> int:
        """Restore a Trainer checkpoint (repro.checkpoint) and publish
        its actor-policy view. The agent must be constructed with the
        config (ring_size etc.) that produced the checkpoint; see
        checkpoint.load_train_state."""
        from repro.checkpoint.ckpt import load_train_state
        state, _ = load_train_state(path, agent, example=example_state)
        return self.publish_from_state(agent, state, delay)

    def get(self):
        """-> (version, params) snapshot of the latest publish."""
        if self._params is None:
            raise RuntimeError("ParamStore is empty: publish params "
                               "(publish / publish_from_state / "
                               "load_checkpoint) before serving")
        return self._version, self._params


# ----------------------------------------------------------- batching
def validate_buckets(buckets) -> Tuple[int, ...]:
    """Normalize/validate a bucket grammar: a strictly increasing tuple
    of positive micro-batch sizes. The largest bucket is the dispatch
    cap. Raises ValueError naming the offending entry."""
    buckets = tuple(int(b) for b in buckets)
    if not buckets:
        raise ValueError("empty bucket set: serving needs at least one "
                         "micro-batch size")
    for i, b in enumerate(buckets):
        if b <= 0:
            raise ValueError(f"bucket sizes must be positive, got {b}")
        if i and b <= buckets[i - 1]:
            raise ValueError(f"bucket sizes must be strictly "
                             f"increasing, got {buckets[i - 1]} "
                             f"before {b}")
    return buckets


def bucket_for(n: int, buckets) -> int:
    """Smallest registered bucket >= n (pad-to-bucket, ops-layer
    style). `n` above the largest bucket is a caller error — the
    batcher caps takes at max(buckets)."""
    if n <= 0:
        raise ValueError(f"cannot bucket an empty batch (n={n})")
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds the largest bucket "
                     f"{buckets[-1]}; take() must cap at it")


class RequestBatcher:
    """Host-side FIFO admission queue for asynchronous requests.

    `submit` assigns a monotonically increasing request id and records
    the arrival time (wall-clock by default; load generators pass
    their scheduled arrival so queueing delay is charged to latency).
    `take` pops the oldest <= `max_n` admissible requests — strictly
    FIFO, never dropping: requests beyond the cap stay queued for the
    next dispatch."""

    def __init__(self):
        self._queue = collections.deque()
        self._next_id = 0

    def __len__(self) -> int:
        return len(self._queue)

    def submit(self, obs, arrival: Optional[float] = None) -> int:
        rid = self._next_id
        self._next_id += 1
        self._queue.append(
            {"id": rid, "obs": obs,
             "arrival": time.perf_counter() if arrival is None
             else arrival})
        return rid

    def next_arrival(self) -> Optional[float]:
        """Arrival time of the oldest queued request (None if empty)."""
        return self._queue[0]["arrival"] if self._queue else None

    def take(self, max_n: int, now: Optional[float] = None) -> List[dict]:
        """Pop up to `max_n` requests in FIFO order. With `now`, only
        requests that have arrived (arrival <= now) are admissible —
        and FIFO means a not-yet-arrived head blocks everything behind
        it, so replayed arrival schedules stay in order."""
        out = []
        while self._queue and len(out) < max_n:
            if now is not None and self._queue[0]["arrival"] > now:
                break
            out.append(self._queue.popleft())
        return out


# ------------------------------------------------------------- engine
class ServeEngine:
    """Batched low-latency inference driver — the Trainer seam's
    traffic-facing mirror (module doc).

    `policy` is any rollout-engine policy (`sample_value`), `obs_space`
    the env's observation Space (padding template), `store` the
    ParamStore the engine reads at every dispatch. One jitted
    `serve_step` program exists per bucket size; `compile_count` counts
    traces (== XLA compiles) and stays flat under live traffic, batch
    size variation and param hot-swap once `warmup()` has run."""

    def __init__(self, policy, obs_space, buckets=(1, 4, 16),
                 store: Optional[ParamStore] = None, seed: int = 0):
        self.policy = policy
        self.obs_space = obs_space
        self.buckets = validate_buckets(buckets)
        self.store = ParamStore() if store is None else store
        self.batcher = RequestBatcher()
        self.results: Dict[int, dict] = {}
        self._base_key = jax.random.PRNGKey(seed)
        self._fns: Dict[int, Any] = {}
        self._compiles = 0
        self._served = 0        # rows dispatched, padding excluded
        self._batches = 0       # dispatches

    @classmethod
    def for_agent(cls, agent, env, **kw):
        """Engine for a registered Agent: its rollout policy + the
        env's observation spec. Publish params separately
        (`store.publish_from_state(agent, state)`)."""
        return cls(agent.policy, env.spec.observation, **kw)

    # -- the jitted per-bucket program ---------------------------------
    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    @property
    def compile_count(self) -> int:
        """Number of serve_step traces so far (tracing is 1:1 with XLA
        compilation here — the zero-recompile pin in tests and
        BENCH_serve.json reads this)."""
        return self._compiles

    @property
    def stats(self) -> Dict[str, int]:
        """Requests served and batches dispatched, warmup included."""
        return {"served": self._served, "batches": self._batches}

    def _bucket_fn(self, bucket: int):
        if bucket in self._fns:
            return self._fns[bucket]
        policy = self.policy

        def serve_step(params, base_key, obs, ids):
            # trace-time side effect: each execution of this Python
            # body is exactly one XLA compilation of this bucket
            self._compiles += 1

            def one(o, i):
                return policy.sample_value(
                    params, o, jax.random.fold_in(base_key, i))

            with jax.named_scope("serve_step"):
                return jax.vmap(one)(obs, ids)

        fn = jax.jit(serve_step)
        self._fns[bucket] = fn
        return fn

    def _pad_rows(self, rows, ids, bucket: int):
        # assemble host-side in numpy: one H2D transfer per dispatch
        # instead of a flurry of tiny stack/pad device ops (the
        # micro-batch path is latency-critical)
        shape = self.obs_space.shape
        dtype = np.dtype(jnp.dtype(self.obs_space.dtype).name)
        obs = np.zeros((bucket,) + shape, dtype)
        for j, r in enumerate(rows):
            obs[j] = np.asarray(r)
        pad_ids = np.full((bucket,), -1, np.int32)
        pad_ids[:len(ids)] = np.asarray(ids, np.int32)
        return obs, pad_ids

    def eval_bucket(self, obs_rows, ids, bucket: int, params=None):
        """Run the bucket's serve_step on explicit rows/ids (padded to
        `bucket`), returning `(action, logp, value)` for the first
        len(obs_rows) rows. This IS the program `step()` dispatches —
        the bucket-parity tests use it as the per-request oracle (one
        request per call, same bucket)."""
        if params is None:
            _, params = self.store.get()
        if not (0 < len(obs_rows) <= bucket):
            raise ValueError(f"{len(obs_rows)} rows do not fit "
                             f"bucket {bucket}")
        obs, pids = self._pad_rows(obs_rows, ids, bucket)
        n = len(obs_rows)
        action, logp, value = self._dispatch(params, obs, pids, n)
        return action[:n], logp[:n], value[:n]

    def _dispatch(self, params, obs, pids, n: int):
        """Every serve_step call goes through here: one padded bucket
        of which the first `n` rows are live."""
        self._served += n
        self._batches += 1
        return self._bucket_fn(len(pids))(params, self._base_key, obs,
                                          pids)

    def warmup(self):
        """Compile every bucket program once (against the current
        params) so live traffic never pays a compile; returns the
        compile count, which stays flat from here on."""
        _, params = self.store.get()
        for b in self.buckets:
            self.eval_bucket([jnp.zeros(self.obs_space.shape,
                                        self.obs_space.dtype)],
                             [0], b, params=params)
        return self._compiles

    # -- the serving loop ----------------------------------------------
    def submit(self, obs, arrival: Optional[float] = None) -> int:
        """Enqueue one observation; returns its request id."""
        return self.batcher.submit(obs, arrival)

    def step(self, now: Optional[float] = None) -> List[dict]:
        """Admit one micro-batch (FIFO, up to the largest bucket, padded
        to the smallest fitting bucket), evaluate it on the current
        ParamStore version, and return the completed responses
        (`{"id", "action", "logp", "value", "version", "latency_s"}`,
        also recorded in `self.results`). Returns [] when nothing is
        admissible."""
        with spans.span("serve.step"):
            with spans.span("serve.admit"):
                reqs = self.batcher.take(self.max_bucket, now=now)
                if not reqs:
                    return []
                n = len(reqs)
                version, params = self.store.get()
                bucket = bucket_for(n, self.buckets)
                obs, pids = self._pad_rows([r["obs"] for r in reqs],
                                           [r["id"] for r in reqs], bucket)
                if spans.enabled():
                    t = time.perf_counter()
                    spans.count("serve.rows", n)
                    spans.count("serve.bucket_rows", bucket)
                    spans.count("serve.queue_wait_s",
                                sum(t - r["arrival"] for r in reqs))
            with spans.span("serve.dispatch"):
                action, logp, value = self._dispatch(params, obs, pids, n)
            with spans.span("serve.read_back"):
                action, logp, value = jax.device_get(
                    (action[:n], logp[:n], value[:n]))
            with spans.span("serve.respond"):
                done = time.perf_counter()
                out = []
                for j, r in enumerate(reqs):
                    resp = {"id": r["id"], "action": action[j],
                            "logp": float(logp[j]),
                            "value": float(value[j]), "version": version,
                            "latency_s": done - r["arrival"]}
                    self.results[r["id"]] = resp
                    out.append(resp)
                return out

    def drain(self) -> List[dict]:
        """Serve until the admission queue is empty (ignores arrival
        times — everything queued is admissible)."""
        out = []
        while len(self.batcher):
            out.extend(self.step())
        return out

    def serve(self, obs_batch) -> jnp.ndarray:
        """Synchronous convenience: submit a whole observation batch,
        drain it through bucketed micro-batches, and return the actions
        stacked in submission order."""
        ids = [self.submit(o) for o in obs_batch]
        self.drain()
        return jnp.stack([jnp.asarray(self.results[i]["action"])
                          for i in ids])
