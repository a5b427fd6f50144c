"""The program's spans and counters (repro.core.spans): off without a
profiler session, recorded with their parents and in the profiler's
trace with one, the collector and compile hooks, the serving path's
spans and counters, and the device phases' named scopes in the
compiled superstep."""
import gc
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.envs as envs
from repro.core import spans
from repro.core.networks import MLPPolicy
from repro.core.serving import ParamStore, ServeEngine
from repro.core.trainer import Trainer, TrainerConfig

SERVE_CHILDREN = ("serve.admit", "serve.dispatch", "serve.read_back",
                  "serve.respond")


@pytest.fixture
def tracing(tmp_path):
    """A profiler session around the test body; -> the trace directory.
    The buffer starts empty."""
    spans.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        if spans.enabled():
            jax.profiler.stop_trace()


def _engine(buckets=(1, 4)):
    env = envs.make("cartpole")
    policy = MLPPolicy.for_spec(env.spec, hidden=(8,))
    store = ParamStore()
    store.publish(policy.init(jax.random.PRNGKey(0)))
    engine = ServeEngine(policy, env.spec.observation, buckets=buckets,
                         store=store, seed=1)
    engine.warmup()
    return env, engine


def _obs(env, n):
    return np.asarray(jax.vmap(env.spec.observation.sample)(
        jax.random.split(jax.random.PRNGKey(2), n)))


def _by_name(snap, name):
    return [(i, s) for i, s in enumerate(snap["spans"]) if s.name == name]


def test_nothing_is_recorded_without_a_session():
    spans.reset()
    assert not spans.enabled()
    with spans.span("outer"):
        with spans.span("inner"):
            spans.count("n", 3)
    gc.collect()
    assert spans.snapshot() == {"spans": [], "counters": {}, "dropped": 0}


def test_spans_nest_and_counters_sum_under_a_session(tracing):
    assert spans.enabled()
    with spans.span("t.outer"):
        with spans.span("t.inner"):
            spans.count("t.n", 3)
        with spans.span("t.inner"):
            spans.count("t.n", 4)
    jax.profiler.stop_trace()
    snap = spans.snapshot()
    (o, outer), = _by_name(snap, "t.outer")
    inner = _by_name(snap, "t.inner")
    assert outer.parent == -1 and len(inner) == 2
    for _, s in inner:
        assert s.parent == o
        assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns
    assert snap["counters"]["t.n"] == (7, 2)
    assert snap["dropped"] == 0

    from jax.profiler import ProfileData
    pb, = Path(tracing).rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(pb)).planes
             if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events}
    assert {"t.outer", "t.inner"} <= names


def test_collector_and_compile_hooks(tracing):
    gc.collect()
    n = 1000 + time.perf_counter_ns() % 1000    # a shape not yet compiled
    jax.jit(lambda x: x * 3.0)(jnp.ones((n,))).block_until_ready()
    jax.profiler.stop_trace()
    snap = spans.snapshot()
    gcs = [s for _, s in _by_name(snap, "host.gc")]
    assert any(s.detail == "2" for s in gcs)
    assert all(s.start_ns <= s.end_ns for s in gcs)
    compiles = [s for _, s in _by_name(snap, "jax.compile")]
    assert any("lambda" in s.detail for s in compiles)
    assert all(0 < s.end_ns - s.start_ns for s in compiles)


def test_bounded_buffer_counts_what_it_drops(tracing, monkeypatch):
    monkeypatch.setattr(spans, "MAX_SPANS", 2)
    for _ in range(3):
        with spans.span("t.s"):
            pass
    jax.profiler.stop_trace()
    snap = spans.snapshot()
    assert len(snap["spans"]) == 2 and snap["dropped"] == 1


def test_serving_window_leaves_no_records_without_a_session():
    env, engine = _engine()
    spans.reset()
    for o in _obs(env, 6):
        engine.submit(o)
    engine.drain()
    assert spans.snapshot() == {"spans": [], "counters": {}, "dropped": 0}


def test_serve_step_spans_and_counters(tracing):
    env, engine = _engine(buckets=(1, 4))
    obs = _obs(env, 6)
    t0 = time.perf_counter()
    arrivals = [t0 - 0.5 + 0.01 * i for i in range(6)]
    for o, a in zip(obs, arrivals):
        engine.submit(o, arrival=a)
    before = time.perf_counter()
    served = engine.drain()                 # 4 rows in bucket 4, then 2 in 4
    after = time.perf_counter()
    jax.profiler.stop_trace()
    snap = spans.snapshot()

    steps = _by_name(snap, "serve.step")
    assert len(steps) == 2
    for i, step in steps:
        # a collector pass between two children would be a child too
        kids = [s for s in snap["spans"]
                if s.parent == i and s.name in SERVE_CHILDREN]
        assert tuple(s.name for s in kids) == SERVE_CHILDREN
        assert step.start_ns <= kids[0].start_ns
        assert kids[-1].end_ns <= step.end_ns
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
    c = snap["counters"]
    assert c["serve.rows"] == (len(served), 2)
    assert c["serve.bucket_rows"] == (8, 2)
    wait, n = c["serve.queue_wait_s"]
    assert n == 2
    assert sum(before - a for a in arrivals) <= wait
    assert wait <= sum(after - a for a in arrivals)


def test_engine_stats_count_rows_and_dispatches():
    env, engine = _engine(buckets=(1, 4))
    assert engine.stats == {"served": 2, "batches": 2}   # warmup: one each
    for o in _obs(env, 6):
        engine.submit(o)
    engine.drain()
    assert engine.stats == {"served": 8, "batches": 4}
    engine.eval_bucket(list(_obs(env, 3)), [0, 1, 2], 4)
    assert engine.stats == {"served": 11, "batches": 5}


def test_superstep_carries_the_phase_scopes():
    env = envs.make("cartpole")
    cfg = TrainerConfig(algo="dqn", iters=2, superstep=2, n_envs=4,
                        unroll=4, algo_kwargs={"replay_capacity": 256,
                                               "hidden": (8,),
                                               "batch_size": 8})
    hlo = Trainer(env, cfg).lower(2).compile().as_text()
    op_names = " ".join(
        part.split('"')[1] for part in hlo.split("op_name=")[1:])
    for scope in ("rollout", "learner", "optimizer", "replay.insert",
                  "replay.sample", "replay.update_priorities"):
        assert f"/{scope}/" in op_names, scope
