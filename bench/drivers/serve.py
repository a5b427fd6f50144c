"""Window driver of the serving cells.

The window drives `ServeEngine.submit` and `ServeEngine.step`
(core/serving.py) with open-loop Poisson arrivals drawn from the seed
at the rate the traffic file fixes, one observation per request. Each
request is timed from its scheduled arrival to the moment `step`
returns its response, so a stall is charged to every request it
delays. Requests due in the window that are still queued when it
closes are served afterwards, within a minute, and their latency
counts the wait.

Set-up builds the policy, publishes the benchmark's weights through a
`ParamStore`, and compiles every bucket (`ServeEngine.warmup`).
"""
import gc
import time

import jax
import numpy as np

from bench import harness

LATE_S = 60.0      # how long due requests may still be served after the window


def arrivals(rate, seconds, seed):
    """Scheduled arrival offsets (s) of a Poisson stream at `rate`/s."""
    rng = np.random.default_rng(seed)
    n = int(rate * seconds * 1.2) + 100
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return t[t < seconds]


def observations(n, traffic, seed):
    """One observation per request, uniform within the traffic file's
    limits of each feature."""
    rng = np.random.default_rng(seed ^ 0x0B5)
    lim = np.asarray(traffic["obs_limits"], np.float32)
    return (rng.uniform(-1.0, 1.0, (n, len(lim))) * lim).astype(np.float32)


def build(cell, seed):
    from repro.core.serving import ParamStore, ServeEngine
    code, sizes, traffic = cell["code"], cell["sizes"], cell["traffic"]
    policy, env = code.serve_policy(sizes)
    store = ParamStore()
    store.publish(code.weights(sizes, seed))
    engine = ServeEngine(policy, env.spec.observation,
                         buckets=tuple(traffic["buckets"]), store=store,
                         seed=seed)
    engine.warmup()
    # `step` cuts each answer to its n live rows on the device, one
    # program per n that `warmup` does not reach: run every n the
    # traffic can bring before the window opens
    from repro.core.serving import bucket_for
    rows = observations(engine.max_bucket, traffic, seed)
    for n in range(1, engine.max_bucket + 1):
        jax.block_until_ready(engine.eval_bucket(
            list(rows[:n]), list(range(n)), bucket_for(n, engine.buckets)))
    return engine


def offered(engine, spans, rate, seconds, traffic, seed, keep=()):
    """Serve one open-loop window. Returns per request its latency (s;
    NaN if never served) and the lateness of its submission (s), the
    observations and request ids, and the responses of the requests at
    the positions `keep`."""
    due = arrivals(rate, seconds, seed)
    obs = observations(len(due), traffic, seed)
    lat = np.full(len(due), np.nan)
    late = np.zeros(len(due))
    keep = set(int(k) for k in keep)
    kept = {}
    start = time.perf_counter() + 0.005
    sched = start + due
    sub = 0
    id0 = None
    while True:
        now = time.perf_counter()
        if sub < len(due) and sched[sub] <= now:
            with spans("bench.generator"):
                while sub < len(due) and sched[sub] <= now:
                    rid = engine.submit(obs[sub], arrival=sched[sub])
                    id0 = rid - sub if id0 is None else id0
                    late[sub] = time.perf_counter() - sched[sub]
                    sub += 1
        if not len(engine.batcher):
            if sub == len(due):
                break
            with spans("bench.wait"):
                time.sleep(max(0.0, sched[sub] - time.perf_counter()))
            continue
        if now > start + seconds + LATE_S:
            break
        with spans("bench.serve_step"):
            out = engine.step()
        for r in out:
            # the client takes each answer out of the engine's record,
            # as a long-running server must: kept, 10^5 answers a window
            # make Python's collector stall the loop
            engine.results.pop(r["id"], None)
            pos = r["id"] - id0
            lat[pos] = r["latency_s"]
            if pos in keep:
                kept[pos] = r
    return {"lat": lat, "late": late, "obs": obs,
            "ids": id0 + np.arange(len(due)), "kept": kept,
            "elapsed": time.perf_counter() - start}


def run(cell, args, seed, devs, spans, t_start, trace_body):
    code, sizes, traffic = cell["code"], cell["sizes"], cell["traffic"]
    engine = build(cell, seed)
    rate = traffic["rate_rps"]
    seconds = traffic["trace_seconds"] if args.trace else args.seconds
    # the requests whose answers are checked: drawn from the seed among
    # all that are due; those served are compared
    n_due = len(arrivals(rate, seconds, seed))
    rng = np.random.default_rng(seed ^ 0x5A)
    keep = rng.choice(n_due, min(traffic["check_requests"], n_due),
                      replace=False)
    gc.collect()
    gc.freeze()     # set-up's objects are never collected again
    t_setup = time.perf_counter()
    out = {"setup_s": t_setup - t_start}
    window = lambda: offered(engine, spans, rate, seconds, traffic, seed,
                             keep)
    if args.trace:
        w, reduced = trace_body(window)
        n_rows = int(np.isfinite(w["lat"]).sum())
        out.update(reduced=reduced, iters=n_rows, kernels={},
                   flops=n_rows * code.serve_flops(sizes))
    else:
        w = window()
    lat = w["lat"]
    served = np.isfinite(lat)
    lat_ms = np.where(served, lat, np.inf) * 1e3
    out.update(attempted=int(len(lat)), failed=int((~served).sum()),
               metrics={"serve_p50_ms": float(np.percentile(lat_ms, 50))})
    print(f"generator lateness p95 "
          f"{float(np.percentile(w['late'], 95)) * 1e3!r} ms, latency p95 "
          f"{float(np.percentile(lat_ms, 95))!r} ms, over {len(lat)} "
          f"requests, {rate} req/s offered", flush=True)
    out["device"] = harness.device_info(devs)
    del engine
    gc.unfreeze()
    gc.collect()
    pick = np.asarray(sorted(w["kept"]), np.int64)
    got = {k: np.asarray([w["kept"][int(i)][k] for i in pick])
           for k in ("action", "logp", "value")}
    ref = code.serve_reference(sizes, seed, w["obs"][pick], w["ids"][pick])
    out["checks"] = code.serve_compare(got, ref, traffic["limits"])
    return out


def sweep(cell, seed, rates, seconds):
    """The knee: p50/p95 and completed rate at each offered rate, in one
    process. Prints one JSON line per rate."""
    import json
    engine = build(cell, seed)
    spans = harness.Spans()
    for rate in rates:
        w = offered(engine, spans, rate, seconds, cell["traffic"], seed)
        lat = w["lat"][np.isfinite(w["lat"])] * 1e3
        print(json.dumps({
            "rate_rps": rate, "requests": len(w["lat"]),
            "served": int(len(lat)),
            "completed_rps": len(lat) / w["elapsed"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "late_p95_ms": float(np.percentile(w["late"], 95)) * 1e3}),
            flush=True)
