"""Shared plumbing of the benchmark: finding a cell's files by name,
the device check, the peaks table, the compile cache, host spans,
tracing and the result line.

Nothing here knows a configuration, a traffic mix or a metric: those
live in files of their own under `bench/configs`, `bench/workloads`
and `bench/metrics`, found by the names in `BENCHMARK.json`.
"""
from __future__ import annotations

import contextlib
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(Exception):
    """The run cannot stand: no accelerator, too few chips, an unknown
    device kind, or a cell the benchmark does not define."""


# ------------------------------------------------------------ lookup
def load_json(path):
    return json.loads(Path(path).read_text())


def load_module(path, name=None):
    """Import a file by path (configuration, metric and driver files are
    named after the entries of BENCHMARK.json, not as packages)."""
    path = Path(path)
    if not path.is_file():
        raise Refused(f"no such file: {path.relative_to(ROOT)}")
    name = name or "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return load_json(ROOT / "BENCHMARK.json")


def cell(name, bench=None):
    """Everything one cell is made of, found by name: the workload entry,
    its configuration entry and file, the traffic file
    `bench/workloads/<cell>.json`, and the configuration's code
    `bench/configs/<config>.py` beside its file of sizes."""
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise Refused(f"unknown workload {name!r}; known: "
                      f"{', '.join(sorted(by_name))}")
    work = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[work["config"]]
    sizes = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "workloads" / f"{name}.json")
    code = load_module(BENCH / "configs" / f"{work['config']}.py")
    return {"workload": work, "config": conf, "sizes": sizes,
            "traffic": traffic, "code": code}


def end_to_end_for(cell_name, bench=None):
    bench = bench or benchmark()
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer_for(cell_name, bench=None):
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    bench = bench or benchmark()
    e2e = {m["name"] for m in end_to_end_for(cell_name, bench)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def metric_reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


# ------------------------------------------------------------- seeds
# The seed of the program's own key stream (the Trainer's action and
# learner keys). The Trainer folds its key into the compiled superstep
# as a constant, so a key from the run's seed would make every new seed
# compile anew; the run's seed makes the weights, envs and data instead.
PROGRAM_KEY_SEED = 0


def seed31(seed: int) -> int:
    """A 31-bit key for JAX from any whole-number seed: JAX keeps only
    the low 32 bits of a larger integer, so seeds that differ above
    them would collide."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


# ----------------------------------------------------- device, peaks
def devices(chips: int):
    """The cell's chips, or Refused: the benchmark runs on a TPU only
    and never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise Refused(f"device kind {device_kind!r} is not in "
                      f"bench/peaks.json")
    return table["devices"][device_kind]


def device_info(devs) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes
    in use on the fullest chip."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of each entry's key), unless
    JAX_COMPILATION_CACHE_DIR names one. Every program is cached,
    however fast it compiles, so a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------- spans
class Spans:
    """Host spans written by the harness around each call into the
    program. With tracing on, each span is also a
    `jax.profiler.TraceAnnotation`, so it lands on the profiler's clock
    beside the device's operations."""

    def __init__(self):
        self.tracing = False
        self.records = []          # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter_ns()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter_ns()))


def traced(body, reduce, keep_dir=None):
    """Run `body()` under the JAX profiler and return
    `(body's result, reduce(path of the .xplane.pb))`. The trace lives
    in a temporary directory (under TMPDIR) that is removed afterwards;
    `keep_dir` keeps a copy."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            result = body()
        finally:
            jax.profiler.stop_trace()
        pbs = sorted(Path(tmp).rglob("*.xplane.pb"))
        if not pbs:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        if keep_dir:
            Path(keep_dir).mkdir(parents=True, exist_ok=True)
            shutil.copy(pbs[-1], Path(keep_dir) / "trace.xplane.pb")
        return result, reduce(str(pbs[-1]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# ------------------------------------------------------------ output
def report_checks(checks):
    """Print each compared number beside its limit, as the last lines
    on standard error, and return them for the result line."""
    out = {}
    for c in checks:
        ok = c["value"] <= c["limit"]
        sys.stderr.write(f"check {c['name']}: {c['value']!r} "
                         f"(limit {c['limit']!r}) "
                         f"{'ok' if ok else 'FAIL'}\n")
        out[c["name"]] = {"value": c["value"], "limit": c["limit"]}
    sys.stderr.flush()
    return out


def checks_pass(checks) -> bool:
    import math
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)
