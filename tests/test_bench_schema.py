"""Bench-schema guard: every repo-root BENCH_*.json must parse against
the repro-bench/v1 shape (benchmarks/common.validate_bench_json), so
the machine-readable perf trajectory can't silently rot; plus the
pinned headlines: BENCH_zero.json (per-device opt_state bytes shrink
~1/shard_size under the ZeRO-2 shard axis; params+opt <= 0.67x under
the ZeRO-3 axis on the transformer trunk; peak live bytes strictly
below replicated under the layer-wise gather), BENCH_hotpath.json
(attention seam rows), BENCH_serve.json (sane p50/p99 grid, zero
recompiles after warmup across hot-swaps), and BENCH_replay.json
(per-device replay bytes <= 0.67x under the 2-shard replay axis)."""
import glob
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(REPO_ROOT))

from benchmarks.common import SCHEMA, validate_bench_json  # noqa: E402

BENCH_FILES = sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json")))


def test_bench_files_exist():
    names = {os.path.basename(p) for p in BENCH_FILES}
    # the committed trajectory: hot path (PR 3), topologies/sync (PR 4),
    # learner sharding (PR 5), policy serving (PR 7), sharded replay
    # (PR 9)
    assert {"BENCH_hotpath.json", "BENCH_topologies.json",
            "BENCH_sync.json", "BENCH_zero.json", "BENCH_serve.json",
            "BENCH_replay.json"} <= names


@pytest.mark.parametrize("path", BENCH_FILES,
                         ids=[os.path.basename(p) for p in BENCH_FILES])
def test_bench_json_matches_schema(path):
    with open(path) as f:
        doc = json.load(f)
    validate_bench_json(doc)
    assert doc["schema"] == SCHEMA
    assert doc["rows"], f"{path} has no rows"


def test_validate_bench_json_names_offending_input():
    good = {"schema": SCHEMA, "benchmark": "x", "backend": "cpu",
            "meta": {}, "rows": [{"name": "a", "us_per_call": 1.0,
                                  "derived": "d"}]}
    validate_bench_json(good)  # sanity: the good doc passes
    for mutate, frag in [
            (lambda d: d.update(schema="v2"), "schema"),
            (lambda d: d.update(rows="nope"), "rows"),
            (lambda d: d.update(meta=None), "meta"),
            (lambda d: d["rows"].append({"name": 1}), "rows[1]"),
            (lambda d: d["rows"][0].update(derived=7), "derived")]:
        doc = json.loads(json.dumps(good))
        mutate(doc)
        with pytest.raises(ValueError) as e:
            validate_bench_json(doc)
        assert frag in str(e.value), (frag, str(e.value))


def test_zero_bench_pins_opt_state_shrink():
    """Acceptance: BENCH_zero.json records per-device opt_state live
    bytes shrinking ~1/shard_size (within flatten-and-pad padding) for
    the size-2 shard axis vs the replicated plan."""
    with open(os.path.join(REPO_ROOT, "BENCH_zero.json")) as f:
        doc = validate_bench_json(json.load(f))
    row = {r["name"]: r["derived"] for r in doc["rows"]}
    derived = row["zero2/opt_state_shrink"]
    kv = dict(item.split("=", 1) for item in derived.split(";"))
    n_shards = doc["meta"]["partition"]["n_shards"]
    ratio = float(kv["ratio"])
    # ~1/shard_size within padding (one padded f32 out of the chunk)
    assert abs(ratio - 1.0 / n_shards) < 0.01, derived
    assert kv["ideal"] == f"1/{n_shards}"
    # and XLA's compiled live-bytes agree the sharded plan is smaller
    assert int(kv["xla_live_saved_bytes"]) > 0, derived


def test_zero_bench_pins_zero3_param_state_shrink():
    """Acceptance (PR 8): BENCH_zero.json records per-device
    params+opt_state bytes under the zero3-role axis at <= 0.67x the
    replicated plan on the transformer trunk (each component ~1/n_shards
    within padding), with XLA argument bytes — the persistent state the
    compiled superstep carries — corroborating. Live bytes are recorded
    too; gather-per-use converts the persistent saving into transient
    temp traffic, so that delta may go either way at 2 shards."""
    with open(os.path.join(REPO_ROOT, "BENCH_zero.json")) as f:
        doc = validate_bench_json(json.load(f))
    rows = {r["name"]: r for r in doc["rows"]}
    kv = dict(item.split("=", 1) for item in
              rows["zero3/param_state_shrink"]["derived"].split(";"))
    n = int(kv["n_shards"])
    assert n == doc["meta"]["partition_zero3"]["n_shards"]
    assert float(kv["threshold"]) == 0.67
    assert float(kv["ratio"]) <= 0.67, kv
    assert abs(float(kv["params_ratio"]) - 1.0 / n) < 0.01, kv
    assert abs(float(kv["opt_ratio"]) - 1.0 / n) < 0.01, kv
    assert int(kv["xla_arg_saved_bytes"]) > 0, kv
    int(kv["xla_live_saved_bytes"])  # present and integral
    for name in ("zero_shard/replicated_trunk", "zero_shard/zero3_trunk"):
        assert rows[name]["us_per_call"] > 0, name
        assert "xla_arg_bytes=" in rows[name]["derived"], name


def test_zero_bench_pins_layerwise_peak_live_shrink():
    """Acceptance (PR 10): with the per-block partition list (gather →
    run → drop one trunk superblock at a time, plus the per-entry
    optimizer apply), XLA peak LIVE bytes — argument + output + temp −
    donated alias of the compiled superstep — at 2 shards land strictly
    BELOW the replicated plan on the transformer trunk. This is the row
    the whole-vector gather could never produce: its full-size temps
    offset the argument saving at any shard count. Holds for the
    committed full run and the --quick regeneration CI does before this
    test."""
    with open(os.path.join(REPO_ROOT, "BENCH_zero.json")) as f:
        doc = validate_bench_json(json.load(f))
    rows = {r["name"]: r for r in doc["rows"]}
    kv = dict(item.split("=", 1) for item in
              rows["zero3_layerwise/peak_live_shrink"]["derived"].split(";"))
    assert float(kv["threshold"]) == 0.95
    assert float(kv["live_ratio"]) <= 0.95, kv
    assert (int(kv["xla_live_bytes_zero3"])
            < int(kv["xla_live_bytes_replicated"])), kv
    assert int(kv["xla_live_saved_bytes"]) > 0, kv
    # the trunk partitions layer-wise: R superblocks + the remainder
    assert int(kv["entries"]) >= 2, kv
    part = doc["meta"]["partition_zero3"]
    assert part["listwise"] is True, part
    assert part["entries"] == int(kv["entries"])
    assert len(part["sizes"]) == part["entries"]
    assert sum(part["sizes"]) == part["size"], part


def test_committed_bench_files_are_full_mode():
    """The committed perf trajectory must be full-mode runs: every
    BENCH_*.json blob at HEAD whose meta carries the `quick` stamp must
    have it False. CI regenerates the working-tree files with --quick
    before running tests, so this guard reads `git show HEAD:<file>` —
    the committed state — not the (legitimately quick) working tree.
    Files written before the stamp existed pass (key absent)."""
    for path in BENCH_FILES:
        rel = os.path.relpath(path, REPO_ROOT)
        proc = subprocess.run(
            ["git", "show", f"HEAD:{rel}"], cwd=REPO_ROOT,
            capture_output=True, text=True)
        if proc.returncode != 0:
            continue  # new file not yet at HEAD (or not a git checkout)
        doc = json.loads(proc.stdout)
        assert doc.get("meta", {}).get("quick") is not True, (
            f"{rel} was committed from a --quick run; regenerate it "
            f"with the full benchmark before committing")


def test_replay_bench_pins_bytes_shrink():
    """Acceptance (PR 9): BENCH_replay.json records per-device replay
    bytes under the 2-shard replay-role axis at <= 0.67x the replicated
    plan (ideal 1/2: each member owns one contiguous half of the ONE
    logical buffer), with XLA argument bytes — the persistent state the
    compiled superstep carries — corroborating, plus the per-sample
    latency rows for the flat fused draw vs the sharded merge. Holds
    for the committed full run and the --quick regeneration CI does
    before this test."""
    with open(os.path.join(REPO_ROOT, "BENCH_replay.json")) as f:
        doc = validate_bench_json(json.load(f))
    rows = {r["name"]: r for r in doc["rows"]}
    kv = dict(item.split("=", 1) for item in
              rows["replay/replay_bytes_shrink"]["derived"].split(";"))
    part = doc["meta"]["partition_replay"]
    assert part["axis"] == "replay" and part["n_shards"] == 2
    assert part["chunk"] * part["n_shards"] == part["capacity"]
    assert float(kv["threshold"]) == 0.67
    assert float(kv["ratio"]) <= 0.67, kv
    assert kv["ideal"] == f"1/{part['n_shards']}"
    assert int(kv["chunk"]) == part["chunk"]
    assert int(kv["sharded_bytes"]) < int(kv["replicated_bytes"]), kv
    assert int(kv["xla_arg_saved_bytes"]) > 0, kv
    for name in ("replay_shard/replicated", "replay_shard/sharded",
                 "replay_sample/flat_fused", "replay_sample/sharded_merge"):
        assert name in rows, sorted(rows)
        assert rows[name]["us_per_call"] > 0, name
    assert "overhead_ratio=" in rows["replay_sample/sharded_merge"][
        "derived"]


def test_hotpath_bench_pins_attention_rows():
    """Acceptance (PR 8): BENCH_hotpath.json times the trunk's
    attention seam three ways — naive jnp full softmax, the
    core/attention.py dispatcher ref, and the Pallas flash kernel — in
    the (B, S, KVH, G, D) grouped-query layout. Holds for the committed
    full run and the --quick regeneration CI does before this test."""
    with open(os.path.join(REPO_ROOT, "BENCH_hotpath.json")) as f:
        doc = validate_bench_json(json.load(f))
    rows = {r["name"]: r for r in doc["rows"]}
    for name in ("attention/naive_jnp", "attention/flash_ref",
                 "attention/flash_kernel"):
        assert name in rows, sorted(rows)
        assert rows[name]["us_per_call"] > 0, name
        assert "S=" in rows[name]["derived"], name
    assert "full_softmax" in rows["attention/naive_jnp"]["derived"]
    assert "interpret=" in rows["attention/flash_kernel"]["derived"]


def test_serve_bench_pins_latency_grid_and_flat_compiles():
    """Acceptance: BENCH_serve.json covers a grid of >= 2 offered loads
    x >= 2 bucket configurations, each cell reporting sane latency
    percentiles (p99 > p50 > 0) and positive delivered throughput, and
    the serve/compile_flat row pins zero recompiles after warmup with
    at least one live hot-swap — holds for the committed full run and
    for the --quick regeneration CI does before this test."""
    with open(os.path.join(REPO_ROOT, "BENCH_serve.json")) as f:
        doc = validate_bench_json(json.load(f))
    rows = {r["name"]: r for r in doc["rows"]}

    def kv(name):
        return dict(item.split("=", 1)
                    for item in rows[name]["derived"].split(";"))

    cells = [n for n in rows if "/load" in n]
    assert len(cells) >= 4, sorted(rows)
    loads, configs = set(), set()
    for name in cells:
        d = kv(name)
        # serve/<algo>/b<cfg>/load<rps>
        configs.add(name.split("/")[2])
        loads.add(float(d["offered_rps"]))
        assert float(d["p99_ms"]) > float(d["p50_ms"]) > 0, (name, d)
        assert float(d["throughput_rps"]) > 0, (name, d)
        assert int(d["n"]) > 0, (name, d)
    assert len(loads) >= 2, loads
    assert len(configs) >= 2, configs
    flat = kv("serve/compile_flat")
    assert flat["recompiles_after_warmup"] == "0", flat
    assert int(flat["warmup_compiles"]) > 0, flat
    assert int(flat["hot_swaps"]) >= 1, flat
