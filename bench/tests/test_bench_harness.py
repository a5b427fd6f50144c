"""The harness finds every part of a cell by name, refuses what it cannot
run, and BENCHMARK.json keeps to its contract. CPU only."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_parts_found_by_name(w):
    cell = harness.cell(w["name"], BENCH)
    assert cell["workload"] is w
    assert cell["config"]["name"] == w["config"]
    assert (harness.BENCH / "drivers" /
            f"{cell['traffic']['driver']}.py").is_file()
    code = cell["code"]
    for hook in ("algorithm", "weights", "control_run", "KERNEL_PATTERNS"):
        assert hasattr(code, hook), hook
    # every per-layer metric of the cell has its reader, and every cell
    # reports setup_s, another end-to-end metric and a per-layer one
    layer = harness.per_layer_for(w["name"], BENCH)
    assert layer
    for m in layer:
        assert callable(harness.metric_reader(m["name"]).read)
    e2e = {m["name"] for m in harness.end_to_end_for(w["name"], BENCH)}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_unknown_cell_and_device_kind_are_refused():
    with pytest.raises(harness.Refused):
        harness.cell("no-such.cell", BENCH)
    with pytest.raises(harness.Refused):
        harness.peaks("TPU v99 imaginary")
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_cpu_is_refused():
    with pytest.raises(harness.Refused, match="no TPU"):
        harness.devices(1)


def test_seed31_spreads_large_seeds():
    s = [harness.seed31(x) for x in (5, 5 + 2 ** 32, 2 ** 33 + 5, 2 ** 31 + 7)]
    assert len(set(s)) == 4 and all(0 <= x < 2 ** 31 for x in s)
    assert harness.seed31(123) == harness.seed31(123)


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    configs = {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
