"""The trace reduction on a small trace recorded on a TPU v5e (two DQN
supersteps of dqn-mlp.replay1m, the events as `trace_reduce.events`
reads them from the .xplane.pb), and on hand-made events for the
collectives. CPU only."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace_reduce  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "dqn_superstep_events.json.gz"
SAMPLE = {"replay_sample": r"^%prioritized_sample_c\b"}


@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.decompress(RECORDED.read_bytes()))


def test_window_busy_and_gaps_add_up(recorded):
    red = trace_reduce.summarize(recorded, SAMPLE)
    host = recorded["host"]
    t0 = min(h[1] for h in host)
    t1 = max(h[1] + h[2] for h in host)
    assert red["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = sum(g[1] for g in red["idle_gaps"])
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"], rel=1e-9)
    # every gap is named by the harness span the host was in
    assert {g[0] for g in red["idle_gaps"]} <= {h[0] for h in host} | {"none"}


def test_kernel_attribution_by_name(recorded):
    red = trace_reduce.summarize(recorded, SAMPLE)
    ops = [o for o in recorded["devices"]["0"]
           if o[0].startswith("%prioritized_sample_c")]
    assert red["kernels"]["replay_sample"]["n"] == len(ops) == 20
    assert red["kernels"]["replay_sample"]["s"] == pytest.approx(
        sum(o[3] for o in ops) / 1e9)
    assert red["op_s"][0][0].startswith("%prioritized_sample_c")
    # loops and calls are not counted by name: their bodies are
    assert not any(n for n, c, _, _ in recorded["devices"]["0"]
                   if c and n in dict(red["op_s"]))
    bd = trace_reduce.breakdown(red)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10


def test_boundary_gap_reader(recorded):
    red = trace_reduce.summarize(recorded, SAMPLE)
    gap = harness.metric_reader("trainer.boundary_gap_ms").read(
        {"reduced": red})
    assert 0 < gap < 5


def test_collectives_and_exposure():
    # one chip: a loop spanning everything, compute [0, 10] and
    # [12, 13], an all-reduce [8, 14]: 6 ns of collective, of which
    # [10, 12] and [13, 14] run with no compute beside them
    ev = {"host": [["bench.dispatch", 0, 20]],
          "devices": {"0": [["%while.1", True, 0, 20],
                            ["%fusion.1", False, 0, 10],
                            ["%all-reduce.3", False, 8, 6],
                            ["%fusion.2", False, 12, 1]]}}
    red = trace_reduce.summarize(ev)
    assert red["collectives"] == {"s": 6e-9, "exposed_s": 3e-9, "n": 1}
    assert red["busy_s"] == pytest.approx(20e-9)
    assert dict(red["op_s"]) == pytest.approx(
        {"%fusion.1": 10e-9, "%all-reduce.3": 6e-9, "%fusion.2": 1e-9})


def test_two_chips_average_and_idle():
    ev = {"host": [["bench.dispatch", 0, 10], ["bench.device_get", 10, 10]],
          "devices": {"0": [["%a.1", False, 0, 20]],
                      "1": [["%a.1", False, 5, 5]]}}
    red = trace_reduce.summarize(ev)
    assert red["busy_s"] == pytest.approx(12.5e-9)
    assert harness.metric_reader("train.idle_share").read(
        {"reduced": red}) == pytest.approx(37.5)
    # chip 1 idles [0, 5] in dispatch and [10, 20] in device_get
    assert sorted((g[0], g[1]) for g in red["idle_gaps"]) == [
        ("bench.device_get", 10e-9), ("bench.dispatch", 5e-9)]
