"""The readers of the program's own spans and counters, on a hand-made
snapshot: each reading, and what each gives when the program recorded
nothing of its own or has no spans at all. CPU only."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from repro.core import spans  # noqa: E402

MS = 1_000_000
SERVE_SPANS = {"serve.admit_ms": 0.5, "serve.dispatch_ms": 1.0,
               "serve.read_back_ms": 1.5, "serve.respond_ms": 0.25}
HOST = ("host.compile_ms.train", "host.compile_ms.serve",
        "host.gc_share.train", "host.gc_share.serve")
SERVE = tuple(SERVE_SPANS) + ("serve.queue_wait_ms", "serve.batch_fill",
                              "serve.device_ms")
CTX = {"reduced": {"busy_s": 0.003, "window_s": 2.0}, "chips": 1}


def _step(t0, parent):
    """One serving step at t0 (ms) whose children take 0.5, 1, 1.5 and
    0.25 ms, as the spans of ServeEngine.step record it."""
    out = [spans.Span("serve.step", t0 * MS, (t0 + 4) * MS, -1, "")]
    t = t0
    for name, d in (("serve.admit", 0.5), ("serve.dispatch", 1.0),
                    ("serve.read_back", 1.5), ("serve.respond", 0.25)):
        out.append(spans.Span(name, int(t * MS), int((t + d) * MS),
                              parent, ""))
        t += d
    return out


SNAP = {"spans": (_step(0, 0) + _step(10, 5)
                  + [spans.Span("host.gc", 20 * MS, 23 * MS, -1, "2"),
                     spans.Span("host.gc", 30 * MS, 31 * MS, 5, "0"),
                     spans.Span("jax.compile", 40 * MS, 52 * MS, -1,
                                "serve_step"),
                     spans.Span("serve.step", 60 * MS, None, -1, "")]),
        "counters": {"serve.rows": (20, 2), "serve.bucket_rows": (32, 2),
                     "serve.queue_wait_s": (0.03, 2)},
        "dropped": 0}
EMPTY = {"spans": [], "counters": {}, "dropped": 0}


def _read(name, snap, monkeypatch, ctx=CTX):
    monkeypatch.setattr(spans, "snapshot", lambda: snap)
    return harness.metric_reader(name).read(ctx)


@pytest.mark.parametrize("name,want", [
    *SERVE_SPANS.items(),
    ("serve.queue_wait_ms", 1.5),          # 30 ms over 20 rows
    ("serve.batch_fill", 62.5),            # 20 of 32 rows
    ("serve.device_ms", 1.5),              # 3 ms busy over 2 dispatches
    ("host.compile_ms.train", 12.0),
    ("host.compile_ms.serve", 12.0),
    ("host.gc_share.train", 0.2),          # 4 ms of 2 s
    ("host.gc_share.serve", 0.2),
])
def test_reading(name, want, monkeypatch):
    assert _read(name, SNAP, monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize("name", SERVE)
def test_serve_readers_give_nothing_without_a_serving_step(name,
                                                           monkeypatch):
    assert _read(name, EMPTY, monkeypatch) is None


@pytest.mark.parametrize("name", HOST)
def test_host_readers_read_zero_when_nothing_ran(name, monkeypatch):
    assert _read(name, EMPTY, monkeypatch) == 0


@pytest.mark.parametrize("name", SERVE + HOST)
def test_readers_give_nothing_for_a_program_without_spans(name,
                                                          monkeypatch):
    # a program from before repro.core.spans
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert harness.metric_reader(name).read(CTX) is None

