"""What the program recorded of itself in the traced window, for the
per-layer readers: the spans and counters of `repro.core.spans`.

The program records only while a profiler session runs, and a run
traces its window alone, so the snapshot holds that window. A program
without spans of its own gives None here, and its readers no reading.
"""


def snapshot():
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.snapshot()


def total_ns(snap, name):
    """Summed duration of the closed spans called `name`."""
    return sum(s.end_ns - s.start_ns for s in snap["spans"]
               if s.name == name and s.end_ns is not None)


def n_spans(snap, name):
    """Number of closed spans called `name`."""
    return sum(1 for s in snap["spans"]
               if s.name == name and s.end_ns is not None)


def counter(snap, name):
    """Running total of the counter `name` (0 if never counted)."""
    return snap["counters"].get(name, (0, 0))[0]


def per_serve_step_ms(name):
    """Mean time of the span `name` per `serve.step`; None if the program
    recorded no serving step."""
    snap = snapshot()
    steps = n_spans(snap, "serve.step") if snap else 0
    if not steps:
        return None
    return total_ns(snap, name) / steps / 1e6


def compile_ms():
    """Summed `jax.compile` spans (backend compiles and persistent-cache
    reads), ms; 0 when nothing was built."""
    snap = snapshot()
    return None if snap is None else total_ns(snap, "jax.compile") / 1e6


def gc_share(window_s):
    """Summed `host.gc` spans (every generation) over the window, %; 0
    when no collector pass ran."""
    snap = snapshot()
    return None if snap is None else (
        100.0 * total_ns(snap, "host.gc") / (window_s * 1e9))
