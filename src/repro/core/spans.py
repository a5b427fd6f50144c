"""The program's own spans and counters, on while a profiler session runs.

There is no switch: recording is on exactly while a JAX profiler session
is active (`jax.profiler.start_trace`, `jax.profiler.trace`, or a capture
through `jax.profiler.start_server`), and off otherwise. Off, `span` and
`count` cost one `enabled()` call each and record nothing.

On, each `span(name)` is also a `jax.profiler.TraceAnnotation`, so it
lands in the profiler's `.xplane.pb` on the host clock beside the
device's operations, and it is appended to a bounded in-memory buffer as
`Span(name, start_ns, end_ns, parent, detail)` on `time.perf_counter_ns()`.
`parent` is the index (in `snapshot()["spans"]`) of the span that
enclosed it on the same thread, or -1; `end_ns` is None while the span is
still open. `count(name, value)` adds to a running total per name.

Two runtime hooks, installed at import, record while on:
  * `host.gc`: each pass of Python's cyclic collector, `detail` its
    generation;
  * `jax.compile`: each backend compile or persistent-cache read of a
    program (`/jax/core/compile/backend_compile_duration`), `detail` the
    function's name. It fires when a program is built, never per call.
    These spans are in the buffer only; the profiler's trace has XLA's
    own compile events.

Nothing here writes files: the profiler's trace is the exporter, and
`snapshot()` hands the records to code in the same process.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import NamedTuple, Optional

import jax

MAX_SPANS = 1 << 18       # records kept; later ones are counted as dropped
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

enabled = jax.profiler.TraceAnnotation.is_enabled
_NOOP = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: Optional[int]
    parent: int
    detail: str


class _Recorder:
    """The buffer, the counters and each thread's stack of open spans.
    A record is a list `[name, start_ns, end_ns, parent, detail]` until
    `snapshot()` turns it into a `Span`."""

    def __init__(self):
        # re-entrant: a collector pass can start, and record its span,
        # inside any allocation made while the lock is held
        self.lock = threading.RLock()
        self.local = threading.local()
        self.reset()

    def reset(self):
        with self.lock:
            self.spans = []
            self.counters = {}
            self.dropped = 0

    def stack(self):
        st = getattr(self.local, "stack", None)
        if st is None:
            st = self.local.stack = []
        return st

    def open(self, name, start_ns, detail=""):
        """Reserve the span's slot; -> its index, or -1 when full."""
        st = self.stack()
        parent = st[-1] if st else -1
        with self.lock:
            if len(self.spans) >= MAX_SPANS:
                self.dropped += 1
                i = -1
            else:
                self.spans.append([name, start_ns, None, parent, detail])
                i = len(self.spans) - 1
        st.append(i)
        return i

    def close(self, i, end_ns):
        self.stack().pop()
        if i >= 0:
            self.spans[i][2] = end_ns

    def add(self, name, start_ns, end_ns, detail):
        """A closed span recorded after the fact (no children)."""
        st = self.stack()
        with self.lock:
            if len(self.spans) >= MAX_SPANS:
                self.dropped += 1
            else:
                self.spans.append([name, start_ns, end_ns,
                                   st[-1] if st else -1, detail])


_REC = _Recorder()


class _Open:
    __slots__ = ("name", "detail", "ann", "i")

    def __init__(self, name, detail):
        self.name, self.detail = name, detail

    def __enter__(self):
        kw = {"detail": self.detail} if self.detail else {}
        self.ann = jax.profiler.TraceAnnotation(self.name, **kw)
        self.ann.__enter__()
        self.i = _REC.open(self.name, time.perf_counter_ns(), self.detail)
        return self

    def __exit__(self, *exc):
        _REC.close(self.i, time.perf_counter_ns())
        return self.ann.__exit__(*exc)


def span(name: str):
    """A context manager timing its body as `name` while tracing is on."""
    return _Open(name, "") if enabled() else _NOOP


def count(name: str, value=1):
    """Add `value` to the running total of `name` while tracing is on."""
    if enabled():
        with _REC.lock:
            total, n = _REC.counters.get(name, (0, 0))
            _REC.counters[name] = (total + value, n + 1)


def snapshot() -> dict:
    """-> {"spans": [Span, ...], "counters": {name: (total, n)},
    "dropped": n}: what was recorded since the last `reset()`."""
    with _REC.lock:
        return {"spans": [Span(*r) for r in _REC.spans],
                "counters": dict(_REC.counters), "dropped": _REC.dropped}


def reset():
    _REC.reset()


# ------------------------------------------------------ runtime hooks
_gc_open = threading.local()


def _on_gc(phase, info):
    if phase == "start":
        s = _Open("host.gc", str(info["generation"])) if enabled() else None
        if s is not None:
            s.__enter__()
        _gc_open.span = s
    else:
        s = getattr(_gc_open, "span", None)
        if s is not None:
            _gc_open.span = None
            s.__exit__(None, None, None)


def _on_compile(event, start_s, end_s, **kw):
    if event == COMPILE_EVENT and enabled():
        end = time.perf_counter_ns()
        _REC.add("jax.compile", end - int((end_s - start_s) * 1e9), end,
                 str(kw.get("fun_name", "")))


gc.callbacks.append(_on_gc)
jax.monitoring.register_event_time_span_listener(_on_compile)
