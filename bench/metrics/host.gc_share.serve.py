"""Share of the traced window spent in Python's cyclic collector, in
the serving cells (moves serve_p50_ms): the program's `host.gc` spans
summed over the window. 0 when no pass ran; a program without spans of
its own: no reading."""
from bench import program_spans


def read(ctx):
    return program_spans.gc_share(ctx["reduced"]["window_s"])
