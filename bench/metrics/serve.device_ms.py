"""Device time per serving step: the chips' busy time in the traced
window (device trace, summed over the cell's chips) over the number of
the program's `serve.dispatch` spans. None recorded: no reading."""
from bench import program_spans


def read(ctx):
    snap = program_spans.snapshot()
    n = program_spans.n_spans(snap, "serve.dispatch") if snap else 0
    if not n:
        return None
    return 1e3 * ctx["reduced"]["busy_s"] * ctx["chips"] / n
