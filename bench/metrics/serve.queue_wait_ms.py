"""Mean time a served request waited in the queue before admission
(the program's `serve.queue_wait_s` counter over its `serve.rows`
counter). No serving step recorded: no reading."""
from bench import program_spans


def read(ctx):
    snap = program_spans.snapshot()
    rows = program_spans.counter(snap, "serve.rows") if snap else 0
    if not rows:
        return None
    return 1e3 * program_spans.counter(snap, "serve.queue_wait_s") / rows
