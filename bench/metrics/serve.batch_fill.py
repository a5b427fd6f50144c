"""Share of the dispatched bucket rows that carried a request (the
program's `serve.rows` counter over its `serve.bucket_rows` counter).
No serving step recorded: no reading."""
from bench import program_spans


def read(ctx):
    snap = program_spans.snapshot()
    rows = program_spans.counter(snap, "serve.bucket_rows") if snap else 0
    if not rows:
        return None
    return 100.0 * program_spans.counter(snap, "serve.rows") / rows
