"""Mean host time per `ServeEngine.step` in its `serve.read_back` span:
the cut to the live rows and `device_get`, which waits for the chip.
From the program's own spans (bench/program_spans.py); none recorded:
no reading."""
from bench import program_spans


def read(ctx):
    return program_spans.per_serve_step_ms("serve.read_back")
