"""Mean host time per `ServeEngine.step` in its `serve.admit` span:
taking requests from the queue, the parameters' version, the bucket and
the numpy pad. From the program's own spans (bench/program_spans.py);
none recorded: no reading."""
from bench import program_spans


def read(ctx):
    return program_spans.per_serve_step_ms("serve.admit")
