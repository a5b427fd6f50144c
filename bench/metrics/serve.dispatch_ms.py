"""Mean host time per `ServeEngine.step` in its `serve.dispatch` span:
the jitted serve_step call, with the host-to-device transfer of its
numpy arguments. From the program's own spans
(bench/program_spans.py); none recorded: no reading."""
from bench import program_spans


def read(ctx):
    return program_spans.per_serve_step_ms("serve.dispatch")
