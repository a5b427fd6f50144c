"""Mean host time of one `ServeEngine.step` call in the traced window,
from the harness's `bench.serve_step` spans: batcher, padding, transfer,
dispatch and the read-back of the answers. The host path has no device
time to read it from."""


def read(ctx):
    t = [e - s for n, s, e in ctx["spans"] if n == "bench.serve_step"]
    return sum(t) / len(t) / 1e6 if t else None
