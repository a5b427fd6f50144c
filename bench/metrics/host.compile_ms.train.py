"""Time spent building programs inside the traced window, in the
training cells (moves env_steps_per_s): the program's `jax.compile`
spans (backend compiles and persistent-cache reads) summed. 0 when
nothing was built; a program without spans of its own: no reading."""
from bench import program_spans


def read(ctx):
    return program_spans.compile_ms()
