"""Sum of ``jax.monitoring`` backend-compile and cache-retrieval durations
before the window."""


def read(ctx):
    return ctx["setup"]["compile_s"]
