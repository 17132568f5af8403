"""Process start to the first timed dispatch: imports, data, weights, compile
or cache retrieval, and the first (compared) steps that warm the window's
shapes. The reference comparison runs after the window and is not in it."""


def read(ctx):
    return ctx["setup"]["setup_s"]
