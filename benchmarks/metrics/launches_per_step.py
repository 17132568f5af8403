"""Top-level device program launches in the traced window per micro-step."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["launches"]:
        return None
    return t["launches"] / ctx["window"]["micro_steps"]
