"""Device busy time of the traced window per micro-step."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"]:
        return None
    return 1e3 * t["busy_s"] / ctx["window"]["micro_steps"]
