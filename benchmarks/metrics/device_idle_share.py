"""1 - union of device op intervals / traced window."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / ctx["window"]["wall_s"])
