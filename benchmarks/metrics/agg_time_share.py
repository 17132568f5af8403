"""Device time of the scatter and gather class ops over device busy time."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"]:
        return None
    spent = t["class_s"].get("scatter", 0.0) + t["class_s"].get("gather", 0.0)
    if spent <= 0.0:
        return None
    return 100.0 * spent / t["busy_s"]
