"""Device time of the collective class ops (the psums of the graph axis:
``all-reduce`` and its kin in ``op_classes.json``) over device busy time, a
chip's average. The wait for the slowest chip is inside them: an all-reduce
ends when every chip has arrived. Nothing to read where the trace has no
such op (a one-chip cell, or a backend that names none)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"]:
        return None
    spent = t["class_s"].get("collective", 0.0)
    if spent <= 0.0:
        return None
    return 100.0 * spent / t["busy_s"]
