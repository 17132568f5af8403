"""Share of the window the step loop waited on the loader: the program's
``data/stall_s`` counter over the window. A cell with no loader in the window
(scanned epochs) has no such counter and reports nothing."""


def read(ctx):
    stall = ctx["window"]["counters"].get("data_stall_s")
    if stall is None:
        return None
    return 100.0 * stall / ctx["window"]["wall_s"]
