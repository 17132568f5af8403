"""The aggregation's share of its HBM roofline: least bytes the gathers and
segment sums of a micro-step move on ONE chip (``counts.agg_bytes`` of the
whole graph over the cell's chips: the partitions share the graph's nodes
and kept edges) over the published HBM bandwidth, against the device time of
the trace's scatter and gather class ops per micro-step, which the reduction
averages over the chips. Nothing to read where the trace has no such op."""

from benchmarks import counts


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    spent = t["class_s"].get("scatter", 0.0) + t["class_s"].get("gather", 0.0)
    if spent <= 0.0:
        return None
    least = counts.agg_bytes(ctx["shapes"]) / ctx["chips"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (spent / ctx["window"]["micro_steps"])
