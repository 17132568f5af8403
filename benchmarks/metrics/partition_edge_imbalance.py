"""The fullest partition's kept edges over the mean partition's, averaged
over the pool's graphs (a count from the driver's set-up): 1 is an even cut.
The edge ops are nine tenths of a step, so this is how much longer the
fullest chip works than the average one, and how much of the collectives'
time is the others waiting for it. A driver that cuts nothing reports none."""


def read(ctx):
    return ctx["window"]["counters"].get("partition_edge_imbalance")
