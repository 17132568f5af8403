"""Share of the window in which the loader was building a batch: the union of
the program's ``data/produce`` spans (collate + put, per batch; the producer's
wait on its full queue is outside them). At 100% the loader sets the pace. A
cell with no loader in the window has no such span and reports nothing."""

from benchmarks import span_window, tracing


def read(ctx):
    w = span_window.window(ctx)
    if w is None:
        return None
    spans, lo, hi = w
    produce = span_window.clipped(spans, "data/produce", lo, hi)
    if not produce:
        return None
    return 100.0 * tracing.union_ns(produce) / (hi - lo)
