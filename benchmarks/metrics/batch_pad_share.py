"""Share of the edge slots the step computed on that held no edge: 100 x
(1 - real / padded) over the batches the program's loader collated in the
window, from its counters ``data/real_edges`` and ``data/padded_edges``
(``GraphLoader`` pads every graph of a batch to the data set's longest edge
list, rounded up to ``edge_bucket``). A few per cent on scenes of one size;
what ragged scenes cost. A program without the counters, or a driver that
does not hand them over, reports nothing."""


def read(ctx):
    counters = ctx["window"].get("counters") or {}
    real, padded = counters.get("data_real_edges"), counters.get("data_padded_edges")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
