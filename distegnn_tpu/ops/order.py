"""Spatial node ordering — static locality preprocessing for the edge ops.

The LargeFluid step is dominated by edge<->node data movement (how far
below HBM bandwidth its gathers and aggregations run: not measured on this
machine). Edge lists are destination(row)-sorted, so aggregation WRITES are
ordered — but with arbitrary node numbering the col-gather side reads node
rows in random order, and each node's CSR edge range references sources
scattered across the whole array.

Sorting nodes along a Z-order (Morton) curve of their positions makes
spatially-near nodes near in memory. Radius-graph neighbours are spatially
near by construction, so after the permutation every gather/scatter touches
a small contiguous region per node — cache- and DMA-friendly on both CPU
and TPU.

This is a *relabeling*, not a model change: FastEGNN is permutation-
equivariant, so training trajectories are identical up to the node
permutation (tests/test_order.py pins this through the model). Applied once
per graph on the host (loader static preprocessing / dataset build), cost
O(n log n) numpy.

The reference has no counterpart (its CUDA scatter kernels hash-combine in
L2); the closest idea is the blocked layout's locality goal
(docs/PERFORMANCE.md) without changing the edge-op lowering at all.
"""

from __future__ import annotations

import numpy as np

# node-indexed arrays a graph dict may carry ([n, ...] leading axis)
_NODE_KEYS = ("node_feat", "node_attr", "loc", "vel", "target")


def morton_codes(loc: np.ndarray, bits: int = 16) -> np.ndarray:
    """Z-order curve code per row of ``loc`` [n, d<=3] -> uint64 [n].

    Coordinates are quantized to ``bits`` levels per axis over the cloud's
    bounding box; codes interleave the axis bits (x bit 0, y bit 0, z bit 0,
    x bit 1, ...), so sorting by code orders points along the Z curve."""
    loc = np.asarray(loc, np.float64)
    n, d = loc.shape
    if d > 3 or bits * d > 63:
        raise ValueError(f"morton_codes: unsupported shape/bits ({d}, {bits})")
    lo = loc.min(axis=0)
    span = np.maximum(loc.max(axis=0) - lo, 1e-300)
    q = ((loc - lo) / span * (2**bits - 1) + 0.5).astype(np.uint64)
    code = np.zeros(n, np.uint64)
    for b in range(bits):
        for ax in range(d):
            code |= ((q[:, ax] >> np.uint64(b)) & np.uint64(1)) << np.uint64(
                b * d + ax)
    return code


def morton_perm(loc: np.ndarray, bits: int = 16) -> np.ndarray:
    """Permutation (new order -> old index) sorting nodes along the Z curve."""
    return np.argsort(morton_codes(loc, bits), kind="stable")


def reorder_graph(g: dict, perm: np.ndarray) -> dict:
    """Apply a node permutation to a graph dict: permute node arrays, remap
    edge_index, and re-sort edges by (row, col) so the row-sorted invariant
    every lowering relies on (GraphBatch.edges_sorted) still holds.

    ``perm[new] = old``; graph-level keys (loc_mean, ...) pass through."""
    known = set(_NODE_KEYS) | {"loc_mean", "edge_index", "edge_attr"}
    for k, v in g.items():
        if k not in known and isinstance(v, np.ndarray):
            # refuse silently-inconsistent output: an unknown array might be
            # node-indexed and would keep its OLD order
            raise ValueError(f"reorder_graph: unknown array key {k!r} — add "
                             "it to _NODE_KEYS (node-indexed) or the "
                             "pass-through set")
    n = g["loc"].shape[0]
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n)
    out = dict(g)
    for k in _NODE_KEYS:
        v = g.get(k)
        if v is not None:
            if v.shape[0] != n:
                raise ValueError(f"reorder_graph: {k} has leading dim "
                                 f"{v.shape[0]}, expected {n}")
            out[k] = np.ascontiguousarray(v[perm])
    ei = inv[np.asarray(g["edge_index"], np.int64)]
    order = np.lexsort((ei[1], ei[0]))
    out["edge_index"] = np.ascontiguousarray(ei[:, order]).astype(np.int32)
    ea = g.get("edge_attr")
    if ea is not None:
        out["edge_attr"] = np.ascontiguousarray(ea[order])
    return out


def morton_reorder_graph(g: dict, bits: int = 16) -> dict:
    """Convenience: reorder a graph dict along the Z curve of its positions."""
    return reorder_graph(g, morton_perm(g["loc"], bits))
