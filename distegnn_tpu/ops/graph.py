"""GraphBatch — the static-shape batched graph container.

The reference carries ragged PyG ``Data(x, pos, vel, attr, target, loc_mean,
edge_index, edge_attr)`` objects concatenated along a flat node axis with a
``batch`` vector (reference datasets/process_dataset.py:114-115). XLA wants
static shapes, so we use a dense layout instead:

  node arrays  [B, N, ...]   padded to N = bucketed max nodes, with node_mask
  edge arrays  [B, E, ...]   padded edge list (local per-graph indices), with
                             edge_mask; padded edges point at node 0 and are
                             masked out of every aggregation
  graph arrays [B, ...]      e.g. loc_mean

This dense layout is what makes the model MXU-friendly: every MLP runs as one
big [B*N(*C), F] matmul, per-graph reductions are masked means over a fixed N,
and under the distributed mesh the N axis holds one spatial partition per
device (see distegnn_tpu.parallel).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class GraphBatch:
    """A batch of B padded graphs (or, distributed: B padded graph *partitions*).

    Shapes (F=node features, A=node attrs, D=edge attrs):
      node_feat [B, N, F] float   node_mask  [B, N]  float 0/1
      loc       [B, N, 3] float   edge_index [B, 2, E] int32 (row=receiver, col=sender)
      vel       [B, N, 3] float   edge_attr  [B, E, D] float
      target    [B, N, 3] float   edge_mask  [B, E] float 0/1
      node_attr [B, N, A] float (A may be 0)
      loc_mean  [B, 3]    float — GLOBAL mean of node positions per graph
                                  (across all partitions when distributed)

    ``edges_sorted`` (static) — True when every graph's edge rows are
    ascending, including the padded tail (padding points at node N-1, the
    last padded slot). Lets aggregations use XLA's sorted-scatter lowering.

    ``edge_block`` (static) — 0, or the node-block size of a blocked edge
    layout (see ops/blocked.py): N is a multiple of edge_block and edge slice
    [b*epb, (b+1)*epb) holds exactly the edges whose row is in node block b.
    Enables the MXU one-hot aggregation kernels; the layout is still a valid
    row-sorted edge list, so every non-kernel path works unchanged.
    """

    node_feat: jnp.ndarray
    node_attr: jnp.ndarray
    loc: jnp.ndarray
    vel: jnp.ndarray
    target: jnp.ndarray
    loc_mean: jnp.ndarray
    node_mask: jnp.ndarray
    edge_index: jnp.ndarray
    edge_attr: jnp.ndarray
    edge_mask: jnp.ndarray
    # [B, E] reverse-edge involution (symmetric graphs): lets backward
    # col-aggregations ride the MXU kernels (blocked layout, ops/blocked.py)
    # or the scatter-free cumsum path (plain sorted layout, ops/segment.py)
    edge_pair: Optional[jnp.ndarray] = None
    edges_sorted: bool = struct.field(pytree_node=False, default=False)
    edge_block: int = struct.field(pytree_node=False, default=0)
    edge_tile: int = struct.field(pytree_node=False, default=0)
    # max REAL in-degree over the batch, rounded up to 8 (0 = not computed).
    # Static: enables the ELL aggregation lowering (segment_impl='ell',
    # ops/segment.py). Computed together with the plain pairing
    # (compute_pair=True) so scatter-only workflows keep one pytree identity.
    max_in_degree: int = struct.field(pytree_node=False, default=0)

    @property
    def batch_size(self) -> int:
        return self.node_feat.shape[0]

    @property
    def max_nodes(self) -> int:
        return self.node_feat.shape[1]

    @property
    def max_edges(self) -> int:
        return self.edge_index.shape[2]

    @property
    def n_node(self) -> jnp.ndarray:
        """[B] float — true node count per graph (per partition when sharded)."""
        return jnp.sum(self.node_mask, axis=1)

    @property
    def edges_per_block(self) -> int:
        """Edge slots per node block (blocked layout only)."""
        assert self.edge_block > 0, "not a blocked layout"
        return self.max_edges // (self.max_nodes // self.edge_block)

    @property
    def row(self) -> jnp.ndarray:
        return self.edge_index[:, 0, :]

    @property
    def col(self) -> jnp.ndarray:
        return self.edge_index[:, 1, :]


def _round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def pad_graphs(
    graphs: Sequence[dict],
    max_nodes: Optional[int] = None,
    max_edges: Optional[int] = None,
    node_bucket: int = 8,
    edge_bucket: int = 128,
    dtype=np.float32,
    edge_block: int = 0,
    edges_per_block: Optional[int] = None,
    edge_tile: int = 512,
    compute_pair: Optional[bool] = None,
    max_in_degree: Optional[int] = None,
) -> "GraphBatch":
    """Pack a list of per-graph numpy dicts into one padded GraphBatch.

    Each dict has keys: node_feat [n,F], loc/vel/target [n,3], edge_index [2,e],
    edge_attr [e,D], optional node_attr [n,A], optional loc_mean [3].
    Bucketing rounds N/E up so nearby sizes share one compiled program.

    ``edge_block > 0`` emits the blocked layout (ops/blocked.py): N rounds up
    to a multiple of edge_block and each node block owns a fixed slice of
    ``edges_per_block`` edge slots (auto: max block degree over the batch,
    rounded to edge_tile; loaders pass a dataset-stable value to avoid
    per-batch recompiles). Requires row-sorted edge input (all in-tree
    builders emit it; unsorted input is stable-sorted here).

    loc_mean contract: when a dict omits loc_mean, it falls back to the mean of
    the dict's OWN positions — correct only for whole (unpartitioned) graphs.
    Partition pipelines MUST pass the global mean explicitly (the partitioners
    in distegnn_tpu.data do), since GraphBatch.loc_mean seeds the replicated
    virtual-node coordinates across devices.

    ``compute_pair`` — attach the reverse-edge involution (``edge_pair``) so
    backward col-aggregations stay scatter-free. ``None`` (auto) keeps the
    historical layouts: on for blocked batches, off for plain ones (the plain
    pairing only pays off with ``segment_impl='cumsum'``; loaders switch it on
    dataset-stably so every batch shares one pytree structure).
    """
    bsz = len(graphs)
    n_max = max(g["loc"].shape[0] for g in graphs)
    if compute_pair is None:
        compute_pair = edge_block > 0
    if edge_block:
        from distegnn_tpu.ops.blocked import (max_block_degree,
                                              prepare_blocked_graph)

        if max_nodes is not None and max_nodes < n_max:
            raise ValueError(f"pad_graphs: max_nodes {max_nodes} < actual {n_max}")
        if max_edges is not None:
            raise ValueError("pad_graphs: max_edges is unsupported with "
                             "edge_block; pass edges_per_block instead")
        if edges_per_block is not None and edges_per_block % edge_tile:
            raise ValueError(f"pad_graphs: edges_per_block {edges_per_block} "
                             f"not a multiple of edge_tile {edge_tile}")
        N = _round_up(max(max_nodes or 0, n_max, 1), edge_block)
        if edges_per_block is None:
            deg = max(max_block_degree(np.sort(g["edge_index"][0]), N, edge_block)
                      for g in graphs)
            edges_per_block = _round_up(max(deg, 1), edge_tile)
        graphs = [prepare_blocked_graph(g, N, edges_per_block, edge_block,
                                        compute_pair=compute_pair)
                  for g in graphs]
        pairs = [g["_edge_pair"] for g in graphs]
        # all-or-nothing across the batch: one pytree structure per layout.
        # Loaders make this dataset-stable by scanning up front and passing
        # compute_pair accordingly (scan_dataset_for_blocking).
        edge_pair = (np.stack(pairs).astype(np.int32)
                     if all(p is not None for p in pairs) else None)
        E = (N // edge_block) * edges_per_block
    else:
        e_max = max(g["edge_index"].shape[1] for g in graphs)
        E = max_edges if max_edges is not None else _round_up(max(e_max, 1), edge_bucket)
        N = max_nodes if max_nodes is not None else _round_up(max(n_max, 1), node_bucket)
        if N < n_max or E < e_max:
            raise ValueError(f"pad_graphs: max_nodes/max_edges ({N},{E}) < actual ({n_max},{e_max})")
        edge_pair = None

    F = graphs[0]["node_feat"].shape[1]
    A = graphs[0].get("node_attr", np.zeros((0, 0))).shape[1] if graphs[0].get("node_attr") is not None else 0
    D = graphs[0]["edge_attr"].shape[1] if graphs[0].get("edge_attr") is not None else 0

    node_feat = np.zeros((bsz, N, F), dtype)
    node_attr = np.zeros((bsz, N, A), dtype)
    loc = np.zeros((bsz, N, 3), dtype)
    vel = np.zeros((bsz, N, 3), dtype)
    target = np.zeros((bsz, N, 3), dtype)
    loc_mean = np.zeros((bsz, 3), dtype)
    node_mask = np.zeros((bsz, N), dtype)
    # padded edges point at the LAST padded slot (N-1): it is masked out of
    # every aggregation anyway, and keeps row indices ascending so the model
    # can use XLA's sorted-scatter lowering (all in-tree edge builders emit
    # row-sorted edge lists — radius_graph_np lexsorts, full_graph_np is
    # row-major, cutoff_edges_np preserves order)
    edge_index = np.full((bsz, 2, E), N - 1, np.int32)
    edge_attr = np.zeros((bsz, E, D), dtype)
    edge_mask = np.zeros((bsz, E), dtype)
    edges_sorted = True

    for b, g in enumerate(graphs):
        n = g["loc"].shape[0]
        e = g["edge_index"].shape[1]
        node_feat[b, :n] = g["node_feat"]
        if A:
            node_attr[b, :n] = g["node_attr"]
        loc[b, :n] = g["loc"]
        vel[b, :n] = g["vel"]
        if g.get("target") is not None:
            target[b, :n] = g["target"]
        loc_mean[b] = g["loc_mean"] if g.get("loc_mean") is not None else g["loc"].mean(axis=0)
        node_mask[b, :n] = 1.0
        edge_index[b, :, :e] = g["edge_index"]
        if (not edge_block) and e and (np.any(np.diff(g["edge_index"][0]) < 0)
                                       or g["edge_index"][0][-1] > N - 1):
            edges_sorted = False  # blocked layouts are ascending by construction
        if D and g.get("edge_attr") is not None:
            edge_attr[b, :e] = g["edge_attr"]
        if edge_block:
            edge_mask[b, :e] = g["_edge_mask"]  # blocked layout: interior padding
        else:
            edge_mask[b, :e] = 1.0

    if not ((not edge_block) and compute_pair and edges_sorted):
        max_in_degree = 0
    else:
        # the static D of the ELL lowering (rounded to 8 so nearby batches
        # share a compiled program). Loaders pass a DATASET-stable value,
        # since a static field that varies across batches retraces the jitted
        # step (same concern as edges_per_block for the blocked layout);
        # an undersized value would silently drop edges, so it is validated.
        deg = max(int(np.bincount(g["edge_index"][0], minlength=1).max())
                  for g in graphs)
        if max_in_degree is None:
            max_in_degree = -(-max(deg, 1) // 8) * 8
        elif max_in_degree < deg:
            raise ValueError(f"pad_graphs: max_in_degree {max_in_degree} < "
                             f"actual batch max in-degree {deg}")
        # plain-layout reverse-edge involution. Computed on each graph's RAW
        # edge list and cached on the graph dict (it is deterministic and
        # index-stable — padding is appended after the real edges), so
        # loaders that re-pad every epoch sort each edge list once, not once
        # per epoch; padded tail slots are (N-1, N-1) self-pairs. All-or-
        # nothing across the batch so the pytree structure stays stable.
        from distegnn_tpu.ops.blocked import pairing_perm_fast

        pairs = []
        for g in graphs:
            e = g["edge_index"].shape[1]
            p = g.get("_plain_pair")
            if p is None or p.shape[0] != e:
                p = pairing_perm_fast(g["edge_index"].astype(np.int64))
                if p is not None:
                    g["_plain_pair"] = p
            if p is None:
                pairs = None
                break
            full = np.arange(E, dtype=np.int32)
            full[:e] = p
            pairs.append(full)
        edge_pair = np.stack(pairs).astype(np.int32) if pairs is not None else None

    return GraphBatch(
        node_feat=node_feat, node_attr=node_attr, loc=loc, vel=vel, target=target,
        loc_mean=loc_mean, node_mask=node_mask, edge_index=edge_index,
        edge_attr=edge_attr, edge_mask=edge_mask, edges_sorted=edges_sorted,
        edge_block=edge_block, edge_tile=edge_tile if edge_block else 0,
        edge_pair=edge_pair, max_in_degree=max_in_degree,
    )


def batch_graphs(graphs: Sequence[dict], **kw) -> "GraphBatch":
    """Alias of pad_graphs (name mirrors a DataLoader collate step)."""
    return pad_graphs(graphs, **kw)
