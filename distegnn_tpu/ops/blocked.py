"""Blocked-CSR edge aggregation — MXU kernels for the scatter/gather hot loop.

A plug-in-era profile (not reproduced on this machine) showed the LargeFluid
train step is NOT compute-bound: XLA's scatter-add ran one [E=1.6M, 64]
edge->node aggregation in 22-33 ms (~19 GB/s effective, vs ~800 GB/s HBM) and
gathers at ~43 GB/s, so the step spent >80% of its time in what the reference
does with CUDA scatter kernels (models/FastEGNN.py:322-337, torch_scatter).

The TPU-native fix is a LAYOUT, not a faster scatter. Edge lists are already
sorted by destination row (ops/graph.py pad_graphs); here we additionally pad
them so that every 256-node *block* owns a fixed-size slice of the edge axis:

    edge slice [b*epb, (b+1)*epb)  holds exactly the edges whose destination
    row lies in node block [b*256, (b+1)*256), padded with masked slots.

With that invariant, both hot ops become *block-local dense matmuls* against a
one-hot incidence tile generated in VMEM — pure MXU work, no scatter at all:

    aggregate:  out[block b] += onehot[tile, 256]^T @ data[tile, F]
    gather:     out[tile]     = onehot[tile, 256]   @ h[block b]

The one-hot tile never touches HBM (built from an iota compare inside the
kernel), so HBM traffic is one streaming read of the edge array and one write
of the node array — the bandwidth floor. FLOP cost is E*256*F ~ 52 GFLOP at
LargeFluid scale: noise for the MXU. The two kernels are exact adjoints, so
``jax.custom_vjp`` wires aggregate-backward = gather and gather-backward =
aggregate, killing the backward-pass scatters too (the round-2 profile's
biggest single line).

The blocked layout is still a valid row-sorted padded edge list, so every
existing code path (XLA fallback, other models, the distributed partitioner)
consumes it unchanged; the kernels are an opt-in fast path keyed on
``GraphBatch.edge_block``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distegnn_tpu import obs, runtime

DEFAULT_BLOCK = 256       # nodes per block = one-hot matmul N dimension
DEFAULT_EDGE_TILE = 512   # edges per grid step = one-hot matmul K dimension


# ---------------------------------------------------------------------------
# Host-side layout builder
# ---------------------------------------------------------------------------

def _blockify_plan(edge_index: np.ndarray, n_nodes_padded: int, epb: int,
                   block: int):
    """One vectorized pass: arbitrary edge order -> (src, dst, blocked index,
    mask). ``src`` are input-edge positions sorted stably by destination row
    (so already-sorted input keeps its order bit-for-bit); ``dst`` is each
    sorted edge's slot ``block_idx*epb + rank_within_block``. No per-block
    Python loop — the whole layout is two argsort/searchsorted sweeps plus
    fancy-index writes."""
    row = edge_index[0]
    e = int(row.shape[0])
    nb = n_nodes_padded // block
    src = np.argsort(row, kind="stable")
    rows = row[src]
    bounds = np.searchsorted(rows, np.arange(nb + 1) * block)
    counts = np.diff(bounds)
    if counts.max(initial=0) > epb:
        raise ValueError(f"blockify_edges: epb={epb} < max block degree {counts.max()}")
    if bounds[-1] != e:
        raise ValueError("blockify_edges: edge rows exceed n_nodes_padded")
    dst = (np.repeat(np.arange(nb, dtype=np.int64) * epb, counts)
           + np.arange(e, dtype=np.int64)
           - np.repeat(bounds[:-1].astype(np.int64), counts))
    E = nb * epb
    new_index = np.empty((2, E), np.int32)
    pad_rows = np.arange(1, nb + 1, dtype=np.int32) * block - 1
    new_index[0] = np.repeat(pad_rows, epb)
    new_index[1] = new_index[0]
    new_index[:, dst] = edge_index[:, src]
    new_mask = np.zeros((E,), np.float32)
    new_mask[dst] = 1.0
    return src, dst, new_index, new_mask


def blockify_edges(
    edge_index: np.ndarray,      # [2, e] int, ANY edge order
    edge_attr: Optional[np.ndarray],  # [e, D] or None
    n_nodes_padded: int,         # N, multiple of `block`
    epb: int,                    # edge slots per block (multiple of edge_tile)
    block: int = DEFAULT_BLOCK,
):
    """Re-lay one graph's edge list into per-block padded slices.

    Returns (edge_index' [2, NB*epb], edge_attr' [NB*epb, D], edge_mask'
    [NB*epb]). Padding slots carry row = col = (their block's last node) so the
    global row ordering stays ascending — the layout remains a legal
    ``edges_sorted`` edge list for the XLA fallback path. Vectorized (one
    NumPy pass, no per-block loop); row-sorted input reproduces the historic
    layout bit-for-bit, arbitrary order is stably row-sorted first.
    """
    src, dst, new_index, new_mask = _blockify_plan(
        edge_index, n_nodes_padded, epb, block)
    D = edge_attr.shape[1] if edge_attr is not None else 0
    new_attr = np.zeros((new_mask.shape[0], D), np.float32)
    if D and edge_attr is not None:
        new_attr[dst] = edge_attr[src]
    return new_index, new_attr, new_mask


class RepackPlan(NamedTuple):
    """Topology-only artifact of :func:`repack_blocked` — everything about a
    graph's blocked layout that does NOT depend on positions/attributes, so a
    session serving the same scene can re-apply it to fresh per-step arrays
    with two fancy-index gathers (the serve prep cache's hit path).

    perm[new] = old Morton node relabel (None when built without loc);
    edge_index/edge_mask are the blocked [2, NB*epb]/[NB*epb] arrays;
    src/dst map client edge k's payload to slot dst via attr'[dst] = attr[src].
    """
    perm: Optional[np.ndarray]
    edge_index: np.ndarray
    edge_mask: np.ndarray
    src: np.ndarray
    dst: np.ndarray
    stamp: tuple                 # (n_nodes_padded, epb, block)

    def apply_edge_attr(self, edge_attr: np.ndarray) -> np.ndarray:
        out = np.zeros((self.edge_mask.shape[0], edge_attr.shape[1]),
                       np.float32)
        out[self.dst] = edge_attr[self.src]
        return out


def repack_blocked(edge_index: np.ndarray, loc: Optional[np.ndarray] = None,
                   *, n_nodes_padded: int, epb: int,
                   block: int = DEFAULT_BLOCK, bits: int = 16) -> RepackPlan:
    """Arbitrary client edge order -> the kernels' Morton/blocked layout in
    one vectorized NumPy pass (sort-by-(block, row), no per-block loop).

    When ``loc`` is given the node ids are first relabeled along the Z-order
    curve (ops/order.py) so spatially-near nodes share blocks. Returns a
    :class:`RepackPlan` whose ``src``/``dst`` index maps let
    position-dependent payloads (edge_attr) be re-laid later without redoing
    the sort.
    """
    ei = np.asarray(edge_index).astype(np.int64, copy=False)
    perm = None
    if loc is not None:
        from distegnn_tpu.ops.order import morton_perm

        perm = morton_perm(np.asarray(loc), bits=bits)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.shape[0], dtype=perm.dtype)
        ei = inv[ei]
    src, dst, new_index, new_mask = _blockify_plan(
        ei, n_nodes_padded, epb, block)
    return RepackPlan(perm=perm, edge_index=new_index, edge_mask=new_mask,
                      src=src, dst=dst,
                      stamp=(n_nodes_padded, epb, block))


def max_block_degree(rows_sorted: np.ndarray, n_nodes_padded: int,
                     block: int = DEFAULT_BLOCK) -> int:
    """Max number of edges landing in any single node block (sorted rows)."""
    nb = n_nodes_padded // block
    bounds = np.searchsorted(rows_sorted, np.arange(nb + 1) * block)
    return int(np.diff(bounds).max(initial=0))


def pairing_perm(edge_index: np.ndarray) -> Optional[np.ndarray]:
    """Reverse-edge involution P: edge_index[:, P[e]] == (col[e], row[e]).

    Radius graphs are symmetric (every (i,j) has its (j,i)), so the transpose
    of the sparse incidence is just a permutation of the edge axis. That lets
    the backward col-scatter — the one aggregation the blocked kernels can't
    reach directly — become gather-by-P + blocked row aggregation (see
    paired_col_gather). Returns None when the edge list isn't symmetric
    (caller falls back to XLA scatter). Works on blocked layouts too: padding
    slots carry row == col and pair among themselves.
    """
    r, c = edge_index[0], edge_index[1]
    by_rc = np.lexsort((c, r))
    by_cr = np.lexsort((r, c))
    pair = np.empty(r.shape[0], np.int64)
    pair[by_rc] = by_cr
    if not (np.array_equal(r[pair], c) and np.array_equal(c[pair], r)):
        return None
    return pair


def pairing_perm_fast(edge_index: np.ndarray) -> Optional[np.ndarray]:
    """:func:`pairing_perm` through the native fast path when available
    (native/blockify.cpp), numpy otherwise. Same contract: a verified
    reverse-edge permutation, or None when the list isn't symmetric."""
    from distegnn_tpu.native import native_pairing

    pair = native_pairing(edge_index)
    if pair is None:
        return pairing_perm(edge_index)
    return None if pair is False else pair


def prepare_blocked_graph(g: dict, n_nodes_padded: int, epb: int, block: int,
                          compute_pair: bool = True) -> dict:
    """Blockify one graph dict in place-of (returns a copy): row-sort if
    needed, re-lay edges per block, and attach the reverse-edge pairing.
    Idempotent: a dict already carrying the matching ``_blockified`` stamp is
    returned unchanged (loaders cache prepared graphs across epochs)."""
    stamp = (n_nodes_padded, epb, block)
    if g.get("_blockified") == stamp:
        return g
    g = dict(g)
    if g.get("_blockified") is not None and g.get("_edge_mask") is not None:
        # already blocked under DIFFERENT layout params (e.g. a session-cached
        # dict co-batched with a denser peer): recover the real edge list from
        # the mask before re-packing — padding slots must not become edges
        keep = g["_edge_mask"] > 0
        g["edge_index"] = g["edge_index"][:, keep]
        if g.get("edge_attr") is not None:
            g["edge_attr"] = g["edge_attr"][keep]
        for k in ("_edge_pair", "_edge_mask", "_blockified"):
            g.pop(k, None)
    if np.any(np.diff(g["edge_index"][0]) < 0):
        order = np.argsort(g["edge_index"][0], kind="stable")
        g["edge_index"] = g["edge_index"][:, order]
        if g.get("edge_attr") is not None:
            g["edge_attr"] = g["edge_attr"][order]
    # native fast path (native/blockify.cpp) with the numpy implementation as
    # the universal fallback — identical layout either way
    from distegnn_tpu.native import native_blockify

    nat = native_blockify(g["edge_index"].astype(np.int64),
                          g.get("edge_attr"), n_nodes_padded, epb, block)
    if nat is not None:
        ei, ea, em = nat
    else:
        ei, ea, em = blockify_edges(g["edge_index"].astype(np.int64),
                                    g.get("edge_attr"), n_nodes_padded, epb, block)
    g["edge_index"], g["edge_attr"], g["_edge_mask"] = ei, ea, em
    g["_edge_pair"] = pairing_perm_fast(ei) if compute_pair else None
    g["_blockified"] = stamp
    return g


def scan_dataset_for_blocking(dataset, n_nodes_padded: int, block: int):
    """One pass over a dataset: (max block degree, every-graph-symmetric).
    Both are layout decisions that must be made ONCE per dataset so every
    batch of a run shares a single pytree structure / compiled program."""
    deg, symmetric = 1, True
    for i in range(len(dataset)):
        ei = dataset[i]["edge_index"]
        deg = max(deg, max_block_degree(np.sort(ei[0]), n_nodes_padded, block))
        symmetric = symmetric and pairing_perm_fast(ei) is not None
    return deg, symmetric


def slot_ids(row: jnp.ndarray, edge_mask: jnp.ndarray, block: int, epb: int) -> jnp.ndarray:
    """Block-local destination ids with a sentinel for padding.

    row/edge_mask: [..., E] in blocked layout. Returns int32 [..., E] where a
    real edge at position k (block k//epb) gets ``row - block_idx*block`` in
    [0, block) and a masked slot gets ``block`` — which matches no one-hot
    column, so masked slots vanish from every kernel without a multiply.
    """
    E = row.shape[-1]
    blk = (jnp.arange(E, dtype=jnp.int32) // epb) * block
    local = row.astype(jnp.int32) - blk
    return jnp.where(edge_mask > 0, local, block)


# ---------------------------------------------------------------------------
# Pallas kernels (single graph; batched wrappers vmap them)
# ---------------------------------------------------------------------------

def _precision_for(dtype):
    # f32 operands: 'highest' makes the MXU one-hot contraction exact (the
    # one-hot factor is 0/1, so only data truncation matters — 3-pass bf16
    # recovers full f32). bf16 operands: default single-pass.
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dtype) == jnp.float32 else jax.lax.Precision.DEFAULT)


def _seg_sum_kernel(slot_ref, data_ref, out_ref, *, block, precision):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    tile = slot_ref.shape[0]
    onehot = (slot_ref[:] == jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1))
    out_ref[:] += jax.lax.dot_general(
        onehot.astype(data_ref.dtype), data_ref[:],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


def _gather_kernel(slot_ref, h_ref, out_ref, *, block, precision):
    tile = slot_ref.shape[0]
    onehot = (slot_ref[:] == jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1))
    out_ref[:] = jax.lax.dot_general(
        onehot.astype(h_ref.dtype), h_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ).astype(out_ref.dtype)


def _layout(E: int, n_nodes: int, block: int, tile: int):
    nb, rem = divmod(n_nodes, block)
    if rem:
        raise ValueError(f"n_nodes {n_nodes} not a multiple of block {block}")
    epb, rem = divmod(E, nb)
    if rem:
        raise ValueError(f"E {E} not a multiple of num_blocks {nb}")
    ept, rem = divmod(epb, tile)
    if rem:
        raise ValueError(f"edges/block {epb} not a multiple of tile {tile}")
    return nb, ept


@functools.partial(jax.jit, static_argnames=("n_nodes", "block", "tile"))
def _seg_sum_impl(data, slot, n_nodes: int, block: int, tile: int):
    """[E, F] + slots -> [N, F] float32 (blocked one-hot MXU aggregation)."""
    E, F = data.shape
    nb, ept = _layout(E, n_nodes, block, tile)
    kern = functools.partial(_seg_sum_kernel, block=block,
                             precision=_precision_for(data.dtype))
    return pl.pallas_call(
        kern,
        grid=(nb, ept),
        in_specs=[
            pl.BlockSpec((tile, 1), lambda b, t: (b * ept + t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, F), lambda b, t: (b * ept + t, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, F), lambda b, t: (b, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_nodes, F), jnp.float32),
        interpret=runtime.use_interpret(),
    )(slot[:, None], data)


@functools.partial(jax.jit, static_argnames=("block", "tile"))
def _gather_impl(h, slot, block: int, tile: int):
    """[N, F] + slots [E] -> [E, F] (blocked one-hot MXU gather)."""
    n_nodes, F = h.shape
    E = slot.shape[0]
    nb, ept = _layout(E, n_nodes, block, tile)
    kern = functools.partial(_gather_kernel, block=block,
                             precision=_precision_for(h.dtype))
    return pl.pallas_call(
        kern,
        grid=(nb, ept),
        in_specs=[
            pl.BlockSpec((tile, 1), lambda b, t: (b * ept + t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, F), lambda b, t: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, F), lambda b, t: (b * ept + t, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((E, F), h.dtype),
        interpret=runtime.use_interpret(),
    )(slot[:, None], h)


# ---------------------------------------------------------------------------
# Differentiable single-graph ops (exact adjoint pair)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _seg_sum(data, slot, n_nodes, block, tile):
    return _seg_sum_impl(data, slot, n_nodes, block, tile)


def _seg_sum_fwd(data, slot, n_nodes, block, tile):
    out = _seg_sum_impl(data, slot, n_nodes, block, tile)
    return out, (slot, jnp.zeros((), data.dtype))


def _seg_sum_bwd(n_nodes, block, tile, res, g):
    slot, proto = res
    return _gather_impl(g.astype(proto.dtype), slot, block, tile), None


_seg_sum.defvjp(_seg_sum_fwd, _seg_sum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gather(h, slot, block, tile):
    return _gather_impl(h, slot, block, tile)


def _gather_fwd(h, slot, block, tile):
    return _gather_impl(h, slot, block, tile), (slot, jnp.zeros((0,) + h.shape[:1], h.dtype))


def _gather_bwd(block, tile, res, g):
    slot, proto = res
    n_nodes = proto.shape[1]
    return _seg_sum_impl(g, slot, n_nodes, block, tile).astype(proto.dtype), None


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _paired_gather(h, col, pair, slot, block, tile):
    return jnp.take(h, col, axis=0)


def _paired_gather_fwd(h, col, pair, slot, block, tile):
    out = jnp.take(h, col, axis=0)
    return out, (pair, slot, jnp.zeros((0,) + h.shape[:1], h.dtype))


def _paired_gather_bwd(block, tile, res, g):
    pair, slot, proto = res
    n_nodes = proto.shape[1]
    grad_h = _seg_sum_impl(jnp.take(g, pair, axis=0), slot, n_nodes, block, tile)
    return grad_h.astype(proto.dtype), None, None, None


_paired_gather.defvjp(_paired_gather_fwd, _paired_gather_bwd)


# ---------------------------------------------------------------------------
# Einsum lowering of the same blocked contraction (impl='einsum')
#
# Identical math to the Pallas kernels, but the one-hot incidence factor is
# MATERIALIZED once per forward as a bf16 [B, nb, epb, block] tensor and every
# aggregation/gather is a plain batched dot XLA schedules itself. Rationale:
# the Pallas kernels run one small (tile x block x F) MXU dot per grid step —
# thousands of steps per call — and the first hardware run measured the
# per-step overhead swamping the dot (plug-in era; not measured on this
# machine). The einsum
# form trades ~E*block*2 bytes of HBM traffic per op (abundant: ~1ms at v5e
# bandwidth for LargeFluid) for zero grid overhead and full XLA pipelining.
#
# f32 exactness without an f32 one-hot: the one-hot factor is exactly
# representable in bf16, so an f32 operand is split into 3 bf16 terms
# (hi/mid/lo, residual ~2^-24 relative) contracted separately and summed in
# f32 — the manual form of XLA's bf16_3x, paying 1x (not 3x) per extra
# operand pass because the one-hot side needs no splitting.
# ---------------------------------------------------------------------------

def onehot_blocks(slot: jnp.ndarray, epb: int, block: int) -> jnp.ndarray:
    """[..., E] slot ids (from :func:`slot_ids`) -> [..., nb, epb, block] bf16
    one-hot incidence. Sentinel slots (== block) match no column and vanish."""
    E = slot.shape[-1]
    nb = E // epb
    s = slot.reshape(slot.shape[:-1] + (nb, epb))
    return (s[..., None] == jnp.arange(block, dtype=jnp.int32)).astype(jnp.bfloat16)


def _bf16_terms(x: jnp.ndarray, n_terms: int = 3):
    """Split x into bf16 terms summing to x up to ~2^-24 relative error.
    bf16 input passes through unsplit."""
    if x.dtype == jnp.bfloat16:
        return [x]
    terms = []
    rem = x.astype(jnp.float32)
    for _ in range(n_terms - 1):
        t = rem.astype(jnp.bfloat16)
        terms.append(t)
        rem = rem - t.astype(jnp.float32)
    terms.append(rem.astype(jnp.bfloat16))
    return terms


def _ein_seg_sum_raw(data: jnp.ndarray, oh: jnp.ndarray) -> jnp.ndarray:
    """[..., E, F] x [..., nb, epb, block] -> [..., nb*block, F] float32."""
    *lead, E, F = data.shape
    nb, epb, block = oh.shape[-3:]
    d = data.reshape(*lead, nb, epb, F)
    out = None
    for t in _bf16_terms(d):
        part = jnp.einsum("...bek,...bef->...bkf", oh, t,
                          preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out.reshape(*lead, nb * block, F)


def _ein_gather_raw(h: jnp.ndarray, oh: jnp.ndarray) -> jnp.ndarray:
    """[..., N, F] x [..., nb, epb, block] -> [..., E, F] float32 (blocked row
    gather; sentinel slots read as 0)."""
    *lead, N, F = h.shape
    nb, epb, block = oh.shape[-3:]
    hh = h.reshape(*lead, nb, block, F)
    out = None
    for t in _bf16_terms(hh):
        part = jnp.einsum("...bek,...bkf->...bef", oh, t,
                          preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out.reshape(*lead, nb * epb, F)


# The raw forms are exact adjoints, but differentiating THROUGH the bf16 term
# split would bf16-round the cotangent (the transpose of an f32->bf16 cast
# rounds); these custom_vjps instead apply the split to the cotangent itself,
# keeping gradients f32-accurate — and, as with the Pallas pair, guaranteeing
# the backward pass contains no scatter.

@jax.custom_vjp
def einsum_segment_sum(data, oh):
    return _ein_seg_sum_raw(data, oh)


def _ein_seg_sum_fwd(data, oh):
    return _ein_seg_sum_raw(data, oh), (oh, jnp.zeros((), data.dtype))


def _ein_seg_sum_bwd(res, g):
    oh, proto = res
    return _ein_gather_raw(g, oh).astype(proto.dtype), None


einsum_segment_sum.defvjp(_ein_seg_sum_fwd, _ein_seg_sum_bwd)


@jax.custom_vjp
def einsum_gather(h, oh):
    return _ein_gather_raw(h, oh).astype(h.dtype)


def _ein_gather_fwd(h, oh):
    return _ein_gather_raw(h, oh).astype(h.dtype), (oh, jnp.zeros((), h.dtype))


def _ein_gather_bwd(res, g):
    oh, proto = res
    return _ein_seg_sum_raw(g, oh).astype(proto.dtype), None


einsum_gather.defvjp(_ein_gather_fwd, _ein_gather_bwd)


@jax.custom_vjp
def _paired_gather_ein(h, col, pair, oh):
    return jnp.take(h, col, axis=0)


def _paired_gather_ein_fwd(h, col, pair, oh):
    return jnp.take(h, col, axis=0), (pair, oh, jnp.zeros((), h.dtype))


def _paired_gather_ein_bwd(res, g):
    pair, oh, proto = res
    grad_h = _ein_seg_sum_raw(jnp.take(g, pair, axis=0), oh)
    return grad_h.astype(proto.dtype), None, None, None


_paired_gather_ein.defvjp(_paired_gather_ein_fwd, _paired_gather_ein_bwd)


# ---------------------------------------------------------------------------
# Public batched API (mirrors ops.segment signatures)
# ---------------------------------------------------------------------------

def blocked_segment_sum(data, slot, num_segments: int, block: int = DEFAULT_BLOCK,
                        tile: int = DEFAULT_EDGE_TILE):
    """Batched [B, E, F] -> [B, N, F] float32. ``slot`` from :func:`slot_ids`
    (masked slots carry the sentinel and contribute nothing)."""
    return jax.vmap(lambda d, s: _seg_sum(d, s, num_segments, block, tile))(data, slot)


def blocked_slot_inv_deg(g, impl: str = "einsum"):
    """(slot ids, 1/max(in-degree,1), one-hot incidence or None) for a blocked
    GraphBatch, or (None, None, None) when g is not blocked. Wrappers call
    this ONCE per forward — row/edge_mask are layer-invariant, so one pass
    serves L layers. ``impl``: 'pallas' (one-hot built in VMEM per kernel) or
    'einsum' (one-hot materialized, ops become plain batched dots)."""
    if g.edge_block <= 0:
        return None, None, None
    slot = slot_ids(g.row, g.edge_mask, g.edge_block, g.edges_per_block)
    if impl == "einsum":
        oh = onehot_blocks(slot, g.edges_per_block, g.edge_block)  # [B,nb,epb,blk]
        # in-degree is just a column sum of the incidence (masked slots carry
        # the sentinel and are all-zero one-hot rows already)
        deg = jnp.sum(oh, axis=-2, dtype=jnp.float32).reshape(
            oh.shape[0], g.max_nodes, 1)
    elif impl == "pallas":
        oh = None
        deg = blocked_segment_sum(g.edge_mask[..., None], slot, g.max_nodes,
                                  g.edge_block, g.edge_tile)
    else:
        raise ValueError(f"unknown blocked impl {impl!r}")
    return slot, 1.0 / jnp.maximum(deg, 1.0), oh


def col_sorted_view(g):
    """``(col_sorted, col_order)`` of a plain GraphBatch (ops/segment.py
    :func:`sort_cols`), or None when g is blocked. Wrappers call this ONCE per
    forward, beside :func:`blocked_slot_inv_deg` and outside any rematted
    layer: the col list is layer-invariant, so one sort serves the L col
    transposes (:meth:`EdgeOps.gather_cols`). A forward without a backward
    reads nothing of it, and the compiler drops the sort."""
    if g.edge_block > 0:
        return None
    from distegnn_tpu.ops.segment import sort_cols

    return sort_cols(g.col)


def _count_gather_pass():
    obs.get_registry().counter("edge/gather_passes").add()


def _count_sorted_row_pass():
    obs.get_registry().counter("edge/sorted_row_passes").add()


def _count_sorted_col_pass():
    obs.get_registry().counter("edge/sorted_col_passes").add()


def _count_row_sum_kernel():
    obs.get_registry().counter("edge/row_sum_kernel").add()


# what a rematted layer keeps of its edge passes (models/fast_egnn.py): the
# names EdgeOps puts on those results, for ``save_only_these_names``
REMAT_KEPT = ("edge_pre", "edge_diff", "edge_agg")


class EdgeOps:
    """The one definition of the edge-op dispatch all model families share:
    row/col gathers and per-destination aggregations, lowered as

      blocked   MXU one-hot kernels when the batch carries the blocked layout
                (with the reverse-edge pairing backward when available);
      cumsum    ``seg_impl='cumsum'`` on a plain row-sorted batch: prefix-sum
                differences with gather-only custom VJPs — no XLA scatter in
                forward OR backward (ops/segment.py cumsum block);
      ell       ``seg_impl='ell'`` on a plain row-sorted batch carrying
                max_in_degree: fixed-degree chained gathers — scatter-free
                AND exact (ops/segment.py ELL block);
      scatter   XLA sorted-scatter otherwise (bit-exact reference path).

    ``slot``/``inv_deg``/``oh`` come from :func:`blocked_slot_inv_deg`
    (hoisted once per forward; plain arrays, so layers stay remat-able).
    ``oh is not None`` selects the einsum lowering, otherwise the Pallas
    kernels. ``col_view`` comes from :func:`col_sorted_view` (hoisted the
    same way): with it the plain col gather transposes into a sorted segment
    sum over the col-sorted edges (ops/segment.py ``gather_cols_sorted``);
    without it, into autodiff's unsorted scatter-add.

    Both directions pack what shares an index set into ONE pass, because a
    gather or a scatter on the chip costs per ROW, not per byte (PERF.md
    section 5): :meth:`agg_rows_pair` carries a layer's two aggregations and
    the count in one segment sum, :meth:`gather_sum_diff` carries the hoisted
    phi_e products and the coordinates in one gather per edge end, so a layer
    has 2 gathers and (from autodiff) 2 transposed scatter-adds where separate
    calls make 4 and 4. The pack is float32: coordinates never pass through
    bf16, and bf16 products widen exactly.

    What the two packed passes return is NAMED (``checkpoint_name``,
    :data:`REMAT_KEPT`), above the choice of lowering like the scopes: a layer
    under ``jax.checkpoint`` with ``save_only_these_names(*REMAT_KEPT)`` keeps
    the pre-activation sum, ``coord_diff`` and the segment sum and recomputes
    only what is computed FROM them, so no gather and no segment sum runs
    twice (their transposes need the indices alone). Outside a checkpoint a
    name is the identity and lowers to nothing.

    The methods carry the device scopes ``edge_gather`` and ``edge_aggregate``
    (``jax.named_scope``), above the choice of lowering: whichever branch
    runs, forward and transposed, its ops name the scope in the HLO's
    ``op_name`` (docs/OBSERVABILITY.md "Device scopes"). Every gather a
    method emits adds one to the ``obs`` counter ``edge/gather_passes`` as
    it is TRACED (a count per compiled program, not per step)."""

    def __init__(self, g, slot=None, inv_deg=None, oh=None,
                 seg_impl: str = "scatter", col_view=None):
        self.g, self.slot, self.inv_deg, self.oh = g, slot, inv_deg, oh
        self.col_view = col_view
        self.blocked = slot is not None
        if seg_impl not in ("scatter", "cumsum", "ell"):
            raise ValueError(f"unknown seg_impl {seg_impl!r}")
        # both scatter-free lowerings need ascending row ids (ELL also the
        # static max_in_degree); keep the exact scatter path when the batch
        # can't support the request
        self.cumsum = (seg_impl == "cumsum" and not self.blocked
                       and g.edges_sorted)
        self.ell = (seg_impl == "ell" and not self.blocked
                    and g.edges_sorted and g.max_in_degree > 0)

    @jax.named_scope("edge_gather")
    def gather_rows(self, data):
        _count_gather_pass()
        if self.blocked:
            if self.oh is not None:
                # the einsum ops are leading-dim polymorphic ('...' batch)
                return einsum_gather(data, self.oh)
            return blocked_gather(data, self.slot, self.g.edge_block,
                                  self.g.edge_tile)
        if self.g.edges_sorted:
            # every branch below transposes into a SORTED segment sum by a
            # rule of its own (cumsum, ell, gather_rows_sorted)
            _count_sorted_row_pass()
        if self.cumsum:
            from distegnn_tpu.ops.segment import gather_rows_cs

            return jax.vmap(gather_rows_cs)(data, self.g.row)
        if self.ell:
            from distegnn_tpu.ops.segment import gather_rows_ell

            D = self.g.max_in_degree
            return jax.vmap(lambda h, r: gather_rows_ell(h, r, D))(data, self.g.row)
        if self.g.edges_sorted:
            from distegnn_tpu.ops.segment import gather_rows_sorted

            return gather_rows_sorted(data, self.g.row)
        return jnp.take_along_axis(data, self.g.row[..., None], axis=1)

    @jax.named_scope("edge_gather")
    def gather_cols(self, data):
        _count_gather_pass()
        g = self.g
        if self.blocked and g.edge_pair is not None:
            if self.oh is not None:
                return jax.vmap(_paired_gather_ein)(data, g.col, g.edge_pair,
                                                    self.oh)
            return paired_col_gather(data, g.col, g.edge_pair, self.slot,
                                     g.edge_block, g.edge_tile)
        if self.cumsum and g.edge_pair is not None:
            from distegnn_tpu.ops.segment import paired_gather_cols_cs

            return jax.vmap(paired_gather_cols_cs)(data, g.col, g.edge_pair,
                                                   g.row, g.edge_mask)
        if self.ell and g.edge_pair is not None:
            from distegnn_tpu.ops.segment import paired_gather_cols_ell

            D = g.max_in_degree
            return jax.vmap(lambda h, c, p, r, m: paired_gather_cols_ell(
                h, c, p, r, m, D))(data, g.col, g.edge_pair, g.row, g.edge_mask)
        if self.col_view is not None:
            from distegnn_tpu.ops.segment import gather_cols_sorted

            _count_sorted_col_pass()
            return gather_cols_sorted(data, g.col, *self.col_view)
        return jnp.take_along_axis(data, g.col[..., None], axis=1)

    @jax.named_scope("edge_gather")
    def gather_sum_diff(self, a, b, x):
        """``(gather_rows(a) + gather_cols(b), gather_rows(x) - gather_cols(x))``
        in ONE gather per edge end: the node tables ``[a | x]`` and
        ``[b | -x]`` ride the row and the col pass together, and autodiff
        transposes each pass into one scatter-add of the packed width.

        The pack is the widest dtype among the operands (float32 for the
        model's f32 coordinates): ``x`` is never rounded, bf16 ``a``/``b``
        widen exactly, their sum is taken in the pack's dtype and rounded
        back to ``a``'s dtype ONCE, and the cotangents of ``a`` and ``b``
        accumulate in the pack's dtype. With f32 operands both results equal
        the separate calls bit for bit (``r + (-c)`` is ``r - c``).

        Blocked layouts keep their four calls: the one-hot kernels run bf16
        operands single-pass and f32 ones six-pass, so widening the products
        would cost more than the saved passes."""
        if self.blocked:
            return (checkpoint_name(self.gather_rows(a) + self.gather_cols(b),
                                    "edge_pre"),
                    checkpoint_name(self.gather_rows(x) - self.gather_cols(x),
                                    "edge_diff"))
        dt = jnp.result_type(a, b, x)
        w, xp = a.shape[-1], x.astype(dt)
        out = (self.gather_rows(jnp.concatenate([a.astype(dt), xp], -1))
               + self.gather_cols(jnp.concatenate([b.astype(dt), -xp], -1)))
        # the barrier makes the 3 coordinate columns a buffer of their own:
        # without it XLA fuses this slice into its consumers in the BACKWARD
        # and keeps the whole packed result alive as the residual (the n-body
        # epoch compiled for a v5e: +1.32 GB of temporaries without, +0.007
        # with; PERF.md section 6, PR 27)
        diff = jax.lax.optimization_barrier(out[..., w:].astype(x.dtype))
        return (checkpoint_name(out[..., :w].astype(a.dtype), "edge_pre"),
                checkpoint_name(diff, "edge_diff"))

    @jax.named_scope("edge_aggregate")
    def _agg(self, data, mean: bool):
        from distegnn_tpu.ops.segment import (segment_mean, segment_mean_cs,
                                              segment_sum, segment_sum_cs)

        g = self.g
        N = g.max_nodes
        if self.blocked:
            if self.oh is not None:
                out = einsum_segment_sum(data, self.oh)
            else:
                out = blocked_segment_sum(data, self.slot, N, g.edge_block,
                                          g.edge_tile)
            if mean:
                out = out * self.inv_deg
            return out.astype(data.dtype)
        if self.cumsum:
            seg_cs = segment_mean_cs if mean else segment_sum_cs
            return jax.vmap(lambda t, r, m: seg_cs(t, r, N, mask=m))(
                data, g.row, g.edge_mask)
        if self.ell:
            from distegnn_tpu.ops.segment import (segment_mean_ell,
                                                  segment_sum_ell)

            seg_el = segment_mean_ell if mean else segment_sum_ell
            D = g.max_in_degree
            return jax.vmap(lambda t, r, m: seg_el(t, r, N, D, mask=m))(
                data, g.row, g.edge_mask)
        seg = segment_mean if mean else segment_sum
        return jax.vmap(lambda t, r, m: seg(
            t, r, N, mask=m, indices_are_sorted=g.edges_sorted))(
            data, g.row, g.edge_mask)

    def agg_rows_mean(self, data):
        """Per-destination mean over real edges (count clamped >= 1)."""
        return self._agg(data, mean=True)

    def agg_rows_sum(self, data):
        return self._agg(data, mean=False)

    @jax.named_scope("edge_aggregate")
    def agg_rows_pair(self, a, b, a_mean: bool, agg_dtype=None):
        """Aggregate TWO edge streams in ONE pass: returns
        (agg_sum_or_mean(a), agg_mean(b)), both float32.

        The round-2 profile puts the step cost in the per-aggregation
        scatters/prefix passes, and every EGCL layer needs exactly two row
        aggregations (coordinate translations + edge features) plus a count.
        Packing them as columns of a single segment sum halves the number of
        aggregation passes per layer — for every lowering: one scatter
        instead of two scatters + a count (op-bound path), one prefix pass
        instead of two (bandwidth-bound cumsum path), one gather sweep
        instead of two (ELL).

        ``agg_dtype='bf16'`` casts the packed stream to bfloat16 before the
        pass, halving the dominant [E, 3+H] read bytes; accumulation stays
        f32 in every lowering (prefix_sum and the ELL reducer accumulate
        f32 by construction; the scatter path scatters into an f32 output).
        NOTE: bf16 rounds the GEOMETRY stream (a = coordinate translations),
        trading exact-at-math-level equivariance for bandwidth — off by
        default, an opt-in whose speed is not measured on this machine.

        Blocked layouts keep their two-call path (mean is a free inv_deg
        multiply there)."""
        if self.blocked:
            # two-call path (mean is a free inv_deg multiply here), but the
            # stream-dtype knob still applies: bf16 operands run the one-hot
            # kernels single-pass instead of f32 precision=HIGHEST 6-pass —
            # the gen-2 blocked configuration
            if agg_dtype in ("bf16", jnp.bfloat16):
                a = a.astype(jnp.bfloat16)
                b = b.astype(jnp.bfloat16)
            out_a = self.agg_rows_sum(a) if not a_mean else self.agg_rows_mean(a)
            return (checkpoint_name(out_a.astype(jnp.float32), "edge_agg"),
                    checkpoint_name(self.agg_rows_mean(b).astype(jnp.float32),
                                    "edge_agg"))
        g = self.g
        B, E = b.shape[0], b.shape[1]
        sa = a.shape[-1]
        dt = jnp.bfloat16 if agg_dtype in ("bf16", jnp.bfloat16) else jnp.float32
        em = g.edge_mask[..., None]
        packed = jnp.concatenate(
            [a.astype(dt), b.astype(dt),
             jnp.ones((B, E, 1), dt)], axis=-1) * em.astype(dt)
        N = g.max_nodes
        if self.cumsum:
            from distegnn_tpu.ops.segment import sorted_segment_sum_cs

            out = jax.vmap(lambda t, r: sorted_segment_sum_cs(t, r, N).astype(
                jnp.float32))(packed, g.row)
        elif self.ell:
            from distegnn_tpu.ops.segment import sorted_segment_sum_ell

            D = g.max_in_degree
            out = jax.vmap(lambda t, r: sorted_segment_sum_ell(
                t, r, N, D).astype(jnp.float32))(packed, g.row)
        elif g.edges_sorted:
            from distegnn_tpu.ops.segment import sorted_row_sum

            # f32 accumulator regardless of stream dtype (a bf16 scatter-add
            # accumulator saturates); the stream is read at its own width
            out = sorted_row_sum(packed, g.row, N, jnp.float32)
        else:
            out = jax.vmap(lambda t, r: jnp.zeros(
                (N, t.shape[-1]), jnp.float32).at[r].add(
                    t.astype(jnp.float32)))(packed, g.row)
        # named before the division: neither the sum nor the count is made
        # again by a rematted layer's backward
        out = checkpoint_name(out, "edge_agg")
        cnt = jnp.maximum(out[..., -1:], 1.0)
        out_a = out[..., :sa] / cnt if a_mean else out[..., :sa]
        return out_a, out[..., sa:-1] / cnt


def blocked_gather(h, slot, block: int = DEFAULT_BLOCK, tile: int = DEFAULT_EDGE_TILE):
    """Batched [B, N, F] -> [B, E, F]; rows fetched block-locally (masked
    slots read as 0). Adjoint of :func:`blocked_segment_sum`."""
    return jax.vmap(lambda hh, s: _gather(hh, s, block, tile))(h, slot)


def paired_col_gather(h, col, pair, slot, block: int = DEFAULT_BLOCK,
                      tile: int = DEFAULT_EDGE_TILE):
    """Batched h[b, col[b, e]] whose BACKWARD is perm-gather + blocked row
    aggregation instead of an unsorted XLA scatter: the transpose of a
    symmetric graph's incidence is the edge permutation ``pair``
    (:func:`pairing_perm`), so grad_h = seg_sum(grad[pair], slot)."""
    return jax.vmap(lambda hh, c, p, s: _paired_gather(hh, c, p, s, block, tile))(
        h, col, pair, slot)
