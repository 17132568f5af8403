"""Fused edge-pipeline kernel — one Pallas pass per EGCL layer over the edges.

The plain lowering of an EGCL layer round-trips HBM 4-6 times per layer at
[E, H] width: gather(hr), gather(hc), phi_e intermediates, trans, then the
aggregation read (docs/PERFORMANCE.md "Where the time goes" — the step is
memory-bound at ~1% MFU while the MXU idles). This kernel streams the sorted
edge array ONCE and keeps everything else in VMEM:

  per edge tile (node block b, tile j):
    gather x/hr/hc from a 3-block VMEM node window   (tpu.dynamic_gather)
    cd = x[row] - x[col]; radial = |cd|^2            (VPU, f32)
    phi_e: two H x H matmuls + silu                  (MXU, bf16)
    phi_x: CoordMLP -> per-edge scalar g             (MXU + VPU)
    trans = cd * g                                   (f32)
    segment-sum into the block accumulator           (one-hot MXU dot,
                                                      2-term bf16 split)

HBM traffic per layer: the edge int/scalar stream + 4x node-window re-reads
+ one [N, H+8] accumulator — ~10x less than the plain path's edge-wide
intermediates. FLOP price: the one-hot aggregation adds ~2*T*F bf16 MXU
work per edge — the cost of having no scatter unit (the reference leans on
CUDA scatter_add_ instead, models/FastEGNN.py:322-337).

Locality contract: node ids are Morton-ordered (ops/order.py) and edges are
the blocked layout (ops/graph.py pad_graphs(edge_block=NB)): edge slice
[b*epb, (b+1)*epb) holds the edges whose receiver row lies in node block b,
row-sorted. The VMEM window covers node blocks {s_b, s_b+1, s_b+2} with
s_b = clip(b-1, 0, nb-3). Measured at Fluid113K density (2026-08-02,
N=113140 Morton-ordered): a 3x2048 window captures ~92% of edges, 3x4096
~95.5%. Out-of-window edges are masked here and routed through the compact
`remote` plain-path arrays built by `split_remote_edges` (ordinary EdgeOps
work at ~5-8% of E).

Status on hardware (TPU v5e, jax 0.9.0 / libtpu 0.0.34): the kernel passes
JAX's Pallas lowering (pinned from the CPU by the lowering checks) but
Mosaic refuses to compile it — the `[T, lanes]` sublane gather below is "Not
implemented: Multiple source vregs along gather dimension" (f32) / "Gather
indices and result have different bitwidths" (bf16). It runs in interpret
mode on the CPU only; on a TPU selecting it fails with that error. ROADMAP
S2/D1 decides between a redesigned gather and deletion.

Gather constraint: the Mosaic lowering of `jnp.take_along_axis(x, i, 0)`
(tpu.dynamic_gather) requires source, indices and output to share one 2-D
shape — so the edge tile T equals the node block NB and a 3-block window
costs 3 gathers + selects. One-hot tiles are chunked (OH_CHUNK) to bound
VMEM: a full [T, T] bf16 one-hot at T=2048 would be 8 MiB.

Numerics: geometry (x, cd, radial, trans) is f32; MLP compute is bf16 when
dtype='bf16' (the flagship compute_dtype); accumulation is ALWAYS f32 via
preferred_element_type — the f32 trans stream is split into two exact bf16
terms (hi+lo carries ~16 mantissa bits, strictly tighter than the
measured-acceptable agg_dtype='bf16' single-term stream).

Differentiation: `fused_edge_layer` is a custom_vjp. The backward is a
second Pallas kernel on the same grid that RECOMPUTES the per-edge forward
from the same VMEM windows (remat at tile scale — no edge-wide residual is
ever saved), then emits: block-local row-side grads, 3-slot window PARTIALS
for the col-side grads (combined by a tiny XLA block shift-add outside —
writing directly to neighbor blocks would race across grid steps), and
weight grads accumulated in constant-index output blocks across the grid.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distegnn_tpu import runtime

DEFAULT_BLOCK = 2048   # node block NB == edge tile T (gather shape contract)
OH_CHUNK = 512         # one-hot aggregation chunk (VMEM bound)
XL = 8                 # x lane padding: [N, 3] f32 stored as [N, 8]


class EdgeWeights(NamedTuple):
    """phi_e (hoisted first Dense, scalar part) + phi_x params, all f32.

    Row-vector convention: biases and the phi_x head are [1, H] so every
    in-kernel tensor is 2-D (TPU vregs are 2-D; 1-D values complicate the
    Mosaic layout for no gain).
    """

    ws: jnp.ndarray   # [S, H] scalar part of hoisted Dense (S = 1 + attr_nf)
    b1: jnp.ndarray   # [1, H]
    w2: jnp.ndarray   # [H, H] phi_e second Dense
    b2: jnp.ndarray   # [1, H]
    w3: jnp.ndarray   # [H, H] phi_x hidden Dense
    b3: jnp.ndarray   # [1, H]
    w4: jnp.ndarray   # [1, H] phi_x head (no bias, xavier gain 1e-3)


def _silu(x):
    # f32 inside, x's dtype out: the Mosaic lowering of a bf16 logistic
    # broadcasts an f32 constant into a bf16 vector and fails verification
    # (and the v5e VPU has no bf16 arithmetic to lose)
    x32 = x.astype(jnp.float32)
    return (x32 * jax.nn.sigmoid(x32)).astype(x.dtype)


def _dsilu(x):
    x32 = x.astype(jnp.float32)
    s = jax.nn.sigmoid(x32)
    return (s * (1.0 + x32 * (1.0 - s))).astype(x.dtype)


def _mm(a, b):
    """``a @ b`` in ``a``'s dtype with an f32 accumulator (Mosaic refuses a
    bf16 x bf16 dot whose accumulator is not 32-bit)."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def _mm_nt(a, b):
    """``a @ b.T`` (2-D), same accumulator rule, no materialized transpose."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(a.dtype)


def _split2(x):
    """2-term bf16 split of f32 (hi+lo ~= 16 mantissa bits)."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


# ---------------------------------------------------------------- layout

def _validate_block(block: int) -> None:
    """The one-hot aggregation is chunked at OH_CHUNK: a block smaller than
    one chunk would run ZERO chunks (all-zero aggregates) and a non-multiple
    would silently drop the tail edges of every tile — fail loudly instead."""
    if block < OH_CHUNK or block % OH_CHUNK:
        raise ValueError(
            f"edge_pipeline requires block to be a multiple of OH_CHUNK="
            f"{OH_CHUNK} and >= {OH_CHUNK} (got block={block}): the chunked "
            f"one-hot aggregation would drop edges otherwise")


def build_edge_blocks(row, col, edge_attr, edge_mask, *, block, n_nodes):
    """Blocked-layout [E] edge arrays -> the kernel's flat HBM layout.

    With T = block, nb = n_nodes/T, epb = E/nb, nt = epb/T tiles per block:
      row_t [nb*nt, T] int32 — block-LOCAL rows; masked slots carry T
                               (matches no one-hot lane)
      col_l [E, 1]     int32 — window-block-local col in [0, T)
      kblk  [E, 1]     int32 — which window slot (0..2) the col falls in
      scal  [E, XL]    f32   — [edge_attr[0:2], active-mask, 0, ...]
    Edges with cols outside the 3-block window are masked out (they belong
    to the remote path, `split_remote_edges`).
    """
    _validate_block(block)
    nb = n_nodes // block
    E = row.shape[0]
    epb = E // nb
    T = block
    if n_nodes % block or E % nb or epb % T:
        raise ValueError(f"layout mismatch: N={n_nodes} E={E} block={block}")
    nt = epb // T

    b_of_edge = jnp.arange(E, dtype=jnp.int32) // epb
    s = jnp.clip(b_of_edge - 1, 0, max(nb - 3, 0))
    row_local = row.astype(jnp.int32) - b_of_edge * T
    col_win = col.astype(jnp.int32) - s * T
    in_win = (col_win >= 0) & (col_win < 3 * T)
    mask = (edge_mask > 0) & in_win
    row_t = jnp.where(mask, row_local, T).reshape(nb * nt, T)
    col_win = jnp.clip(col_win, 0, 3 * T - 1)
    kblk = col_win // T
    col_l = col_win - kblk * T

    ea = edge_attr.astype(jnp.float32)
    scal = jnp.concatenate(
        [ea[:, :2], mask[:, None].astype(jnp.float32),
         jnp.zeros((E, XL - 3), jnp.float32)], axis=1)
    return row_t, col_l[:, None], kblk[:, None], scal


def _remote_sel(edge_index: np.ndarray, block: int, n_nodes: int) -> np.ndarray:
    """Boolean [e] mask of edges OUTSIDE the 3-block VMEM window — the single
    definition of the remote classification (mirrors build_edge_blocks)."""
    if n_nodes % block:
        raise ValueError(f"n_nodes={n_nodes} not a multiple of block={block}")
    row, col = edge_index[0], edge_index[1]
    br, bc = row // block, col // block
    nb = n_nodes // block
    s = np.clip(br - 1, 0, max(nb - 3, 0))
    return (bc < s) | (bc > s + 2)


def count_remote_edges(edge_index: np.ndarray, *, block: int,
                       n_nodes: int) -> int:
    """Number of out-of-window edges (loader scans use this to pick a
    dataset-stable remote pad without materializing the split)."""
    return int(_remote_sel(np.asarray(edge_index), block, n_nodes).sum())


def split_remote_edges(edge_index: np.ndarray, edge_attr: np.ndarray,
                       *, block: int, n_nodes: int,
                       n_pad: Optional[int] = None
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """numpy (loader-side): extract the out-of-window edges into a compact
    row-sorted plain edge list for the XLA remote path.

    ``n_nodes`` is the padded node count of the blocked layout; ``nb`` MUST
    be derived from it exactly as `build_edge_blocks` does (n_nodes // block),
    NOT inferred from the edges — with trailing node blocks that receive no
    edges the two would disagree on the window clamp near the top and an edge
    could be classified in-window by one function and remote by the other
    (double-counted or dropped once both paths are aggregated).

    Returns (remote_edge_index [2, Er], remote_edge_attr [Er, D],
    remote_mask [Er]) padded to ``n_pad`` (default: next multiple of 128).
    Padding points at node 0 with mask 0 — the pad_graphs convention.
    """
    r_idx = remote_selection(edge_index, block=block, n_nodes=n_nodes)
    return pad_remote_list(edge_index[:, r_idx], edge_attr[r_idx],
                           n_pad=n_pad)


def remote_selection(edge_index: np.ndarray, *, block: int,
                     n_nodes: int) -> np.ndarray:
    """Row-sorted indices of the out-of-window edges — the expensive half of
    :func:`split_remote_edges`, split out so the serve session cache can store
    it once per topology and re-gather fresh attrs per request."""
    remote = _remote_sel(edge_index, block, n_nodes)
    row = edge_index[0]
    r_idx = np.where(remote)[0]
    return r_idx[np.argsort(row[r_idx], kind="stable")]


def pad_remote_list(ei_r: np.ndarray, ea_r: np.ndarray,
                    n_pad: Optional[int] = None
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a compact remote edge list to ``n_pad`` (default next multiple of
    128); padding points at node 0 with mask 0 — the pad_graphs convention."""
    er = ei_r.shape[1]
    if n_pad is None:
        n_pad = max(((er + 127) // 128) * 128, 128)
    if er > n_pad:
        raise ValueError(f"{er} remote edges exceed pad {n_pad}")
    ei = np.zeros((2, n_pad), np.int32)
    ea = np.zeros((n_pad, ea_r.shape[1]), ea_r.dtype)
    m = np.zeros((n_pad,), np.float32)
    ei[:, :er] = ei_r
    ea[:er] = ea_r
    m[:er] = 1.0
    return ei, ea, m


# ---------------------------------------------------------------- kernels

def _gather3(refs, idx_loc, kblk, T, lanes):
    """Select-gather from the 3 window blocks: refs are VMEM refs [T,lanes],
    idx_loc [T, 1] block-local rows, kblk [T, 1] in {0,1,2}."""
    idx = jnp.broadcast_to(idx_loc, (T, lanes))
    out = jnp.zeros((T, lanes), refs[0].dtype)
    for k in range(3):
        g = jnp.take_along_axis(refs[k][...], idx, axis=0)
        out = jnp.where(jnp.broadcast_to(kblk == k, (T, lanes)), g, out)
    return out


def _onehot_agg(seg_row, data):
    """[T, F] tile -> [T, F] f32 block rows: chunked one-hot MXU dots.
    seg_row [1, T] block-local rows (T == masked/no-op)."""
    T, F = data.shape
    out = jnp.zeros((T, F), jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, OH_CHUNK), 0)
    for c in range(T // OH_CHUNK):
        sl = slice(c * OH_CHUNK, (c + 1) * OH_CHUNK)
        seg = seg_row[:, sl]
        oh = (rows == jnp.broadcast_to(seg, (T, OH_CHUNK))).astype(jnp.bfloat16)
        chunk = data[sl, :]
        out = out + jax.lax.dot_general(
            oh, chunk.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return out


def _edge_fwd_math(x_own, x_win, p_own, p_win, row_t, col, kblk, scal,
                   w: EdgeWeights, T, H, dtype):
    """Shared per-tile forward math (the backward recomputes through this).

    Returns the per-edge intermediates needed by both directions."""
    mask = scal[:, 2:3]                                    # [T, 1] f32
    row_c = jnp.minimum(row_t, T - 1).reshape(T, 1)        # clip masked slots
    x_r = jnp.take_along_axis(x_own[...], jnp.broadcast_to(row_c, (T, XL)), 0)
    x_c = _gather3(x_win, col, kblk, T, XL)
    p_r = jnp.take_along_axis(p_own[...], jnp.broadcast_to(row_c, (T, 2 * H)), 0)
    p_c = _gather3(p_win, col, kblk, T, 2 * H)
    hr_e, hc_e = p_r[:, :H], p_c[:, H:]

    cd = (x_r - x_c) * mask                                # [T, XL] f32
    radial = jnp.sum(cd * cd, axis=1, keepdims=True)       # [T, 1] f32
    sfeat = jnp.concatenate([radial, scal[:, 0:2]], axis=1).astype(dtype)
    t1 = ((hr_e + hc_e).astype(dtype) + _mm(sfeat, w.ws.astype(dtype))
          + w.b1.astype(dtype))
    y1 = _silu(t1)
    t2 = _mm(y1, w.w2.astype(dtype)) + w.b2.astype(dtype)
    ef = _silu(t2)                                         # [T, H] edge_feat
    t3 = _mm(ef, w.w3.astype(dtype)) + w.b3.astype(dtype)
    y2 = _silu(t3)
    g = jnp.sum(y2.astype(jnp.float32) * w.w4, axis=1, keepdims=True) * mask
    return mask, cd, sfeat, t1, y1, t2, ef, t3, y2, g


def _fwd_kernel(row_t_ref, col_ref, kblk_ref, scal_ref,
                xo_ref, x0_ref, x1_ref, x2_ref,
                po_ref, p0_ref, p1_ref, p2_ref,
                ws_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, w4_ref,
                out_ref, *, T, H, dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = EdgeWeights(ws_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
                    w3_ref[...], b3_ref[...], w4_ref[...])
    row_t = row_t_ref[...]                                 # [1, T]
    mask, cd, _, _, _, _, ef, _, _, g = _edge_fwd_math(
        xo_ref, (x0_ref, x1_ref, x2_ref), po_ref, (p0_ref, p1_ref, p2_ref),
        row_t, col_ref[...], kblk_ref[...], scal_ref[...], w, T, H, dtype)

    trans = cd[:, 0:3] * g                                 # [T, 3] f32
    hi, lo = _split2(trans)
    data = jnp.concatenate(
        [hi, lo, mask.astype(jnp.bfloat16), jnp.zeros((T, 1), jnp.bfloat16),
         (ef * mask.astype(ef.dtype)).astype(jnp.bfloat16)], axis=1)
    out_ref[...] += _onehot_agg(row_t, data)               # [T, H+8]


def _bwd_kernel(row_t_ref, col_ref, kblk_ref, scal_ref,
                xo_ref, x0_ref, x1_ref, x2_ref,
                po_ref, p0_ref, p1_ref, p2_ref,
                ws_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, w4_ref,
                gp_ref,
                drow_ref, dcol_ref, dws_ref, db1_ref, dw2_ref, db2_ref,
                dw3_ref, db3_ref, dw4_ref, *, T, H, dtype):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        drow_ref[...] = jnp.zeros_like(drow_ref)
        dcol_ref[...] = jnp.zeros_like(dcol_ref)

    @pl.when(jnp.logical_and(b == 0, j == 0))
    def _():
        dws_ref[...] = jnp.zeros_like(dws_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        db2_ref[...] = jnp.zeros_like(db2_ref)
        dw3_ref[...] = jnp.zeros_like(dw3_ref)
        db3_ref[...] = jnp.zeros_like(db3_ref)
        dw4_ref[...] = jnp.zeros_like(dw4_ref)

    w = EdgeWeights(ws_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
                    w3_ref[...], b3_ref[...], w4_ref[...])
    row_t = row_t_ref[...]
    col, kblk, scal = col_ref[...], kblk_ref[...], scal_ref[...]
    mask, cd, sfeat, t1, y1, t2, ef, t3, y2, g = _edge_fwd_math(
        xo_ref, (x0_ref, x1_ref, x2_ref), po_ref, (p0_ref, p1_ref, p2_ref),
        row_t, col, kblk, scal, w, T, H, dtype)

    # upstream per-edge grads: gather the own-block packed cotangent by row
    row_c = jnp.minimum(row_t, T - 1).reshape(T, 1)
    gt = jnp.take_along_axis(gp_ref[...], jnp.broadcast_to(row_c, (T, H + 8)), 0)
    # lanes 3..XL-1 of the packed cotangent (and of cd) are zero, so the
    # coordinate terms stay XL wide and need no lane scatter
    d_trans = gt[:, 0:XL] * mask                           # [T, XL] f32
    d_ef_up = gt[:, XL:] * mask                            # [T, H] f32

    # trans = cd[:, :3] * g
    d_g = jnp.sum(cd * d_trans, axis=1, keepdims=True)     # [T, 1]

    # g = sum(y2 * w4) * mask
    d_y2 = (d_g * w.w4).astype(dtype)                      # [T, H]
    dw4_ref[...] += jnp.sum(y2.astype(jnp.float32) * d_g, axis=0,
                            keepdims=True)
    d_t3 = d_y2 * _dsilu(t3)
    d_ef = d_ef_up.astype(dtype) + _mm_nt(d_t3, w.w3.astype(dtype))
    dw3_ref[...] += jax.lax.dot_general(
        ef, d_t3, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # ef^T d_t3
    db3_ref[...] += jnp.sum(d_t3.astype(jnp.float32), axis=0, keepdims=True)

    d_t2 = d_ef * _dsilu(t2)
    d_y1 = _mm_nt(d_t2, w.w2.astype(dtype))
    dw2_ref[...] += jax.lax.dot_general(
        y1, d_t2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db2_ref[...] += jnp.sum(d_t2.astype(jnp.float32), axis=0, keepdims=True)

    d_t1 = d_y1 * _dsilu(t1)                               # [T, H]
    dws_ref[...] += jax.lax.dot_general(
        sfeat, d_t1, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[0:dws_ref.shape[0]]
    db1_ref[...] += jnp.sum(d_t1.astype(jnp.float32), axis=0, keepdims=True)

    d_sfeat = _mm_nt(d_t1, w.ws.astype(dtype))          # [T, S]
    d_radial = d_sfeat[:, 0:1].astype(jnp.float32) * mask
    # radial = sum(cd^2); trans = cd * g
    d_cd = 2.0 * cd * d_radial + d_trans * g               # [T, XL] f32

    # ---- aggregate: row side (own block), col side (3-slot window partials)
    d_t1m = d_t1 * mask.astype(d_t1.dtype)
    hi, lo = _split2(d_cd[:, 0:3])
    row_data = jnp.concatenate(
        [hi, lo, jnp.zeros((T, 2), jnp.bfloat16),
         d_t1m.astype(jnp.bfloat16)], axis=1)              # [T, H+8]
    drow_ref[...] += _onehot_agg(row_t, row_data)

    # col-side per-edge payload: d_hc = d_t1, d_x_col = -d_cd
    chi, clo = _split2(-d_cd[:, 0:3])
    col_data = jnp.concatenate(
        [chi, clo, jnp.zeros((T, 2), jnp.bfloat16),
         d_t1m.astype(jnp.bfloat16)], axis=1)              # [T, H+8]
    # mask out edges NOT in window slot k, then aggregate by col-local row;
    # masked/out-of-slot edges carry col row T via the same no-op trick
    for k in range(3):
        in_k = (kblk == k) & (mask > 0)
        seg = jnp.where(in_k, col, T).reshape(1, T)
        part = _onehot_agg(seg, col_data)
        dcol_ref[:, k * (H + 8):(k + 1) * (H + 8)] += part


# ---------------------------------------------------------------- wrappers

def _common_specs(T, H, nb, nt, wshapes):
    """in_specs shared by both kernels: edge blocks, node windows, weights."""
    def edge(spec_shape):
        return pl.BlockSpec(spec_shape, lambda b, j: (b * nt + j, 0),
                            memory_space=pltpu.VMEM)

    def own(lanes):
        return pl.BlockSpec((T, lanes), lambda b, j: (b, 0),
                            memory_space=pltpu.VMEM)

    def win(k, lanes):
        return pl.BlockSpec(
            (T, lanes),
            lambda b, j, k=k: (jnp.clip(b - 1, 0, max(nb - 3, 0)) + k, 0),
            memory_space=pltpu.VMEM)

    def const(shape):
        return pl.BlockSpec(shape, lambda b, j: (0, 0),
                            memory_space=pltpu.VMEM)

    # row ids ride a [nb*nt, 1, T] array: a (1, T) block of a 2-D
    # [nb*nt, T] array breaks the TPU (8, 128) block rule, a squeezed leading
    # axis over full trailing dims does not
    row_spec = pl.BlockSpec((None, 1, T), lambda b, j: (b * nt + j, 0, 0),
                            memory_space=pltpu.VMEM)
    return ([row_spec, edge((T, 1)), edge((T, 1)), edge((T, XL)),
             own(XL), win(0, XL), win(1, XL), win(2, XL),
             own(2 * H), win(0, 2 * H), win(1, 2 * H), win(2, 2 * H)]
            + [const(s) for s in wshapes])


def _pack_inputs(x, hr, hc, weights, n_nodes, dtype):
    xp = jnp.zeros((n_nodes, XL), jnp.float32).at[:, 0:3].set(x)
    pk = jnp.concatenate([hr, hc], axis=1).astype(dtype)
    wlist = [weights.ws, weights.b1, weights.w2, weights.b2,
             weights.w3, weights.b3, weights.w4]
    return xp, pk, wlist


def _check_grid(n_nodes: int, block: int) -> int:
    """The win(k) BlockSpec index maps address node blocks s..s+2; with
    nb < 3 they would index past the array and rely on unspecified Mosaic
    out-of-bounds block clamping — reject small graphs loudly (route them
    through the plain EdgeOps path instead)."""
    _validate_block(block)
    nb = n_nodes // block
    if nb < 3:
        raise ValueError(
            f"fused_edge_layer needs at least 3 node blocks (n_nodes="
            f"{n_nodes}, block={block} -> nb={nb}): the 3-block VMEM window "
            f"would index out of bounds; use the plain EdgeOps path for "
            f"graphs smaller than {3 * block} padded nodes")
    return nb


def _fused_fwd_impl(x, hr, hc, row_t, col_l, kblk, scal, weights,
                    *, block, dtype_name):
    T = block
    n_nodes, H = hr.shape[0], hr.shape[1]
    nb = _check_grid(n_nodes, T)
    nt = row_t.shape[0] // nb
    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    xp, pk, wlist = _pack_inputs(x, hr, hc, weights, n_nodes, dtype)

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, T=T, H=H, dtype=dtype),
        grid=(nb, nt),
        in_specs=_common_specs(T, H, nb, nt, [w.shape for w in wlist]),
        out_specs=pl.BlockSpec((T, H + 8), lambda b, j: (b, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_nodes, H + 8), jnp.float32),
        interpret=runtime.use_interpret(),
    )(row_t[:, None, :], col_l, kblk, scal, xp, xp, xp, xp, pk, pk, pk, pk,
      *wlist)
    trans = out[:, 0:3] + out[:, 3:6]       # 2-term bf16 recombine
    count = out[:, 6]
    ef_sum = out[:, 8:]
    return trans, count, ef_sum


def _fused_bwd_impl(x, hr, hc, row_t, col_l, kblk, scal, weights,
                    g_trans, g_ef, *, block, dtype_name):
    T = block
    n_nodes, H = hr.shape[0], hr.shape[1]
    nb = _check_grid(n_nodes, T)
    nt = row_t.shape[0] // nb
    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    xp, pk, wlist = _pack_inputs(x, hr, hc, weights, n_nodes, dtype)
    g_pack = jnp.concatenate(
        [g_trans.astype(jnp.float32),
         jnp.zeros((n_nodes, XL - 3), jnp.float32),
         g_ef.astype(jnp.float32)], axis=1)                # [N, H+8]

    wshapes = [w.shape for w in wlist]
    gp_spec = pl.BlockSpec((T, H + 8), lambda b, j: (b, 0),
                           memory_space=pltpu.VMEM)
    out_specs = (
        pl.BlockSpec((T, H + 8), lambda b, j: (b, 0),
                     memory_space=pltpu.VMEM),              # row-side grads
        pl.BlockSpec((T, 3 * (H + 8)), lambda b, j: (b, 0),
                     memory_space=pltpu.VMEM),              # col window partials
    ) + tuple(pl.BlockSpec(s, lambda b, j: (0, 0), memory_space=pltpu.VMEM)
              for s in wshapes)
    out_shapes = (
        jax.ShapeDtypeStruct((n_nodes, H + 8), jnp.float32),
        jax.ShapeDtypeStruct((n_nodes, 3 * (H + 8)), jnp.float32),
    ) + tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in wshapes)

    drow, dcol, dws, db1, dw2, db2, dw3, db3, dw4 = pl.pallas_call(
        functools.partial(_bwd_kernel, T=T, H=H, dtype=dtype),
        grid=(nb, nt),
        in_specs=_common_specs(T, H, nb, nt, wshapes) + [gp_spec],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=runtime.use_interpret(),
    )(row_t[:, None, :], col_l, kblk, scal, xp, xp, xp, xp, pk, pk, pk, pk,
      *wlist, g_pack)

    # row-side: d_x (+cd side) and d_hr live in the own block
    d_x = drow[:, 0:3] + drow[:, 3:6]
    d_hr = drow[:, 8:]
    # col-side: window slot k of block b lands on node block s_b + k
    F = H + 8
    parts = dcol.reshape(nb, T, 3, F)
    s = np.clip(np.arange(nb) - 1, 0, max(nb - 3, 0))
    acc = jnp.zeros((nb, T, F), jnp.float32)
    for k in range(3):
        acc = acc.at[s + k].add(parts[:, :, k, :])
    acc = acc.reshape(n_nodes, F)
    d_x = d_x + acc[:, 0:3] + acc[:, 3:6]
    d_hc = acc[:, 8:]
    d_w = EdgeWeights(dws, db1, dw2, db2, dw3, db3, dw4)
    return d_x, d_hr, d_hc, d_w


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def fused_edge_layer(x, hr, hc, row_t, col_l, kblk, scal, weights,
                     block: int = DEFAULT_BLOCK, dtype_name: str = "bf16"):
    """Fused phi_e + phi_x + row aggregation over the blocked edge arrays.

    Args:
      x    [N, 3] f32 coordinates (Morton-ordered, block-padded)
      hr   [N, H] hoisted row features (h @ W_row, node axis)
      hc   [N, H] hoisted col features
      row_t/col_l/kblk/scal — `build_edge_blocks` output
      weights — EdgeWeights
    Returns (trans_sum [N, 3] f32, count [N] f32, ef_sum [N, H] f32): the
    UN-normalized in-window segment sums; the caller adds the remote-path
    sums and normalizes (coords_agg mean) outside.
    """
    return _fused_fwd_impl(x, hr, hc, row_t, col_l, kblk, scal, weights,
                           block=block, dtype_name=dtype_name)


def _fel_fwd(x, hr, hc, row_t, col_l, kblk, scal, weights, block, dtype_name):
    out = _fused_fwd_impl(x, hr, hc, row_t, col_l, kblk, scal, weights,
                          block=block, dtype_name=dtype_name)
    return out, (x, hr, hc, row_t, col_l, kblk, scal, weights)


def _fel_bwd(block, dtype_name, res, g):
    x, hr, hc, row_t, col_l, kblk, scal, weights = res
    g_trans, _g_count, g_ef = g     # count is data-independent (mask sum)
    d_x, d_hr, d_hc, d_w = _fused_bwd_impl(
        x, hr, hc, row_t, col_l, kblk, scal, weights,
        g_trans, g_ef, block=block, dtype_name=dtype_name)
    zero = lambda a: jnp.zeros_like(a)
    return (d_x, d_hr.astype(hr.dtype), d_hc.astype(hc.dtype),
            zero(row_t), zero(col_l), zero(kblk), zero(scal), d_w)


fused_edge_layer.defvjp(_fel_fwd, _fel_bwd)
