"""Cross-layer fused megakernel — L EGCL layers with the graph VMEM-resident.

The per-layer fused pipeline (ops/edge_pipeline.py) streams the blocked edge
array through VMEM once per layer, but between layers every tensor — node
features, geometry, the blocked-CSR edge structure — round-trips HBM, so an
L-layer FastEGNN pays the O(E)-scale HBM traffic L times. This kernel runs
the WHOLE layer stack in one Pallas grid:

  grid = (L,)   # one sequential grid step per EGCL layer

  step l:
    read node state (h, x, X, Hv) from the parity-selected half of a
      double-buffered VMEM scratch window (layer boundary = a VMEM swap,
      not an HBM round-trip)
    write the layer-INPUT state to the l-indexed checkpoint output
      (the backward's remat anchors — O(L * N * H), never O(E))
    in-window edges: the same per-tile forward as ops/edge_pipeline
      (_edge_fwd_math + chunked one-hot MXU aggregation) over the
      VMEM-resident blocked edge stream — read from HBM ONCE for all L
      layers instead of once per layer
    remote tail: the out-of-window edge list (plain per-edge math, exactly
      EGCLVel's dense tail) evaluated in-kernel with exact f32 one-hot
      gathers/segment-dots — summed into the same aggregates per layer
    virtual-node section: phi_ev/phi_xv/phi_X/phi_v/phi_h/phi_hv (+phi_g)
      as raw matmuls over values, bit-matching the Flax module math
    write the updated state to the OTHER scratch half + the final outputs

  per-layer weights are stacked along a leading L axis and streamed one
  layer per grid step via (1, a, b) BlockSpecs — VMEM stays bounded in L.

HBM traffic per forward step (the fused_stack vs fused lever,
`hbm_bytes_per_step` below is the quantitative model):

  per-layer fused:  L x (edge stream + 4x node-window re-reads
                         + accumulator + boundary state)
  fused_stack:      1 x edge stream + L x (weights + checkpoint write)
                         + boundary state once

Differentiation: `fused_egnn_stack` is a custom_vjp. The forward kernel
checkpoints only the per-layer INPUT node state; the backward walks the
layers in reverse, re-running each layer through `_layer_ref` — a pure-JAX
single-layer reference whose in-window edge pass IS `fused_edge_layer`, so
the per-edge activations are rematerialized at tile scale inside its Pallas
backward and no O(E)-wide residual is ever saved. VMEM and residual memory
both stay bounded in L.

Scale contract: everything here must FIT — the whole graph (blocked edge
stream + node state + one layer of weights + remote one-hots) is
VMEM-resident. `estimate_stack_vmem_bytes` models the residency and
`fused_egnn_stack` raises a typed `StackVmemBudgetError` when the estimate
exceeds the declared budget instead of letting XLA spill silently. The
Fluid113K flagship does NOT fit by design — keep `edge_impl: fused` there;
fused_stack targets rung-scale serving graphs (serve/engine.py pads to
rungs), where one multi-layer executable per (rung, L) drops per-request
HBM traffic ~Lx. Under a (graph/tensor) mesh the layer-boundary collectives
cannot cross a Pallas grid, so FastEGNN falls back to the per-layer fused
path with the SAME param tree (models/fast_egnn.py) — the megakernel is the
single-chip serving/training lowering.

Status on hardware: the in-window pass is ops/edge_pipeline's tile math, so
Mosaic refuses this kernel for the same sublane gather (see that module);
interpret mode on the CPU only.

Parity contract (tests/test_layer_pipeline.py): interpret-mode forward
within 1e-6 and grads within 1e-5 of the per-layer fused path at
L in {1, 2, 4}, including remote tails and trailing empty blocks. The
in-window tile math is shared code (bitwise); the remote tail and the
virtual section differ only by f32 reassociation (one-hot dots vs
segment_sum order).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distegnn_tpu import runtime
from distegnn_tpu.ops.edge_pipeline import (
    OH_CHUNK, XL, EdgeWeights, _check_grid, _edge_fwd_math, _mm, _onehot_agg,
    _silu, _split2, fused_edge_layer,
)

# Honest single-core VMEM budget (pallas_guide: ~16 MiB/core). The estimate
# below must stay under this (or an explicit per-model override) or the op
# refuses to trace.
DEFAULT_STACK_VMEM_BUDGET = 16 * 1024 * 1024


class StackVmemBudgetError(ValueError):
    """The megakernel's VMEM residency estimate exceeds the declared budget.

    Raised at trace time (typed, catchable) instead of letting the TPU
    compiler spill the resident graph to HBM silently — a spilled megakernel
    is strictly worse than the per-layer fused path it replaces. Fix: use
    `edge_impl: fused` for this shape, shrink the rung, or raise the budget
    knowingly via StackConfig.vmem_budget."""


class StackConfig(NamedTuple):
    """Static (hashable) megakernel configuration — custom_vjp nondiff arg."""

    n_layers: int
    block: int               # node block == edge tile T (edge_pipeline contract)
    hidden: int              # H
    channels: int            # C virtual channels
    node_attr_nf: int = 0    # A (0 = batch carries no node_attr)
    has_gravity: bool = False
    residual: bool = True
    coords_mean: bool = True  # coords_agg == 'mean'
    dtype_name: str = "f32"   # 'f32' | 'bf16' (message-MLP compute dtype)
    vmem_budget: int = DEFAULT_STACK_VMEM_BUDGET


SCALAR_NF = 3  # radial + 2 edge attrs — the fused kernel's scalar lanes


def stack_weight_shapes(cfg: StackConfig) -> Dict[str, Tuple[int, int]]:
    """Per-layer 2-D shapes of every stacked weight, keyed by kernel name.

    The stacked container is {key: [L, a, b]} — a RUNTIME VIEW of the same
    param tree the per-layer fused path declares (models/fast_egnn.py
    stacks/reshapes the Flax leaves; checkpoints are identical across
    edge_impl 'fused' <-> 'fused_stack')."""
    H, C, A = cfg.hidden, cfg.channels, cfg.node_attr_nf
    shapes = {
        # phi_e + phi_x head (edge_pipeline EdgeWeights layout: row biases,
        # e_w4 pre-transposed to [1, H])
        "e_w1": (2 * H + SCALAR_NF, H), "e_b1": (1, H),
        "e_w2": (H, H), "e_b2": (1, H),
        "e_w3": (H, H), "e_b3": (1, H), "e_w4": (1, H),
        # phi_ev: MLP([H, H], act_last=True) on [h, Hv, |vcd|, m_X]
        "ev_k0": (2 * H + 1 + C, H), "ev_b0": (1, H),
        "ev_k1": (H, H), "ev_b1": (1, H),
        # phi_xv / phi_X: CoordMLP (no last bias)
        "xv_k0": (H, H), "xv_b0": (1, H), "xv_k1": (H, 1),
        "X_k0": (H, H), "X_b0": (1, H), "X_k1": (H, 1),
        # phi_v: MLP([H, 1])
        "v_k0": (H, H), "v_b0": (1, H), "v_k1": (H, 1), "v_b1": (1, 1),
        # phi_h: MLP([H, H]) on [h, agg_h, agg_v(, node_attr)]
        "h_k0": (3 * H + A, H), "h_b0": (1, H),
        "h_k1": (H, H), "h_b1": (1, H),
        # phi_hv: MLP([H, H]) on [Hv^T, agg_Hv]
        "hv_k0": (2 * H, H), "hv_b0": (1, H),
        "hv_k1": (H, H), "hv_b1": (1, H),
    }
    if cfg.has_gravity:
        shapes.update({"g_k0": (H, H), "g_b0": (1, H),
                       "g_k1": (H, 1), "g_b1": (1, 1)})
    return shapes


# ------------------------------------------------------------ memory models

def estimate_stack_vmem_bytes(cfg: StackConfig, *, n_nodes: int,
                              n_edges: int, remote_pad: int) -> int:
    """Model of the megakernel's peak VMEM residency in bytes.

    Everything with a constant-index BlockSpec is resident for the whole
    grid; per-layer weights and checkpoints stream one block at a time, so
    the estimate is (by design) bounded in L — the L-dependence lives in HBM
    traffic, not VMEM. Conservative where it matters: temporaries that
    coexist (edge-tile intermediates, remote one-hots, the virtual-section
    activations) are all counted."""
    H, C, A = cfg.hidden, cfg.channels, cfg.node_attr_nf
    N, E, R = n_nodes, n_edges, remote_pad
    db = 2 if cfg.dtype_name == "bf16" else 4
    T = cfg.block
    w_bytes = 4 * sum(a * b for a, b in stack_weight_shapes(cfg).values())
    items = {
        # blocked edge stream (row_t + col_l + kblk are i32, scal is f32 XL)
        "edge_stream": E * (4 + 4 + 4 + XL * 4),
        # node inputs: x/v packed [N, XL] f32, h0 [N, H] f32, mask, attrs
        "node_inputs": N * (2 * XL * 4 + H * 4 + 4 + A * 4),
        # double-buffered state window (2x) + final outputs + one ckpt block
        "state_scratch": 2 * N * (XL + H) * 4,
        "outputs": 2 * N * (XL + H) * 4,
        # one layer of stacked weights, x2 for the streamed double buffer
        "layer_weights": 2 * w_bytes,
        # hoisted products hr/hc + packed pk per layer
        "hoisted": N * 4 * H * db,
        # per-block [T, H+8] f32 accumulator assembled to [N, H+8]
        "accumulator": N * (H + 8) * 4 + T * (H + 8) * 4,
        # remote tail: compact arrays + the two [R, N] f32 one-hot gathers
        "remote": R * (XL * 4 + 8 + 3 * H * db) + 2 * R * N * 4,
        # virtual section activations: v_in + vef (+ vcd/trans_X f32)
        "virtual": N * C * ((2 * H + 1 + C) + 2 * H) * db + 2 * N * 3 * C * 4,
    }
    return int(sum(items.values()))


def check_stack_vmem(cfg: StackConfig, *, n_nodes: int, n_edges: int,
                     remote_pad: int) -> int:
    """Raise StackVmemBudgetError if the estimate exceeds cfg.vmem_budget."""
    est = estimate_stack_vmem_bytes(cfg, n_nodes=n_nodes, n_edges=n_edges,
                                    remote_pad=remote_pad)
    if est > cfg.vmem_budget:
        raise StackVmemBudgetError(
            f"fused_stack megakernel needs ~{est / 2**20:.1f} MiB VMEM-resident "
            f"state (N={n_nodes}, E={n_edges}, R={remote_pad}, H={cfg.hidden}, "
            f"block={cfg.block}) but the budget is "
            f"{cfg.vmem_budget / 2**20:.1f} MiB — the graph must fit on-chip "
            f"for the cross-layer fusion to pay. Use edge_impl='fused' for "
            f"this shape, shrink the serving rung, or raise "
            f"StackConfig.vmem_budget explicitly")
    return est


def hbm_bytes_per_step(impl: str, *, n_nodes: int, n_edges: int, hidden: int,
                       channels: int, n_layers: int, remote_pad: int = 0,
                       node_attr_nf: int = 0,
                       dtype_name: str = "f32") -> Dict[str, int]:
    """Analytic HBM-bytes-per-forward-step model for the three edge lowerings.

    This is the CPU-trace-era evidence model (docs/PERFORMANCE.md): derived
    purely from shapes, reproducible from `scripts/microbench_ops.py`, and
    NOT a hardware measurement. Assumptions: every HBM operand is read or
    written exactly once per use-site (infinite cache within one kernel, no
    reuse across kernels), weights are re-read per layer, remote arrays are
    i32/f32 compact lists. Returns {"total": bytes, ...itemized}.
    """
    N, E, H, C, L, R, A = (n_nodes, n_edges, hidden, channels, n_layers,
                           remote_pad, node_attr_nf)
    db = 2 if dtype_name == "bf16" else 4
    edge_stream = E * (4 + 4 + 4 + XL * 4)      # row_t/col/kblk + scal
    remote_stream = R * (8 + XL * 4)
    state = N * (XL * 4 + XL * 4 + H * 4 + 4 + A * 4)   # x, v, h, mask, attr
    cfg = StackConfig(n_layers=L, block=OH_CHUNK, hidden=H, channels=C,
                      node_attr_nf=A, has_gravity=False,
                      dtype_name=dtype_name)
    w_layer = 4 * sum(a * b for a, b in stack_weight_shapes(cfg).values())
    virt = N * C * H * db                        # vef spill per layer (XLA)
    if impl == "fused_stack":
        items = {
            "edge_stream_once": edge_stream,
            "remote_once": remote_stream,
            "state_io": 2 * state,
            "weights_L": L * w_layer,
            "ckpt_writes": L * N * (XL + H) * 4,
        }
    elif impl == "fused":
        # per layer: edge stream + 4 node-window re-read passes + accumulator
        # + the layer-boundary state round-trip + the XLA virtual section
        per_layer = (edge_stream + remote_stream
                     + 4 * N * (XL * 4 + 2 * H * db)
                     + N * (H + 8) * 4
                     + 2 * state + w_layer + 2 * virt)
        items = {"per_layer_x_L": L * per_layer}
    elif impl == "plain":
        # per layer: edge-wide [E, H] intermediates round-trip ~5x (gather
        # hr, gather hc, edge_feat write+read, trans) + aggregation read
        per_layer = (E * H * db * 5 + E * 3 * 4 * 2 + 2 * state
                     + w_layer + 2 * virt)
        items = {"per_layer_x_L": L * per_layer}
    else:
        raise ValueError(f"unknown impl {impl!r}")
    items["total"] = int(sum(items.values()))
    return items


# ------------------------------------------------------- shared layer math
#
# Every helper below operates on VALUES (plain jnp arrays), so the SAME code
# runs inside the Pallas kernel (on ref[...] reads) and inside `_layer_ref`
# (the pure-JAX backward reference). That sharing is the parity argument.

def _cast(dt):
    return (lambda a: a.astype(dt)) if dt is not None else (lambda a: a)


def _dense(x, k, b, dt):
    """nn.Dense(dtype=dt) on values: promote inputs AND params to dt."""
    c = _cast(dt)
    y = _mm(c(x), c(k))
    if b is not None:
        y = y + c(b)
    return y


def _mlp2(x, k0, b0, k1, b1, dt, act_last=False):
    """MLP([s0, s1]) on values — TorchDense/TorchDense with silu between."""
    y = _silu(_dense(x, k0, b0, dt))
    y = _dense(y, k1, b1, dt)
    return _silu(y) if act_last else y


def _coord_head(x, k0, b0, k1, dt):
    """CoordMLP on values: Dense(H) -> silu -> Dense(1, no bias) -> f32."""
    y = _silu(_dense(x, k0, b0, dt))
    return _dense(y, k1, None, dt).astype(jnp.float32)


def _remote_edge_math(x_r, x_c, hr_r, hc_c, rattr, rm, w, H, dt):
    """Per-edge remote-tail math on pre-gathered values (EGCLVel's dense
    tail, models/fast_egnn.py): returns (cd_r [R,3], g_r [R,1], ef_r [R,H]).
    The caller chooses the gather/scatter lowering (segment_sum in XLA,
    exact f32 one-hot dots in-kernel)."""
    c = _cast(dt)
    cd_r = (x_r - x_c) * rm
    radial = jnp.sum(cd_r * cd_r, axis=-1, keepdims=True)
    sfeat = c(jnp.concatenate([radial, rattr[:, :2]], axis=-1))
    t1 = hr_r + hc_c + _mm(sfeat, c(w["e_w1"][2 * H:])) + c(w["e_b1"])
    ef_r = _silu(_mm(_silu(t1), c(w["e_w2"])) + c(w["e_b2"]))
    y2 = _silu(_mm(ef_r, c(w["e_w3"])) + c(w["e_b3"]))
    g_r = (y2.astype(jnp.float32) @ w["e_w4"].T) * rm
    return cd_r, g_r, ef_r


def _virtual_and_update(h, x, v, X, Hv, agg, agg_h, nm, nattr, gvec, w,
                        cfg: StackConfig):
    """The full post-aggregation EGCL section on unbatched values — virtual
    edges, coordinate/velocity/gravity updates, node + virtual feature
    updates. Exactly EGCLVel's math (models/fast_egnn.py:289-373) with the
    batch axis dropped and the Flax modules replaced by their raw matmuls.

    h [N,H] f32, x [N,3] f32, v [N,3] f32, X [3,C] f32, Hv [H,C] f32,
    agg [N,3] f32, agg_h [N,H] f32, nm [N,1] f32 node mask."""
    H, C = cfg.hidden, cfg.channels
    dt = None if cfg.dtype_name == "f32" else jnp.bfloat16
    N = h.shape[0]

    # virtual-edge geometry on the PRE-update coordinates
    vcd = X[None, :, :] - x[:, :, None]                       # [N, 3, C]
    virtual_radial = jnp.linalg.norm(vcd, axis=1, keepdims=True)  # [N, 1, C]

    # exact global coordinate mean over real nodes (global_node_mean,
    # axis_name=None — the mesh fallback handles the sharded case)
    cnt_n = jnp.maximum(jnp.sum(nm.astype(x.dtype)), 1.0)
    coord_mean = jnp.sum(x * nm, axis=0) / cnt_n              # [3]
    Xc = X - coord_mean[:, None]                              # [3, C]
    m_X = jnp.einsum("dc,de->ce", Xc, Xc)                     # [C, C]

    v_in = jnp.concatenate(
        [jnp.broadcast_to(h[:, None, :], (N, C, H)),
         jnp.broadcast_to(Hv.T[None, :, :], (N, C, H)),
         jnp.swapaxes(virtual_radial, 1, 2),                  # [N, C, 1]
         jnp.broadcast_to(m_X[None, :, :], (N, C, C))], axis=-1)
    vef = _mlp2(v_in, w["ev_k0"], w["ev_b0"], w["ev_k1"], w["ev_b1"], dt,
                act_last=True)                                # [N, C, H]
    vef = vef * nm[:, :, None].astype(vef.dtype)

    # real + virtual coordinate updates
    x = x + agg
    phi_xv = _coord_head(vef, w["xv_k0"], w["xv_b0"], w["xv_k1"], dt)
    x = x + jnp.mean(-vcd * jnp.swapaxes(phi_xv, 1, 2), axis=-1)
    x = x + _mlp2(h, w["v_k0"], w["v_b0"], w["v_k1"], w["v_b1"],
                  dt).astype(jnp.float32) * v
    if cfg.has_gravity:
        x = x + _mlp2(h, w["g_k0"], w["g_b0"], w["g_k1"], w["g_b1"],
                      dt).astype(jnp.float32) * gvec
    x = x * nm

    trans_X = vcd * jnp.swapaxes(
        _coord_head(vef, w["X_k0"], w["X_b0"], w["X_k1"], dt), 1, 2)
    X = X + jnp.sum(trans_X * nm[:, :, None], axis=0) / cnt_n  # [3, C]

    # node feature update
    agg_v = jnp.mean(vef, axis=1)                             # [N, H]
    n_in = [h, agg_h, agg_v]
    if cfg.node_attr_nf:
        n_in.append(nattr)
    out = _mlp2(jnp.concatenate([a.astype(jnp.float32) for a in n_in],
                                axis=-1),
                w["h_k0"], w["h_b0"], w["h_k1"], w["h_b1"], dt)
    h = (h + out) if cfg.residual else out * jnp.ones_like(h)
    h = h * nm

    # virtual feature update
    agg_Hv = jnp.sum(vef.astype(jnp.float32) * nm[:, :, None],
                     axis=0) / cnt_n                          # [C, H]
    hv_in = jnp.concatenate([Hv.T, agg_Hv], axis=-1)          # [C, 2H]
    out_v = _mlp2(hv_in, w["hv_k0"], w["hv_b0"], w["hv_k1"], w["hv_b1"],
                  dt).T                                       # [H, C]
    Hv = (Hv + out_v) if cfg.residual else out_v * jnp.ones_like(Hv)
    return h, x, X, Hv


def _inwindow_acc(xp, pk, row_t, col_l, kblk, scal, ew: EdgeWeights,
                  T, H, nb, nt, dtype):
    """In-window blocked edge pass on values — bitwise the fused_edge_layer
    forward (_fwd_kernel's tile loop with the grid unrolled in Python):
    returns the packed [N, H+8] f32 aggregate [trans_hi, trans_lo, count,
    pad, ef_sum]."""
    accs = []
    for b in range(nb):
        s = min(max(b - 1, 0), max(nb - 3, 0))
        xo = xp[b * T:(b + 1) * T]
        xw = tuple(xp[(s + k) * T:(s + k + 1) * T] for k in range(3))
        po = pk[b * T:(b + 1) * T]
        pw = tuple(pk[(s + k) * T:(s + k + 1) * T] for k in range(3))
        acc = jnp.zeros((T, H + 8), jnp.float32)
        for j in range(nt):
            t = b * nt + j
            rt = row_t[t][None, :]                            # [1, T]
            e0 = t * T
            mask, cd, _, _, _, _, ef, _, _, g = _edge_fwd_math(
                xo, xw, po, pw, rt, col_l[e0:e0 + T], kblk[e0:e0 + T],
                scal[e0:e0 + T], ew, T, H, dtype)
            trans = cd[:, 0:3] * g
            hi, lo = _split2(trans)
            data = jnp.concatenate(
                [hi, lo, mask.astype(jnp.bfloat16),
                 jnp.zeros((T, 1), jnp.bfloat16),
                 (ef * mask.astype(ef.dtype)).astype(jnp.bfloat16)], axis=1)
            acc = acc + _onehot_agg(rt, data)
        accs.append(acc)
    return jnp.concatenate(accs, axis=0)                      # [N, H+8]


def _onehot_rows(idx, n):
    """Exact f32 one-hot [R, n] of node indices — the in-kernel gather /
    segment-dot lowering for the remote tail (no scatter unit on TPU; f32
    0/1 entries keep gathers exact and sums f32-accumulated)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], n), 1)
    return (cols == idx[:, None]).astype(jnp.float32)


def _segdot(G, val):
    """G^T @ val without materializing the transpose: [R,N]^T [R,F] -> [N,F]."""
    return jax.lax.dot_general(G, val, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------- the kernel

def _stack_kernel(*refs, cfg: StackConfig, names, nb, nt):
    """One grid step == one EGCL layer. See module docstring for the plan."""
    n_in = len(names)
    d = dict(zip(names, refs[:n_in]))
    (out_h, out_x, out_X, out_Hv,
     ck_h, ck_x, ck_X, ck_Hv) = refs[n_in:n_in + 8]
    (hA, hB, xA, xB, XA, XB, HvA, HvB) = refs[n_in + 8:]

    l = pl.program_id(0)
    T, H, C = cfg.block, cfg.hidden, cfg.channels
    dt = None if cfg.dtype_name == "f32" else jnp.bfloat16
    dtype = jnp.float32 if dt is None else jnp.bfloat16
    N = d["h0"].shape[0]

    @pl.when(l == 0)
    def _():
        hA[...] = d["h0"][...]
        xA[...] = d["xp0"][...]
        XA[...] = d["X0"][...]
        HvA[...] = d["Hv0"][...]

    even = (l % 2) == 0
    h = jnp.where(even, hA[...], hB[...])
    xp = jnp.where(even, xA[...], xB[...])
    Xp = jnp.where(even, XA[...], XB[...])
    Hv = jnp.where(even, HvA[...], HvB[...])

    # checkpoint the layer-INPUT state (l-indexed blocks — the bwd anchors)
    ck_h[...] = h[None]
    ck_x[...] = xp[None]
    ck_X[...] = Xp[None]
    ck_Hv[...] = Hv[None]

    # this layer's weight slices ([1, a, b] blocks -> [a, b])
    w = {k: d["w:" + k][...][0] for k in stack_weight_shapes(cfg)}

    x3 = xp[:, 0:3]
    X = Xp[0:3, :]
    nm = d["nm"][...]
    v3 = d["vp"][...][:, 0:3]

    # hoisted phi_e node products (HoistedEdgeMLP algebra)
    c = _cast(dt)
    hr = _mm(c(h), c(w["e_w1"][:H]))
    hc = _mm(c(h), c(w["e_w1"][H:2 * H]))
    pk = jnp.concatenate([hr, hc], axis=1).astype(dtype)

    # in-window blocked edges — the shared tile math, edge stream VMEM-hot
    ew = EdgeWeights(ws=w["e_w1"][2 * H:], b1=w["e_b1"], w2=w["e_w2"],
                     b2=w["e_b2"], w3=w["e_w3"], b3=w["e_b3"], w4=w["e_w4"])
    acc = _inwindow_acc(xp, pk, d["row_t"][...], d["col_l"][...],
                        d["kblk"][...], d["scal"][...], ew, T, H, nb, nt,
                        dtype)
    trans_sum = acc[:, 0:3] + acc[:, 3:6]
    count = acc[:, 6:7]
    ef_sum = acc[:, 8:]

    # remote tail: exact one-hot gathers + f32 segment dots
    rr = d["rr"][...][:, 0]
    rc = d["rc"][...][:, 0]
    rsc = d["rsc"][...]
    Gr = _onehot_rows(rr, N)
    Gc = _onehot_rows(rc, N)
    x_r, x_c = Gr @ x3, Gc @ x3
    hr_r = (Gr @ hr.astype(jnp.float32)).astype(hr.dtype)
    hc_c = (Gc @ hc.astype(jnp.float32)).astype(hc.dtype)
    rm = rsc[:, 2:3]
    cd_r, g_r, ef_r = _remote_edge_math(x_r, x_c, hr_r, hc_c, rsc[:, 0:2],
                                        rm, w, H, dt)
    trans_sum = trans_sum + _segdot(Gr, cd_r * g_r)
    count = count + _segdot(Gr, rm)
    ef_sum = ef_sum + _segdot(Gr, ef_r.astype(jnp.float32) * rm)

    cnt = jnp.maximum(count, 1.0)
    agg = trans_sum / cnt if cfg.coords_mean else trans_sum
    agg_h = ef_sum / cnt

    gvec = d["gvec"][...][0, 0:3] if cfg.has_gravity else None
    nattr = d["nattr"][...] if cfg.node_attr_nf else None
    h2, x2, X2, Hv2 = _virtual_and_update(h, x3, v3, X, Hv, agg, agg_h, nm,
                                          nattr, gvec, w, cfg)

    xp2 = jnp.concatenate([x2, jnp.zeros((N, XL - 3), jnp.float32)], axis=1)
    Xp2 = jnp.concatenate([X2, jnp.zeros((XL - 3, C), jnp.float32)], axis=0)

    # swap: write the updated state into the OTHER buffer half
    @pl.when(even)
    def _():
        hB[...] = h2
        xB[...] = xp2
        XB[...] = Xp2
        HvB[...] = Hv2

    @pl.when(jnp.logical_not(even))
    def _():
        hA[...] = h2
        xA[...] = xp2
        XA[...] = Xp2
        HvA[...] = Hv2

    # finals (constant-index outputs: the last grid step's write survives)
    out_h[...] = h2
    out_x[...] = xp2
    out_X[...] = Xp2
    out_Hv[...] = Hv2


def _stack_fwd_impl(cfg: StackConfig, h0, x0, v, X0, Hv0, node_mask,
                    node_attr, gravity, edge_arrs, remote_arrs, wstack):
    """Build operands, run the megakernel, unpack results + checkpoints."""
    row_t, col_l, kblk, scal = edge_arrs
    rr, rc, rattr, rmask = remote_arrs
    N, H = h0.shape
    C = cfg.channels
    T = cfg.block
    L = cfg.n_layers
    nb = _check_grid(N, T)
    nt = row_t.shape[0] // nb
    E = col_l.shape[0]
    R = rr.shape[0]
    if L < 1:
        raise ValueError(f"fused_egnn_stack needs n_layers >= 1 (got {L})")
    check_stack_vmem(cfg, n_nodes=N, n_edges=E, remote_pad=R)

    xp0 = jnp.zeros((N, XL), jnp.float32).at[:, 0:3].set(x0)
    vp = jnp.zeros((N, XL), jnp.float32).at[:, 0:3].set(
        v.astype(jnp.float32))
    X0p = jnp.zeros((XL, C), jnp.float32).at[0:3, :].set(X0)
    nm = node_mask.astype(jnp.float32)[:, None]
    rsc = jnp.concatenate(
        [rattr[:, :2].astype(jnp.float32),
         rmask.astype(jnp.float32)[:, None],
         jnp.zeros((R, XL - 3), jnp.float32)], axis=1)

    wkeys = sorted(stack_weight_shapes(cfg))
    names = ["row_t", "col_l", "kblk", "scal", "xp0", "h0", "vp", "X0",
             "Hv0", "nm"]
    operands = [row_t, col_l, kblk, scal, xp0, h0.astype(jnp.float32), vp,
                X0p, Hv0.astype(jnp.float32), nm]
    if cfg.node_attr_nf:
        names.append("nattr")
        operands.append(node_attr.astype(jnp.float32))
    if cfg.has_gravity:
        names.append("gvec")
        operands.append(jnp.zeros((1, XL), jnp.float32).at[0, 0:3].set(
            gravity.astype(jnp.float32)))
    names += ["rr", "rc", "rsc"] + ["w:" + k for k in wkeys]
    operands += [rr.astype(jnp.int32)[:, None], rc.astype(jnp.int32)[:, None],
                 rsc] + [wstack[k] for k in wkeys]

    def const(shape):
        return pl.BlockSpec(shape, lambda l: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    def per_layer(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda l: (l,) + (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    in_specs = [const(op.shape) for op in operands[:len(names) - len(wkeys)]]
    in_specs += [per_layer(stack_weight_shapes(cfg)[k]) for k in wkeys]

    out_specs = (const((N, H)), const((N, XL)), const((XL, C)),
                 const((H, C)),
                 per_layer((N, H)), per_layer((N, XL)), per_layer((XL, C)),
                 per_layer((H, C)))
    out_shape = (jax.ShapeDtypeStruct((N, H), jnp.float32),
                 jax.ShapeDtypeStruct((N, XL), jnp.float32),
                 jax.ShapeDtypeStruct((XL, C), jnp.float32),
                 jax.ShapeDtypeStruct((H, C), jnp.float32),
                 jax.ShapeDtypeStruct((L, N, H), jnp.float32),
                 jax.ShapeDtypeStruct((L, N, XL), jnp.float32),
                 jax.ShapeDtypeStruct((L, XL, C), jnp.float32),
                 jax.ShapeDtypeStruct((L, H, C), jnp.float32))
    scratch = [pltpu.VMEM((N, H), jnp.float32),
               pltpu.VMEM((N, H), jnp.float32),
               pltpu.VMEM((N, XL), jnp.float32),
               pltpu.VMEM((N, XL), jnp.float32),
               pltpu.VMEM((XL, C), jnp.float32),
               pltpu.VMEM((XL, C), jnp.float32),
               pltpu.VMEM((H, C), jnp.float32),
               pltpu.VMEM((H, C), jnp.float32)]

    outs = pl.pallas_call(
        functools.partial(_stack_kernel, cfg=cfg, names=tuple(names),
                          nb=nb, nt=nt),
        grid=(L,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=runtime.use_interpret(),
    )(*operands)
    (oh, ox, oX, oHv, ckh, ckx, ckX, ckHv) = outs
    out = (oh, ox[:, 0:3], oX[0:3, :], oHv)
    cks = (ckh, ckx[:, :, 0:3], ckX[:, 0:3, :], ckHv)
    return out, cks


# ------------------------------------------------- backward layer reference

def _layer_ref(cfg: StackConfig, h, x, v, X, Hv, node_mask, node_attr,
               gravity, edge_arrs, remote_arrs, w):
    """Pure-JAX single-layer reference — the backward rematerializes through
    this. Its in-window edge pass IS `fused_edge_layer`, whose Pallas
    backward recomputes the per-edge activations from the same VMEM windows
    (remat at tile scale), so differentiating this function never saves an
    O(E)-wide residual."""
    H = cfg.hidden
    dt = None if cfg.dtype_name == "f32" else jnp.bfloat16
    c = _cast(dt)
    row_t, col_l, kblk, scal = edge_arrs
    rr, rc, rattr, rmask = remote_arrs
    N = x.shape[0]

    w1 = w["e_w1"]
    hr = _mm(c(h), c(w1[:H]))
    hc = _mm(c(h), c(w1[H:2 * H]))
    ew = EdgeWeights(ws=w1[2 * H:], b1=w["e_b1"], w2=w["e_w2"], b2=w["e_b2"],
                     w3=w["e_w3"], b3=w["e_b3"], w4=w["e_w4"])
    trans_sum, count, ef_sum = fused_edge_layer(
        x, hr, hc, row_t, col_l, kblk, scal, ew, cfg.block, cfg.dtype_name)

    rm = rmask[:, None]
    x_r, x_c = jnp.take(x, rr, axis=0), jnp.take(x, rc, axis=0)
    hr_r, hc_c = jnp.take(hr, rr, axis=0), jnp.take(hc, rc, axis=0)
    cd_r, g_r, ef_r = _remote_edge_math(x_r, x_c, hr_r, hc_c, rattr, rm, w,
                                        H, dt)
    trans_sum = trans_sum + jax.ops.segment_sum(cd_r * g_r, rr,
                                                num_segments=N)
    count = count + jax.ops.segment_sum(rmask, rr, num_segments=N)
    ef_sum = ef_sum + jax.ops.segment_sum(ef_r.astype(jnp.float32) * rm, rr,
                                          num_segments=N)

    cnt = jnp.maximum(count, 1.0)[:, None]
    agg = trans_sum / cnt if cfg.coords_mean else trans_sum
    agg_h = ef_sum / cnt
    nm = node_mask.astype(jnp.float32)[:, None]
    return _virtual_and_update(h, x, v, X, Hv, agg, agg_h, nm, node_attr,
                               gravity, w, cfg)


# -------------------------------------------------------------- custom_vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_egnn_stack(cfg: StackConfig, h0, x0, v, X0, Hv0, node_mask,
                     node_attr, gravity, edge_arrs, remote_arrs, wstack):
    """Run all L EGCL layers in one Pallas megakernel (single graph).

    Args:
      cfg        StackConfig (static)
      h0         [N, H] f32 embedded node features
      x0         [N, 3] f32 coordinates (Morton-ordered, block-padded)
      v          [N, 3] f32 velocities
      X0         [3, C] f32 initial virtual coordinates
      Hv0        [H, C] f32 initial virtual features
      node_mask  [N] f32
      node_attr  [N, A] f32 or None (cfg.node_attr_nf == 0)
      gravity    [3] f32 or None (cfg.has_gravity == False)
      edge_arrs  build_edge_blocks output (row_t, col_l, kblk, scal)
      remote_arrs (rr [R] i32, rc [R] i32, rattr [R, >=2] f32, rmask [R] f32)
      wstack     {key: [L, a, b]} stacked per-layer weights
                 (stack_weight_shapes layout — a runtime view of the same
                 param tree as the per-layer fused path)

    Returns (h [N,H], x [N,3], X [3,C], Hv [H,C]) after L layers.

    Cotangent contract: grads flow to h0/x0/v/X0/Hv0 and wstack; the
    batch-borne constants (masks, edge/remote arrays, node_attr, gravity)
    get zero cotangents — the `_fel_bwd` convention.
    """
    out, _ = _stack_fwd_impl(cfg, h0, x0, v, X0, Hv0, node_mask, node_attr,
                             gravity, edge_arrs, remote_arrs, wstack)
    return out


def _stack_fwd(cfg, h0, x0, v, X0, Hv0, node_mask, node_attr, gravity,
               edge_arrs, remote_arrs, wstack):
    out, cks = _stack_fwd_impl(cfg, h0, x0, v, X0, Hv0, node_mask, node_attr,
                               gravity, edge_arrs, remote_arrs, wstack)
    res = (cks, v, node_mask, node_attr, gravity, edge_arrs, remote_arrs,
           wstack)
    return out, res


def _stack_bwd(cfg, res, ct):
    (cks, v, node_mask, node_attr, gravity, edge_arrs, remote_arrs,
     wstack) = res
    ck_h, ck_x, ck_X, ck_Hv = cks
    dh, dx, dX, dHv = ct
    dv = jnp.zeros_like(v)
    dw_layers = []
    for l in reversed(range(cfg.n_layers)):
        wl = {k: wstack[k][l] for k in wstack}

        def f(h_, x_, v_, X_, Hv_, w_):
            return _layer_ref(cfg, h_, x_, v_, X_, Hv_, node_mask, node_attr,
                              gravity, edge_arrs, remote_arrs, w_)

        _, vjp = jax.vjp(f, ck_h[l], ck_x[l], v, ck_X[l], ck_Hv[l], wl)
        dh, dx, dv_l, dX, dHv, dwl = vjp((dh, dx, dX, dHv))
        dv = dv + dv_l
        dw_layers.append(dwl)
    dws = {k: jnp.stack([dwl[k] for dwl in reversed(dw_layers)])
           for k in wstack}
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    return (dh, dx, dv, dX, dHv, zeros(node_mask), zeros(node_attr),
            zeros(gravity), zeros(edge_arrs), zeros(remote_arrs), dws)


fused_egnn_stack.defvjp(_stack_fwd, _stack_bwd)
