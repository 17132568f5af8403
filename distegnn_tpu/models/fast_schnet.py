"""FastSchNet — the FastEGNN virtual-node skeleton whose real-node coordinate
update is a 1-interaction SchNet, TPU-native.

Re-design of reference models/FastSchNet.py (SchNet_GCL_vel + FastSchNet,
256 LoC): per layer, (a) real coordinates move by the SchNet equivariant
update (embedding bypassed: the layer feeds its own hidden features,
FastSchNet.py:121-126 with embedding=False), (b) the virtual-node machinery is
exactly FastEGNN's (phi_ev / phi_xv / phi_X / phi_h / phi_hv) minus the real
phi_x/phi_v paths (SchNet provides those), (c) all global means are LOCAL —
the reference model carries no distributed code (SURVEY.md §2.4). The
``axis_name`` hook still generalizes it to the mesh (a capability the
reference lacks); default None preserves reference behavior.

The reference's 1-interaction SchNet sublayer also allocates a CFConv feature
path whose output is discarded (SchNet.forward updates h after pos and
FastSchNet keeps only pos, FastSchNet.py:121-126) — dead weights (the reason
its DDP runs need find_unused_parameters=True); not replicated here. Its
unused ``W`` parameter (FastSchNet.py:219) is likewise dropped.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from distegnn_tpu.models.common import (
    MLP, CoordMLP, HoistedEdgeMLP, HoistedGate, TorchDense,
)
from distegnn_tpu.ops.blocked import EdgeOps, blocked_slot_inv_deg
from distegnn_tpu.models.schnet import GaussianSmearing
from distegnn_tpu.ops.graph import GraphBatch
from distegnn_tpu.parallel.collectives import global_node_mean


class SchNetGCLVel(nn.Module):
    """One FastSchNet layer (reference SchNet_GCL_vel, FastSchNet.py:8-204)."""

    hidden_nf: int
    virtual_channels: int
    node_attr_nf: int = 0
    edge_attr_nf: int = 0
    cutoff: float = 10.0
    num_gaussians: int = 50
    residual: bool = True
    attention: bool = False
    normalize: bool = False
    tanh: bool = False
    has_gravity: bool = False
    axis_name: Optional[str] = None
    epsilon: float = 1e-8
    hoist_edge_mlp: bool = True  # phi_e + gate first Dense on the node axis
    seg_impl: str = "scatter"
    # one packed aggregation pass for the layer's two row aggregations
    # (coordinate update + edge features; EdgeOps.agg_rows_pair — the same
    # fusion FastEGNN applies)
    fuse_agg: bool = True
    agg_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, h, x, v, X, Hv, g: GraphBatch, gravity=None,
                 slot=None, inv_deg=None, oh=None):
        H, C = self.hidden_nf, self.virtual_channels
        node_mask, edge_mask = g.node_mask, g.edge_mask
        nm = node_mask[..., None]
        B, N = h.shape[0], h.shape[1]
        ops = EdgeOps(g, slot, inv_deg, oh, seg_impl=self.seg_impl)

        # normalize is accepted for config parity but is a no-op here AS IN THE
        # REFERENCE: its coord2radial normalizes coord_diff, which FastSchNet
        # then never consumes (only radial and the SchNet sublayer's raw
        # positions are used, FastSchNet.py:169-186)
        vcd = X[:, None, :, :] - x[..., None]                            # [B, N, 3, C]
        virtual_radial = jnp.linalg.norm(vcd, axis=2, keepdims=True)

        # real edge messages phi_e (FastSchNet.py:102-108); hoisted mode never
        # gathers raw h at all — phi_e AND the SchNet gate below move node-side
        # matmul products instead, and raw_diff rides phi_e's gathers
        edge_attr = g.edge_attr if self.edge_attr_nf else None
        if self.hoist_edge_mlp:
            edge_feat, raw_diff, radial = HoistedEdgeMLP(
                H, 1 + self.edge_attr_nf, name="phi_e")(h, x, edge_attr, ops)
        else:
            raw_diff = ops.gather_rows(x) - ops.gather_cols(x)
            radial = jnp.sum(raw_diff**2, axis=-1, keepdims=True)
            e_scalars = (radial if edge_attr is None
                         else jnp.concatenate([radial, edge_attr], axis=-1))
            h_row, h_col = ops.gather_rows(h), ops.gather_cols(h)
            edge_feat = MLP([H, H], act_last=True, name="phi_e")(
                jnp.concatenate([h_row, h_col, e_scalars], axis=-1))
        if self.attention:
            edge_feat = edge_feat * jax.nn.sigmoid(TorchDense(1, name="att")(edge_feat))
        edge_feat = edge_feat * edge_mask[..., None]

        # LOCAL coordinate mean + virtual Gram (FastSchNet.py:190-193)
        coord_mean = global_node_mean(x, node_mask, axis_name=None)
        Xc = X - coord_mean[:, :, None]
        m_X = jnp.einsum("bdc,bde->bce", Xc, Xc)

        v_in = jnp.concatenate(
            [
                jnp.broadcast_to(h[:, :, None, :], (B, N, C, H)),
                jnp.broadcast_to(jnp.swapaxes(Hv, 1, 2)[:, None, :, :], (B, N, C, H)),
                jnp.swapaxes(virtual_radial, 2, 3),
                jnp.broadcast_to(m_X[:, None, :, :], (B, N, C, C)),
            ],
            axis=-1,
        )
        vef = MLP([H, H], act_last=True, name="phi_ev")(v_in)
        if self.attention:
            vef = vef * jax.nn.sigmoid(TorchDense(1, name="att_v")(vef))
        vef = vef * node_mask[:, :, None, None]

        # real coordinate update = 1-interaction SchNet (coord_model_by_schnet,
        # FastSchNet.py:121-126 -> SchNet.py:191-198): RAW interatomic
        # distances and directions regardless of normalize — the reference's
        # SchNet sublayer always works on bare positions
        edge_weight = jnp.linalg.norm(raw_diff + 1e-30, axis=-1)
        gauss = GaussianSmearing(0.0, self.cutoff, self.num_gaussians, name="smearing")(edge_weight)
        if self.hoist_edge_mlp:
            gate = HoistedGate(1, self.num_gaussians, H,
                               name="schnet_coord_update")(h, gauss, ops)
        else:
            gate = TorchDense(1, name="schnet_coord_update")(
                jnp.concatenate([gauss, h_row, h_col], axis=-1))
        if self.fuse_agg:
            agg_x, agg_h_f = ops.agg_rows_pair(
                raw_diff * gate, edge_feat, a_mean=True,
                agg_dtype=self.agg_dtype)
        else:
            agg_x, agg_h_f = ops.agg_rows_mean(raw_diff * gate), None
        x = x + agg_x

        # virtual pull on real nodes (phi_xv / coord_mlp_r_virtual)
        phi_xv = CoordMLP(H, tanh=self.tanh, name="phi_xv")(vef)
        x = x + jnp.mean(-vcd * jnp.swapaxes(phi_xv, 2, 3), axis=-1)
        if self.has_gravity:
            x = x + MLP([H, 1], name="phi_g")(h) * gravity
        x = x * nm

        # virtual coordinate update (phi_X / coord_mlp_v_virtual)
        trans_X = vcd * jnp.swapaxes(CoordMLP(H, tanh=self.tanh, name="phi_X")(vef), 2, 3)
        X = X + global_node_mean(trans_X, node_mask, self.axis_name)

        # feature updates phi_h / phi_hv (FastSchNet.py:140-166)
        agg_h = agg_h_f if agg_h_f is not None else ops.agg_rows_mean(edge_feat)
        agg_v = jnp.mean(vef, axis=2)
        n_in = [h, agg_h, agg_v]
        if self.node_attr_nf:
            n_in.append(g.node_attr)
        out = MLP([H, H], name="phi_h")(jnp.concatenate(n_in, axis=-1))
        h = ((h + out) if self.residual else out) * nm

        agg_Hv = global_node_mean(vef, node_mask, self.axis_name)        # [B, C, H]
        hv_in = jnp.concatenate([jnp.swapaxes(Hv, 1, 2), agg_Hv], axis=-1)
        out_v = jnp.swapaxes(MLP([H, H], name="phi_hv")(hv_in), 1, 2)
        Hv = (Hv + out_v) if self.residual else out_v

        return h, x, Hv, X


class FastSchNet(nn.Module):
    """FastSchNet wrapper (reference FastSchNet.py:207-238)."""

    node_feat_nf: int
    node_attr_nf: int = 0
    edge_attr_nf: int = 0
    hidden_nf: int = 64
    virtual_channels: int = 3
    n_layers: int = 4
    cutoff: float = 10.0
    residual: bool = True
    attention: bool = False
    normalize: bool = False
    tanh: bool = False
    gravity: Optional[Tuple[float, float, float]] = None
    axis_name: Optional[str] = None
    blocked_impl: str = "einsum"  # blocked-layout edge-op lowering ('pallas'|'einsum')
    hoist_edge_mlp: bool = True   # phi_e + gate first Dense on the node axis
    segment_impl: str = "scatter"  # plain-layout lowering ('scatter'|'cumsum'|'ell')
    fuse_agg: bool = True          # packed per-layer aggregation (SchNetGCLVel)
    agg_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, g: GraphBatch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        assert self.virtual_channels > 0, "virtual_channels must be > 0"
        B = g.batch_size
        H, C = self.hidden_nf, self.virtual_channels

        Hv0 = self.param("virtual_node_feat", nn.initializers.normal(1.0), (1, H, C))
        Hv = jnp.broadcast_to(Hv0, (B, H, C))
        X = jnp.repeat(g.loc_mean[:, :, None], C, axis=2)

        h = TorchDense(H, name="embedding_in")(g.node_feat)
        x, v = g.loc, g.vel
        gravity = jnp.asarray(self.gravity, jnp.float32) if self.gravity is not None else None

        slot, inv_deg, oh = blocked_slot_inv_deg(g, self.blocked_impl)

        for i in range(self.n_layers):
            h, x, Hv, X = SchNetGCLVel(
                hidden_nf=H, virtual_channels=C,
                node_attr_nf=self.node_attr_nf, edge_attr_nf=self.edge_attr_nf,
                cutoff=self.cutoff, residual=self.residual,
                attention=self.attention, normalize=self.normalize,
                tanh=self.tanh, has_gravity=self.gravity is not None,
                axis_name=self.axis_name, hoist_edge_mlp=self.hoist_edge_mlp,
                seg_impl=self.segment_impl,
                fuse_agg=self.fuse_agg,
                agg_dtype=self.agg_dtype,
                name=f"gcl_{i}",
            )(h, x, v, X, Hv, g, gravity=gravity, slot=slot, inv_deg=inv_deg,
              oh=oh)
        return x, X
