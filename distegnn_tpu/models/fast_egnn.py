"""FastEGNN / DistEGNN — the paper's core model, TPU-native.

Re-design of reference models/FastEGNN.py (E_GCL_vel + FastEGNN, 336 LoC):
EGNN with C learnable *virtual nodes* per graph; in distributed (DistEGNN)
mode each device owns one spatial partition of the graph and the virtual-node
state is the only cross-partition channel — exactly three global weighted
means per layer (reference models/FastEGNN.py:258-261, 191-200, 220-234),
realized here as `psum` over the mesh 'graph' axis instead of NCCL allreduces.

Layout: dense batched GraphBatch ([B,N,...] + masks, see ops/graph.py). Every
MLP application is one large matmul over [B*N(*C), F] — MXU-shaped — and the
whole L-layer forward traces into a single XLA program with no host sync.

Shape legend: B graphs, N padded nodes (per partition), E padded edges,
H hidden, C virtual channels.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from distegnn_tpu import obs
from distegnn_tpu.models.common import (
    MLP, CoordMLP, HoistedEdgeMLP, TorchDense, resolve_dtype,
)
from distegnn_tpu.ops.blocked import (REMAT_KEPT, EdgeOps,
                                      blocked_slot_inv_deg)
from distegnn_tpu.ops.graph import GraphBatch
from distegnn_tpu.ops.segment import masked_sum
from distegnn_tpu.parallel.collectives import (
    global_node_mean, tp_copy, tp_reduce,
)


class EGCLVel(nn.Module):
    """E(n)-equivariant conv layer with velocity + virtual-node channels.

    Mirrors reference E_GCL_vel (models/FastEGNN.py:46-276): MLPs phi_e,
    phi_ev, phi_x, phi_xv, phi_X, phi_v, phi_h, phi_hv (+ optional attention
    gates and gravity head), with the three distributed global means marked.
    """

    hidden_nf: int
    virtual_channels: int
    node_attr_nf: int = 0
    edge_attr_nf: int = 0
    residual: bool = True
    attention: bool = False
    normalize: bool = False
    coords_agg: str = "mean"
    tanh: bool = False
    has_gravity: bool = False
    axis_name: Optional[str] = None  # mesh axis of graph partitions ('graph') or None
    # mesh axis of the hidden-dim shards ('tensor') or None. When set, each
    # chip computes a 1/T hidden slice of phi_e/phi_x/phi_h per edge/node
    # block, with exactly one collective per MLP at the layer boundary:
    # phi_e — node-level tiled all-gather of the hoisted h@W products;
    # phi_x — partial per-edge scalars ride coord_diff and the segment sum
    #         to the node axis, then ONE psum of the [B,N,3] aggregate;
    # phi_h — Megatron column/row split closed by ONE psum of [B,N,H].
    # Virtual-node MLPs (C channels, tiny) stay replicated. Params stay FULL
    # on every chip — slicing happens at compute time — so the param tree,
    # checkpoints, and the (data, graph) gradient psum are unchanged.
    tensor_axis: Optional[str] = None
    epsilon: float = 1e-8
    # compute dtype of the invariant-message MLPs ('bf16' or None=f32). All
    # GEOMETRY (coord_diff, radial, coordinate updates, aggregations) stays
    # f32, so equivariance is exact at math level — bf16 only widens noise in
    # invariant channels. See tests/test_equivariance.py::test_bf16.
    compute_dtype: Optional[str] = None
    # evaluate phi_e's first Dense on the node axis (HoistedEdgeMLP): same
    # math, E/N x fewer matmul rows, no [E, 2H+S] concat. False restores the
    # reference-shaped concat MLP (different param tree — not ckpt-compatible)
    hoist_edge_mlp: bool = True
    seg_impl: str = "scatter"  # plain-layout aggregation lowering ('scatter'|'cumsum'|'ell')
    # one packed aggregation pass per layer (translations + edge features +
    # count ride a single segment sum — EdgeOps.agg_rows_pair) instead of
    # two aggregations and a count. Same math; accumulation is ALWAYS f32 in
    # the packed pass, so under compute_dtype=bf16 it is slightly MORE
    # precise than the legacy two-call path (whose bf16 edge_feat
    # aggregation accumulated in bf16) — not bit-identical for bf16 models;
    # fuse_agg=False restores the legacy numerics exactly.
    fuse_agg: bool = True
    # stream dtype of the packed aggregation ('bf16' halves the [E,3+H] read
    # bytes; accumulation stays f32). bf16 ROUNDS THE COORDINATE
    # TRANSLATIONS — equivariance becomes approximate at bf16 noise level.
    # Opt-in (its speed: not measured on this machine), None = f32.
    agg_dtype: Optional[str] = None

    @nn.compact
    def __call__(
        self,
        h: jnp.ndarray,          # [B, N, H] node features
        x: jnp.ndarray,          # [B, N, 3] coordinates
        v: jnp.ndarray,          # [B, N, 3] velocities
        X: jnp.ndarray,          # [B, 3, C] virtual coordinates (global objects)
        Hv: jnp.ndarray,         # [B, H, C] virtual features (global objects)
        g: GraphBatch,
        gravity: Optional[jnp.ndarray] = None,  # [3]
        slot: Optional[jnp.ndarray] = None,     # [B, E] blocked-layout slots
        inv_deg: Optional[jnp.ndarray] = None,  # [B, N, 1] 1/max(in-degree, 1)
        oh: Optional[jnp.ndarray] = None,       # [B, nb, epb, block] einsum incidence
        # tiled serving (serve/tiled.py): the layer runs over ONE tile of a
        # larger scene. tile_coord_mean is the precomputed SCENE-global
        # coordinate mean (replaces psum #1 — a tile-local mean would be
        # wrong); tile_partials=True returns the tile's masked-sum
        # contributions to psums #2/#3 instead of applying them (the
        # executor closes X/Hv once per layer via tiled_virtual_update).
        # Correct because every cross-node quantity here (vcd, m_X, vef,
        # trans_X) is computed from LAYER-INPUT X/Hv/x.
        tile_coord_mean: Optional[jnp.ndarray] = None,  # [B, 3]
        tile_partials: bool = False,
    ) -> Tuple[jnp.ndarray, ...]:
        H, C = self.hidden_nf, self.virtual_channels
        dt = resolve_dtype(self.compute_dtype)
        node_mask = g.node_mask                      # [B, N]
        edge_mask = g.edge_mask                      # [B, E]
        nm = node_mask[..., None]
        ops = EdgeOps(g, slot, inv_deg, oh, seg_impl=self.seg_impl)

        if self.coords_agg not in ("sum", "mean"):
            raise ValueError(f"Wrong coords_agg parameter {self.coords_agg!r}")

        # --- real edge messages phi_e (:144-150) and the real-edge
        # geometry (reference coord2radial, :237-246)
        if not self.hoist_edge_mlp and self.tensor_axis is not None:
            raise ValueError(
                "tensor parallelism requires hoist_edge_mlp=True "
                "(phi_e's collective is the node-level gather of the "
                "hoisted products; the concat-shaped phi_e would "
                "need a per-edge gather)")
        with jax.named_scope("edge_mlp"):
            if self.hoist_edge_mlp:
                # coord_diff [B, E, 3] and radial [B, E, 1] come out of the
                # hoisted products' own gathers: one pass per edge end
                edge_feat, coord_diff, radial = HoistedEdgeMLP(
                    H, 1 + self.edge_attr_nf, name="phi_e", dtype=dt,
                    tensor_axis=self.tensor_axis)(
                        h, x, g.edge_attr if self.edge_attr_nf else None, ops)
            else:
                coord_diff = ops.gather_rows(x) - ops.gather_cols(x)
                radial = jnp.sum(coord_diff**2, axis=-1, keepdims=True)
                e_in = [ops.gather_rows(h), ops.gather_cols(h), radial]
                if self.edge_attr_nf:
                    e_in.append(g.edge_attr)
                edge_feat = MLP([H, H], act_last=True, name="phi_e", dtype=dt)(
                    jnp.concatenate(e_in, axis=-1))
            if self.attention:
                gate_e = jax.nn.sigmoid(TorchDense(1, name="att", dtype=dt)(edge_feat))
                edge_feat = edge_feat * gate_e                       # [B, E, H]
            edge_feat = edge_feat * edge_mask[..., None].astype(edge_feat.dtype)
        if self.normalize:
            norm = jax.lax.stop_gradient(jnp.sqrt(radial)) + self.epsilon
            coord_diff = coord_diff / norm

        # --- virtual-edge geometry (:252-253): every node sees all C virtual nodes
        with jax.named_scope("virtual_update"):
            vcd = X[:, None, :, :] - x[..., None]                       # [B, N, 3, C]
            virtual_radial = jnp.linalg.norm(vcd, axis=2, keepdims=True)  # [B, N, 1, C]

            # ---------- psum #1: exact global coordinate mean (:258-261)
            coord_mean = (tile_coord_mean if tile_coord_mean is not None
                          else global_node_mean(x, node_mask, self.axis_name))  # [B, 3]

            # --- invariant virtual mixing m_X: Gram of centered virtual coords (:263-264)
            Xc = X - coord_mean[:, :, None]                              # [B, 3, C]
            m_X = jnp.einsum("bdc,bde->bce", Xc, Xc)                    # [B, C, C]

            # --- virtual edge messages phi_ev (:153-163): [B, N, C, 2H+1+C] -> [B, N, C, H]
            B, N = h.shape[0], h.shape[1]
            v_in = jnp.concatenate(
                [
                    jnp.broadcast_to(h[:, :, None, :], (B, N, C, H)),
                    jnp.broadcast_to(jnp.swapaxes(Hv, 1, 2)[:, None, :, :], (B, N, C, H)),
                    jnp.swapaxes(virtual_radial, 2, 3),                  # [B, N, C, 1]
                    jnp.broadcast_to(m_X[:, None, :, :], (B, N, C, C)),
                ],
                axis=-1,
            )
            vef = MLP([H, H], act_last=True, name="phi_ev", dtype=dt)(v_in)  # [B, N, C, H]
            if self.attention:
                gate = jax.nn.sigmoid(TorchDense(1, name="att_v", dtype=dt)(vef))
                vef = vef * gate
            vef = vef * node_mask[:, :, None, None].astype(vef.dtype)    # zero padded nodes

        # --- real coordinate update (coord_model_vel, :166-188).
        # tensor-parallel phi_x returns a rank-local PARTIAL scalar; it
        # rides coord_diff and the row aggregation (all linear) to the
        # node axis, where ONE psum of [B, N, 3] closes the MLP —
        # per-edge traffic never crosses the tensor axis. coord_diff is
        # tp_copy-wrapped so its cotangent (partial per rank) is summed.
        with jax.named_scope("coord_update"):
            cdm = (tp_copy(coord_diff, self.tensor_axis)
                   if self.tensor_axis is not None else coord_diff)
            trans = cdm * CoordMLP(H, tanh=self.tanh, name="phi_x", dtype=dt,
                                   tensor_axis=self.tensor_axis)(edge_feat)  # [B, E, 3]
        if self.fuse_agg:
            # both per-layer aggregations (+ the count) in ONE pass (blocked
            # layouts keep two calls inside but honor the agg_dtype knob)
            agg, agg_h_f = ops.agg_rows_pair(
                trans, edge_feat, a_mean=(self.coords_agg == "mean"),
                agg_dtype=self.agg_dtype)
        else:
            agg = (ops.agg_rows_sum(trans) if self.coords_agg == "sum"
                   else ops.agg_rows_mean(trans))                    # [B, N, 3]
            agg_h_f = None
        if self.tensor_axis is not None:
            agg = tp_reduce(agg, self.tensor_axis)
        with jax.named_scope("coord_update"):
            x = x + agg

            phi_xv = CoordMLP(H, tanh=self.tanh, name="phi_xv", dtype=dt)(vef)  # [B, N, C, 1]
            trans_v = jnp.mean(-vcd * jnp.swapaxes(phi_xv, 2, 3), axis=-1)  # [B, N, 3]
            x = x + trans_v
            x = x + MLP([H, 1], name="phi_v", dtype=dt)(h).astype(jnp.float32) * v
            if self.has_gravity:
                x = x + MLP([H, 1], name="phi_g", dtype=dt)(h).astype(jnp.float32) * gravity
            x = x * nm  # keep padding clean

        # ---------- psum #2: virtual coordinate update (coord_model_virtual, :191-200)
        with jax.named_scope("virtual_update"):
            trans_X = vcd * jnp.swapaxes(CoordMLP(H, tanh=self.tanh, name="phi_X", dtype=dt)(vef), 2, 3)  # [B, N, 3, C]
            if tile_partials:
                transX_part = masked_sum(trans_X, node_mask, axis=1)     # [B, 3, C]
            else:
                X = X + global_node_mean(trans_X, node_mask, self.axis_name)  # [B, 3, C]

        # --- node feature update (node_model, :203-217)
        agg_h = agg_h_f if agg_h_f is not None else ops.agg_rows_mean(edge_feat)
        with jax.named_scope("node_update"):
            agg_v = jnp.mean(vef, axis=2)                                # [B, N, H]
            n_in = [h, agg_h, agg_v]
            if self.node_attr_nf:
                n_in.append(g.node_attr)
            out = MLP([H, H], name="phi_h", dtype=dt,
                      tensor_axis=self.tensor_axis)(jnp.concatenate(
                          [a.astype(jnp.float32) for a in n_in], axis=-1))
            h = (h + out) if self.residual else out
            h = h * nm

        # ---------- psum #3: virtual feature update (node_model_virtual, :220-234)
        with jax.named_scope("virtual_update"):
            if tile_partials:
                # same numerator/denominator as the two global_node_means above,
                # summed across tiles by the executor — phi_hv is applied there
                # (flax ignores the unused phi_hv subtree in this mode)
                vef_part = masked_sum(vef.astype(jnp.float32), node_mask, axis=1)  # [B, C, H]
                count = jnp.sum(node_mask.astype(jnp.float32), axis=1)   # [B]
                return h, x, transX_part, vef_part, count
            agg_Hv = global_node_mean(vef.astype(jnp.float32), node_mask, self.axis_name)  # [B, C, H]
            hv_in = jnp.concatenate([jnp.swapaxes(Hv, 1, 2), agg_Hv], axis=-1)  # [B, C, 2H]
            out_v = jnp.swapaxes(MLP([H, H], name="phi_hv", dtype=dt)(hv_in), 1, 2)  # [B, H, C]
            Hv = (Hv + out_v) if self.residual else out_v

        return h, x, Hv, X


@jax.named_scope("virtual_update")
def tiled_virtual_update(gcl_params, Hv, X, transX_sum, vef_sum, count, *,
                         residual: bool = True,
                         compute_dtype: Optional[str] = None):
    """Close one tiled layer's virtual-node state from per-tile partials.

    ``transX_sum`` [B,3,C], ``vef_sum`` [B,C,H] and ``count`` [B] are the
    sums of the ``tile_partials=True`` outputs over ALL tiles of the scene;
    dividing by the total count reproduces psums #2/#3 of the monolithic
    EGCLVel exactly (same numerator, same denominator, different summation
    order), then phi_hv — whose subtree EGCLVel skipped in tile mode — is
    applied here, once per layer instead of once per tile."""
    dt = resolve_dtype(compute_dtype)
    H = Hv.shape[1]
    cnt = jnp.maximum(count, 1.0)[:, None, None]
    X = X + transX_sum / cnt
    agg_Hv = vef_sum / cnt                                           # [B, C, H]
    hv_in = jnp.concatenate([jnp.swapaxes(Hv, 1, 2), agg_Hv], axis=-1)
    out_v = jnp.swapaxes(
        MLP([H, H], dtype=dt).apply({"params": gcl_params["phi_hv"]},
                                    hv_in), 1, 2)                    # [B, H, C]
    Hv = (Hv + out_v) if residual else out_v
    return Hv, X


@jax.named_scope("virtual_update")
def reduce_tile_partials(transX_part, vef_part, count, valid, axis_name):
    """Cross-device reduction of one tile ROUND's virtual-node partials
    (serve/mesh_tiled.py): each device of the round holds ONE tile's
    ``tile_partials=True`` outputs; masking by the slot's validity flag
    (ragged rounds carry zero-filled pad slots — their node_mask is already
    all-zero, the flag hard-guarantees it) and psumming over the round's
    device axis gives every device the round's summed partials. The host
    accumulates these round sums across rounds and feeds the layer total to
    :func:`tiled_virtual_update` — the same numerators/denominator as the
    sequential per-tile accumulation, in a different summation order."""
    v = valid.astype(jnp.float32)
    transX = jax.lax.psum(transX_part * v, axis_name)
    vef = jax.lax.psum(vef_part * v, axis_name)
    cnt = jax.lax.psum(count * v, axis_name)
    return transX, vef, cnt


class FastEGNN(nn.Module):
    """FastEGNN / DistEGNN wrapper (reference models/FastEGNN.py:279-307).

    Forward takes a GraphBatch and returns (node_loc_pred [B,N,3],
    virtual_node_loc [B,3,C]). Set ``axis_name='graph'`` under shard_map for
    the distributed (DistEGNN) mode — same weights, same math, exact global
    means via psum.
    """

    node_feat_nf: int
    node_attr_nf: int = 0
    edge_attr_nf: int = 0
    hidden_nf: int = 64
    virtual_channels: int = 3
    n_layers: int = 4
    residual: bool = True
    attention: bool = False
    normalize: bool = False
    tanh: bool = False
    gravity: Optional[Tuple[float, float, float]] = None
    axis_name: Optional[str] = None
    # mesh axis for hidden-dim tensor parallelism ('tensor') or None; see
    # EGCLVel.tensor_axis. hidden_nf must be divisible by the axis size.
    tensor_axis: Optional[str] = None
    compute_dtype: Optional[str] = None  # 'bf16' -> MXU-native message MLPs
    hoist_edge_mlp: bool = True  # phi_e first Dense on the node axis (see EGCLVel)
    # lowering of the blocked-layout edge ops (used only when the batch
    # carries edge_block > 0): 'einsum' = one-hot materialized once per
    # forward, ops are batched dots (default — no Pallas grid overhead);
    # 'pallas' = one-hot built in VMEM per kernel
    blocked_impl: str = "einsum"
    # plain-layout aggregation lowering (ops/segment.py): 'scatter' (XLA
    # sorted scatter, bit-exact), 'cumsum' (scatter-free prefix-sum
    # differences — f32-accumulated, sums carry ~|prefix|*eps rounding), or
    # 'ell' (scatter-free fixed-degree gathers — exact)
    segment_impl: str = "scatter"
    # recompute each layer's activations in the backward pass instead of
    # keeping them in HBM: layer activations are O(E*H) (about 2,100 bytes an
    # edge a layer), so remat trades recompute for the memory that bounds
    # graph size / batch per chip (jax.checkpoint). What the layer's edge
    # passes RETURN is kept (EdgeOps, REMAT_KEPT: the pre-activation sum in
    # the compute dtype, coord_diff and the packed segment sum, 2H + 12
    # bytes an edge at bf16) and only the MLPs run again: a gather or a
    # scatter is the dearest op of the step, an MLP the cheapest
    # (docs/PERFORMANCE.md "Remat memory scaling"; gauge
    # ``model/remat_saved_bytes``)
    remat: bool = False
    fuse_agg: bool = True          # packed per-layer aggregation (EGCLVel)
    agg_dtype: Optional[str] = None  # 'bf16' packed-aggregation stream (EGCLVel)

    @nn.compact
    def __call__(self, g: GraphBatch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        assert self.virtual_channels > 0, "virtual_channels must be > 0"
        B = g.batch_size
        H, C = self.hidden_nf, self.virtual_channels

        # learnable virtual feature seed, shared across graphs (:288, torch.randn init)
        Hv0 = self.param("virtual_node_feat", nn.initializers.normal(1.0), (1, H, C))
        Hv = jnp.broadcast_to(Hv0, (B, H, C))
        # virtual coords start at the global location mean, replicated C times (:300)
        X = jnp.repeat(g.loc_mean[:, :, None], C, axis=2)                # [B, 3, C]

        with jax.named_scope("embed"):
            h = TorchDense(H, name="embedding_in")(g.node_feat)  # f32: one small matmul
        x, v = g.loc, g.vel
        gravity = jnp.asarray(self.gravity, jnp.float32) if self.gravity is not None else None

        # blocked layout: slot ids + in-degree reciprocal (+ einsum incidence),
        # shared by all layers
        slot, inv_deg, oh = blocked_slot_inv_deg(g, self.blocked_impl)

        layer_cls = EGCLVel
        if self.remat:
            layer_cls = nn.remat(EGCLVel, policy=(
                jax.checkpoint_policies.save_only_these_names(*REMAT_KEPT)))
        obs.get_registry().gauge("model/remat_saved_bytes").set(
            self._remat_saved_bytes(g))
        for i in range(self.n_layers):
            h, x, Hv, X = layer_cls(
                hidden_nf=H,
                virtual_channels=C,
                node_attr_nf=self.node_attr_nf,
                edge_attr_nf=self.edge_attr_nf,
                residual=self.residual,
                attention=self.attention,
                normalize=self.normalize,
                tanh=self.tanh,
                has_gravity=self.gravity is not None,
                axis_name=self.axis_name,
                tensor_axis=self.tensor_axis,
                compute_dtype=self.compute_dtype,
                hoist_edge_mlp=self.hoist_edge_mlp,
                seg_impl=self.segment_impl,
                fuse_agg=self.fuse_agg,
                agg_dtype=self.agg_dtype,
                name=f"gcl_{i}",
            )(h, x, v, X, Hv, g, gravity=gravity, slot=slot, inv_deg=inv_deg,
              oh=oh)

        return x, X

    def _remat_saved_bytes(self, g: GraphBatch) -> int:
        """Bytes the rematted layers of THIS trace keep of their edge passes
        (beside their inputs), from the shapes of the named values: a layer
        keeps ``[B, E, H]`` in the compute dtype and ``f32[B, E, 3]`` where
        phi_e is hoisted, and the packed ``f32[B, N, 3+H+1]`` segment sum
        where the aggregation is fused (a blocked batch makes two sums and
        no count column). 0 without remat."""
        if not self.remat:
            return 0
        B, E = g.row.shape
        H = self.hidden_nf
        item = jnp.dtype(resolve_dtype(self.compute_dtype) or jnp.float32).itemsize
        per_edge = (H * item + 12) if self.hoist_edge_mlp else 0
        count_column = 0 if g.edge_block > 0 else 1
        per_node = 4 * (3 + H + count_column) if self.fuse_agg else 0
        return self.n_layers * B * (E * per_edge + g.max_nodes * per_node)
