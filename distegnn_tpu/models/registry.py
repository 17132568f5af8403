"""Model factory — parity with reference get_model (main.py:58-92).

Dispatches model_name -> flax module. Per-dataset SchNet interatomic cutoffs
mirror reference main.py:69-76 (nbody 1, protein 10, Water-3D 0.035).
"""

from __future__ import annotations

from typing import Optional

_SCHNET_CUTOFFS = {"nbody_100": 1.0, "protein": 10.0, "Water-3D": 0.035}


def _import_model(module: str, cls: str):
    """Import a model class, turning a missing module into a clear error
    (some families land in later build stages; see SURVEY.md §7.2)."""
    import importlib

    try:
        mod = importlib.import_module(f"distegnn_tpu.models.{module}")
    except ModuleNotFoundError as e:
        raise NotImplementedError(
            f"model class {cls} (distegnn_tpu.models.{module}) is not implemented yet"
        ) from e
    return getattr(mod, cls)


def get_model(model_config, world_size: int = 1, dataset_name: Optional[str] = None,
              axis_name: Optional[str] = None, tensor_axis: Optional[str] = None):
    """model_config: attribute-style config (see distegnn_tpu.config).

    ``axis_name`` is the mesh axis for distributed (DistEGNN-style) runs; pass
    'graph' when calling under shard_map, None single-device — replaces the
    reference's world_size branches inside the model.

    ``tensor_axis`` is the mesh axis for hidden-dim tensor parallelism
    ('tensor' when parallel.mesh.tensor > 1, else None). Only FastEGNN
    supports it; config validation rejects tensor>1 for other families.
    """
    name = model_config.model_name
    if tensor_axis is not None and name != "FastEGNN":
        raise ValueError(
            f"tensor parallelism (parallel.mesh.tensor > 1) is only "
            f"implemented for FastEGNN, not {name!r}")
    if name == "FastEGNN":
        from distegnn_tpu.models.fast_egnn import FastEGNN
        return FastEGNN(
            node_feat_nf=model_config.node_feat_nf,
            node_attr_nf=model_config.node_attr_nf,
            edge_attr_nf=model_config.edge_attr_nf,
            hidden_nf=model_config.hidden_nf,
            virtual_channels=model_config.virtual_channels,
            n_layers=model_config.n_layers,
            normalize=model_config.normalize,
            gravity=None,
            axis_name=axis_name,
            tensor_axis=tensor_axis,
            compute_dtype=model_config.get("compute_dtype"),
            remat=bool(model_config.get("remat", False)),
            blocked_impl=model_config.get("blocked_impl", "einsum"),
            hoist_edge_mlp=bool(model_config.get("hoist_edge_mlp", True)),
            segment_impl=model_config.get("segment_impl", "scatter"),
            fuse_agg=bool(model_config.get("fuse_agg", True)),
            agg_dtype=model_config.get("agg_dtype"),
        )
    if name == "FastRF":
        FastRF = _import_model("fast_rf", "FastRF")
        return FastRF(
            edge_attr_nf=model_config.edge_attr_nf,
            hidden_nf=model_config.hidden_nf,
            n_layers=model_config.n_layers,
            virtual_channels=model_config.virtual_channels,
            axis_name=axis_name,
            blocked_impl=model_config.get("blocked_impl", "einsum"),
            segment_impl=model_config.get("segment_impl", "scatter"),
        )
    if name in ("FastSchNet", "SchNet"):
        cutoff = _SCHNET_CUTOFFS.get(dataset_name)
        if cutoff is None:
            raise ValueError(f"no SchNet cutoff known for dataset {dataset_name!r}")
        if name == "FastSchNet":
            FastSchNet = _import_model("fast_schnet", "FastSchNet")
            return FastSchNet(
                node_feat_nf=model_config.node_feat_nf,
                node_attr_nf=model_config.node_attr_nf,
                edge_attr_nf=model_config.edge_attr_nf,
                hidden_nf=model_config.hidden_nf,
                virtual_channels=model_config.virtual_channels,
                n_layers=model_config.n_layers,
                normalize=model_config.normalize,
                cutoff=cutoff,
                axis_name=axis_name,
                blocked_impl=model_config.get("blocked_impl", "einsum"),
                hoist_edge_mlp=bool(model_config.get("hoist_edge_mlp", True)),
                segment_impl=model_config.get("segment_impl", "scatter"),
                fuse_agg=bool(model_config.get("fuse_agg", True)),
                agg_dtype=model_config.get("agg_dtype"),
            )
        SchNet = _import_model("schnet", "SchNet")
        return SchNet(hidden_channels=model_config.hidden_nf, cutoff=cutoff)
    if name == "EGNN":
        EGNN = _import_model("basic", "EGNN")
        return EGNN(
            n_layers=model_config.n_layers,
            in_node_nf=model_config.node_feat_nf,
            in_edge_nf=model_config.edge_attr_nf,
            hidden_nf=model_config.hidden_nf,
            with_v=True,
        )
    if name == "RF":
        RFVel = _import_model("basic", "RFVel")
        return RFVel(
            hidden_nf=model_config.hidden_nf,
            edge_attr_nf=model_config.edge_attr_nf,
            n_layers=model_config.n_layers,
        )
    if name == "TFN":
        TFNDynamics = _import_model("se3.dynamics", "TFNDynamics")
        return TFNDynamics(nf=model_config.hidden_nf // 2, n_layers=model_config.n_layers,
                           num_degrees=2)
    if name == "SE3Transformer":
        # capability extension: the reference assembles OurSE3Transformer
        # (models.py:207) but never serves it from its factory
        SE3TransformerDynamics = _import_model("se3.dynamics", "SE3TransformerDynamics")
        return SE3TransformerDynamics(nf=model_config.hidden_nf // 2,
                                      n_layers=model_config.n_layers, num_degrees=2)
    if name == "FastTFN":
        FastTFN = _import_model("fast_tfn", "FastTFN")
        return FastTFN(
            node_feat_nf=model_config.node_feat_nf,
            node_attr_nf=model_config.node_attr_nf,
            edge_attr_nf=model_config.edge_attr_nf,
            hidden_nf=model_config.hidden_nf,
            virtual_channels=model_config.virtual_channels,
            n_layers=model_config.n_layers,
            normalize=model_config.normalize,
        )
    if name == "Linear":
        LinearDynamics = _import_model("basic", "LinearDynamics")
        return LinearDynamics()
    raise NotImplementedError(f"Model {name} not implemented")
