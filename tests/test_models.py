"""Model-family tests: SE(3)/E(n) equivariance + jit/finite checks for every
model the factory serves (reference test coverage was FastEGNN-only,
equivariant_test.py; SURVEY.md §4 asks us to generalize it)."""

import numpy as np
import jax
import pytest

from distegnn_tpu.config import ConfigDict
from distegnn_tpu.models.basic import EGNN, GNN, FullMLP, LinearDynamics, RFVel
from distegnn_tpu.models.fast_rf import FastRF
from distegnn_tpu.models.fast_schnet import FastSchNet
from distegnn_tpu.models.registry import get_model
from distegnn_tpu.models.schnet import SchNet
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.utils.rotate import random_rotate
from tests.test_equivariance import _random_graph, _transform


def _pair(rng, **kw):
    g = _random_graph(rng, **kw)
    R = random_rotate(rng).astype(np.float32)
    t = (rng.normal(size=(3,)) * 5).astype(np.float32)
    gb = pad_graphs([g], node_bucket=1, edge_bucket=1)
    gb_r = pad_graphs([_transform(g, R, t)], node_bucket=1, edge_bucket=1)
    return gb, gb_r, R, t


MODELS = {
    "TFN": lambda: __import__("distegnn_tpu.models.se3.dynamics", fromlist=["TFNDynamics"]
                              ).TFNDynamics(nf=8, n_layers=2, num_degrees=2),
    "SE3Transformer": lambda: __import__(
        "distegnn_tpu.models.se3.dynamics", fromlist=["SE3TransformerDynamics"]
    ).SE3TransformerDynamics(nf=8, n_layers=2, num_degrees=2, n_heads=2),
    "FastTFN": lambda: __import__("distegnn_tpu.models.fast_tfn", fromlist=["FastTFN"]
                                  ).FastTFN(node_feat_nf=1, node_attr_nf=0, edge_attr_nf=1,
                                            hidden_nf=16, virtual_channels=2, n_layers=2),
    "EGHN": lambda: __import__("distegnn_tpu.models.eghn", fromlist=["EGHN"]).EGHN(
        in_node_nf=1, in_edge_nf=1, hidden_nf=16, n_cluster=3,
        layer_per_block=2, layer_pooling=2),
    "FastRF": lambda: FastRF(edge_attr_nf=1, hidden_nf=32, virtual_channels=3, n_layers=3),
    "FastSchNet": lambda: FastSchNet(node_feat_nf=1, edge_attr_nf=1, hidden_nf=32,
                                     virtual_channels=3, n_layers=2, cutoff=10.0),
    "SchNet": lambda: SchNet(hidden_channels=32, num_interactions=3, cutoff=10.0),
    "EGNN": lambda: EGNN(n_layers=3, in_node_nf=1, in_edge_nf=1, hidden_nf=32, with_v=True),
    "RF": lambda: RFVel(hidden_nf=32, edge_attr_nf=1, n_layers=3),
    "Linear": lambda: LinearDynamics(),
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_model_se3_equivariance(rng, name):
    model = MODELS[name]()
    gb, gb_r, R, t = _pair(rng)
    params = model.init(jax.random.PRNGKey(0), gb)
    out, _ = model.apply(params, gb)
    out_r, _ = model.apply(params, gb_r)
    np.testing.assert_allclose(np.asarray(out[0]) @ R + t, np.asarray(out_r[0]),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("name", sorted(MODELS) + ["GNN", "MLP"])
def test_model_jits_and_is_finite(rng, name):
    builders = dict(MODELS,
                    GNN=lambda: GNN(n_layers=2, in_node_nf=1, in_edge_nf=1, hidden_nf=16),
                    MLP=lambda: FullMLP(hidden_nf=16))
    model = builders[name]()
    graphs = [_random_graph(rng, n=8, e=14) for _ in range(3)]
    gb = pad_graphs(graphs)
    params = model.init(jax.random.PRNGKey(1), gb)
    out, _ = jax.jit(model.apply)(params, gb)
    assert out.shape == (3, gb.max_nodes, 3)
    assert np.all(np.isfinite(np.asarray(out)))


def _remap_fused_mlp(node):
    """concat tree phi_e/TorchDense_0/Dense_0 (fused first Dense) +
    TorchDense_1 -> hoisted tree phi_e/{kernel,bias} + TorchDense_0."""
    return {
        "kernel": node["TorchDense_0"]["Dense_0"]["kernel"],
        "bias": node["TorchDense_0"]["Dense_0"]["bias"],
        "TorchDense_0": node["TorchDense_1"],
    }


def _assert_hoisted_equals_concat(m_h, m_c, gb, remap):
    """Shared hoisting-equivalence check: remap the fused params of the
    concat model into the hoisted tree, then compare outputs and per-leaf
    gradients (leaf-by-leaf through the SAME remap — catches misrouted
    cotangents that a scalar-sum comparison would let cancel)."""
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    p_c = jax.device_get(m_c.init(jax.random.PRNGKey(0), gb))
    p_h = remap(p_c)
    x_c, X_c = m_c.apply(p_c, gb)
    x_h, X_h = m_h.apply(p_h, gb)
    np.testing.assert_allclose(x_h, x_c, atol=1e-5)
    np.testing.assert_allclose(X_h, X_c, atol=1e-5)

    def loss(m, p):
        x, _ = m.apply(p, gb)
        return jnp.sum((x - gb.target) ** 2 * gb.node_mask[..., None])

    flat_c = ravel_pytree(remap(jax.grad(lambda p: loss(m_c, p))(p_c)))[0]
    flat_h = ravel_pytree(jax.grad(lambda p: loss(m_h, p))(p_h))[0]
    scale = np.maximum(np.abs(flat_c).max(), 1.0)
    np.testing.assert_allclose(flat_h / scale, flat_c / scale, atol=1e-5)


def test_hoisted_edge_mlp_equals_concat_mlp(rng):
    """FastEGNN hoist_edge_mlp=True computes the SAME function as the
    reference-shaped concat MLP."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    g = _random_graph(rng, n=40, e=120, feat_nf=1, edge_nf=2)
    gb = pad_graphs([g], node_bucket=1, edge_bucket=1)
    kw = dict(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
              virtual_channels=2, n_layers=2)

    def remap(tree):
        out = jax.device_get(tree)
        for i in range(kw["n_layers"]):
            gcl = out["params"][f"gcl_{i}"]
            gcl["phi_e"] = _remap_fused_mlp(gcl["phi_e"])
        return out

    _assert_hoisted_equals_concat(FastEGNN(**kw, hoist_edge_mlp=True),
                                  FastEGNN(**kw, hoist_edge_mlp=False),
                                  gb, remap)


def test_fastschnet_hoisted_equals_concat(rng):
    """FastSchNet hoisting covers BOTH phi_e and the SchNet coordinate gate
    (concat orders differ: MLP is [h_row, h_col, scalars], gate is
    [gauss, h_row, h_col] — the hoisted modules slice to match, so the raw
    kernels map 1:1)."""
    g = _random_graph(rng, n=40, e=120, feat_nf=1, edge_nf=2)
    gb = pad_graphs([g], node_bucket=1, edge_bucket=1)
    kw = dict(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
              virtual_channels=2, n_layers=2, cutoff=2.0)

    def remap(tree):
        out = jax.device_get(tree)
        for i in range(kw["n_layers"]):
            gcl = out["params"][f"gcl_{i}"]
            gcl["phi_e"] = _remap_fused_mlp(gcl["phi_e"])
            gate = gcl["schnet_coord_update"]["Dense_0"]
            gcl["schnet_coord_update"] = {"kernel": gate["kernel"],
                                          "bias": gate["bias"]}
        return out

    _assert_hoisted_equals_concat(FastSchNet(**kw, hoist_edge_mlp=True),
                                  FastSchNet(**kw, hoist_edge_mlp=False),
                                  gb, remap)


def test_fast_models_padding_invariance(rng):
    """Padded batches must give identical real-node outputs (masking audit
    for the new families, mirroring the FastEGNN test)."""
    for build in (MODELS["FastRF"], MODELS["FastSchNet"], MODELS["SchNet"],
                  MODELS["EGNN"], MODELS["RF"]):
        model = build()
        g = _random_graph(rng)
        tight = pad_graphs([g], node_bucket=1, edge_bucket=1)
        padded = pad_graphs([g], max_nodes=16, max_edges=64)
        params = model.init(jax.random.PRNGKey(0), tight)
        out_tight, _ = model.apply(params, tight)
        out_pad, _ = model.apply(params, padded)
        np.testing.assert_allclose(np.asarray(out_tight[0]), np.asarray(out_pad[0, :10]),
                                   atol=1e-4, rtol=0)


def test_fast_schnet_normalize_equivariance(rng):
    model = FastSchNet(node_feat_nf=1, edge_attr_nf=1, hidden_nf=32,
                       virtual_channels=3, n_layers=2, cutoff=10.0, normalize=True)
    gb, gb_r, R, t = _pair(rng)
    params = model.init(jax.random.PRNGKey(0), gb)
    out, _ = model.apply(params, gb)
    out_r, _ = model.apply(params, gb_r)
    np.testing.assert_allclose(np.asarray(out[0]) @ R + t, np.asarray(out_r[0]),
                               atol=1e-4, rtol=0)


def test_egcl_classic_and_egmn_run(rng):
    """Library classes outside the factory (reference E_GCL basic.py:69-164,
    EGMN basic.py:339-356) stay importable and equivariant-sane."""
    from distegnn_tpu.models.basic import EGCLClassic, EGMN

    g = _random_graph(rng)
    gb = pad_graphs([g], node_bucket=1, edge_bucket=1)
    layer = EGCLClassic(hidden_nf=16, edge_attr_nf=1)
    h0 = np.tile(gb.node_feat, (1, 1, 16)).astype(np.float32)
    params = layer.init(jax.random.PRNGKey(0), h0, gb.loc, gb)
    h1, x1 = layer.apply(params, h0, gb.loc, gb)
    assert np.all(np.isfinite(np.asarray(x1)))

    net = EGMN(n_layers=2, n_vector_input=2, hidden_dim=8)
    Z = [rng.normal(size=(5, 3)).astype(np.float32) for _ in range(2)]
    s = rng.normal(size=(5, 8)).astype(np.float32)
    p = net.init(jax.random.PRNGKey(1), Z, s)
    vec, sc = net.apply(p, Z, s)
    R = random_rotate(rng).astype(np.float32)
    vec_r, sc_r = net.apply(p, [z @ R for z in Z], s)
    np.testing.assert_allclose(np.asarray(vec) @ R, np.asarray(vec_r), atol=1e-5)


def test_equivariant_scalar_net(rng):
    """The O(n)-universal scalarization block (reference basic.py:194-238,
    serving EGMN/EGHN): output vector rotates with the inputs, scalar is
    invariant."""
    from distegnn_tpu.models.basic import EquivariantScalarNet

    net = EquivariantScalarNet(n_vector_input=2, hidden_dim=16)
    Z = rng.normal(size=(5, 3, 2)).astype(np.float32)
    s = rng.normal(size=(5, 4)).astype(np.float32)
    params = net.init(jax.random.PRNGKey(0), Z, s)
    vec, scal = net.apply(params, Z, s)
    R = random_rotate(rng).astype(np.float32)
    vec_r, scal_r = net.apply(params, np.einsum("ndk,de->nek", Z, R), s)
    np.testing.assert_allclose(np.asarray(vec) @ R, np.asarray(vec_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(scal), np.asarray(scal_r), atol=1e-5)


def test_registry_serves_all_families(rng):
    """get_model dispatch parity with reference main.py:58-92."""
    base = dict(model_name="FastEGNN", normalize=False, hidden_nf=16, n_layers=2,
                virtual_channels=2, node_feat_nf=1, node_attr_nf=0, edge_attr_nf=1,
                checkpoint=None)
    gb = pad_graphs([_random_graph(rng)])
    for name in ("FastEGNN", "FastRF", "FastSchNet", "SchNet", "EGNN", "RF", "Linear",
                 "TFN", "FastTFN", "SE3Transformer"):
        cfg = ConfigDict(dict(base, model_name=name))
        model = get_model(cfg, world_size=1, dataset_name="nbody_100")
        params = model.init(jax.random.PRNGKey(0), gb)
        out, _ = model.apply(params, gb)
        assert np.all(np.isfinite(np.asarray(out))), name


_TREE_KW = dict(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                virtual_channels=2, n_layers=2)


def _param_tree(model, gb):
    """{path: (shape, dtype)} of the params ``model.init`` would make."""
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), gb)
    return {jax.tree_util.keystr(k): (a.shape, str(a.dtype))
            for k, a in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("model_kw,edge_block", [
    (dict(segment_impl=s), b) for s in ("scatter", "cumsum", "ell")
    for b in (0, 256)
] + [(kw, 0) for kw in (dict(blocked_impl="pallas"), dict(remat=True),
                        dict(compute_dtype="bf16"), dict(fuse_agg=False),
                        dict(agg_dtype="bf16"))],
    ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items()) if isinstance(v, dict) else f"block{v}")
def test_fastegnn_param_tree_is_one_tree_whatever_the_lowering(rng, model_kw, edge_block):
    """A checkpoint trains under one lowering and serves under any: names,
    shapes and dtypes of FastEGNN's params do not depend on segment_impl,
    blocked_impl, the batch layout, remat, the compute or aggregation dtype
    or fuse_agg (``hoist_edge_mlp`` alone changes the tree, and says so)."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    g = _random_graph(rng, n=40, e=120, feat_nf=1, edge_nf=2)
    want = _param_tree(FastEGNN(**_TREE_KW),
                       pad_graphs([dict(g)], node_bucket=1, edge_bucket=1))
    assert sum(int(np.prod(s)) for s, _ in want.values()) == 9474
    if edge_block:
        gb = pad_graphs([dict(g)], edge_block=edge_block)
    else:   # cumsum and ell read the pairing and the static in-degree
        gb = pad_graphs([dict(g)], node_bucket=1, edge_bucket=1,
                        compute_pair=True)
    got = _param_tree(FastEGNN(**_TREE_KW, **model_kw), gb)
    assert got == want
