"""Packed per-layer aggregation (EdgeOps.agg_rows_pair, model fuse_agg):
one segment-sum pass carries coordinate translations + edge features +
count. Parity against the two-call path for every plain lowering, forward
and gradients, plus the opt-in bf16 stream (VERDICT r3 #1 prepared attack)."""

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from distegnn_tpu.ops.blocked import EdgeOps
from distegnn_tpu.ops.graph import pad_graphs


def _graph(rng, n=24):
    from distegnn_tpu.data import build_nbody_graph

    loc = rng.normal(size=(n, 3))
    vel = rng.normal(size=(n, 3))
    charges = rng.choice([1.0, -1.0], size=(n, 1))
    return build_nbody_graph(loc, vel, charges, loc + 0.1 * vel, radius=-1.0)


@pytest.fixture
def batch(rng):
    return pad_graphs([_graph(rng, 24), _graph(rng, 17)], compute_pair=True,
                      max_in_degree=32)


@pytest.mark.parametrize("seg", ["scatter", "cumsum", "ell"])
@pytest.mark.parametrize("a_mean", [True, False])
def test_agg_rows_pair_matches_two_calls(batch, rng, seg, a_mean):
    ops = EdgeOps(batch, seg_impl=seg)
    B, E = batch.row.shape
    a = jnp.asarray(rng.standard_normal((B, E, 3)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((B, E, 7)).astype(np.float32))
    out_a, out_b = ops.agg_rows_pair(a, b, a_mean=a_mean)
    # reference: the existing two-call path (these mask internally)
    ref_a = ops.agg_rows_mean(a) if a_mean else ops.agg_rows_sum(
        a * batch.edge_mask[..., None])
    ref_b = ops.agg_rows_mean(b)
    np.testing.assert_allclose(out_a, ref_a, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(out_b, ref_b, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("seg", ["scatter", "cumsum", "ell"])
def test_agg_rows_pair_grads_match(batch, rng, seg):
    ops = EdgeOps(batch, seg_impl=seg)
    B, E = batch.row.shape
    a = jnp.asarray(rng.standard_normal((B, E, 3)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((B, E, 5)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(
        (batch.max_nodes, 1)).astype(np.float32))

    def fused(a, b):
        oa, ob = ops.agg_rows_pair(a, b, a_mean=True)
        return jnp.sum(oa * w) + jnp.sum(ob * w)

    def ref(a, b):
        return (jnp.sum(ops.agg_rows_mean(a) * w)
                + jnp.sum(ops.agg_rows_mean(b) * w))

    ga = jax.grad(fused, argnums=(0, 1))(a, b)
    gr = jax.grad(ref, argnums=(0, 1))(a, b)
    for x, y in zip(ga, gr):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=2e-5)


def test_agg_rows_pair_bf16_stream(batch, rng):
    """bf16 packed stream: f32 accumulation keeps values at bf16 input-round
    accuracy (NOT bf16-accumulation accuracy)."""
    ops = EdgeOps(batch, seg_impl="scatter")
    B, E = batch.row.shape
    a = jnp.asarray(rng.standard_normal((B, E, 3)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((B, E, 7)).astype(np.float32))
    out_a, out_b = ops.agg_rows_pair(a, b, a_mean=True, agg_dtype="bf16")
    ref_a = ops.agg_rows_mean(a)
    ref_b = ops.agg_rows_mean(b)
    assert out_a.dtype == jnp.float32
    np.testing.assert_allclose(out_a, ref_a, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(out_b, ref_b, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("seg", ["scatter", "cumsum", "ell"])
def test_fastegnn_fuse_agg_parity(batch, rng, seg):
    """Full model: fuse_agg=True (default) vs fuse_agg=False, forward +
    gradients, per lowering."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    g = batch
    kw = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=16, virtual_channels=3,
              n_layers=2, segment_impl=seg)
    m_f = FastEGNN(**kw)                    # fused (default)
    m_u = FastEGNN(**kw, fuse_agg=False)    # two-call path
    params = m_f.init(jax.random.PRNGKey(0), g)

    out_f = m_f.apply(params, g)
    out_u = m_u.apply(params, g)
    np.testing.assert_allclose(out_f[0], out_u[0], rtol=1e-5, atol=5e-5)
    np.testing.assert_allclose(out_f[1], out_u[1], rtol=1e-5, atol=5e-5)

    def loss(m):
        def f(p):
            loc, X = m.apply(p, g)
            return jnp.sum((loc - g.target) ** 2 * g.node_mask[..., None])
        return f

    g_f = jax.grad(loss(m_f))(params)
    g_u = jax.grad(loss(m_u))(params)
    flat_f, _ = jax.flatten_util.ravel_pytree(g_f)
    flat_u, _ = jax.flatten_util.ravel_pytree(g_u)
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_u),
                               rtol=2e-3, atol=2e-4)


def test_fastegnn_blocked_batch_ignores_fuse(rng):
    """Blocked layouts keep their two-call path: fuse_agg must be a no-op."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    g = pad_graphs([_graph(rng, 24), _graph(rng, 17)], edge_block=8)
    kw = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=16, virtual_channels=3,
              n_layers=2)
    params = FastEGNN(**kw).init(jax.random.PRNGKey(0), g)
    out_f = FastEGNN(**kw, fuse_agg=True).apply(params, g)
    out_u = FastEGNN(**kw, fuse_agg=False).apply(params, g)
    np.testing.assert_allclose(out_f[0], out_u[0], atol=0, rtol=0)


@pytest.mark.parametrize("seg", ["scatter", "cumsum"])
def test_fastschnet_fuse_agg_parity(batch, rng, seg):
    """FastSchNet applies the same per-layer aggregation fusion."""
    from distegnn_tpu.models.fast_schnet import FastSchNet

    g = batch
    kw = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=16, virtual_channels=3,
              n_layers=2, segment_impl=seg)
    m_f = FastSchNet(**kw)
    m_u = FastSchNet(**kw, fuse_agg=False)
    params = m_f.init(jax.random.PRNGKey(0), g)
    out_f = m_f.apply(params, g)
    out_u = m_u.apply(params, g)
    np.testing.assert_allclose(out_f[0], out_u[0], rtol=1e-5, atol=5e-5)
    np.testing.assert_allclose(out_f[1], out_u[1], rtol=1e-5, atol=5e-5)


def test_fastegnn_fuse_agg_bf16_compute(batch, rng):
    """compute_dtype=bf16 models: the fused path accumulates f32 where the
    legacy path accumulated bf16, so outputs agree only to bf16 rounding —
    the documented (and precision-improving) numerics delta."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    g = batch
    kw = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=16, virtual_channels=3,
              n_layers=2, compute_dtype="bf16")
    m_f = FastEGNN(**kw)
    m_u = FastEGNN(**kw, fuse_agg=False)
    params = m_f.init(jax.random.PRNGKey(0), g)
    out_f = m_f.apply(params, g)
    out_u = m_u.apply(params, g)
    np.testing.assert_allclose(np.asarray(out_f[0], np.float32),
                               np.asarray(out_u[0], np.float32),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(out_f[1], np.float32),
                               np.asarray(out_u[1], np.float32),
                               rtol=3e-2, atol=3e-2)


def test_fastegnn_agg_dtype_bf16_within_bf16_band_of_f32(batch, rng):
    """``agg_dtype: bf16`` on the model (the op alone:
    test_agg_rows_pair_bf16_stream): the packed aggregation streams bf16 and
    accumulates f32, so the prediction moves by bf16 rounding and no more."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    g = batch
    kw = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=16, virtual_channels=3,
              n_layers=2)
    m32, m16 = FastEGNN(**kw), FastEGNN(**kw, agg_dtype="bf16")
    params = m32.init(jax.random.PRNGKey(0), g)
    out32, out16 = m32.apply(params, g), m16.apply(params, g)
    assert out16[0].dtype == jnp.float32
    assert not np.array_equal(out16[0], out32[0])       # the knob is read
    for a, b in zip(out16, out32):
        np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)
