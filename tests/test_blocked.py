"""Blocked-CSR MXU aggregation kernels (ops/blocked.py) — exactness vs the
XLA scatter path, adjoint gradients, and end-to-end FastEGNN parity on the
blocked layout. Kernels run in Pallas interpret mode off-TPU, so these tests
validate the same code path the TPU compiles."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distegnn_tpu.ops.blocked import (
    blocked_gather,
    blocked_segment_sum,
    blockify_edges,
    max_block_degree,
    slot_ids,
)
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.ops.segment import segment_sum


BLOCK, TILE = 256, 512


def _random_blocked_case(rng, n_nodes=1024, e=6000, feat=8):
    row = np.sort(rng.integers(0, n_nodes - 77, e)).astype(np.int64)
    col = rng.integers(0, n_nodes, e).astype(np.int64)
    epb = -(-max_block_degree(row, n_nodes, BLOCK) // TILE) * TILE
    ei, _, em = blockify_edges(np.stack([row, col]), None, n_nodes, epb, BLOCK)
    slots = slot_ids(jnp.asarray(ei[0])[None], jnp.asarray(em)[None], BLOCK, epb)
    E = ei.shape[1]
    data = np.zeros((E, feat), np.float32)
    data[em > 0] = rng.normal(size=(e, feat)).astype(np.float32)
    return row, ei, em, slots, jnp.asarray(data)


def test_blockify_preserves_sorted_layout():
    rng = np.random.default_rng(0)
    row, ei, em, _, _ = _random_blocked_case(rng)
    assert np.all(np.diff(ei[0]) >= 0)          # still a legal sorted edge list
    assert np.array_equal(ei[0][em > 0], row)   # real edges in original order
    epb = ei.shape[1] // (1024 // BLOCK)
    blk = np.arange(ei.shape[1]) // epb
    assert np.all(ei[0] // BLOCK == blk)        # block invariant


def test_segment_sum_matches_scatter():
    rng = np.random.default_rng(1)
    row, ei, em, slots, data = _random_blocked_case(rng)
    ref = segment_sum(data, jnp.asarray(ei[0]), 1024, mask=jnp.asarray(em))
    out = blocked_segment_sum(data[None], slots, 1024, BLOCK, TILE)[0]
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_gather_matches_take():
    rng = np.random.default_rng(2)
    _, ei, em, slots, _ = _random_blocked_case(rng)
    h = jnp.asarray(rng.normal(size=(1024, 8)).astype(np.float32))
    ref = np.where(em[:, None] > 0, np.asarray(h)[ei[0]], 0.0)
    out = blocked_gather(h[None], slots, BLOCK, TILE)[0]
    np.testing.assert_allclose(out, ref, atol=0)


def test_adjoint_gradients():
    rng = np.random.default_rng(3)
    _, ei, em, slots, data = _random_blocked_case(rng)
    h = jnp.asarray(rng.normal(size=(1024, 8)).astype(np.float32))

    g_seg = jax.grad(lambda d: jnp.sum(
        blocked_segment_sum(d[None], slots, 1024, BLOCK, TILE) ** 2))(data)
    g_ref = jax.grad(lambda d: jnp.sum(
        segment_sum(d, jnp.asarray(ei[0]), 1024, mask=jnp.asarray(em)) ** 2))(data)
    np.testing.assert_allclose(g_seg, g_ref, atol=2e-4)

    g_gat = jax.grad(lambda hh: jnp.sum(
        blocked_gather(hh[None], slots, BLOCK, TILE) * data[None]))(h)
    g_gref = jax.grad(lambda hh: jnp.sum(
        jnp.where(jnp.asarray(em)[:, None] > 0, hh[jnp.asarray(ei[0])], 0.0)
        * data))(h)
    np.testing.assert_allclose(g_gat, g_gref, atol=2e-4)


def test_bf16_path():
    rng = np.random.default_rng(4)
    _, ei, em, slots, data = _random_blocked_case(rng)
    out = blocked_segment_sum(data.astype(jnp.bfloat16)[None], slots, 1024, BLOCK, TILE)[0]
    ref = segment_sum(data, jnp.asarray(ei[0]), 1024, mask=jnp.asarray(em))
    assert out.dtype == jnp.float32  # bf16 in, f32 accumulate out
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-1)


def _nbody_like_graphs(rng, n_graphs=2, n=300):
    graphs = []
    for _ in range(n_graphs):
        loc = rng.normal(size=(n, 3)).astype(np.float32)
        vel = rng.normal(size=(n, 3)).astype(np.float32)
        # symmetric radius-style graph, rows sorted
        d = np.linalg.norm(loc[:, None] - loc[None, :], axis=-1)
        row, col = np.nonzero((d < 1.2) & ~np.eye(n, dtype=bool))
        dist = d[row, col]
        graphs.append({
            "node_feat": np.linalg.norm(vel, axis=1, keepdims=True).astype(np.float32),
            "loc": loc, "vel": vel, "target": loc + 0.1 * vel,
            "edge_index": np.stack([row, col]).astype(np.int64),
            "edge_attr": np.repeat(dist[:, None], 2, axis=1).astype(np.float32),
        })
    return graphs


@pytest.mark.parametrize("compute_dtype,blocked_impl,empty_blocks", [
    (None, "pallas", 0), (None, "einsum", 0),
    ("bf16", "pallas", 0), ("bf16", "einsum", 0),
    (None, "pallas", 2), (None, "einsum", 2)])
def test_fastegnn_blocked_parity(compute_dtype, blocked_impl, empty_blocks):
    """Same graphs, blocked vs plain layout -> same FastEGNN output + grads
    (both blocked lowerings: Pallas kernels and the einsum contraction).
    ``empty_blocks``: ``max_nodes`` leaves that many trailing node blocks
    with no real node and no real edge (what a loader's dataset-wide
    ``max_nodes`` does to a small batch)."""
    from distegnn_tpu.models.fast_egnn import FastEGNN

    rng = np.random.default_rng(5)
    graphs = _nbody_like_graphs(rng)
    plain = pad_graphs([dict(g) for g in graphs])
    blocked = pad_graphs([dict(g) for g in graphs], edge_block=BLOCK, edge_tile=TILE,
                         max_nodes=(2 + empty_blocks) * BLOCK)
    assert blocked.edge_block == BLOCK
    assert blocked.max_nodes == (2 + empty_blocks) * BLOCK
    assert not np.asarray(blocked.edge_mask).reshape(2, 2 + empty_blocks, -1)[:, 2:].any()

    model = FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                     virtual_channels=2, n_layers=2, compute_dtype=compute_dtype,
                     blocked_impl=blocked_impl)
    params = model.init(jax.random.PRNGKey(0), plain)

    tol = 1e-5 if compute_dtype is None else 5e-2
    xp, Xp = model.apply(params, plain)
    xb, Xb = model.apply(params, blocked)
    n = plain.max_nodes  # blocked pads N up to a block multiple
    np.testing.assert_allclose((xb * blocked.node_mask[..., None])[:, :n],
                               xp * plain.node_mask[..., None], atol=tol)
    np.testing.assert_allclose(Xb, Xp, atol=tol)

    def loss(p, g):
        x, _ = model.apply(p, g)
        return jnp.sum((x - g.target) ** 2 * g.node_mask[..., None])

    from jax.flatten_util import ravel_pytree

    gp = jax.grad(loss)(params, plain)
    gb = jax.grad(loss)(params, blocked)
    flat_p = ravel_pytree(gp)[0]
    flat_b = ravel_pytree(gb)[0]
    scale = jnp.maximum(jnp.abs(flat_p).max(), 1.0)
    np.testing.assert_allclose(flat_b / scale, flat_p / scale, atol=5 * tol)


def test_graph_loader_blocked_layout():
    """GraphLoader(edge_block=...) emits a dataset-stable blocked layout."""
    from distegnn_tpu.data.loader import GraphDataset, GraphLoader

    rng = np.random.default_rng(6)
    ds = GraphDataset(_nbody_like_graphs(rng, n_graphs=6, n=200))
    ld = GraphLoader(ds, batch_size=2, shuffle=True, seed=3, edge_block=BLOCK)
    batches = list(ld)
    assert len(batches) == 3
    for b in batches:
        assert b.edge_block == BLOCK
        assert b.max_nodes == ld.max_nodes and b.max_edges == ld.max_edges
        # block invariant on every batch
        epb = b.edges_per_block
        blk = np.arange(b.max_edges) // epb
        rows = np.asarray(b.row)
        assert np.all(rows // BLOCK == blk[None, :])


def test_einsum_ops_match_plain():
    """The einsum lowering's primitives: fwd + custom-VJP grads == plain XLA.
    The custom VJPs exist because differentiating through the bf16 term split
    would bf16-round the cotangent (~1e-2 error observed); with them the
    gradients must sit at f32 noise level."""
    from distegnn_tpu.ops.blocked import (
        _paired_gather_ein, einsum_gather, einsum_segment_sum, onehot_blocks,
        pairing_perm,
    )

    rng = np.random.default_rng(11)
    g = _nbody_like_graphs(rng, n_graphs=1, n=120)[0]
    ei = g["edge_index"]
    n = 120
    n_pad = -(-n // BLOCK) * BLOCK
    epb = -(-max_block_degree(np.sort(ei[0]), n_pad, BLOCK) // 8) * 8
    bei, _, em = blockify_edges(ei, None, n_pad, epb, BLOCK)
    pair = pairing_perm(bei)
    assert pair is not None
    slot = slot_ids(jnp.asarray(bei[0]), jnp.asarray(em), BLOCK, epb)
    oh = onehot_blocks(slot, epb, BLOCK)
    E = bei.shape[1]
    mask = jnp.asarray(em)[:, None]
    x = jnp.asarray(rng.normal(size=(E, 8)).astype(np.float32)) * mask
    h = jnp.asarray(rng.normal(size=(n_pad, 8)).astype(np.float32))

    # tolerances: f32 accumulation-order noise on sums of O(100) edges/node
    ref = segment_sum(x, jnp.asarray(bei[0]), n_pad, mask=jnp.asarray(em))
    np.testing.assert_allclose(einsum_segment_sum(x, oh), ref, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        einsum_gather(h, oh), np.where(em[:, None] > 0, np.asarray(h)[bei[0]], 0.0),
        atol=2e-6)

    g1 = jax.grad(lambda hh: jnp.sum(jnp.sin(einsum_gather(hh, oh)) * mask))(h)
    g2 = jax.grad(lambda hh: jnp.sum(jnp.sin(hh[jnp.asarray(bei[0])]) * mask))(h)
    np.testing.assert_allclose(g1, g2, atol=1e-4)  # gather grad = a seg-sum

    col, pj = jnp.asarray(bei[1]), jnp.asarray(pair)
    g3 = jax.grad(lambda hh: jnp.sum(jnp.cos(_paired_gather_ein(hh, col, pj, oh)) * mask))(h)
    g4 = jax.grad(lambda hh: jnp.sum(jnp.cos(hh[col]) * mask))(h)
    np.testing.assert_allclose(g3, g4, atol=1e-4)

    g5 = jax.grad(lambda xx: jnp.sum(jnp.tanh(einsum_segment_sum(xx, oh))))(x)
    g6 = jax.grad(lambda xx: jnp.sum(jnp.tanh(
        segment_sum(xx, jnp.asarray(bei[0]), n_pad, mask=jnp.asarray(em)))))(x)
    np.testing.assert_allclose(g5 * mask, g6 * mask, atol=1e-4)


def test_pairing_perm():
    from distegnn_tpu.ops.blocked import pairing_perm

    rng = np.random.default_rng(8)
    g = _nbody_like_graphs(rng, n_graphs=1, n=120)[0]
    batch = pad_graphs([g], edge_block=BLOCK)
    assert batch.edge_pair is not None
    ei = np.asarray(batch.edge_index[0])
    pair = np.asarray(batch.edge_pair[0])
    assert np.array_equal(ei[0][pair], ei[1])
    assert np.array_equal(ei[1][pair], ei[0])

    # directed (asymmetric) list -> no pairing, model falls back
    ei_dir = g["edge_index"][:, g["edge_index"][0] < g["edge_index"][1]]
    assert pairing_perm(ei_dir) is None
    g2 = dict(g, edge_index=ei_dir,
              edge_attr=np.ones((ei_dir.shape[1], 2), np.float32))
    b2 = pad_graphs([g2], edge_block=BLOCK)
    assert b2.edge_pair is None


@pytest.mark.parametrize("edge_block", [0, BLOCK])
def test_remat_same_outputs_and_grads(edge_block):
    """model.remat recomputes activations; results must be identical —
    including through the blocked Pallas custom-VJP kernels."""
    from distegnn_tpu.models.fast_egnn import FastEGNN
    from jax.flatten_util import ravel_pytree

    rng = np.random.default_rng(9)
    kw_pad = dict(edge_block=edge_block) if edge_block else {}
    batch = pad_graphs(_nbody_like_graphs(rng, n_graphs=1, n=120), **kw_pad)
    kw = dict(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
              virtual_channels=2, n_layers=2)
    m0, m1 = FastEGNN(**kw), FastEGNN(**kw, remat=True)
    params = m0.init(jax.random.PRNGKey(0), batch)

    def loss(m, p):
        x, _ = m.apply(p, batch)
        return jnp.sum((x - batch.target) ** 2 * batch.node_mask[..., None])

    np.testing.assert_allclose(loss(m1, params), loss(m0, params), rtol=1e-6)
    g0 = ravel_pytree(jax.grad(lambda p: loss(m0, p))(params))[0]
    g1 = ravel_pytree(jax.grad(lambda p: loss(m1, p))(params))[0]
    np.testing.assert_allclose(g1, g0, atol=1e-6)


@pytest.mark.parametrize("blocked_impl", ["pallas", "einsum"])
@pytest.mark.parametrize("model_name", ["FastRF", "FastSchNet"])
def test_other_fast_models_blocked_parity(model_name, blocked_impl):
    """FastRF / FastSchNet: blocked layout == plain layout (fwd + grads)."""
    from jax.flatten_util import ravel_pytree

    rng = np.random.default_rng(10)
    graphs = _nbody_like_graphs(rng)
    plain = pad_graphs([dict(g) for g in graphs])
    blocked = pad_graphs([dict(g) for g in graphs], edge_block=BLOCK)
    assert blocked.edge_pair is not None

    if model_name == "FastRF":
        from distegnn_tpu.models.fast_rf import FastRF

        model = FastRF(edge_attr_nf=2, hidden_nf=16, virtual_channels=2,
                       n_layers=2, blocked_impl=blocked_impl)
    else:
        from distegnn_tpu.models.fast_schnet import FastSchNet

        model = FastSchNet(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                           virtual_channels=2, n_layers=2, cutoff=2.0,
                           blocked_impl=blocked_impl)
    params = model.init(jax.random.PRNGKey(0), plain)

    xp, Xp = model.apply(params, plain)
    xb, Xb = model.apply(params, blocked)
    n = plain.max_nodes
    np.testing.assert_allclose((xb * blocked.node_mask[..., None])[:, :n],
                               xp * plain.node_mask[..., None], atol=1e-5)
    np.testing.assert_allclose(Xb, Xp, atol=1e-5)

    def loss(p, g):
        x, _ = model.apply(p, g)
        return jnp.sum((x - g.target) ** 2 * g.node_mask[..., None])

    gp = ravel_pytree(jax.grad(loss)(params, plain))[0]
    gb = ravel_pytree(jax.grad(loss)(params, blocked))[0]
    scale = jnp.maximum(jnp.abs(gp).max(), 1.0)
    np.testing.assert_allclose(gb / scale, gp / scale, atol=5e-5)


def test_gen2_shapes_big_tile_small_scale():
    """Gen-2 kernel configuration (block 512 x tile 2048, bf16 streams)
    scaled down to interpret-mode size: block > tile-disproportionate shapes
    and the bf16 single-pass path stay exact vs the scatter reference."""
    rng = np.random.default_rng(7)
    n_nodes, block, tile = 256, 64, 128
    e = 1500
    row = np.sort(rng.integers(0, n_nodes, e)).astype(np.int64)
    col = rng.integers(0, n_nodes, e).astype(np.int64)
    epb = -(-max_block_degree(row, n_nodes, block) // tile) * tile
    ei, _, em = blockify_edges(np.stack([row, col]), None, n_nodes, epb, block)
    slots = slot_ids(jnp.asarray(ei[0])[None], jnp.asarray(em)[None], block, epb)
    E = ei.shape[1]
    data = np.zeros((E, 8), np.float32)
    data[em > 0] = rng.normal(size=(e, 8)).astype(np.float32)
    db = jnp.asarray(data).astype(jnp.bfloat16)

    out = blocked_segment_sum(db[None], slots, n_nodes, block, tile)[0]
    ref = segment_sum(db.astype(jnp.float32), jnp.asarray(ei[0]), n_nodes,
                      mask=jnp.asarray(em))
    # bf16 inputs, f32 accumulation: error is input-rounding level only
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)

    h = jnp.asarray(rng.normal(size=(n_nodes, 8)).astype(np.float32)).astype(
        jnp.bfloat16)
    g_out = blocked_gather(h[None], slots, block, tile)[0]
    ref_g = jnp.where(jnp.asarray(em)[:, None] > 0,
                      jnp.take(h, jnp.asarray(ei[0]), axis=0), 0)
    np.testing.assert_allclose(
        np.asarray(g_out, np.float32),
        np.asarray(jnp.asarray(ref_g, jnp.float32)), rtol=0, atol=0)
