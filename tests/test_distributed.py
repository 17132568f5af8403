"""Distributed-path tests on the 8-virtual-CPU-device mesh (SURVEY.md §4:
multi-device tests that need no pod).

The core invariant: DistEGNN over P partitions must equal FastEGNN on the
union graph — the reference preserves this by construction (disjoint
partitions + 3 weighted allreduces per layer, models/FastEGNN.py:310-319);
here it is an executable test."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from distegnn_tpu.data import GraphDataset, ShardedGraphLoader, build_nbody_graph
from distegnn_tpu.data.partition import assign_partitions, split_graph
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.parallel.launch import make_distributed_steps
from distegnn_tpu.parallel.mesh import GRAPH_AXIS, make_mesh
from distegnn_tpu.train import TrainState, make_eval_step, make_optimizer, make_train_step

NPARTS = 4


def _graph(rng, n=32):
    loc = rng.normal(size=(n, 3))
    vel = rng.normal(size=(n, 3))
    charges = rng.choice([1.0, -1.0], size=(n, 1))
    target = loc + 0.1 * vel
    return build_nbody_graph(loc, vel, charges, target, radius=-1.0, cutoff_rate=0.0)


def _union_of_parts(parts):
    """Re-assemble partition dicts into one whole-graph dict with the SAME
    edge set (each partition's local edges, indices offset)."""
    out = {k: None for k in parts[0]}
    offset = 0
    cat = {k: [] for k in ("node_feat", "node_attr", "loc", "vel", "target", "edge_attr")}
    eidx = []
    for p in parts:
        for k in cat:
            if p.get(k) is not None:
                cat[k].append(p[k])
        eidx.append(p["edge_index"] + offset)
        offset += p["loc"].shape[0]
    for k, v in cat.items():
        out[k] = np.concatenate(v, axis=0) if v else None
    out["edge_index"] = np.concatenate(eidx, axis=1)
    out["loc_mean"] = parts[0]["loc_mean"]
    return out


@pytest.mark.parametrize("method", ["random", "kmeans", "metis"])
def test_partition_covers_all_nodes_balanced(rng, method):
    g = _graph(rng, n=64)
    labels = assign_partitions(g["loc"], NPARTS, method, outer_radius=2.0, seed=0)
    assert labels.shape == (64,)
    counts = np.bincount(labels, minlength=NPARTS)
    assert counts.sum() == 64 and (counts > 0).all()
    if method == "random":
        assert counts.max() - counts.min() <= 1  # exact balance
    elif method == "metis":
        # like METIS, the refining partitioner trades exact balance for cut
        # quality within a small slack (+-1 per bisection level)
        assert counts.max() - counts.min() <= 2 * NPARTS.bit_length()
    parts = split_graph(g, NPARTS, method, inner_radius=1.5, outer_radius=2.0, seed=0)
    assert sum(p["loc"].shape[0] for p in parts) == 64
    for p in parts:
        np.testing.assert_allclose(p["loc_mean"], g["loc"].mean(axis=0), atol=1e-6)
        if p["edge_index"].shape[1]:
            d = np.linalg.norm(p["loc"][p["edge_index"][0]] - p["loc"][p["edge_index"][1]], axis=1)
            assert (d < 1.5).all()  # inner-radius edges only


@pytest.fixture(scope="module")
def dist_setup():
    rng = np.random.default_rng(7)
    g = _graph(rng, n=32)
    parts = split_graph(g, NPARTS, "random", inner_radius=2.5, outer_radius=None, seed=3)
    union = _union_of_parts(parts)

    model_1 = FastEGNN(node_feat_nf=2, hidden_nf=16, virtual_channels=3, n_layers=3)
    model_P = model_1.copy(axis_name=GRAPH_AXIS)
    union_batch = pad_graphs([union])
    params = model_1.init(jax.random.PRNGKey(0), union_batch)

    # stacked [P, B=1, ...] partition batch with shard-wide common padding
    n_max = max(p["loc"].shape[0] for p in parts)
    e_max = max(p["edge_index"].shape[1] for p in parts)
    part_batches = [pad_graphs([p], max_nodes=n_max + 2, max_edges=e_max + 8) for p in parts]
    stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *part_batches)
    mesh = make_mesh(n_graph=NPARTS, devices=jax.devices()[:NPARTS])
    return model_1, model_P, params, union_batch, stacked, mesh, parts


def test_distributed_forward_matches_union(dist_setup):
    model_1, model_P, params, union_batch, stacked, mesh, parts = dist_setup

    loc_1, X_1 = jax.jit(model_1.apply)(params, union_batch)

    fwd = jax.jit(jax.shard_map(
        lambda pr, b: model_P.apply(pr, jax.tree.map(lambda x: x[0], b)),
        mesh=mesh, in_specs=(P(), P(GRAPH_AXIS)),
        out_specs=(P(GRAPH_AXIS), P()), check_vma=False,
    ))
    loc_P, X_P = fwd(params, stacked)

    # virtual nodes are global objects: identical across the mesh
    np.testing.assert_allclose(np.asarray(X_P), np.asarray(X_1), atol=1e-4)

    # real nodes: compare per-partition slices to the union's node blocks
    # (out_specs P(GRAPH_AXIS) concatenates per-device [B,N,3] on axis 0 -> [P*B,N,3])
    offset = 0
    loc_P = np.asarray(loc_P)
    loc_1 = np.asarray(loc_1)[0]
    for i, p in enumerate(parts):
        n = p["loc"].shape[0]
        np.testing.assert_allclose(loc_P[i, :n], loc_1[offset:offset + n], atol=1e-4)
        offset += n


def test_distributed_loss_and_grads_match_union(dist_setup):
    import optax

    model_1, model_P, params, union_batch, stacked, mesh, parts = dist_setup
    # SGD so the param delta is proportional to the gradient (Adam would
    # normalize away the gradient scale and amplify float noise)
    tx = optax.sgd(1e-2)

    step_1 = jax.jit(make_train_step(model_1, tx, mmd_weight=0.0, mmd_sigma=1.5, mmd_samples=3))
    train_P, eval_P = make_distributed_steps(model_P, tx, mesh, mmd_weight=0.0,
                                             mmd_sigma=1.5, mmd_samples=3)

    key = jax.random.PRNGKey(5)
    s1 = TrainState.create(params, tx)
    sP = TrainState.create(params, tx)
    s1_next, m1 = step_1(s1, union_batch, key)
    sP_next, mP = train_P(sP, stacked, key)

    np.testing.assert_allclose(float(mP["loss"]), float(m1["loss"]), rtol=1e-5)
    # identical global gradient -> identical replicated update on every device
    for a, b in zip(jax.tree.leaves(s1_next.params), jax.tree.leaves(sP_next.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    ev_1 = jax.jit(make_eval_step(model_1))
    np.testing.assert_allclose(float(eval_P(params, stacked)),
                               float(ev_1(params, union_batch)), rtol=1e-5)


def test_2d_mesh_matches_single_device(rng):
    """(data=2, graph=2) mesh: 2 different graphs, each split into 2 spatial
    partitions, one partition per device. One SGD step must equal the
    single-device step on the padded 2-graph union batch (VERDICT r1 item 4:
    the data axis in actual use). MMD off for exactness (its sample draw is
    per-device by design, reference utils/train.py:124-139)."""
    import optax

    from distegnn_tpu.parallel.mesh import DATA_AXIS

    D = Pn = 2
    graphs, unions, per_d = [], [], []
    for d in range(D):
        g = _graph(rng, n=20 + 4 * d)
        parts = split_graph(g, Pn, "random", inner_radius=2.5, outer_radius=None, seed=d)
        per_d.append(parts)
        unions.append(_union_of_parts(parts))
    n_max = max(p["loc"].shape[0] for parts in per_d for p in parts)
    e_max = max(p["edge_index"].shape[1] for parts in per_d for p in parts)
    stacks = []
    for parts in per_d:
        pbs = [pad_graphs([p], max_nodes=n_max + 2, max_edges=e_max + 8) for p in parts]
        stacks.append(jax.tree.map(lambda *xs: np.stack(xs, axis=0), *pbs))
    batch_2d = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *stacks)  # [D, P, 1, ...]

    union_batch = pad_graphs(unions)  # [2, N, ...] — both graphs in one batch

    model_1 = FastEGNN(node_feat_nf=2, hidden_nf=16, virtual_channels=3, n_layers=3)
    model_P = model_1.copy(axis_name=GRAPH_AXIS)
    params = model_1.init(jax.random.PRNGKey(0), union_batch)
    tx = optax.sgd(1e-2)

    mesh = make_mesh(n_graph=Pn, n_data=D, devices=jax.devices()[:4])
    train_P, eval_P = make_distributed_steps(model_P, tx, mesh, mmd_weight=0.0,
                                             mmd_sigma=1.5, mmd_samples=2)
    step_1 = jax.jit(make_train_step(model_1, tx, mmd_weight=0.0, mmd_sigma=1.5,
                                     mmd_samples=2))

    key = jax.random.PRNGKey(9)
    s1, m1 = step_1(TrainState.create(params, tx), union_batch, key)
    sP, mP = train_P(TrainState.create(params, tx), batch_2d, key)

    np.testing.assert_allclose(float(mP["loss"]), float(m1["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(sP.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    ev_1 = jax.jit(make_eval_step(model_1))
    np.testing.assert_allclose(float(eval_P(params, batch_2d)),
                               float(ev_1(params, union_batch)), rtol=1e-5)


def test_sharded_loader_data_parallel_layout(rng):
    """data_parallel=D splits each partition shard's draw into [D, P, B, ...]
    with consecutive graphs of the seeded order going to consecutive data
    shards."""
    parts = [_graph(rng, n=8) for _ in range(2)]
    shards = [GraphDataset([p] * 4) for p in parts]  # P=2 shards, 4 graphs each
    flat = ShardedGraphLoader(shards, batch_size=4, shuffle=False, seed=0)
    dp = ShardedGraphLoader(shards, batch_size=2, shuffle=False, seed=0, data_parallel=2)
    (b_flat,), (b_dp,) = list(flat), list(dp)
    assert b_dp.loc.shape[:3] == (2, 2, 2)  # [D, P, B]
    # graph g of shard p at flat position [p, d*B+b] lands at [d, p, b]
    np.testing.assert_array_equal(b_dp.loc[1, 0, 1], b_flat.loc[0, 3])
    np.testing.assert_array_equal(b_dp.loc[0, 1, 0], b_flat.loc[1, 0])


def test_sharded_loader_with_distributed_step(dist_setup):
    model_1, model_P, params, _, _, mesh, parts = dist_setup
    # loaders over P shards (each shard = a dataset of one partition per graph)
    shards = [GraphDataset([p, p]) for p in parts]
    sl = ShardedGraphLoader(shards, batch_size=2, shuffle=True, seed=1)
    sl.set_epoch(0)
    tx = make_optimizer(1e-3)
    train_P, _ = make_distributed_steps(model_P, tx, mesh, mmd_weight=0.03,
                                        mmd_sigma=1.5, mmd_samples=2)
    state = TrainState.create(params, tx)
    for batch in sl:
        state, metrics = train_P(state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(metrics["loss"]))


def test_distributed_cumsum_matches_scatter(dist_setup):
    """segment_impl='cumsum' under shard_map (vmapped searchsorted/cumsum +
    psum virtual-node sync) matches the scatter lowering on the same
    partition stack."""
    _, model_P, params, _, _, mesh, parts = dist_setup
    n_max = max(p["loc"].shape[0] for p in parts)
    e_max = max(p["edge_index"].shape[1] for p in parts)
    part_batches = [pad_graphs([p], max_nodes=n_max + 2, max_edges=e_max + 8,
                               compute_pair=True) for p in parts]
    stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *part_batches)
    assert stacked.edge_pair is not None

    def fwd_of(m):
        return jax.jit(jax.shard_map(
            lambda pr, b: m.apply(pr, jax.tree.map(lambda x: x[0], b)),
            mesh=mesh, in_specs=(P(), P(GRAPH_AXIS)),
            out_specs=(P(GRAPH_AXIS), P()), check_vma=False,
        ))

    loc_sc, X_sc = fwd_of(model_P)(params, stacked)
    loc_cs, X_cs = fwd_of(model_P.copy(segment_impl="cumsum"))(params, stacked)
    np.testing.assert_allclose(np.asarray(X_cs), np.asarray(X_sc), atol=1e-4)
    np.testing.assert_allclose(np.asarray(loc_cs), np.asarray(loc_sc), atol=1e-4)

    # the ELL lowering rides the same pairing + static max_in_degree
    assert stacked.max_in_degree > 0
    loc_el, X_el = fwd_of(model_P.copy(segment_impl="ell"))(params, stacked)
    np.testing.assert_allclose(np.asarray(X_el), np.asarray(X_sc), atol=1e-5)
    np.testing.assert_allclose(np.asarray(loc_el), np.asarray(loc_sc), atol=1e-5)


def test_metis_partition_quality_pinned():
    """Pin the native multilevel partitioner's quality on a Fluid113K-like
    cloud (VERDICT r2 #5 / r3 #5): since the round-4 multilevel rewrite
    (HEM coarsening + weighted FM + k-way uncoarsening refinement +
    coarsest restarts) metis BEATS kmeans at 113k/8-way (cut 0.0298 vs
    0.0360, docs/artifacts/partition_quality_113k_r4.json); at this test's
    reduced 5k scale allow parity-with-margin. Guards regressions in
    native/partition.cpp."""
    import scripts.partition_quality as pq
    from distegnn_tpu.ops.radius import radius_graph_np

    loc = pq.fluid_cloud(5000, seed=0)
    edge_index = radius_graph_np(loc, pq.RADIUS)
    q = {}
    for method in ("random", "kmeans", "metis"):
        labels = assign_partitions(loc, 8, method, outer_radius=pq.RADIUS, seed=0)
        q[method] = pq.quality(labels, edge_index, 8)
    assert q["metis"]["cut_fraction"] <= 1.15 * q["kmeans"]["cut_fraction"]
    assert q["metis"]["cut_fraction"] <= 0.25 * q["random"]["cut_fraction"]
    assert q["metis"]["node_imbalance"] <= 1.05
