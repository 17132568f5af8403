"""Tests for the data layer: n-body simulator physics invariants, pipeline
caching, loader determinism (SURVEY.md §4: physics-simulator self-checks +
runtime invariants become real tests)."""

import numpy as np
import pytest

from distegnn_tpu.data import (
    ChargedSystem,
    GraphDataset,
    GraphLoader,
    ShardedGraphLoader,
    generate_nbody_files,
    process_nbody_cutoff,
    simulate_trajectory,
)


def test_simulator_momentum_isolated():
    # isolated charged balls: pairwise equal-and-opposite forces conserve momentum
    rng = np.random.default_rng(0)
    sys_ = ChargedSystem(rng, n_isolated=20, delta_t=0.001)
    p0 = sys_.V.sum(axis=0)
    for _ in range(200):
        sys_.step()
    p1 = sys_.V.sum(axis=0)
    np.testing.assert_allclose(p0, p1, atol=1e-8)


def test_simulator_stick_constraints_preserved():
    rng = np.random.default_rng(1)
    sys_ = ChargedSystem(rng, n_isolated=4, n_stick=3, delta_t=0.001)
    lengths = [s["length"] for s in sys_.sticks]
    for _ in range(500):
        sys_.step()
    sys_.check()  # raises on violation (reference physical_objects.py:135-145)
    for s, l0 in zip(sys_.sticks, lengths):
        i0, i1 = s["idx"]
        assert abs(np.linalg.norm(sys_.X[i1] - sys_.X[i0]) - l0) < 1e-6


def test_simulator_hinge_constraints_preserved():
    rng = np.random.default_rng(2)
    sys_ = ChargedSystem(rng, n_isolated=2, n_hinge=2, delta_t=0.001)
    for _ in range(300):
        sys_.step()
    sys_.check()


def test_trajectory_shapes():
    rng = np.random.default_rng(3)
    loc, vel, charges, edges = simulate_trajectory(rng, length=500, sample_freq=100, n_isolated=10)
    assert loc.shape == (5, 10, 3)
    assert vel.shape == (5, 10, 3)
    assert charges.shape == (10, 1)
    np.testing.assert_allclose(edges, charges @ charges.T)


@pytest.fixture(scope="module")
def nbody_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("nbody")
    generate_nbody_files(
        str(d / "nbody_10"), n_isolated=10, num_train=6, num_valid=3, num_test=3,
        length=500, sample_freq=100, seed=7,
    )
    return str(d)


def test_generate_reference_file_layout(nbody_dir):
    import os
    loc = np.load(os.path.join(nbody_dir, "nbody_10", "loc_train_charged10_0_0_1.npy"))
    assert loc.shape == (6, 5, 10, 3)


def test_process_and_load(nbody_dir):
    paths = process_nbody_cutoff(nbody_dir, "nbody_10", max_samples=6, radius=-1,
                                 frame_0=1, frame_T=3, cutoff_rate=0.0, tag="charged10_0_0_1")
    ds = GraphDataset(paths[0])
    assert len(ds) == 6
    g = ds[0]
    assert g["node_feat"].shape == (10, 2)
    assert g["edge_index"].shape == (2, 90)  # full graph: 10*9
    assert g["edge_attr"].shape == (90, 2)
    # caching: second call returns same paths without recompute
    assert process_nbody_cutoff(nbody_dir, "nbody_10", max_samples=6, radius=-1,
                                frame_0=1, frame_T=3, cutoff_rate=0.0, tag="charged10_0_0_1") == paths


def test_cutoff_rate_drops_edges(nbody_dir):
    paths = process_nbody_cutoff(nbody_dir, "nbody_10", max_samples=6, radius=-1,
                                 frame_0=1, frame_T=3, cutoff_rate=0.5, tag="charged10_0_0_1")
    ds = GraphDataset(paths[0])
    assert ds[0]["edge_index"].shape[1] == 45  # int(90 * 0.5)


def test_loader_determinism_and_drop_last(nbody_dir):
    paths = process_nbody_cutoff(nbody_dir, "nbody_10", max_samples=6, radius=-1,
                                 frame_0=1, frame_T=3, cutoff_rate=0.0, tag="charged10_0_0_1")
    ds = GraphDataset(paths[0])
    la = GraphLoader(ds, batch_size=4, shuffle=True, seed=5)
    lb = GraphLoader(ds, batch_size=4, shuffle=True, seed=5)
    la.set_epoch(3); lb.set_epoch(3)
    assert len(la) == 1  # drop_last: 6 // 4
    a = next(iter(la)); b = next(iter(lb))
    np.testing.assert_array_equal(np.asarray(a.loc), np.asarray(b.loc))  # identical across "hosts"
    la.set_epoch(4)
    c = next(iter(la))
    assert not np.array_equal(np.asarray(a.loc), np.asarray(c.loc))  # reshuffled next epoch


def test_sharded_loader_stacks_partitions(nbody_dir):
    paths = process_nbody_cutoff(nbody_dir, "nbody_10", max_samples=6, radius=-1,
                                 frame_0=1, frame_T=3, cutoff_rate=0.0, tag="charged10_0_0_1")
    ds = GraphDataset(paths[0])
    sl = ShardedGraphLoader([ds, ds], batch_size=2, shuffle=False)
    batch = next(iter(sl))
    assert batch.loc.shape[0] == 2  # leading partition axis
    np.testing.assert_array_equal(batch.loc[0], batch.loc[1])


@pytest.mark.parametrize("sharded", [False, True], ids=["train_scan", "train_stream"])
def test_loader_call_forms_of_the_benchmark_drivers(nbody_dir, sharded):
    """benchmarks/drivers/train_scan.py:62-65 and train_stream.py:160-163
    construct the loaders with exactly these keywords (tier-1 does not run
    benchmarks/tests): the forms construct and yield a batch."""
    paths = process_nbody_cutoff(nbody_dir, "nbody_10", max_samples=6, radius=-1,
                                 frame_0=1, frame_T=3, cutoff_rate=0.0, tag="charged10_0_0_1")
    ds = GraphDataset(paths[0])
    if sharded:
        loader = ShardedGraphLoader(
            [ds, ds], 2, shuffle=True, seed=3,
            node_bucket=8, edge_bucket=128, data_parallel=1,
            edge_block=0, split_remote=False, pairing=None)
        lead = (2, 2)       # [P, B]
    else:
        loader = GraphLoader(
            ds, 2, shuffle=True, seed=3,
            node_bucket=8, edge_bucket=128,
            edge_block=0, split_remote=False, pairing=None)
        lead = (2,)
    batch = next(iter(loader))
    assert batch.loc.shape == lead + (16, 3)
    assert batch.edge_block == 0 and batch.edge_pair is None
