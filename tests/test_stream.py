"""Out-of-core streamed pipeline gates (distegnn_tpu/data/stream.py).

The contract under test: a streamed epoch is BITWISE-identical to the
in-memory epoch (same seed, same order, same padded batches) while host
residency stays bounded by the shard LRU; a prefetch producer crash reaches
the trainer as a typed error, never a hang; the skew-balance partition pass
caps the measured work imbalance; and a truncated read (the torn-NFS shape)
is healed by the full-read retry instead of escaping it.
"""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys

import jax
import numpy as np
import pytest

from distegnn_tpu.data import (
    GraphDataset,
    GraphLoader,
    PrefetchCrashError,
    PrefetchLoader,
    ShardChecksumError,
    StreamedGraphDataset,
    open_dataset,
    write_shards,
)
from distegnn_tpu.ops.radius import radius_graph_np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_graphs(n_graphs=10, n_lo=20, n_hi=48, seed=0, with_optional=True):
    rng = np.random.default_rng(seed)
    graphs = []
    for _ in range(n_graphs):
        n = int(rng.integers(n_lo, n_hi))
        loc = rng.normal(size=(n, 3)).astype(np.float32)
        vel = rng.normal(size=(n, 3)).astype(np.float32)
        ei = radius_graph_np(loc, 1.5).astype(np.int32)
        dist = np.linalg.norm(loc[ei[0]] - loc[ei[1]], axis=1)
        graphs.append({
            "node_feat": np.linalg.norm(vel, axis=1, keepdims=True).astype(np.float32),
            "node_attr": (rng.normal(size=(n, 2)).astype(np.float32)
                          if with_optional else None),
            "loc": loc,
            "vel": vel,
            "target": (loc + 0.1 * vel if with_optional else None),
            "loc_mean": loc.mean(axis=0),
            "edge_index": ei,
            "edge_attr": np.repeat(dist[:, None], 2, axis=1).astype(np.float32),
        })
    return graphs


def _assert_graph_equal(a, b):
    for k in ("node_feat", "node_attr", "loc", "vel", "target", "loc_mean",
              "edge_index", "edge_attr"):
        if b.get(k) is None:
            assert a.get(k) is None, k
        else:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)


@pytest.mark.io
def test_manifest_round_trip_bitwise(tmp_path):
    graphs = _make_graphs(10)
    manifest = write_shards(graphs, str(tmp_path), shard_size=3)
    assert manifest["n_graphs"] == 10
    assert len(manifest["shards"]) == 4          # 3+3+3+1
    assert manifest["shards"][-1]["n_graphs"] == 1
    on_disk = json.load(open(tmp_path / "manifest.json"))
    assert on_disk == manifest
    ds = StreamedGraphDataset(str(tmp_path))
    assert len(ds) == 10
    assert ds.size_maxima() == GraphDataset(graphs).size_maxima()
    for i in range(10):
        _assert_graph_equal(ds[i], graphs[i])


@pytest.mark.io
def test_optional_fields_absent_round_trip(tmp_path):
    graphs = _make_graphs(4, with_optional=False)
    write_shards(graphs, str(tmp_path), shard_size=2)
    ds = StreamedGraphDataset(str(tmp_path))
    for i in range(4):
        assert ds[i]["node_attr"] is None and ds[i]["target"] is None
        _assert_graph_equal(ds[i], graphs[i])


@pytest.mark.io
def test_nonuniform_optional_fields_rejected(tmp_path):
    graphs = _make_graphs(4)
    graphs[2]["target"] = None
    with pytest.raises(ValueError, match="present in some graphs"):
        write_shards(graphs, str(tmp_path))


@pytest.mark.io
def test_checksum_reject(tmp_path):
    graphs = _make_graphs(6)
    write_shards(graphs, str(tmp_path), shard_size=2)
    shard = tmp_path / "shard_00001.npz"
    data = bytearray(shard.read_bytes())
    data[len(data) // 2] ^= 0xFF  # one flipped bit deep in the payload
    shard.write_bytes(bytes(data))
    ds = StreamedGraphDataset(str(tmp_path))
    _assert_graph_equal(ds[0], graphs[0])  # shard 0 untouched
    with pytest.raises(ShardChecksumError):
        ds[2]  # first graph of the corrupted shard
    clean = StreamedGraphDataset(str(tmp_path), verify=False)
    assert clean.manifest["format"] == "distegnn-shards-v1"


@pytest.mark.io
def test_shard_lru_bound_random_access(tmp_path):
    graphs = _make_graphs(12)
    write_shards(graphs, str(tmp_path), shard_size=2)  # 6 shards
    ds = StreamedGraphDataset(str(tmp_path), cache_shards=2)
    rng = np.random.default_rng(3)
    for i in rng.integers(0, 12, size=60):
        _assert_graph_equal(ds[int(i)], graphs[int(i)])
        assert ds.open_shards <= 2  # RSS proxy: never more than the cache
    assert ds.open_shards == 2


@pytest.mark.io
def test_streamed_epoch_bitwise_parity(tmp_path):
    """Full shuffled epoch (two epochs) through GraphLoader: streamed batches
    must be bitwise-identical to in-memory batches — the epoch order lives in
    the seeded permutation, not the residency model."""
    graphs = _make_graphs(10)
    write_shards(graphs, str(tmp_path), shard_size=3)
    mem = GraphLoader(GraphDataset(graphs), 2, shuffle=True, seed=7)
    st = GraphLoader(StreamedGraphDataset(str(tmp_path), cache_shards=2),
                     2, shuffle=True, seed=7)
    assert len(mem) == len(st) == 5
    for epoch in range(2):
        mem.set_epoch(epoch)
        st.set_epoch(epoch)
        for a, b in zip(mem, st):
            jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.io
@pytest.mark.slow
def test_streamed_epoch_parity_blocked(tmp_path):
    """The expensive lane: blocked layout over a streamed dataset — the
    loader's dataset scan (edges-per-block) and blockify must see identical
    graphs through the LRU."""
    graphs = _make_graphs(8, n_lo=40, n_hi=80, seed=1)
    write_shards(graphs, str(tmp_path), shard_size=2)
    kw = dict(batch_size=2, shuffle=True, seed=11, edge_block=8, edge_tile=8)
    mem = GraphLoader(GraphDataset(graphs), **kw)
    st = GraphLoader(StreamedGraphDataset(str(tmp_path), cache_shards=2), **kw)
    for epoch in range(2):
        mem.set_epoch(epoch)
        st.set_epoch(epoch)
        for a, b in zip(mem, st):
            jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.io
def test_open_dataset_routes_by_source(tmp_path):
    graphs = _make_graphs(4)
    write_shards(graphs, str(tmp_path / "shards"), shard_size=2)
    pkl = tmp_path / "data.pkl"
    pkl.write_bytes(pickle.dumps(graphs))
    assert isinstance(open_dataset(str(tmp_path / "shards")), StreamedGraphDataset)
    assert isinstance(open_dataset(str(pkl)), GraphDataset)
    assert isinstance(open_dataset(graphs), GraphDataset)


@pytest.mark.io
def test_in_memory_list_adopted_without_copy():
    graphs = _make_graphs(3)
    ds = GraphDataset(graphs)
    assert ds.graphs is graphs  # the double-memory spike fix
    # morton still must not mutate the caller's list
    ds2 = GraphDataset(graphs, node_order="morton")
    assert ds2.graphs is not graphs
    _assert_graph_equal(graphs[0], _make_graphs(3)[0])


@pytest.mark.io
def test_host_bytes_gauge_logged():
    from distegnn_tpu import obs

    gauge = obs.get_registry().gauge("data/host_bytes")
    before = gauge.value
    graphs = _make_graphs(3)
    GraphDataset(graphs)
    expected = sum(v.nbytes for g in graphs for v in g.values()
                   if isinstance(v, np.ndarray))
    assert gauge.value >= before + expected


@pytest.mark.io
def test_prefetch_bitwise_parity_and_gauges(tmp_path):
    from distegnn_tpu import obs

    graphs = _make_graphs(8)
    write_shards(graphs, str(tmp_path), shard_size=3)
    ds = StreamedGraphDataset(str(tmp_path), cache_shards=2)
    plain = GraphLoader(GraphDataset(graphs), 2, shuffle=True, seed=5)
    pf = PrefetchLoader(GraphLoader(ds, 2, shuffle=True, seed=5), depth=2)
    assert len(pf) == len(plain)
    pf.set_epoch(1)
    plain.set_epoch(1)
    got = list(pf)
    want = list(plain)
    assert len(got) == len(want) == 4
    for a, b in zip(want, got):
        jax.tree.map(np.testing.assert_array_equal, a, b)
    assert obs.get_registry().gauge("data/prefetch_depth").value == 2
    # depth=0 degrades to the synchronous blocking path, same batches
    pf0 = PrefetchLoader(GraphLoader(ds, 2, shuffle=True, seed=5), depth=0)
    pf0.set_epoch(1)
    for a, b in zip(want, pf0):
        jax.tree.map(np.testing.assert_array_equal, a, b)


@pytest.mark.io
def test_prefetch_crash_is_typed_not_hang():
    class DyingLoader:
        def set_epoch(self, e):
            pass

        def __len__(self):
            return 3

        def __iter__(self):
            yield {"x": np.zeros(2)}
            raise OSError("disk fell off mid-epoch")

    it = iter(PrefetchLoader(DyingLoader(), depth=2))
    next(it)  # the batch produced before the crash still arrives
    with pytest.raises(PrefetchCrashError) as ei:
        next(it)
    assert isinstance(ei.value.__cause__, OSError)


@pytest.mark.io
def test_prefetch_abandoned_iteration_joins_producer(tmp_path):
    import threading

    graphs = _make_graphs(8)
    write_shards(graphs, str(tmp_path), shard_size=2)
    loader = GraphLoader(StreamedGraphDataset(str(tmp_path)), 1, shuffle=False)
    before = threading.active_count()
    it = iter(PrefetchLoader(loader, depth=1))
    next(it)
    it.close()  # trainer bails mid-epoch (early stop, crash, ^C)
    assert threading.active_count() <= before + 1  # producer joined, not leaked


@pytest.mark.io
def test_partition_balance_on_skewed_graph():
    """Dense cluster + sparse halo: the spatial partitioners hand one part
    the hot spot; the balance pass must bring max/mean work under 1.15."""
    from distegnn_tpu.data.partition import (
        balance_partitions, assign_partitions, imbalance_ratio, node_work,
        partition_work, split_graph,
    )

    rng = np.random.default_rng(0)
    dense = rng.normal(scale=0.15, size=(1200, 3))
    sparse = rng.uniform(-4, 4, size=(1800, 3))
    pos = np.concatenate([dense, sparse]).astype(np.float32)
    inner = 0.35
    labels = assign_partitions(pos, 8, "metis", outer_radius=1.0, seed=0)
    work = node_work(pos, inner)
    before = imbalance_ratio(partition_work(labels, work, 8))
    assert before > 1.15  # the skew is real, or the gate proves nothing
    balanced, b, a = balance_partitions(pos, labels, 8, inner)
    assert b == pytest.approx(before)
    assert a <= 1.15
    after = imbalance_ratio(partition_work(balanced, work, 8))
    assert after == pytest.approx(a)
    # end to end through split_graph: measured LOCAL work (nodes + rebuilt
    # edges) also lands under the gate
    g = {
        "node_feat": np.ones((pos.shape[0], 1), np.float32),
        "node_attr": None, "loc": pos,
        "vel": np.zeros_like(pos), "target": None,
        "loc_mean": pos.mean(0),
        "edge_index": np.zeros((2, 0), np.int32),
        "edge_attr": np.zeros((0, 2), np.float32),
    }
    parts = split_graph(g, 8, "metis", inner_radius=inner, outer_radius=1.0,
                        seed=0, balance=True)
    local = np.array([p["loc"].shape[0] + p["edge_index"].shape[1]
                      for p in parts], np.float64)
    assert imbalance_ratio(local) <= 1.15


@pytest.mark.io
def test_truncated_read_healed_by_retry(tmp_path):
    """The torn-NFS shape: open() succeeds, the payload is short. One bad
    read must heal inside the bounded retry; persistent truncation must
    still fail hard with the underlying error."""
    from distegnn_tpu.data.loader import _OPEN_ATTEMPTS
    from distegnn_tpu.testing.faults import truncated_read

    graphs = _make_graphs(4)
    pkl = tmp_path / "data.pkl"
    pkl.write_bytes(pickle.dumps(graphs))
    with truncated_read(fail_times=1) as calls:
        ds = GraphDataset(str(pkl))
    assert calls["n"] >= 2  # one truncated read + one clean retry
    _assert_graph_equal(ds[1], graphs[1])
    with truncated_read(fail_times=_OPEN_ATTEMPTS * 2):
        with pytest.raises((EOFError, pickle.UnpicklingError, ValueError, OSError)):
            GraphDataset(str(pkl))


@pytest.mark.io
def test_truncated_shard_read_healed_by_retry(tmp_path):
    from distegnn_tpu.testing.faults import truncated_read

    graphs = _make_graphs(4)
    write_shards(graphs, str(tmp_path), shard_size=2)
    ds = StreamedGraphDataset(str(tmp_path), cache_shards=1)
    with truncated_read(fail_times=1) as calls:
        _assert_graph_equal(ds[0], graphs[0])
    assert calls["n"] >= 2  # CRC caught the short read, retry healed it


@pytest.mark.io
def test_shard_dataset_script_round_trip(tmp_path):
    graphs = _make_graphs(5)
    pkl = tmp_path / "processed.pkl"
    pkl.write_bytes(pickle.dumps(graphs))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "shard_dataset.py"),
         "--input", str(pkl), "--out", str(tmp_path / "shards"),
         "--shard-size", "2"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_graphs"] == 5 and rec["n_shards"] == 3
    ds = open_dataset(str(tmp_path / "shards"))
    assert isinstance(ds, StreamedGraphDataset)
    for i in range(5):
        _assert_graph_equal(ds[i], graphs[i])
