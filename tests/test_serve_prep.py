"""Session-affinity prep cache (distegnn_tpu/serve/prep.py) and the online
blocked re-pack (ops.blocked.repack_blocked): hits are bitwise-identical to
misses, topology changes invalidate cleanly, eviction is LRU, and the
re-packed layout aggregates exactly like the raw edge list."""

import numpy as np
import pytest

from distegnn_tpu.ops.blocked import max_block_degree, repack_blocked
from distegnn_tpu.serve import (BucketLadder, ServeMetrics, SessionPrepCache,
                                synthetic_graph)

pytestmark = pytest.mark.serve


def _ladder():
    return BucketLadder(node_floor=64, edge_floor=256, growth=2.0,
                        node_multiple=8, edge_multiple=128,
                        max_nodes=4096, max_edges=65536)


def _assert_graph_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        if a[k] is None:
            assert b[k] is None
        else:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                          err_msg=f"key {k!r} differs")


# -------------------------------------------------------------- plain plans

def test_plain_hit_bitwise_identical_to_miss():
    cache = SessionPrepCache(4, ladder=_ladder(), metrics=ServeMetrics())
    g = synthetic_graph(40, seed=1)
    miss = cache.prepare("s1", g)
    hit = cache.prepare("s1", g)
    assert miss.hit is False and hit.hit is True
    assert miss.bucket == hit.bucket and miss.perm is None
    _assert_graph_equal(miss.graph, hit.graph)
    snap = cache.metrics.snapshot()
    assert snap["session_hits"] == 1 and snap["session_misses"] == 1


def test_plain_hit_with_moved_positions_not_invalidated():
    """Frames move, topology doesn't: new positions on the same edge_index
    stay a HIT, and the fresh positions flow through to the prepared dict."""
    cache = SessionPrepCache(4, ladder=_ladder())
    g = synthetic_graph(40, seed=2)
    cache.prepare("s", g)
    g2 = dict(g)
    g2["loc"] = g["loc"] + np.float32(0.01)
    res = cache.prepare("s", g2)
    assert res.hit is True
    np.testing.assert_array_equal(res.graph["loc"], g2["loc"])


def test_topology_change_clean_miss_not_eviction():
    m = ServeMetrics()
    cache = SessionPrepCache(4, ladder=_ladder(), metrics=m)
    g = synthetic_graph(40, seed=3)
    cache.prepare("s", g)
    g2 = dict(g)
    g2["edge_index"] = g["edge_index"][:, :-2]   # drop two edges
    g2["edge_attr"] = g["edge_attr"][:-2]
    res = cache.prepare("s", g2)
    assert res.hit is False                      # stale plan never replayed
    snap = m.snapshot()
    assert snap["session_misses"] == 2 and snap["session_evictions"] == 0
    assert len(cache) == 1                       # replaced in place


def test_lru_eviction_counts_and_drops_oldest():
    m = ServeMetrics()
    cache = SessionPrepCache(2, ladder=_ladder(), metrics=m)
    gs = {f"s{k}": synthetic_graph(40, seed=10 + k) for k in range(3)}
    cache.prepare("s0", gs["s0"])
    cache.prepare("s1", gs["s1"])
    cache.prepare("s2", gs["s2"])                # evicts s0
    assert len(cache) == 2
    assert m.snapshot()["session_evictions"] == 1
    assert cache.prepare("s0", gs["s0"]).hit is False   # s0 gone
    assert cache.prepare("s2", gs["s2"]).hit is True    # s2 kept


# ------------------------------------------------------------ blocked plans

def test_blocked_hit_bitwise_identical_and_stamped():
    cache = SessionPrepCache(4, ladder=_ladder(),
                             layout_opts={"edge_block": 256})
    g = synthetic_graph(90, seed=4)
    miss = cache.prepare("s", g)
    hit = cache.prepare("s", g)
    assert miss.hit is False and hit.hit is True
    _assert_graph_equal(miss.graph, hit.graph)
    out = miss.graph
    assert out["_blockified"] is not None        # pad_graphs prep is a no-op
    assert out["_edge_pair"] is None
    assert miss.perm is not None and sorted(miss.perm) == list(range(90))
    # the perm is undone by indexing: permuted loc at inverse matches raw
    np.testing.assert_array_equal(out["loc"], np.asarray(g["loc"])[miss.perm])


def test_blocked_plan_stamp_is_the_one_pad_batch_derives():
    """The plan mirrors ``pad_batch``'s node snap (bucket.n up to a block
    multiple, no floor on the block count): the prepared dict goes through
    ``pad_batch`` with its stamp intact, i.e. without a second blockify."""
    ladder = _ladder()
    cache = SessionPrepCache(4, ladder=ladder, layout_opts={"edge_block": 256})
    g = synthetic_graph(90, seed=4)            # one block: bucket.n = 128
    res = cache.prepare("s", g)
    N, epb, block = res.graph["_blockified"]
    assert (N, block) == (256, 256)
    batch, _ = ladder.pad_batch([res.graph], res.bucket, 1, edge_block=256)
    assert (batch.max_nodes, batch.edges_per_block) == (N, epb)
    np.testing.assert_array_equal(np.asarray(batch.edge_index[0]),
                                  res.graph["edge_index"])
    np.testing.assert_array_equal(np.asarray(batch.edge_mask[0]),
                                  res.graph["_edge_mask"])


def test_blocked_plan_aggregation_parity():
    """The re-packed edge list computes the same per-node aggregate as the
    raw one: sum of edge_attr into rows, masked padding contributing zero."""
    g = synthetic_graph(90, seed=5)
    cache = SessionPrepCache(2, ladder=_ladder(),
                             layout_opts={"edge_block": 256})
    res = cache.prepare("s", g)
    out = res.graph
    ei, ea = np.asarray(g["edge_index"]), np.asarray(g["edge_attr"])
    # raw aggregate, relabeled into the plan's node order
    inv = np.empty_like(res.perm)
    inv[res.perm] = np.arange(len(res.perm))
    raw = np.zeros((len(res.perm), ea.shape[1]), np.float32)
    np.add.at(raw, inv[ei[0]], ea)
    packed = np.zeros_like(raw)
    m = np.asarray(out["_edge_mask"], bool)
    rows = np.asarray(out["edge_index"][0])[m]
    assert (rows < len(res.perm)).all()   # real rows are real nodes
    np.add.at(packed, rows, np.asarray(out["edge_attr"])[m])
    np.testing.assert_allclose(packed, raw, atol=1e-5, rtol=0)


def test_repack_blocked_invariants_direct():
    """repack_blocked alone: rows land inside their block's slice, padding
    slots are self-loops on the block's last node, and apply_edge_attr moves
    attrs to exactly the slots their edges moved to."""
    rng = np.random.default_rng(0)
    n, e, block, epb = 512, 900, 256, 512
    ei = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)]).astype(np.int32)
    plan = repack_blocked(ei, None, n_nodes_padded=n, epb=epb, block=block)
    nb = n // block
    out_ei = np.asarray(plan.edge_index)
    mask = np.asarray(plan.edge_mask, bool)
    assert out_ei.shape == (2, nb * epb) and mask.sum() == e
    for b in range(nb):
        sl = slice(b * epb, (b + 1) * epb)
        rows = out_ei[0, sl]
        assert ((rows >= b * block) & (rows < (b + 1) * block)).all()
        # padding slots: row == col == the block's last node
        pad = ~mask[sl]
        assert (rows[pad] == (b + 1) * block - 1).all()
        assert (out_ei[1, sl][pad] == (b + 1) * block - 1).all()
    # attr transport: each real slot carries its source edge's attr
    attr = rng.normal(size=(e, 3)).astype(np.float32)
    moved = plan.apply_edge_attr(attr)
    # multiset equality per (row, col): sort both sides canonically
    raw = sorted(map(tuple, np.concatenate(
        [ei.T.astype(np.float32), attr], axis=1).tolist()))
    packed = sorted(map(tuple, np.concatenate(
        [out_ei.T[mask].astype(np.float32), moved[mask]], axis=1).tolist()))
    assert raw == packed
    # epb honored the block-degree floor
    assert epb >= max_block_degree(np.sort(ei[0]), n, block)
