"""Tiled giant-scene serving (ops/tiling.py + serve/tiled.py + the engine /
queue / gateway dispatch): exact parity of the tiled forward against the
monolithic engine, the byte-bounded session
prep cache, the BucketLadder rung boundary contract, and — slow lane — a
million-node scene served end-to-end over HTTP through ONE compiled tile
executable (CompileWatcher-certified, no recompile after warmup, no 413)."""

import json
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.obs.metrics import MetricsRegistry
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.ops.tiling import plan_tiles
from distegnn_tpu.serve import (BucketLadder, BucketOverflowError,
                                InferenceEngine, RequestQueue, ServeMetrics,
                                SessionPrepCache, TiledExecutor,
                                TiledOverflowError, synthetic_graph)
from distegnn_tpu.serve.prep import nbytes_of
from distegnn_tpu.serve.registry import ModelRegistry
from distegnn_tpu.serve.transport import Gateway

pytestmark = pytest.mark.serve


def _model(n_layers=2):
    return FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                    virtual_channels=2, n_layers=n_layers)


def _norm_err(pred, ref):
    return float(np.abs(pred - ref).max() / np.abs(ref).max())


# ------------------------------------------------------------ tile planning

def test_plan_tiles_covers_every_node_and_edge_once():
    g = synthetic_graph(500, radius=0.2, seed=11)
    plan = plan_tiles(g["edge_index"], g["loc"], g["edge_attr"],
                      tile_nodes=128, halo_floor=16, edge_floor=256)
    assert plan.n_tiles >= 2
    # the tiles partition [0, n) in Morton order
    covered = sorted((s.start, s.stop) for s in plan.tiles)
    assert covered[0][0] == 0 and covered[-1][1] == 500
    assert all(a[1] == b[0] for a, b in zip(covered, covered[1:]))
    # perm/inv_perm are inverse bijections
    assert (plan.perm[plan.inv_perm] == np.arange(500)).all()
    # every edge lands in exactly one tile (receiver's tile)
    assert sum(s.edge_index.shape[1] for s in plan.tiles) \
        == g["edge_index"].shape[1]
    assert 0.0 < plan.halo_fraction < 1.0
    # the single-executable invariant: ONE padded shape serves every tile
    assert all(s.n_halo <= plan.halo_pad for s in plan.tiles)
    assert all(s.edge_index.shape[1] <= plan.edge_pad for s in plan.tiles)
    assert plan.padded_nodes == plan.tile_nodes + plan.halo_pad
    assert isinstance(plan.shape_key, tuple)


# ----------------------------------------------------- tiled forward parity

@pytest.mark.parametrize("layout_opts", [None, {"edge_block": 32}],
                         ids=["plain_engine", "blocked_engine"])
def test_tiled_parity_plain(layout_opts):
    """Tiled executor == monolithic forward (1e-5 scale-normalized), halo
    edges and virtual-node aggregation included. An engine that serves its
    ladder batches blocked (``layout_opts``) still tiles giant scenes in the
    plain layout: the tile plan has no block fields."""
    model = _model()
    g = synthetic_graph(400, radius=0.2, seed=5)
    tight = pad_graphs([g], node_bucket=1, edge_bucket=1)
    params = model.init(jax.random.PRNGKey(0), tight)
    ref = np.asarray(model.apply(params, tight)[0])[0]

    eng = InferenceEngine(model, params, layout_opts=layout_opts)
    tx = TiledExecutor(eng, {"tile_nodes": 128, "halo_floor": 16,
                             "edge_floor": 256})
    plan = tx.plan(dict(g))
    assert plan.shape_key == (plan.tile_nodes + plan.halo_pad, plan.edge_pad)
    out = tx.predict(dict(g), plan=plan)
    assert out["tiles"] >= 2          # actually exercised halo exchange
    assert _norm_err(out["prediction"], ref) <= 1e-5


def test_tiled_overflow_is_typed_413_material():
    model = _model()
    g = synthetic_graph(50, seed=0)
    params = model.init(jax.random.PRNGKey(0),
                        pad_graphs([g], node_bucket=1, edge_bucket=1))
    tx = TiledExecutor(InferenceEngine(model, params),
                       {"max_nodes": 40, "tile_nodes": 16})
    with pytest.raises(TiledOverflowError, match="serve.tiled.max_nodes"):
        tx.predict(dict(g))
    # subclasses BucketOverflowError: the gateway's 413 mapping rides free
    assert issubclass(TiledOverflowError, BucketOverflowError)


# ------------------------------------------- bucket ladder boundaries (sat 2)

def test_rung_exact_powers_of_growth():
    """Exact powers of the growth factor must land ON their rung, not one
    above — the float-log fixup at serve/buckets.py:_rung."""
    lad = BucketLadder(node_floor=64, edge_floor=256, growth=2.0,
                       node_multiple=8, edge_multiple=128)
    for k in range(0, 8):
        size = 64 * 2 ** k
        b = lad.bucket_for(size, 256)
        assert b.n == size, f"exact power {size} -> rung {b.n}"
    # one past the power steps up exactly one rung
    b = lad.bucket_for(64 * 2 ** 3 + 1, 256)
    assert b.n == 64 * 2 ** 4


def test_rung_admits_sizes_equal_to_caps():
    lad = BucketLadder(max_nodes=65536, max_edges=1 << 20)
    b = lad.bucket_for(65536, 1 << 20)     # == cap on both axes: admitted
    assert b.n == 65536 and b.e == 1 << 20


def test_rung_overflow_message_names_tiled_fallback():
    lad = BucketLadder(max_nodes=65536, max_edges=1 << 20)
    with pytest.raises(BucketOverflowError) as ei:
        lad.bucket_for(65537, 256)
    msg = str(ei.value)
    assert "serve.max_nodes" in msg and "serve.tiled" in msg
    with pytest.raises(BucketOverflowError) as ei:
        lad.bucket_for(64, (1 << 20) + 1)
    assert "serve.max_edges" in str(ei.value)


# --------------------------------------- byte-bounded session cache (sat 1)

def test_session_cache_bytes_evicts_to_fit():
    """serve.session_cache_bytes: nbytes accounting, LRU evict-to-fit, and
    the serve/session_cache_bytes gauge."""
    metrics = ServeMetrics()
    cache = SessionPrepCache(capacity=64, ladder=BucketLadder(),
                             metrics=metrics, max_bytes=4096)
    plan_bytes = 1500  # three fit (4500 > 4096 -> evict oldest)

    def build():
        return np.zeros(plan_bytes, np.uint8)

    g = synthetic_graph(10, seed=0)
    for sid in ("a", "b", "c"):
        cache.prepare_tile(sid, g, build)
    assert len(cache) == 2                 # "a" evicted to fit "c"
    assert cache.bytes_used <= 4096
    _, hit_b = cache.prepare_tile("b", g, build)
    assert hit_b is True
    _, hit_a = cache.prepare_tile("a", g, build)   # must rebuild
    assert hit_a is False
    snap = metrics.registry.gauge("serve/session_cache_bytes").value
    assert snap == cache.bytes_used > 0
    assert metrics.registry.counter("serve/session_evictions").value >= 2

    # same-session replacement is NOT an eviction and never over-counts
    ev_before = metrics.registry.counter("serve/session_evictions").value
    g2 = synthetic_graph(12, seed=1)       # new topology -> rebuild in place
    cache.prepare_tile("a", g2, build)
    assert metrics.registry.counter("serve/session_evictions").value \
        == ev_before


def test_nbytes_of_walks_nested_plans():
    arr = np.zeros((10, 3), np.float32)
    assert nbytes_of(arr) == 120
    assert nbytes_of({"a": arr, "b": [arr, arr]}) == 360
    assert nbytes_of(("fp", arr)) == 120   # non-arrays cost nothing
    assert nbytes_of(None) == 0


def test_prepare_tile_fingerprint_invalidation():
    cache = SessionPrepCache(capacity=8, ladder=BucketLadder())
    g = synthetic_graph(20, seed=3)
    calls = []

    def build():
        calls.append(1)
        return {"plan": np.ones(4)}

    p1, hit1 = cache.prepare_tile("s", g, build)
    p2, hit2 = cache.prepare_tile("s", g, build)
    assert (hit1, hit2) == (False, True) and len(calls) == 1
    g2 = dict(g)
    g2["edge_index"] = g["edge_index"][:, :-2]   # topology changed
    _, hit3 = cache.prepare_tile("s", g2, build)
    assert hit3 is False and len(calls) == 2


# --------------------------------------------------- gateway dispatch (e2e)

def _post(url, payload, timeout=180.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _payload(g, **extra):
    p = {"positions": g["loc"].tolist(), "velocities": g["vel"].tolist(),
         "node_feat": g["node_feat"].tolist(),
         "edge_index": g["edge_index"].tolist(),
         "edge_attr": g["edge_attr"].tolist()}
    p.update(extra)
    return p


@pytest.fixture()
def tiled_gateway():
    """Small ladder (cap 64) + tiled executor: a 300-node scene is above the
    cap and must dispatch to the tiled path instead of 413."""
    model = _model()
    g = synthetic_graph(300, radius=0.2, seed=7)
    tight = pad_graphs([g], node_bucket=1, edge_bucket=1)
    params = model.init(jax.random.PRNGKey(0), tight)
    ref = np.asarray(model.apply(params, tight)[0])[0]
    metrics = ServeMetrics()
    eng = InferenceEngine(model, params, max_batch=2, metrics=metrics,
                          ladder=BucketLadder(max_nodes=64, max_edges=4096),
                          session_cache=4, session_cache_bytes=1 << 22,
                          tiled={"tile_nodes": 96, "halo_floor": 16,
                                 "edge_floor": 256})
    q = RequestQueue(eng, request_timeout_ms=120_000.0, metrics=metrics)
    reg = ModelRegistry.single("nbody", eng, q, feat_nf=1, edge_attr_nf=2)
    reg.start()
    gw = Gateway(reg, port=0, metrics_registry=MetricsRegistry())
    t = threading.Thread(target=gw.serve_forever, daemon=True)
    t.start()
    yield gw, g, ref, eng
    gw.drain()
    t.join(timeout=30.0)
    gw.close()


def test_gateway_dispatches_above_cap_to_tiled(tiled_gateway):
    gw, g, ref, eng = tiled_gateway
    status, body = _post(gw.url("/v1/models/nbody/predict"),
                         _payload(g, session_id="sc"))
    resp = json.loads(body)
    assert status == 200, body[:400]
    pred = np.asarray(resp["prediction"], np.float32)
    assert _norm_err(pred, ref) <= 1e-5
    assert resp["tiled"]["tiles"] >= 2
    assert 0.0 < resp["tiled"]["halo_fraction"] < 1.0
    assert resp["session"]["hit"] is False
    # repeat: the session cache serves the tile plan back
    status, body = _post(gw.url("/v1/models/nbody/predict"),
                         _payload(g, session_id="sc"))
    assert json.loads(body)["session"]["hit"] is True


def test_gateway_streams_per_tile_progress(tiled_gateway):
    gw, g, ref, eng = tiled_gateway
    status, body = _post(gw.url("/v1/models/nbody/predict?stream=1"),
                         _payload(g))
    assert status == 200, body[:400]
    lines = [json.loads(ln) for ln in body.strip().split("\n")]
    done = lines[-1]
    assert done["done"] is True and done["cancelled"] is False
    pred = np.asarray(done["prediction"], np.float32)
    assert _norm_err(pred, ref) <= 1e-5
    progress = [ln for ln in lines[:-1] if "tile" in ln]
    assert len(progress) == done["tiled"]["tiles"] * done["tiled"]["layers"]


def test_gateway_tiled_bound_is_413(tiled_gateway):
    gw, g, ref, eng = tiled_gateway
    eng.tiled.max_nodes = 200           # below the 300-node scene
    try:
        status, body = _post(gw.url("/v1/models/nbody/predict"), _payload(g))
    finally:
        eng.tiled.max_nodes = 4_194_304
    resp = json.loads(body)
    assert status == 413 and resp["type"] == "BucketOverflow"
    assert "serve.tiled.max_nodes" in resp["error"]


# ------------------------------------------------- million-node slow lane

def _lattice_scene(side):
    """side^3-node lattice with +/-x neighbor edges: million-node scale
    without an O(N log N) radius build. Locality-friendly by construction,
    so the Morton plan keeps halos small."""
    n = side ** 3
    idx = np.arange(n, dtype=np.int64)
    x, y, z = idx // (side * side), (idx // side) % side, idx % side
    loc = np.stack([x, y, z], axis=1).astype(np.float32)
    loc += np.random.default_rng(0).uniform(-0.1, 0.1, loc.shape
                                            ).astype(np.float32)
    has_right = x < side - 1
    src = idx[has_right]
    dst = src + side * side
    ei = np.concatenate([np.stack([src, dst]), np.stack([dst, src])],
                        axis=1).astype(np.int32)
    d = np.linalg.norm(loc[ei[0]] - loc[ei[1]], axis=1)[:, None]
    vel = np.zeros_like(loc)
    vel[:, 0] = 0.01
    return {"node_feat": np.ones((n, 1), np.float32), "loc": loc,
            "vel": vel, "edge_index": ei,
            "edge_attr": np.repeat(d, 2, axis=1).astype(np.float32)}


@pytest.mark.slow
def test_million_node_scene_serves_with_one_executable(tmp_path):
    """The acceptance gate: >= 1M nodes through POST /v1/models/<name>/
    predict on CPU with exactly ONE tile-layer executable compiled (no
    recompile after warmup — CompileWatcher-certified) and no 413."""
    import base64

    from distegnn_tpu.obs import jaxprobe

    side = 100                          # 1_000_000 nodes, ~1.98M edges
    g = _lattice_scene(side)
    assert g["loc"].shape[0] == 1_000_000

    model = _model()
    tiny = synthetic_graph(20, seed=0)
    params = model.init(jax.random.PRNGKey(0),
                        pad_graphs([tiny], node_bucket=1, edge_bucket=1))
    metrics = ServeMetrics()
    eng = InferenceEngine(
        model, params, metrics=metrics,
        session_cache=4, session_cache_bytes=1 << 30,
        tiled={"tile_nodes": 262_144, "timeout_factor": 16.0})
    q = RequestQueue(eng, request_timeout_ms=600_000.0, metrics=metrics)
    reg = ModelRegistry.single("nbody", eng, q, feat_nf=1, edge_attr_nf=2)
    reg.start()
    gw = Gateway(reg, port=0, metrics_registry=MetricsRegistry())
    t = threading.Thread(target=gw.serve_forever, daemon=True)
    t.start()

    watcher = jaxprobe.install_compile_watcher()
    try:
        # warmup: one tiled pass in the serve_warmup phase compiles the
        # tile-rung executables
        jaxprobe.set_phase("serve_warmup")
        warm = eng.predict_tiled(dict(g))
        assert warm["tiles"] >= 2
        layer_keys = [k for k in eng._cache if k[0] == "tile_layer"]
        assert len(layer_keys) == 1     # ONE executable for all tiles/layers
        watcher.mark_warmup_done()

        def f32(a):
            a = np.ascontiguousarray(a, dtype="<f4")
            return {"b64": base64.b64encode(a.tobytes()).decode(),
                    "shape": list(a.shape)}

        ei = np.ascontiguousarray(g["edge_index"], dtype="<i4")
        payload = {"positions": f32(g["loc"]), "velocities": f32(g["vel"]),
                   "node_feat": f32(g["node_feat"]),
                   "edge_attr": f32(g["edge_attr"]),
                   "edge_index": {"b64":
                                  base64.b64encode(ei.tobytes()).decode(),
                                  "shape": list(ei.shape)},
                   "encoding": "b64", "session_id": "giant"}
        status, body = _post(gw.url("/v1/models/nbody/predict"), payload,
                             timeout=3600.0)
        resp = json.loads(body)
        assert status == 200, body[:400]              # served — not a 413
        shape = resp["prediction"]["shape"]
        assert shape == [1_000_000, 3]
        raw = base64.b64decode(resp["prediction"]["b64"])
        pred = np.frombuffer(raw, "<f4").reshape(shape)
        assert np.isfinite(pred).all()
        assert resp["tiled"]["tiles"] == warm["tiles"]
        # the warmed executables served the giant request: zero new compiles
        assert watcher.snapshot()["compiles_after_warmup"] == 0
        assert [k for k in eng._cache if k[0] == "tile_layer"] == layer_keys
    finally:
        jaxprobe.deactivate_compile_watcher()
        gw.drain()
        t.join(timeout=60.0)
        gw.close()
