"""Device-parallel tiled serving (serve/mesh_tiled.py + ops/tiling.py round
scheduling): LPT round planning, mesh-vs-sequential exactness on 8 virtual
CPU devices (ragged rounds included), the round-boundary disconnect
contract, tile-plan portability across a devices
reconfig, the one-executable-per-(shape_key, D) invariant, and — slow lane —
a million-node scene through rounds of 8 with zero recompiles after warmup.

Runs on 8 virtual CPU devices (tests/conftest.py); on a real four-chip host
the rounds run once in chip_smoke.py. Multi-chip speed: not measured.
"""

import json
import threading

import jax
import numpy as np
import pytest

from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.obs.metrics import MetricsRegistry
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.ops.tiling import plan_rounds, plan_tiles, tile_work
from distegnn_tpu.serve import (BucketLadder, InferenceEngine, RequestQueue,
                                ServeMetrics, SessionPrepCache, TiledExecutor,
                                synthetic_graph)
from distegnn_tpu.serve.mesh_tiled import resolve_devices
from distegnn_tpu.serve.prep import nbytes_of
from distegnn_tpu.serve.registry import ModelRegistry
from distegnn_tpu.serve.transport import Gateway

from test_tiled import _lattice_scene, _model, _norm_err, _payload, _post

pytestmark = pytest.mark.serve


# --------------------------------------------------------- round scheduling

def test_plan_rounds_covers_every_tile_once():
    g = synthetic_graph(500, radius=0.2, seed=11)
    plan = plan_tiles(g["edge_index"], g["loc"], g["edge_attr"],
                      tile_nodes=128, halo_floor=16, edge_floor=256)
    T = plan.n_tiles
    for D in (1, 2, 3, 8):
        sched = plan_rounds(plan, D)
        assert sched.n_devices == D
        assert sched.n_rounds == -(-T // D)
        flat = [t for r in sched.rounds for t in r]
        assert sorted(flat) == list(range(T))       # each tile exactly once
        assert all(len(r) <= D for r in sched.rounds)
        assert sched.round_imbalance >= 1.0


def test_plan_rounds_lpt_balances_skewed_work():
    """LPT over an adversarial work vector: the heavy tile must not share a
    round with the next-heaviest — imbalance stays far below the sorted-
    chunking assignment that pairs them."""
    g = synthetic_graph(600, radius=0.2, seed=3)
    plan = plan_tiles(g["edge_index"], g["loc"], g["edge_attr"],
                      tile_nodes=128, halo_floor=16, edge_floor=256)
    T = plan.n_tiles
    assert T >= 4
    work = np.ones(T)
    work[0] = 100.0
    work[1] = 90.0
    sched = plan_rounds(plan, 2, work=work)
    rounds_of = {t: i for i, r in enumerate(sched.rounds) for t in r}
    assert rounds_of[0] != rounds_of[1]             # heavies split apart
    naive_imb = (190.0 / (work.sum() / sched.n_rounds))
    assert sched.round_imbalance < naive_imb


def test_tile_work_matches_plan_model():
    g = synthetic_graph(400, radius=0.2, seed=5)
    plan = plan_tiles(g["edge_index"], g["loc"], g["edge_attr"],
                      tile_nodes=128, halo_floor=16, edge_floor=256)
    w = tile_work(plan)
    assert w.shape == (plan.n_tiles,)
    assert (w == [s.n_own + s.edge_index.shape[1]
                  for s in plan.tiles]).all()


def test_resolve_devices_auto_clamp_and_degenerate():
    avail = jax.local_device_count()
    assert avail == 8                       # conftest virtual-device contract
    assert resolve_devices("auto") == avail
    assert resolve_devices(4) == 4
    assert resolve_devices(99) == avail     # clamped, never an error
    assert resolve_devices("auto", n_tiles=1) == 1   # nothing to parallelize
    assert resolve_devices(4, n_tiles=0) == 1


# --------------------------------------------- mesh-vs-sequential exactness

def _seq_and_executor():
    model = _model()
    g = synthetic_graph(400, radius=0.2, seed=5)
    tight = pad_graphs([g], node_bucket=1, edge_bucket=1)
    params = model.init(jax.random.PRNGKey(0), tight)
    eng = InferenceEngine(model, params)
    tx = TiledExecutor(eng, {"tile_nodes": 128, "halo_floor": 16,
                             "edge_floor": 256})
    seq = tx.predict(dict(g))
    assert seq["tiles"] >= 2 and seq["devices"] == 1
    assert seq["rounds"] == seq["tiles"]    # sequential: one tile per round
    return g, tx, eng, seq


def test_mesh_parity_plain_even_rounds():
    """D divides the tile count: every round is full; parity is exact and
    the round count drops D-fold vs sequential on the SAME plan."""
    g, tx, eng, seq = _seq_and_executor()
    T = seq["tiles"]
    D = 4
    assert T % D == 0
    tx.devices = D
    out = tx.predict(dict(g))
    assert out["devices"] == D
    assert out["rounds"] == T // D
    assert out["round_ms"] > 0 and out["halo_gather_ms"] >= 0
    assert _norm_err(out["prediction"], seq["prediction"]) <= 1e-6
    # gauges fed from the mesh run
    gv = eng.metrics.registry.gauge
    assert gv("serve/tiled_devices").value == D
    assert gv("serve/tiled_round_ms").value > 0


def test_mesh_parity_plain_ragged_round():
    """Tile count NOT divisible by D: the last round carries zero-masked
    filler slots whose partials must contribute exactly nothing."""
    g, tx, eng, seq = _seq_and_executor()
    T = seq["tiles"]
    D = 3
    assert T % D != 0
    tx.devices = D
    out = tx.predict(dict(g))
    assert out["rounds"] == -(-T // D)
    assert _norm_err(out["prediction"], seq["prediction"]) <= 1e-6


def test_mesh_one_executable_per_shape_and_devices():
    """A mesh-only engine compiles exactly ONE tile-layer executable, keyed
    by the sequential rung key extended with D."""
    model = _model()
    g = synthetic_graph(400, radius=0.2, seed=5)
    tight = pad_graphs([g], node_bucket=1, edge_bucket=1)
    params = model.init(jax.random.PRNGKey(0), tight)
    eng = InferenceEngine(model, params)
    tx = TiledExecutor(eng, {"tile_nodes": 128, "halo_floor": 16,
                             "edge_floor": 256, "devices": 4})
    out = tx.predict(dict(g))
    assert out["devices"] == 4
    keys = [k for k in eng._cache if k[0] == "tile_layer"]
    assert len(keys) == 1
    assert keys[0][-1] == 4                 # ...and it is the D-keyed one
    tx.predict(dict(g))                     # same rung, same D: cache hit
    assert [k for k in eng._cache if k[0] == "tile_layer"] == keys


# ------------------------------------------- round-boundary cancel contract

def test_mesh_disconnect_cancels_at_round_boundary():
    g, tx, eng, seq = _seq_and_executor()
    tx.devices = 4
    seen = []

    def progress(**info):
        seen.append(info)
        return False                        # "client disconnected"

    out = tx.predict(dict(g), progress=progress)
    assert out["cancelled"] is True
    assert out["prediction"] is None
    assert len(seen) == 1                   # stopped after the FIRST round
    assert seen[0]["round"] == 0 and seen[0]["layer"] == 0
    assert seen[0]["n_rounds"] == seq["tiles"] // 4
    assert seen[0]["n_tiles"] == seq["tiles"]


# ----------------------------------- plan portability across devices change

def test_tile_plan_portable_across_devices_reconfig():
    """A plan session-cached at devices: 1 is reused BITWISE (cache hit, no
    rebuild) after the executor is reconfigured to devices: 4 — shape_key
    carries no device count — and nbytes_of still charges the plan."""
    model = _model()
    g = synthetic_graph(400, radius=0.2, seed=5)
    tight = pad_graphs([g], node_bucket=1, edge_bucket=1)
    params = model.init(jax.random.PRNGKey(0), tight)
    eng = InferenceEngine(model, params)
    tx = TiledExecutor(eng, {"tile_nodes": 128, "halo_floor": 16,
                             "edge_floor": 256, "devices": 1})
    cache = SessionPrepCache(capacity=4, ladder=BucketLadder(),
                             max_bytes=1 << 22)
    builds = []

    def build():
        builds.append(1)
        return tx.plan(dict(g))

    plan1, hit1 = cache.prepare_tile("sess", g, build)
    seq = tx.predict(dict(g), plan=plan1)
    assert (hit1, len(builds)) == (False, 1)

    tx.devices = 4                          # deploy-time reconfig
    plan2, hit2 = cache.prepare_tile("sess", g, build)
    assert hit2 is True and len(builds) == 1    # no rebuild...
    assert plan2 is plan1                       # ...the SAME plan object
    assert tx._plan_ok(plan2, g["loc"].shape[0])
    out = tx.predict(dict(g), plan=plan2)       # and it serves at D=4
    assert out["devices"] == 4
    assert _norm_err(out["prediction"], seq["prediction"]) <= 1e-6
    assert nbytes_of(plan2) > 0                 # byte-charging still covers it


# --------------------------------------------------- gateway per-round e2e

@pytest.fixture()
def mesh_gateway():
    """Tiled gateway with serve.tiled.devices: 4 — the 300-node scene above
    the cap serves through device-parallel rounds."""
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
                                 "edge_floor": 256, "devices": 4})
    q = RequestQueue(eng, request_timeout_ms=120_000.0, metrics=metrics)
    reg = ModelRegistry.single("nbody", eng, q, feat_nf=1, edge_attr_nf=2)
    reg.start()
    gw = Gateway(reg, port=0, metrics_registry=MetricsRegistry())
    t = threading.Thread(target=gw.serve_forever, daemon=True)
    t.start()
    yield gw, g, ref
    gw.drain()
    t.join(timeout=30.0)
    gw.close()


def test_gateway_mesh_serves_and_reports_rounds(mesh_gateway):
    gw, g, ref = mesh_gateway
    status, body = _post(gw.url("/v1/models/nbody/predict"), _payload(g))
    resp = json.loads(body)
    assert status == 200, body[:400]
    pred = np.asarray(resp["prediction"], np.float32)
    assert _norm_err(pred, ref) <= 1e-5
    st = resp["tiled"]
    assert st["devices"] == 4
    assert st["rounds"] == -(-st["tiles"] // 4)
    assert st["round_ms"] > 0


def test_gateway_mesh_streams_per_round_progress(mesh_gateway):
    gw, g, ref = mesh_gateway
    status, body = _post(gw.url("/v1/models/nbody/predict?stream=1"),
                         _payload(g))
    assert status == 200, body[:400]
    lines = [json.loads(ln) for ln in body.strip().split("\n")]
    done = lines[-1]
    assert done["done"] is True and done["cancelled"] is False
    pred = np.asarray(done["prediction"], np.float32)
    assert _norm_err(pred, ref) <= 1e-5
    progress = [ln for ln in lines[:-1] if "round" in ln]
    assert len(progress) == done["tiled"]["rounds"] * done["tiled"]["layers"]
    assert all("tile" not in ln for ln in progress)   # per-ROUND lines
    assert progress[0]["n_rounds"] == done["tiled"]["rounds"]


# ------------------------------------------------- million-node slow lane

@pytest.mark.slow
def test_million_node_mesh_rounds_one_executable(tmp_path):
    """The mesh acceptance gate: 1M nodes through rounds of 8 virtual
    devices with exactly ONE tile-layer executable per (shape_key, D), zero
    recompiles after warmup (CompileWatcher-certified), and the round count
    dropped 8x vs the sequential tile walk of the same plan."""
    from distegnn_tpu.obs import jaxprobe

    side = 100                          # 1_000_000 nodes
    g = _lattice_scene(side)
    model = _model()
    tiny = synthetic_graph(20, seed=0)
    params = model.init(jax.random.PRNGKey(0),
                        pad_graphs([tiny], node_bucket=1, edge_bucket=1))
    eng = InferenceEngine(
        model, params, session_cache=4, session_cache_bytes=1 << 30,
        tiled={"tile_nodes": 131_072, "timeout_factor": 16.0,
               "devices": 8})

    watcher = jaxprobe.install_compile_watcher()
    try:
        jaxprobe.set_phase("serve_warmup")
        warm = eng.predict_tiled(dict(g))
        assert warm["devices"] == 8
        assert warm["rounds"] == -(-warm["tiles"] // 8)
        assert warm["rounds"] * 8 < warm["tiles"] + 8   # ~8x fewer dispatches
        layer_keys = [k for k in eng._cache if k[0] == "tile_layer"]
        assert len(layer_keys) == 1 and layer_keys[0][-1] == 8
        watcher.mark_warmup_done()

        out = eng.predict_tiled(dict(g))
        assert np.isfinite(out["prediction"]).all()
        assert out["rounds"] == warm["rounds"]
        assert watcher.snapshot()["compiles_after_warmup"] == 0
        assert [k for k in eng._cache if k[0] == "tile_layer"] == layer_keys
    finally:
        jaxprobe.deactivate_compile_watcher()
