"""Serving stack (distegnn_tpu/serve): bucket ladder, compile cache,
micro-batcher, metrics, and the bench harness — all CPU, in-process."""

import json
import threading

import jax
import numpy as np
import pytest

from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.serve import (Bucket, BucketLadder, BucketOverflowError,
                                InferenceEngine, QueueFullError, RequestQueue,
                                RequestTimeoutError, ServeMetrics,
                                synthetic_graph)

pytestmark = pytest.mark.serve


def _model():
    return FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                    virtual_channels=2, n_layers=2)


def _init(model, graph):
    tight = pad_graphs([graph], node_bucket=1, edge_bucket=1)
    return model.init(jax.random.PRNGKey(0), tight)


def _reference(model, params, graph):
    """Direct model.apply on the unpadded graph — the numerics oracle."""
    tight = pad_graphs([graph], node_bucket=1, edge_bucket=1)
    x, _ = model.apply(params, tight)
    return np.asarray(x[0])


# ---------------------------------------------------------------- ladder

def test_ladder_geometric_rungs():
    lad = BucketLadder(node_floor=64, edge_floor=256, growth=2.0,
                       node_multiple=8, edge_multiple=128,
                       max_nodes=1024, max_edges=4096)
    assert lad.bucket_for(1, 1) == Bucket(64, 256)
    assert lad.bucket_for(64, 256) == Bucket(64, 256)   # exact rung, no jump
    assert lad.bucket_for(65, 257) == Bucket(128, 512)
    assert lad.bucket_for(300, 2000) == Bucket(512, 2048)
    assert lad.bucket_for(1024, 4096) == Bucket(1024, 4096)
    # N and E bucket independently
    assert lad.bucket_for(65, 1) == Bucket(128, 256)


def test_ladder_overflow_rejected():
    lad = BucketLadder(max_nodes=256, max_edges=1024)
    with pytest.raises(BucketOverflowError):
        lad.bucket_for(257, 10)
    with pytest.raises(BucketOverflowError):
        lad.bucket_for(10, 1025)


def test_ladder_monotone_and_admitting():
    lad = BucketLadder(node_floor=16, edge_floor=32, growth=1.5,
                       max_nodes=2048, max_edges=8192)
    prev = Bucket(0, 0)
    for n, e in [(1, 1), (16, 32), (17, 33), (100, 500), (999, 4000)]:
        b = lad.bucket_for(n, e)
        assert b.n >= n and b.e >= e          # admits the request
        assert b.n >= prev.n and b.e >= prev.e  # monotone in request size
        prev = b


# ---------------------------------------------------------------- engine

def test_engine_predict_matches_direct_apply():
    model = _model()
    g = synthetic_graph(40, seed=1)
    params = _init(model, g)
    eng = InferenceEngine(model, params, max_batch=4)
    out = eng.predict(g)
    np.testing.assert_allclose(out, _reference(model, params, g),
                               atol=1e-4, rtol=0)


def test_engine_cache_hit_miss_eviction():
    model = _model()
    g1, g2, g3 = (synthetic_graph(n, seed=s)
                  for n, s in ((30, 1), (90, 2), (200, 3)))
    params = _init(model, g1)
    eng = InferenceEngine(model, params, max_batch=2, cache_size=2)
    eng.predict(g1)
    eng.predict(g1)           # hit
    eng.predict(g2)           # miss (second bucket)
    eng.predict(g3)           # miss + evicts the LRU entry (cache_size=2)
    st = eng.cache_stats()
    assert st["misses"] == 3 and st["hits"] == 1
    assert st["evictions"] == 1 and st["live"] == 2
    eng.predict(g1)           # evicted -> recompiles: miss again
    assert eng.cache_stats()["misses"] == 4


def test_engine_warmup_compiles_distinct_rungs_once():
    model = _model()
    g = synthetic_graph(50, seed=4)
    params = _init(model, g)
    eng = InferenceEngine(model, params, max_batch=2)
    sizes = [(50, g["edge_index"].shape[1])] * 3
    warmed = eng.warmup(sizes)
    assert len(warmed) == 1
    assert eng.cache_stats()["misses"] == 1


# ---------------------------------------------------------------- queue e2e

def test_queue_end_to_end_concurrent():
    """The acceptance run: >= 20 concurrent submissions, >= 3 distinct
    (N, E) sizes; every response matches direct apply on the unpadded
    graph; cache misses == distinct buckets; hits >= misses."""
    model = _model()
    base_graphs = [synthetic_graph(n, seed=s)
                   for n, s in ((40, 10), (90, 11), (180, 12))]
    sizes = {(g["loc"].shape[0], g["edge_index"].shape[1])
             for g in base_graphs}
    assert len(sizes) >= 3
    params = _init(model, base_graphs[0])
    metrics = ServeMetrics()
    eng = InferenceEngine(model, params, max_batch=2, metrics=metrics)
    refs = [_reference(model, params, g) for g in base_graphs]
    expected_buckets = {eng.ladder.bucket_of_graph(g) for g in base_graphs}

    n_req = 24
    jobs = [base_graphs[i % 3] for i in range(n_req)]
    futures = [None] * n_req
    errors = []

    with RequestQueue(eng, batch_deadline_ms=20.0, queue_capacity=64,
                      request_timeout_ms=30_000.0) as q:
        def submit(i):
            try:
                futures[i] = q.submit(jobs[i])
            except Exception as e:   # pragma: no cover - should not happen
                errors.append(e)

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        results = [f.result(timeout=120.0) for f in futures]

    for i, out in enumerate(results):
        np.testing.assert_allclose(out, refs[i % 3], atol=1e-4, rtol=0,
                                   err_msg=f"request {i} diverged")

    snap = metrics.snapshot()
    assert snap["cache_misses"] == len(expected_buckets)
    assert snap["cache_hits"] >= snap["cache_misses"]
    assert snap["requests_completed"] == n_req
    assert snap["requests_failed"] == 0 and snap["requests_timeout"] == 0
    assert snap["batches_executed"] >= len(expected_buckets)
    assert 0 < snap["batch_fill_ratio"] <= 1
    assert snap["latency_p99_ms"] >= snap["latency_p50_ms"] > 0


def test_queue_backpressure_queue_full():
    model = _model()
    g = synthetic_graph(30, seed=5)
    params = _init(model, g)
    eng = InferenceEngine(model, params, max_batch=2)
    q = RequestQueue(eng, batch_deadline_ms=50.0, queue_capacity=2,
                     request_timeout_ms=10_000.0)
    # NOT started: the dispatcher never drains, so capacity fills
    q._started = True  # allow submits without a running dispatcher
    q.submit(g)
    q.submit(g)
    with pytest.raises(QueueFullError):
        q.submit(g)
    assert eng.metrics.snapshot()["requests_rejected"] == 1


def test_queue_overflow_graph_rejected_at_submit():
    model = _model()
    g = synthetic_graph(30, seed=6)
    params = _init(model, g)
    eng = InferenceEngine(model, params, max_batch=2,
                          ladder=BucketLadder(max_nodes=64, max_edges=4096))
    with RequestQueue(eng) as q:
        with pytest.raises(BucketOverflowError):
            q.submit(synthetic_graph(100, seed=7))


def test_queue_request_timeout_surfaced():
    model = _model()
    g = synthetic_graph(30, seed=8)
    params = _init(model, g)
    eng = InferenceEngine(model, params, max_batch=2)
    q = RequestQueue(eng, batch_deadline_ms=10_000.0, queue_capacity=8,
                     request_timeout_ms=30.0)
    q._started = True          # no dispatcher: requests age in the ingress
    fut = q.submit(g)
    import time

    time.sleep(0.06)
    q._started = False
    q._fail_all(RequestTimeoutError("drained"))
    with pytest.raises(RequestTimeoutError):
        fut.result(timeout=1.0)


def test_stop_drains_admitted_requests():
    model = _model()
    g = synthetic_graph(30, seed=9)
    params = _init(model, g)
    eng = InferenceEngine(model, params, max_batch=4)
    q = RequestQueue(eng, batch_deadline_ms=5_000.0, queue_capacity=16,
                     request_timeout_ms=60_000.0).start()
    futs = [q.submit(g) for _ in range(3)]
    q.stop(drain=True)   # long deadline: only the drain can flush these
    for f in futs:
        assert f.result(timeout=1.0).shape == (30, 3)


# ---------------------------------------------------------------- metrics

def test_metrics_snapshot_schema_and_json():
    m = ServeMetrics()
    m.submitted(5)
    m.batch_done(2, 4, [1.5, 2.5], [0.5, 0.7])
    m.cache_event(hit=False)
    m.cache_event(hit=True)
    snap = json.loads(m.to_json())
    assert snap["requests_submitted"] == 5
    assert snap["requests_completed"] == 2
    assert snap["batch_fill_ratio"] == 0.5
    assert snap["cache_hits"] == 1 and snap["cache_misses"] == 1
    assert snap["latency_p50_ms"] > 0
    for v in snap.values():
        assert isinstance(v, (int, float))


# ---------------------------------------------------------------- rollout

def test_engine_rollout_pads_and_unpads():
    model = _model()
    n = 100   # not a multiple of edge_block: engine must pad to 256
    g = synthetic_graph(n, seed=13)
    params = _init(model, g)
    eng = InferenceEngine(
        model, params, max_batch=1,
        rollout_opts={"radius": 0.35, "max_degree": 64, "max_per_cell": 64})
    traj = eng.rollout(g["loc"], g["vel"], steps=2)
    assert traj.shape == (2, n, 3)
    assert np.isfinite(traj).all()
    assert eng.cache_stats()["misses"] == 1
    eng.rollout(g["loc"], g["vel"], steps=2)   # same shape+steps: cache hit
    assert eng.cache_stats()["hits"] == 1


def test_rollout_batch_matches_sequential_b1():
    """The tentpole parity bar: batched rollouts return the SAME trajectories
    as sequential B=1 engine.rollout calls, to 1e-6 — vmapping over the scene
    axis changes throughput, never numbers."""
    model = _model()
    g = synthetic_graph(48, seed=14)
    params = _init(model, g)
    eng = InferenceEngine(
        model, params, max_batch=4,
        rollout_opts={"radius": 0.35, "max_degree": 64, "max_per_cell": 64})
    scenes = []
    for k in range(3):     # underfilled batch: 3 scenes, max_batch=4
        gk = synthetic_graph(48, seed=20 + k)
        scenes.append({"loc": gk["loc"], "vel": gk["vel"], "steps": 3})
    batched = eng.rollout_batch(scenes)
    assert len(batched) == 3
    for s, traj in zip(scenes, batched):
        assert traj.shape == (3, 48, 3)
        ref = eng.rollout(s["loc"], s["vel"], 3)
        np.testing.assert_allclose(traj, ref, atol=1e-6, rtol=0)


def test_rollout_batch_mixed_steps_typed_error():
    from distegnn_tpu.serve import MixedRolloutStepsError

    model = _model()
    g = synthetic_graph(32, seed=15)
    params = _init(model, g)
    eng = InferenceEngine(
        model, params, max_batch=4,
        rollout_opts={"radius": 0.35, "max_degree": 64, "max_per_cell": 64})
    scenes = [{"loc": g["loc"], "vel": g["vel"], "steps": 2},
              {"loc": g["loc"], "vel": g["vel"], "steps": 5}]
    with pytest.raises(MixedRolloutStepsError):
        eng.rollout_batch(scenes)


def test_queue_coalesces_rollouts_one_batch():
    """Co-submitted same-rung same-steps rollouts share ONE batched
    executable call, and every future resolves to its own scene's
    trajectory."""
    model = _model()
    g = synthetic_graph(40, seed=16)
    params = _init(model, g)
    eng = InferenceEngine(
        model, params, max_batch=4,
        rollout_opts={"radius": 0.35, "max_degree": 64, "max_per_cell": 64})
    q = RequestQueue(eng, batch_deadline_ms=150.0, queue_capacity=16,
                     request_timeout_ms=120_000.0)
    scenes = [{"loc": synthetic_graph(40, seed=30 + k)["loc"],
               "vel": synthetic_graph(40, seed=30 + k)["vel"], "steps": 2}
              for k in range(4)]
    with q:
        futures = [q.submit_rollout(s) for s in scenes]
        results = [f.result(timeout=180.0) for f in futures]
    batches = eng.metrics.snapshot()["batches_executed"]
    assert batches <= 2    # 4 co-arrivals into at most 2 batches (1 when
    #                        the deadline window catches all four)
    for s, traj in zip(scenes, results):
        ref = eng.rollout(s["loc"], s["vel"], 2)
        np.testing.assert_allclose(traj, ref, atol=1e-6, rtol=0)


# ---------------------------------------------------------------- bench

def test_serve_bench_cli_one_json_line(capsys):
    from scripts.serve_bench import main as bench_main

    rc = bench_main(["--requests", "12", "--rate", "500",
                     "--sizes", "24,48", "--seed", "7"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.strip().splitlines() if ln]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["metric"] == "serve_throughput"
    assert rec["unit"] == "req/s"
    assert rec["value"] > 0
    assert rec["snapshot"]["requests_completed"] > 0
    assert rec["snapshot"]["cache_misses"] >= 1


def test_serve_bench_rollout_leg_traces_on_cpu(capsys):
    """The rollout BENCH line can never silently vanish: a tiny CPU trace of
    `serve_bench.py --workload rollout` must emit exactly ONE JSON line with
    the batched-vs-baseline fields."""
    from scripts.serve_bench import main as bench_main

    rc = bench_main(["--workload", "rollout", "--rollout-scenes", "2",
                     "--rollout-steps", "2", "--sizes", "24",
                     "--max-batch", "2", "--rate", "500", "--obs-dir", "",
                     "--seed", "7"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.strip().splitlines() if ln]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["metric"] == "serve_rollout_throughput"
    assert rec["unit"] == "scenes*steps/s"
    assert rec["value"] > 0
    assert rec["baseline_b1"] > 0 and rec["baseline_solo"] > 0
    assert rec["vs_baseline"] > 0
    assert rec["max_batch"] == 2 and rec["steps"] == 2
    assert rec["scenes_completed"] == 2   # value credits only finished work
    assert rec["snapshot"]["requests_completed"] == 2


def test_blocked_layout_engine_one_executable_per_rung_and_matches_plain():
    """``layout_opts={'edge_block': ...}`` serves every batch in the blocked
    layout: a warmed rung is ONE executable (keyed on the resulting shapes,
    edge_block included), later predicts hit it, and the prediction is the
    plain-layout engine's."""
    model = FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=16,
                     virtual_channels=2, n_layers=2)
    g = synthetic_graph(40, seed=1)
    layout = dict(edge_block=32)
    eng = InferenceEngine(model, None, max_batch=2, layout_opts=layout)
    b0 = eng.ladder.bucket_of_graph(g)
    init_batch, _ = eng.ladder.pad_batch([g], b0, 2, **layout)
    eng.params = model.init(jax.random.PRNGKey(0), init_batch)

    warmed = eng.warmup([(40, g["edge_index"].shape[1])])
    assert len(warmed) == 1
    for _ in range(3):
        out = eng.predict(g)
    st = eng.cache_stats()
    assert st["live"] == 1 and st["misses"] == 1 and st["hits"] == 3
    (key,) = list(eng._cache)
    assert key == ("predict", 64, 1024, 32, 2)   # N, E, edge_block, max_batch

    plain = InferenceEngine(model, eng.params, max_batch=2).predict(g)
    assert out.shape == (40, 3)
    np.testing.assert_allclose(out, plain, rtol=0, atol=1e-5 * max(
        1.0, float(np.abs(plain).max())))
