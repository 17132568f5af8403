"""distegnn_tpu.serve — bucketed-batching inference (docs/SERVING.md).

Request path: HTTP gateway (transport.py) -> ModelRegistry route ->
RequestQueue.submit(graph) -> bucket ladder -> micro-batcher ->
InferenceEngine per-bucket compile cache -> ServeFuture result. All
components of one model share one ServeMetrics snapshot; the gateway adds
process-wide admission/latency series and a /metrics scrape endpoint.
"""

from distegnn_tpu.serve.buckets import (Bucket, BucketLadder,
                                        BucketOverflowError, synthetic_graph)
from distegnn_tpu.serve.engine import (InferenceEngine,
                                       MixedRolloutStepsError,
                                       RolloutOverflowError)
from distegnn_tpu.serve.metrics import ServeMetrics
from distegnn_tpu.serve.prep import PrepPlan, PrepResult, SessionPrepCache
from distegnn_tpu.serve.queue import (DispatcherCrashError, QueueFullError,
                                      RequestQueue, RequestTimeoutError,
                                      ServeFuture, WorkerLostError)
from distegnn_tpu.serve.replica import (ModelUnavailableError, Replica,
                                        ReplicaSet, WorkerQueue,
                                        WorkerReplica)
from distegnn_tpu.serve.supervisor import ReplicaSupervisor
from distegnn_tpu.serve.tiled import TiledExecutor, TiledOverflowError

__all__ = [
    "Bucket", "BucketLadder", "BucketOverflowError", "synthetic_graph",
    "InferenceEngine", "MixedRolloutStepsError", "RolloutOverflowError",
    "ServeMetrics", "PrepPlan", "PrepResult", "SessionPrepCache",
    "QueueFullError", "RequestQueue", "RequestTimeoutError", "ServeFuture",
    "DispatcherCrashError", "WorkerLostError", "ModelUnavailableError",
    "Replica", "ReplicaSet", "WorkerQueue", "WorkerReplica",
    "ReplicaSupervisor", "SwapError", "SwapInProgressError",
    "TiledExecutor", "TiledOverflowError",
    "engine_from_config", "engine_with_params_from_config", "Gateway",
    "ModelEntry", "ModelRegistry", "PayloadError",
]


def __getattr__(name):
    # transport/registry import lazily: the in-process serve stack must not
    # pay for (or depend on) the HTTP layer, and registry->engine_from_config
    # would otherwise be a load-time cycle through this package __init__
    if name in ("Gateway", "PayloadError"):
        from distegnn_tpu.serve import transport

        return getattr(transport, name)
    if name in ("ModelEntry", "ModelRegistry", "SwapError",
                "SwapInProgressError"):
        from distegnn_tpu.serve import registry

        return getattr(registry, name)
    raise AttributeError(name)


def engine_from_config(cfg, model, params, metrics=None):
    """Build (InferenceEngine, RequestQueue) from a config's ``serve:``
    section (distegnn_tpu.config defaults; queue NOT started)."""
    s = cfg.serve
    ladder = BucketLadder(
        node_floor=s.node_floor, edge_floor=s.edge_floor, growth=s.growth,
        node_multiple=s.node_multiple, edge_multiple=s.edge_multiple,
        max_nodes=s.max_nodes, max_edges=s.max_edges)
    metrics = metrics or ServeMetrics()
    engine = InferenceEngine(
        model, params, ladder=ladder, max_batch=s.max_batch,
        cache_size=s.cache_size, donate=s.donate, metrics=metrics,
        rollout_opts=(s.rollout.to_dict() if s.get("rollout") else None),
        session_cache=int(s.get("session_cache", 0) or 0),
        session_cache_bytes=int(s.get("session_cache_bytes", 0) or 0),
        tiled=(s.tiled.to_dict() if s.get("tiled")
               and s.tiled.get("enable") else None))
    q = RequestQueue(
        engine, batch_deadline_ms=s.batch_deadline_ms,
        queue_capacity=s.queue_capacity,
        request_timeout_ms=s.request_timeout_ms,
        result_margin_s=float(s.get("result_margin_s", 30.0)),
        metrics=metrics)
    return engine, q


def engine_with_params_from_config(cfg, metrics=None, checkpoint=None):
    """The registry's full deterministic model+engine+params recipe, shared
    with the process-worker child (serve/worker.py) so BOTH sides of the
    IPC boundary hold bitwise-identical params: seeded ``model.init`` on a
    ladder-padded synthetic graph, then an optional checksummed checkpoint
    restore. ``checkpoint`` overrides ``cfg.model.checkpoint`` — the worker
    respawn path after a hot-swap, where the child must come back up on the
    SWAPPED version, not the config's original. Returns
    ``(model, engine, queue, params)``; the queue is NOT started."""
    import jax

    from distegnn_tpu.models.registry import get_model

    model = get_model(cfg.model, dataset_name=cfg.data.dataset_name)
    metrics = metrics or ServeMetrics()
    engine, queue = engine_from_config(cfg, model, params=None,
                                       metrics=metrics)
    feat_nf = int(cfg.model.node_feat_nf)
    edge_nf = int(cfg.model.edge_attr_nf)
    seed = int(cfg.get("seed", 0) or 0)
    g = synthetic_graph(2, seed=seed, feat_nf=feat_nf, edge_attr_nf=edge_nf)
    b0 = engine.ladder.bucket_of_graph(g)
    init_batch, _ = engine.ladder.pad_batch([g], b0, 1,
                                            **engine._layout_opts)
    params = model.init(jax.random.PRNGKey(seed), init_batch)
    ckpt = checkpoint if checkpoint is not None else cfg.model.get("checkpoint")
    if ckpt:
        from distegnn_tpu.train.checkpoint import restore_params

        params = restore_params(str(ckpt), params)
    engine.params = params
    return model, engine, queue, params
