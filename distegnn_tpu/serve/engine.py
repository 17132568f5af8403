"""InferenceEngine — per-bucket jit compile cache over a model's apply fn.

The training loop compiles ONE program per run (static shapes, data/loader).
Serving sees heterogeneous graphs, so the engine quantizes every request to a
`BucketLadder` rung and keeps one compiled executable per rung in a bounded
LRU — the GSPMD serving recipe (arXiv:2105.04663): a small set of padded
shapes amortizes XLA compilation across all traffic.

Two entry points:
  - ``predict_batch`` — one model step over up to ``max_batch`` same-bucket
    graphs. The batch axis is ALWAYS padded to ``max_batch`` (replicating a
    real graph), so a bucket owns exactly one executable regardless of how
    full its micro-batches run — compile count == rung count, and the
    batch-fill ratio is a metrics problem, not a compile-cache problem.
  - ``rollout`` — K autoregressive steps via `rollout.make_rollout_fn`
    (radius graph rebuilt on device each step); per-step capacity overflow
    flags are checked after the scan and surfaced as RolloutOverflowError,
    never silently dropped (the rollout.py contract).

Donation: on TPU the padded input batch is donated to the executable
(``donate_argnums``) so XLA reuses its buffers for the outputs — the steady
state allocates nothing per request. CPU ignores donation (and warns), so
``donate='auto'`` enables it only when the backend is a TPU.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.obs import jaxprobe
from distegnn_tpu.serve.buckets import Bucket, BucketLadder
from distegnn_tpu.serve.metrics import ServeMetrics


def _rid_attrs(request_ids: Optional[Sequence[str]]) -> dict:
    """serve/execute span attrs for a batch's trace ids; {} when the batch
    carries none (in-proc callers) so untraced events stay compact."""
    ids = [r for r in (request_ids or []) if r is not None]
    return {"request_ids": ids} if ids else {}


class RolloutOverflowError(RuntimeError):
    """A rollout step overflowed the static radius-graph capacity bounds
    (max_per_cell / max_degree) — results would silently drop edges."""


class CanaryError(RuntimeError):
    """The blue/green canary forward pass rejected candidate params
    (non-finite outputs or a shape mismatch) — the swap must roll back."""


class MixedRolloutStepsError(ValueError):
    """A rollout micro-batch mixed different steps-K. The scan length is
    static (part of the compiled executable), so scenes with different K can
    never share a batch — the batcher keys on (rung, steps) to prevent this;
    hitting it through ``rollout_batch`` directly is a caller bug."""


class InferenceEngine:
    """Bucketed, compile-cached inference over one model + params.

    Args:
      model: a flax module whose ``apply(params, GraphBatch)`` returns a
        tuple with predicted positions ``[B, N, 3]`` first (the registry
        contract), or pass ``apply_fn`` explicitly.
      params: the model params pytree.
      ladder: BucketLadder (default: serving defaults).
      max_batch: fixed padded batch of every compiled program.
      cache_size: max live executables; least-recently-used rungs are
        evicted (and recompiled on return — counted in metrics).
      donate: True | False | 'auto' (TPU only).
      rollout_opts: kwargs forwarded to make_rollout_fn (radius, max_degree,
        max_per_cell, edge_block, ...) — required for ``rollout``.
      layout_opts: kwargs forwarded to ``ladder.pad_batch`` (edge_block,
        edge_tile): ``{'edge_block': 256}`` serves every batch in the
        blocked layout.
      session_cache: capacity of the session-affinity prep cache
        (serve/prep.py) exposed as ``engine.prep_cache``; 0 (default)
        disables it.
      session_cache_bytes: byte bound on the prep cache's stored plans
        (evict-to-fit; 0 = entry-count bound only). Million-node tile
        plans make the entry count a poor proxy for host RSS.
      tiled: ``serve.tiled:`` config dict — builds the tiled executor
        (serve/tiled.py) for scenes above the ladder cap; None disables.
    """

    def __init__(self, model, params, *, ladder: Optional[BucketLadder] = None,
                 max_batch: int = 8, cache_size: int = 32,
                 donate: Any = "auto", metrics: Optional[ServeMetrics] = None,
                 apply_fn: Optional[Callable] = None,
                 rollout_opts: Optional[dict] = None,
                 layout_opts: Optional[dict] = None,
                 session_cache: int = 0,
                 session_cache_bytes: int = 0,
                 tiled: Optional[dict] = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if cache_size < 1:
            raise ValueError("cache_size must be >= 1")
        self.model = model
        self.params = params
        self.ladder = ladder or BucketLadder()
        self.max_batch = int(max_batch)
        self.cache_size = int(cache_size)
        self.metrics = metrics or ServeMetrics()
        self._apply_fn = apply_fn or (
            lambda p, batch: model.apply(p, batch)[0])
        self._rollout_opts = dict(rollout_opts or {})
        self._layout_opts = dict(layout_opts or {})
        # session-affinity prep cache (serve/prep.py): 0 disables. Created
        # here so the transport finds it on the engine and its hit/miss
        # counters share this engine's metrics registry.
        if session_cache:
            from distegnn_tpu.serve.prep import SessionPrepCache

            self.prep_cache: Optional[SessionPrepCache] = SessionPrepCache(
                int(session_cache), ladder=self.ladder,
                layout_opts=self._layout_opts, metrics=self.metrics,
                max_bytes=int(session_cache_bytes))
        else:
            self.prep_cache = None
        # tiled executor (serve/tiled.py): scenes above the ladder cap run
        # as a scan over fixed-shape tiles instead of 413-rejecting
        if tiled is not None:
            from distegnn_tpu.serve.tiled import TiledExecutor

            self.tiled: Optional["TiledExecutor"] = TiledExecutor(self, tiled)
        else:
            self.tiled = None
        if donate == "auto":
            donate = jax.default_backend() == "tpu"
        self._donate = bool(donate)
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
        # one lock for the cache; device execution itself is serialized by
        # the runtime, and the batcher calls from a single dispatch thread
        self._lock = threading.Lock()

    # ---- compile cache ---------------------------------------------------
    def _compiled(self, key: Tuple, build: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._cache.get(key)
            if fn is not None:
                self._cache.move_to_end(key)
                self.metrics.cache_event(hit=True)
                return fn
            evicted = 0
            while len(self._cache) >= self.cache_size:
                self._cache.popitem(last=False)
                evicted += 1
            fn = build()
            self._cache[key] = fn
            self.metrics.cache_event(hit=False, evicted=evicted)
            # cache misses land on the event stream: a miss AFTER warmup is
            # either an un-warmed rung (fine, once) or an eviction storm
            obs.event("serve/cache_miss", key=repr(key), evicted=evicted)
            return fn

    def cache_stats(self) -> Dict[str, int]:
        with self._lock:
            live = len(self._cache)
        snap = self.metrics.snapshot()
        return {"live": live, "hits": int(snap["cache_hits"]),
                "misses": int(snap["cache_misses"]),
                "evictions": int(snap["cache_evictions"])}

    # ---- one-step prediction --------------------------------------------
    def _build_predict(self, bucket: Bucket) -> Callable:
        donate = (1,) if self._donate else ()
        jitted = jax.jit(self._apply_fn, donate_argnums=donate)
        return jitted

    def predict_batch(self, graphs: Sequence[dict],
                      bucket: Optional[Bucket] = None,
                      request_ids: Optional[Sequence[str]] = None,
                      ) -> List[np.ndarray]:
        """Run one model step over same-bucket graphs; returns the UNPADDED
        per-graph predicted positions ``[n_i, 3]`` (numpy, host-synced).
        ``request_ids`` (gateway trace ids, position-aligned with ``graphs``)
        are stamped on the ``serve/execute`` span."""
        if not graphs:
            return []
        if len(graphs) > self.max_batch:
            raise ValueError(f"{len(graphs)} graphs > max_batch {self.max_batch}")
        if bucket is None:
            bs = [self.ladder.bucket_of_graph(g) for g in graphs]
            # elementwise max: the rung admitting every graph on BOTH axes
            bucket = Bucket(max(b.n for b in bs), max(b.e for b in bs))
        batch, n_real = self.ladder.pad_batch(graphs, bucket, self.max_batch,
                                              **self._layout_opts)
        # key on the RESULTING shapes, not the rung: blocked layouts derive
        # edges_per_block per batch, and two rungs that pad to the same
        # shapes may share one executable (plain layout keys reduce to the
        # (bucket.n, bucket.e, max_batch) triple)
        fn = self._compiled(("predict", batch.max_nodes, batch.max_edges,
                             batch.edge_block, self.max_batch),
                            lambda: self._build_predict(bucket))
        with obs.span("serve/execute", n=batch.max_nodes, e=batch.max_edges,
                      filled=n_real, capacity=self.max_batch,
                      **_rid_attrs(request_ids)):
            x = np.asarray(fn(self.params, batch))       # [max_batch, N, 3]
        return [x[i, : graphs[i]["loc"].shape[0]].copy()
                for i in range(n_real)]

    def predict(self, graph: dict) -> np.ndarray:
        """Single-graph convenience wrapper over ``predict_batch``."""
        return self.predict_batch([graph])[0]

    def warmup(self, sizes: Sequence[Tuple[int, int]]) -> List[Bucket]:
        """Pre-compile the rungs admitting the given (n_nodes, n_edges)
        sizes (distinct rungs only). Returns the warmed buckets."""
        from distegnn_tpu.serve.buckets import synthetic_graph

        jaxprobe.set_phase("serve_warmup")
        warmed: List[Bucket] = []
        with obs.span("serve/warmup", rungs=0) as sp:
            for n, e in sizes:
                b = self.ladder.bucket_for(n, e)
                if b in warmed:
                    continue
                # a tiny probe graph: the compiled shape is fixed by (bucket,
                # max_batch) alone, and padding admits any graph under the rung
                g = synthetic_graph(2, seed=0,
                                    feat_nf=self._probe_feat_nf(),
                                    edge_attr_nf=self._probe_edge_attr_nf())
                self.predict_batch([g], bucket=b)
                warmed.append(b)
            sp.set(rungs=len(warmed))
        jaxprobe.set_phase("serve")
        return warmed

    def _probe_feat_nf(self) -> int:
        return int(getattr(self.model, "node_feat_nf", 1) or 1)

    def _probe_edge_attr_nf(self) -> int:
        return int(getattr(self.model, "edge_attr_nf", 2) or 0)

    # ---- tiled giant-scene path (serve/tiled.py) ------------------------
    @property
    def tiled_enabled(self) -> bool:
        """True when scenes above the ladder cap dispatch to the tiled
        executor instead of 413-rejecting."""
        return self.tiled is not None and self.tiled.enable

    def predict_tiled(self, graph: dict,
                      request_id: Optional[str] = None,
                      progress: Optional[Callable] = None) -> dict:
        """One giant scene through the tile executor. The transport stashes
        a session-cached plan on the graph as ``_tile_plan``; absent (or
        built for a different layout) the executor replans inline. Plans
        carry no device count, so the same cached plan serves sequentially
        or as device-parallel rounds (``serve.tiled.devices``,
        serve/mesh_tiled.py) unchanged."""
        if self.tiled is None:
            raise RuntimeError(
                "engine built without serve.tiled config; giant scenes "
                "cannot be served")
        plan = graph.pop("_tile_plan", None)
        return self.tiled.predict(graph, plan=plan, request_id=request_id,
                                  progress=progress)

    @property
    def rollout_enabled(self) -> bool:
        """True when the engine was built with rollout_opts — the public
        capability flag the registry/transport consult instead of reaching
        into ``_rollout_opts``."""
        return bool(self._rollout_opts)

    def params_digest(self) -> str:
        """16-byte blake2b over the flattened param leaves (shape- and
        dtype-tagged, in canonical tree order) — the cross-process parity
        fingerprint. The parent compares its own digest against a worker
        child's at the spawn handshake, so silently divergent init (seed or
        environment drift) becomes a typed WorkerSpawnError instead of a
        wrong answer. Both sides build params through
        ``engine_with_params_from_config``, so the treedefs (and hence the
        leaf order) match by construction."""
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        for leaf in jax.tree_util.tree_leaves(self.params):
            arr = np.asarray(leaf)
            h.update(str(arr.shape).encode())
            h.update(str(arr.dtype).encode())
            h.update(np.ascontiguousarray(arr).tobytes())
        return h.hexdigest()

    # ---- blue/green canary ----------------------------------------------
    def canary(self, params, buckets: Sequence[Bucket]) -> int:
        """Forward CANDIDATE params through each bucket's compiled
        executable on a synthetic graph, without flipping ``self.params``.

        Reuses the exact predict compile-cache keys, so canarying warmed
        rungs compiles nothing new. Raises :class:`CanaryError` on
        non-finite outputs or a shape mismatch; returns the number of rungs
        checked. Used by the registry's blue/green swap before a replica is
        flipped to new params.
        """
        from distegnn_tpu.serve.buckets import synthetic_graph

        g = synthetic_graph(2, seed=0, feat_nf=self._probe_feat_nf(),
                            edge_attr_nf=self._probe_edge_attr_nf())
        checked = 0
        for b in buckets or [self.ladder.bucket_of_graph(g)]:
            batch, _ = self.ladder.pad_batch([g], b, self.max_batch,
                                             **self._layout_opts)
            fn = self._compiled(("predict", batch.max_nodes, batch.max_edges,
                                 batch.edge_block, self.max_batch),
                                lambda: self._build_predict(b))
            out = np.asarray(fn(params, batch))
            if out.shape != (self.max_batch, batch.max_nodes, 3):
                raise CanaryError(
                    f"canary output shape {out.shape} != expected "
                    f"{(self.max_batch, batch.max_nodes, 3)} on rung {b}")
            n_real = int(g["loc"].shape[0])
            if not np.isfinite(out[0, :n_real]).all():
                raise CanaryError(
                    f"canary produced non-finite outputs on rung {b} "
                    f"(candidate params are poisoned)")
            checked += 1
        return checked

    # ---- K-step rollout --------------------------------------------------
    def _rollout_fn_opts(self) -> dict:
        """rollout_opts resolved against the MODEL's feature widths: the
        rollout defaults (speed [N,1], distance-twice [E,2]) only fit models
        with those exact widths, so when the config doesn't pin a
        feature_fn/edge_attr_fn, replicate the defaults to match."""
        opts = dict(self._rollout_opts)
        nf = self._probe_feat_nf()
        if "feature_fn" not in opts and nf != 1:
            opts["feature_fn"] = lambda v: jnp.repeat(
                jnp.linalg.norm(v, axis=-1, keepdims=True), nf, axis=-1)
        ef = self._probe_edge_attr_nf()
        if "edge_attr_fn" not in opts and ef != 2:
            def edge_attr_fn(x, ei, em, _ef=max(ef, 1)):
                d = jnp.linalg.norm(x[ei[0]] - x[ei[1]], axis=-1,
                                    keepdims=True)
                return jnp.repeat(d, _ef, axis=-1) * em[:, None]

            opts["edge_attr_fn"] = edge_attr_fn
        return opts

    def rollout_rung(self, n: int) -> int:
        """Padded node count the rollout path compiles for a scene of ``n``
        nodes: the node-ladder rung rounded up to a multiple of the rollout
        edge_block. The batcher groups rollout requests on this value (plus
        steps) so same-rung scenes share one executable."""
        if not self._rollout_opts:
            raise ValueError("engine built without rollout_opts; pass "
                             "rollout_opts={'radius': ..., 'max_degree': ...}")
        edge_block = int(self._rollout_opts.get("edge_block", 256))
        rung = self.ladder._rung(n, self.ladder.node_floor,
                                 self.ladder.node_multiple,
                                 self.ladder.max_nodes, "nodes")
        return -(-max(rung, edge_block) // edge_block) * edge_block

    def rollout(self, loc0: np.ndarray, vel0: np.ndarray, steps: int,
                node_mask: Optional[np.ndarray] = None) -> np.ndarray:
        """K-step autoregressive rollout of one graph; returns the UNPADDED
        trajectory [steps, n, 3]. Raises RolloutOverflowError if any step
        overflowed the static neighbor-capacity bounds."""
        from distegnn_tpu.rollout import make_rollout_fn

        n = int(loc0.shape[0])
        n_pad = self.rollout_rung(n)
        opts = self._rollout_fn_opts()
        loc_p = np.zeros((n_pad, 3), np.float32)
        vel_p = np.zeros((n_pad, 3), np.float32)
        mask = np.zeros((n_pad,), np.float32)
        loc_p[:n], vel_p[:n] = loc0, vel0
        mask[:n] = (node_mask if node_mask is not None else np.ones(n)).astype(np.float32)

        def build():
            ro = make_rollout_fn(self.model, **opts)
            return jax.jit(functools.partial(ro, steps=int(steps)))

        fn = self._compiled(("rollout", n_pad, int(steps)), build)
        traj, over = fn(self.params, jnp.asarray(loc_p), jnp.asarray(vel_p),
                        jnp.asarray(mask))
        if bool(np.asarray(over).any()):
            self.metrics.failed()
            raise RolloutOverflowError(
                f"rollout overflowed radius-graph capacity at steps "
                f"{np.nonzero(np.asarray(over))[0].tolist()}; raise "
                f"max_degree/max_per_cell in rollout_opts")
        return np.asarray(traj)[:, :n]

    def rollout_batch(self, scenes: Sequence[dict],
                      request_ids: Optional[Sequence[str]] = None,
                      ) -> List[np.ndarray]:
        """Batched K-step rollout over same-rung scenes.

        Each scene dict carries ``loc`` [n, 3], ``vel`` [n, 3], ``steps``
        (int), and optionally ``node_mask`` [n]. All scenes MUST share the
        same ``steps`` (the scan length is compiled in) — mixing raises
        :class:`MixedRolloutStepsError`. Scenes are padded to one common
        node rung and the scene axis to ``max_batch`` (replicating scene 0,
        copies discarded), so a (rung, steps) pair owns exactly one
        executable — the predict-path batching contract, applied to
        rollouts. Returns per-scene UNPADDED trajectories [steps, n_i, 3].
        """
        if not scenes:
            return []
        if len(scenes) > self.max_batch:
            raise ValueError(f"{len(scenes)} scenes > max_batch {self.max_batch}")
        from distegnn_tpu.rollout import make_batched_rollout_fn

        steps_set = {int(s["steps"]) for s in scenes}
        if len(steps_set) != 1:
            raise MixedRolloutStepsError(
                f"rollout batch mixes steps {sorted(steps_set)}; scenes with "
                f"different K cannot share a compiled scan")
        steps = steps_set.pop()
        ns = [int(s["loc"].shape[0]) for s in scenes]
        n_pad = max(self.rollout_rung(n) for n in ns)
        B = self.max_batch
        loc_p = np.zeros((B, n_pad, 3), np.float32)
        vel_p = np.zeros((B, n_pad, 3), np.float32)
        mask = np.zeros((B, n_pad), np.float32)
        for i, (s, n) in enumerate(zip(scenes, ns)):
            loc_p[i, :n], vel_p[i, :n] = s["loc"], s["vel"]
            nm = s.get("node_mask")
            mask[i, :n] = (nm if nm is not None else np.ones(n)).astype(np.float32)
        # fill pad slots with scene 0 so the replicated work is well-posed
        # (an all-zero scene would collapse every node into one radius cell)
        for i in range(len(scenes), B):
            loc_p[i], vel_p[i], mask[i] = loc_p[0], vel_p[0], mask[0]

        opts = self._rollout_fn_opts()

        def build():
            ro = make_batched_rollout_fn(self.model, **opts)
            return jax.jit(functools.partial(ro, steps=steps))

        fn = self._compiled(("rollout_batch", n_pad, steps, B), build)
        with obs.span("serve/execute", n=n_pad, e=0, filled=len(scenes),
                      capacity=B, workload="rollout", steps=steps,
                      **_rid_attrs(request_ids)):
            traj, over = fn(self.params, jnp.asarray(loc_p),
                            jnp.asarray(vel_p), jnp.asarray(mask))
            traj = np.asarray(traj)                      # [B, steps, n_pad, 3]
        over = np.asarray(over)[: len(scenes)]           # replicas don't count
        if bool(over.any()):
            self.metrics.failed()
            bad = [(int(i), np.nonzero(over[i])[0].tolist())
                   for i in np.nonzero(over.any(axis=1))[0]]
            raise RolloutOverflowError(
                f"batched rollout overflowed radius-graph capacity "
                f"(scene, steps): {bad}; raise max_degree/max_per_cell in "
                f"rollout_opts")
        return [traj[i, :, :n].copy() for i, n in enumerate(ns)]

    def rollout_stream(self, scene: dict, emit,
                       request_id: Optional[str] = None) -> dict:
        """Chunked K-step rollout of ONE scene, delivering the trajectory
        incrementally through ``emit`` (a :class:`~distegnn_tpu.serve.queue.
        StreamSink`-shaped object: ``put_chunk(start_step, traj)`` plus a
        ``cancelled`` flag polled between chunks).

        The steps axis is executed as successive ``chunk_steps``-length
        compiled scans with the (loc, vel) carry threaded between them
        host-side — the same per-step update as one long scan (the carry
        rule mirrors rollout.py: ``v_next = (x_next - x) * velocity_scale``
        when ``velocity_from_delta``), so the first chunk arrives after
        ~chunk/K of the work and a client disconnect stops the remaining
        compute at the next chunk boundary. The compile-cache key is the
        single-scene ``("rollout", n_pad, chunk)`` rung, shared with the
        unbatched path. Returns a summary dict (steps_total / steps_done /
        cancelled / chunk_steps)."""
        from distegnn_tpu.rollout import make_rollout_fn

        steps = int(scene["steps"])
        chunk = max(1, int(scene.get("chunk_steps", 8) or 8))
        n = int(scene["loc"].shape[0])
        n_pad = self.rollout_rung(n)
        opts = self._rollout_fn_opts()
        vel_from_delta = bool(opts.get("velocity_from_delta", True))
        vscale = float(opts.get("velocity_scale", 1.0))
        loc_p = np.zeros((n_pad, 3), np.float32)
        vel_p = np.zeros((n_pad, 3), np.float32)
        mask = np.zeros((n_pad,), np.float32)
        loc_p[:n], vel_p[:n] = scene["loc"], scene["vel"]
        nm = scene.get("node_mask")
        mask[:n] = (nm if nm is not None else np.ones(n)).astype(np.float32)

        done = 0
        while done < steps:
            if getattr(emit, "cancelled", False):
                break
            c = min(chunk, steps - done)

            def build(_c=c):
                ro = make_rollout_fn(self.model, **opts)
                return jax.jit(functools.partial(ro, steps=_c))

            fn = self._compiled(("rollout", n_pad, c), build)
            with obs.span("serve/execute", n=n_pad, e=0, filled=1,
                          capacity=1, workload="rollout_stream", steps=c,
                          **_rid_attrs([request_id])):
                traj, over = fn(self.params, jnp.asarray(loc_p),
                                jnp.asarray(vel_p), jnp.asarray(mask))
                traj = np.asarray(traj)                  # [c, n_pad, 3]
            if bool(np.asarray(over).any()):
                self.metrics.failed()
                raise RolloutOverflowError(
                    f"streamed rollout overflowed radius-graph capacity at "
                    f"steps {(done + np.nonzero(np.asarray(over))[0]).tolist()}"
                    f"; raise max_degree/max_per_cell in rollout_opts")
            # thread the carry exactly as the scan body would have
            prev = loc_p if c == 1 else traj[c - 2]
            new_loc = traj[c - 1].copy()
            if vel_from_delta:
                vel_p = ((new_loc - prev) * vscale).astype(np.float32)
            loc_p = new_loc
            emit.put_chunk(done, traj[:, :n].copy())
            done += c
        return {"steps_total": steps, "steps_done": done,
                "cancelled": done < steps, "chunk_steps": chunk}
