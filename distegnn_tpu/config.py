"""Config system — YAML schema + CLI overrides + derived fields.

Mirrors the reference's config flow (main.py:96-157): a four-section YAML
(model/data/train/log + seed), an argparse layer that overrides 11 chosen
fields one-by-one, and derived fields injected at load time (world_size,
exp_name). The reference uses EasyDict with zero validation; here we add a
defaults/validation layer (SURVEY.md §5.6 flags its absence as a gap) while
keeping the exact same YAML schema so reference configs load unchanged.

TPU deltas:
  - ``train.device`` (a CUDA ordinal in the reference) is accepted but ignored;
    device placement is the mesh's job (distegnn_tpu.parallel.mesh).
  - ``data.world_size`` derives from ``len(jax.devices())`` (reference:
    torch.cuda.device_count(), main.py:143) but may be overridden for
    CPU-simulated meshes.
"""

from __future__ import annotations

import argparse
import copy
import time
from typing import Any, Mapping, Optional

import yaml


class ConfigDict(dict):
    """dict with attribute access, recursively (the EasyDict role)."""

    def __init__(self, data: Optional[Mapping] = None):
        super().__init__()
        for k, v in (data or {}).items():
            self[k] = ConfigDict(v) if isinstance(v, Mapping) and not isinstance(v, ConfigDict) else v

    def __getattr__(self, name: str) -> Any:
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __setattr__(self, name: str, value: Any) -> None:
        self[name] = ConfigDict(value) if isinstance(value, Mapping) and not isinstance(value, ConfigDict) else value

    def __deepcopy__(self, memo):
        return ConfigDict({k: copy.deepcopy(v, memo) for k, v in self.items()})

    def to_dict(self) -> dict:
        return {k: v.to_dict() if isinstance(v, ConfigDict) else v for k, v in self.items()}


# Defaults merged under the YAML (YAML wins). Field set = union of the five
# reference configs (config/*.yaml) — same names, same sections.
_DEFAULTS: dict = {
    "seed": 43,
    "model": {
        "model_name": "FastEGNN",
        "normalize": False,
        "hidden_nf": 64,
        "n_layers": 4,
        "virtual_channels": 3,
        "node_feat_nf": 2,
        "node_attr_nf": 0,
        "edge_attr_nf": 2,
        "checkpoint": None,
        # TPU knobs: 'bf16' runs invariant-channel MLPs at MXU-native
        # precision (geometry stays f32 — see docs/PERFORMANCE.md); remat
        # recomputes each layer's MLPs in backward (what its edge passes
        # return is kept), trading FLOPs for HBM headroom
        "compute_dtype": None,
        "remat": False,
        # lowering of the blocked edge ops (only used when data.edge_block>0):
        # 'einsum' (one-hot materialized once per forward, aggregations and
        # gathers become batched MXU dots — default) or 'pallas' (one-hot
        # built in VMEM per kernel) — see ops/blocked.py
        "blocked_impl": "einsum",
        # FastEGNN + FastSchNet: evaluate the edge MLPs' first Dense on the
        # node axis (FastEGNN's phi_e; FastSchNet's phi_e AND its SchNet
        # coordinate gate) — same math, E/N x fewer matmul rows. Flipping it
        # changes those models' param trees (checkpoints are incompatible
        # across the flag; restore fails with a clear error)
        "hoist_edge_mlp": True,
        # plain-layout aggregation lowering (see ops/segment.py; Fast*
        # families only): 'scatter' = XLA sorted scatter (bit-exact),
        # 'cumsum' = scatter-free prefix-sum differences (f32-rounded),
        # 'ell' = scatter-free fixed-degree gathers (exact).
        "segment_impl": "scatter",
        # one packed aggregation pass per EGCL layer (translations + edge
        # features + count in a single segment sum; EdgeOps.agg_rows_pair)
        "fuse_agg": True,
        # packed-aggregation stream dtype: null (f32) or 'bf16' (halves the
        # dominant read bytes; f32 accumulation; rounds geometry columns —
        # measured opt-in, see docs/PERFORMANCE.md round-4 attack)
        "agg_dtype": None,
    },
    "data": {
        "data_dir": "./data",
        "dataset_name": "nbody_100",
        "max_samples": 5000,
        "batch_size": 1,
        "accelerate_mode": "cutoff_edges",  # or 'distribute'
        # cutoff_edges mode:
        "radius": -1.0,
        "cutoff_rate": 0.0,
        # distribute mode:
        "outer_radius": None,
        "inner_radius": None,
        "split_mode": "metis",
        # per-dataset frame selection:
        "frame_0": 30,
        "frame_T": 40,
        "delta_t": 20,
        "backbone": True,
        "test_rot": False,
        "test_trans": False,
        # spatial node relabeling for edge-op locality (TPU-only knob;
        # ops/order.py): 'none' or 'morton' (Z-curve sort of positions —
        # model-equivalent up to permutation, cache-friendly gathers)
        "node_order": "none",
        # padding buckets (TPU-only knobs; static-shape batching):
        "node_bucket": 8,
        "edge_bucket": 128,
        # blocked edge layout for the MXU aggregation kernels (ops/blocked.py):
        # 0 = off; 256 = recommended for large graphs (>= a few thousand nodes)
        "edge_block": 0,
        # mesh data axis (TPU-only): graphs-per-step = batch_size *
        # data_parallel, sharded over DATA_AXIS; devices used =
        # world_size * data_parallel (distegnn_tpu/parallel/mesh.py)
        "data_parallel": 1,
        # input pipeline (data/stream.py): prefetch_depth batches produced
        # ahead by a background thread (0 = synchronous blocking put);
        # stream_shard_cache decoded shards resident per StreamedGraphDataset
        # when a dataset path is a shard directory (scripts/shard_dataset.py)
        "prefetch_depth": 2,
        "stream_shard_cache": 4,
    },
    "train": {
        "learning_rate": 5e-4,
        "weight_decay": 1e-12,
        "epochs": 2500,
        "early_stop": 2500,
        "device": None,  # accepted for reference-config compat; unused on TPU
        "mmd": {"sigma": 1.5, "weight": 0.03, "samples": 3},
        "accumulation_steps": 1,
        "warmup_epochs": 0,
        "scheduler": "None",
        # TPU-only: 'auto'|True|False — run each epoch as ONE lax.scan program
        # over a device-resident dataset (train/scan_epoch.py). 'auto' enables
        # it for single-process cutoff_edges runs whose dataset fits in HBM.
        "scan_epochs": "auto",
        # resilience layer (docs/ROBUSTNESS.md):
        # resume: null (fresh run) | 'auto' (scan log.log_dir for the newest
        # CHECKSUM-VALID checkpoint, skipping corrupt/truncated files) | an
        # explicit checkpoint path (fails loudly if corrupt)
        "resume": None,
        # mid-epoch wall-clock checkpoint cadence in seconds (0 = only the
        # best/last eval-epoch saves); step_<n>.ckpt files rotate, keeping
        # the newest keep_checkpoints
        "checkpoint_interval_s": 0,
        "keep_checkpoints": 3,
        # non-finite epoch loss: roll back to the last finite state, multiply
        # the LR by divergence_lr_decay, retry — up to divergence_retries
        # times before declaring the run dead in log.json (0 = old
        # stop-on-NaN behavior)
        "divergence_retries": 2,
        "divergence_lr_decay": 0.5,
    },
    # serving layer (distegnn_tpu/serve, docs/SERVING.md) — the bucket
    # ladder, micro-batcher, and compile cache of the inference engine
    "serve": {
        # geometric (N, E) shape ladder: rung k = floor * growth^k rounded
        # to the multiples; requests above the caps are rejected (admission
        # control), not compiled
        "node_floor": 64,
        "edge_floor": 256,
        "growth": 2.0,
        "node_multiple": 8,
        "edge_multiple": 128,
        "max_nodes": 65536,
        "max_edges": 1 << 20,
        # micro-batcher: coalesce same-bucket requests up to max_batch or
        # until the oldest has waited batch_deadline_ms; every compiled
        # program runs at EXACTLY max_batch (one executable per rung)
        "max_batch": 8,
        "batch_deadline_ms": 5.0,
        # bounded ingress (submits beyond it fail fast = backpressure) and
        # per-request queued-time deadline
        "queue_capacity": 256,
        "request_timeout_ms": 1000.0,
        # compile-cache LRU size (live executables) and input-buffer
        # donation: 'auto' = donate on TPU only (CPU ignores donation)
        "cache_size": 32,
        "donate": "auto",
        # hard-deadline headroom on top of request_timeout_ms: a no-timeout
        # ServeFuture.result() waits at most timeout+margin, so a wedged
        # dispatcher surfaces as RequestTimeoutError (the gateway's 504)
        "result_margin_s": 30.0,
        # optional K-step rollout serving (rollout.make_rollout_fn kwargs);
        # null disables the rollout endpoint
        "rollout": None,
        # session-affinity graph-prep cache (serve/prep.py): capacity of the
        # per-model LRU keyed on the client session_id; 0 disables. A hit
        # skips Morton relabel + blocked re-pack for repeat-topology
        # requests (prep_ms ~ gather-only).
        "session_cache": 64,
        # byte bound for the same cache (plan nbytes accounting, evict-to-
        # fit): tile plans for million-node scenes are MBs each, so the
        # entry-count bound alone could pin GBs. 0 = unbounded by bytes.
        "session_cache_bytes": 1 << 30,
        # tiled giant-scene executor (serve/tiled.py): requests above
        # serve.max_nodes serve through a scan over fixed-shape tiles with
        # host-side halo exchange instead of 413-rejecting. Defaults match
        # serve/tiled.py TILED_DEFAULTS (keep in sync); enable: false keeps
        # the hard 413 behavior.
        "tiled": {
            "enable": False,
            # admission bound for the tiled path itself (TiledOverflowError
            # beyond it — still a 413, naming this knob)
            "max_nodes": 4_194_304,
            # own-node slots per tile; tile rung axes (halo, edges) are
            # geometric above their floors so every giant scene lands on a
            # small set of compiled tile shapes
            "tile_nodes": 65536,
            "halo_floor": 1024,
            "edge_floor": 8192,
            "growth": 2.0,
            # tiled requests run L x n_tiles invocations: their queue/result
            # deadlines stretch by this factor over request_timeout_ms
            "timeout_factor": 8.0,
            # device-parallel tile rounds (serve/mesh_tiled.py): 'auto'
            # takes every local device, N is clamped to what exists, 1
            # keeps the sequential single-device tile loop. Plans are
            # device-count-independent, so this can change per deploy
            # without invalidating session-cached tile plans.
            "devices": 1,
        },
        # shared-nothing engine replicas per model (serve/replica.py): each
        # replica owns its own engine + dispatcher queue behind one
        # round-robin ReplicaSet; >= 2 enables failover of in-flight
        # requests when a replica crashes or wedges
        "replicas": 1,
        # replica execution backend: 'thread' keeps every replica's engine
        # in the gateway process; 'process' moves each replica into an
        # out-of-process worker child (serve/worker.py — crash/OOM/GIL
        # isolation under the same supervision contract; predictions stay
        # bitwise-identical to the thread backend)
        "workers": "thread",
        # process-worker knobs (only read when workers: process): spawn
        # handshake budget (child jax import + engine build + warm rungs),
        # child heartbeat cadence, and the SIGTERM->SIGKILL escalation grace
        "worker": {
            "spawn_timeout_s": 120.0,
            "heartbeat_s": 0.5,
            "kill_grace_s": 3.0,
        },
        # replica supervisor knobs (serve/supervisor.py): heartbeat cadence,
        # wedge (no batch progress) deadline, worker heartbeat-staleness
        # deadline (process backend only), restart exponential backoff,
        # and the per-replica circuit breaker. Keys are splatted into
        # ReplicaSupervisor(**...), so only these eight are accepted.
        "supervisor": {
            "heartbeat_s": 0.25,
            "wedge_timeout_s": 60.0,
            "worker_heartbeat_timeout_s": 10.0,
            "backoff_base_s": 0.5,
            "backoff_max_s": 30.0,
            "breaker_threshold": 3,
            "breaker_cooldown_s": 30.0,
            "healthy_reset_s": 60.0,
        },
        # SLO-driven replica autoscaler (serve/autoscale.py): a per-model
        # control loop over the windowed SLO gauges (queue depth, shed rate,
        # p99) that grows/shrinks the ReplicaSet live. Disabled by default —
        # a static fleet stays exactly as configured.
        "autoscale": {
            "enable": False,
            "min_replicas": 1,
            "max_replicas": 4,
            # control-loop cadence and per-direction cooldowns (a scale
            # action suppresses further actions in the SAME direction for
            # its cooldown; up may still interrupt a down-calm streak)
            "interval_s": 0.5,
            "scale_up_cooldown_s": 2.0,
            "scale_down_cooldown_s": 10.0,
            # replicas added/retired per decision
            "step": 1,
            # scale-up triggers: queued requests per healthy replica, window
            # shed-rate fraction, optional absolute predict-p99 ceiling (ms,
            # null = p99 does not trigger)
            "queue_high": 4.0,
            "shed_high": 0.01,
            "p99_high_ms": None,
            # scale-down gate: per-replica depth below queue_low AND zero
            # window shed for idle_rounds consecutive evaluations
            "queue_low": 0.5,
            "idle_rounds": 3,
            # drain budget when retiring a replica (in-flight work finishes
            # before the queue stops — at-most-once is never sacrificed)
            "drain_timeout_s": 30.0,
        },
        # priority admission (serve/transport.py): interactive predicts
        # outrank bulk rollouts when the gateway saturates. Bulk work only
        # uses up to bulk_max_inflight_frac of the inflight budget, and is
        # deferred outright while the SLO window is degraded (shed rate
        # past degrade_shed_rate, or predict p99 past degrade_p99_ms).
        # Clients override the class with the priority header.
        "priority": {
            "enable": True,
            "header": "X-Priority",
            "bulk_max_inflight_frac": 0.75,
            "degrade_shed_rate": 0.05,
            "degrade_p99_ms": None,
            # Retry-After multiplier for deferred/shed bulk requests
            "bulk_retry_factor": 4.0,
            # predicts whose body is >= this many bytes default to the bulk
            # class (tiled giant scenes); 0 disables the size heuristic
            "bulk_content_bytes": 4_194_304,
        },
        # chunked streaming rollouts (POST .../rollout?stream=1): the steps
        # axis executes as successive chunk_steps-length compiled scans with
        # the carry threaded between, so the first chunk arrives after
        # ~chunk_steps/K of the work and a client disconnect cancels the
        # remaining chunks. Non-streaming requests are untouched.
        "stream": {
            "chunk_steps": 8,
        },
        # multi-model routing (serve/registry.py): null = one model from
        # THIS config; else a list of {name, config_path?, overrides?}
        # entries, each owning its own engine + queue + warmup
        "models": None,
        # HTTP transport front-end (serve/transport.py,
        # scripts/serve_gateway.py): bind address, gateway-level inflight
        # shed gate (429 before the queue sees the request), drain grace,
        # and the synthetic node counts warmed per model at startup
        "gateway": {
            "host": "127.0.0.1",
            "port": 8008,
            "max_inflight": 64,
            "drain_grace_s": 10.0,
            "warmup_nodes": [48, 96],
        },
    },
    # mesh layout (distegnn_tpu/parallel/mesh.py): the 3D device mesh
    # (data, graph, tensor). data/graph null = derive from data.data_parallel
    # and the device count (the legacy 2D behavior); tensor = hidden-dim
    # tensor parallelism degree T (NeutronTP-style feature split; FastEGNN
    # only, model.hidden_nf % T == 0, data*graph*tensor == devices used).
    # Omitting the section (or tensor: 1) is bitwise-identical to the 2D mesh.
    "parallel": {
        "mesh": {
            "data": None,
            "graph": None,
            "tensor": 1,
        },
    },
    # observability (distegnn_tpu/obs, docs/OBSERVABILITY.md) — structured
    # tracing + run metrics + JAX compile/memory probes. Default-on: spans
    # and events cost ~1us each and the writer is buffered; `enable: false`
    # is the kill switch (no event files, all hooks become no-ops).
    "obs": {
        "enable": True,
        # process 0 writes <exp_dir>/obs/events.jsonl; per_host gives every
        # process its own events_p<i>.jsonl (load-imbalance hunts)
        "per_host": False,
        # install the jax.monitoring compile watcher (recompiles-after-warmup
        # are the #1 silent perf bug; see scripts/obs_report.py --check)
        "jax_probe": True,
        # epoch/step/dispatch_s/stall_s on the host epoch loop's train/step
        # spans (a scanned epoch is one span; train/epoch_end events are
        # always emitted)
        "step_events": True,
        # writer buffering: flush every N events or T seconds
        "buffer_events": 256,
        "flush_interval_s": 2.0,
    },
    # service-level objectives (distegnn_tpu/obs/slo.py): declarative
    # thresholds scored against the event stream (obs_report --slo) or a
    # live GET /metrics scrape (scripts/traffic_gen.py). Null thresholds
    # declare no objective; window_s sizes the gateway's rolling-window
    # slo/window_* gauges.
    "slo": {
        "enable": True,
        "window_s": 60.0,
        # per-route latency ceilings on SUCCESSFUL responses, e.g.
        #   routes: {predict: {p99_ms: 250.0}, rollout: {p99_ms: 2000.0}}
        "routes": {},
        "error_rate_max": None,   # 5xx fraction ceiling (incl. 504)
        "shed_rate_max": None,    # 429 fraction ceiling
        "batch_fill_min": None,   # floor on filled/capacity slots
        "session_hit_min": None,  # floor on session prep-cache hit rate
    },
    # continuous train->serve promotion (distegnn_tpu/promote,
    # docs/SERVING.md "Continuous promotion"): the trainer publishes each
    # rotated checkpoint as a candidate into watch_dir; the gateway's
    # Promoter canaries it on one quarantined replica, replays a shadow
    # sample of live traffic against it, and promotes fleet-wide or rolls
    # back on the SLO window + prediction-drift gates.
    "promote": {
        "enable": False,          # gateway-side promoter control loop
        "publish": False,         # trainer-side candidate publishing
        "watch_dir": "",          # conveyor directory (shared by both ends)
        "model": "",              # registry entry to promote ("" = first)
        "interval_s": 1.0,        # promoter poll cadence
        "history": 4,             # candidates retained in watch_dir
        "shadow_sample": 0.25,    # fraction of live predicts teed to canary
        "min_shadow": 8,          # shadow comparisons required per verdict
        "max_shadow_inflight": 8, # outstanding shadow submits ceiling
        "gate_timeout_s": 30.0,   # max canary window before forced verdict
        "drift_ceiling": 0.05,    # per-rung mean relative divergence ceiling
        "max_error_rate": 0.0,    # SLO-window 5xx ceiling during canary
    },
    "log": {
        "log_dir": "./logs",
        "test_interval": 2,
        # run parallel/checks.assert_replicated on eval epochs (the reference's
        # startup broadcast+allclose rank check, made continuous)
        "check_consistency": True,
        # capture a jax.profiler trace of this epoch (0 = off) into
        # <exp_dir>/trace/ — open with TensorBoard/Perfetto/xprof. The
        # reference's profiling story is a no-op shim (SURVEY.md §5.1); here
        # it is a first-class flag on the training surface.
        "trace_epoch": 0,
        "wandb": {"enable": False, "offline": True, "api_key": "", "project": "", "entity": ""},
    },
}

_VALID_SPLIT_MODES = ("random", "metis", "spectral", "kmeans")
_VALID_ACCEL_MODES = ("cutoff_edges", "distribute")


def _merge(base: dict, override: Mapping) -> dict:
    out = copy.deepcopy(base)
    for k, v in override.items():
        if v is None and isinstance(out.get(k), dict):
            continue  # bare `section:` header in YAML — keep the defaults
        if isinstance(v, Mapping) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def load_config(path: str, overrides: Optional[Mapping] = None) -> ConfigDict:
    """Load YAML, merge over defaults, apply overrides, validate, derive."""
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    cfg = ConfigDict(_merge(_DEFAULTS, raw))
    if overrides:
        apply_overrides(cfg, overrides)
    validate_config(cfg)
    return cfg


# CLI-overridable fields: name -> (section path, type). Parity with the
# reference's argparse block (main.py:96-140) minus the torch device plumbing.
_CLI_FIELDS = {
    "lr": ("train.learning_rate", float),
    "seed": ("seed", int),
    "model_name": ("model.model_name", str),
    "batch_size": ("data.batch_size", int),
    "split_mode": ("data.split_mode", str),
    "early_stop": ("train.early_stop", int),
    "checkpoint": ("model.checkpoint", str),
    "cutoff_rate": ("data.cutoff_rate", float),
    "outer_radius": ("data.outer_radius", float),
    "inner_radius": ("data.inner_radius", float),
    "virtual_channels": ("model.virtual_channels", int),
    "epochs": ("train.epochs", int),
    "world_size": ("data.world_size", int),
    # TPU-only extension: mesh data axis size (not a reference flag)
    "data_parallel": ("data.data_parallel", int),
    # TPU-only extension: hidden-dim tensor parallelism degree T
    # (parallel.mesh.tensor; mesh grows a third axis when > 1)
    "tensor_parallel": ("parallel.mesh.tensor", int),
    # resilience: 'auto' or an explicit checkpoint path (train.resume)
    "resume": ("train.resume", str),
}

# Keys this program had and has no more. A config file or a command line is
# input from outside: one that still carries such a key is refused by name,
# never run on the path that is left.
_REMOVED_KEYS = ("model.edge_impl", "model.stack_vmem_budget")
_REMOVED_CLI = ("edge_impl",)


def removed_error(what: str) -> ValueError:
    return ValueError(
        f"{what} was removed: the fused Pallas edge pipelines it belonged to "
        "never compiled on the TPU (CHANGES.md, PR 21 and PR 32), and "
        "EdgeOps is the one real-edge path; delete it")


def _refuse_removed_keys(cfg: ConfigDict) -> None:
    for dotted in _REMOVED_KEYS:
        section, key = dotted.split(".")
        if key in (cfg.get(section) or {}):
            raise removed_error(dotted)


def _set_path(cfg: ConfigDict, dotted: str, value: Any) -> None:
    node = cfg
    parts = dotted.split(".")
    for p in parts[:-1]:
        node = node[p]
    node[parts[-1]] = value


def apply_overrides(cfg: ConfigDict, overrides: Mapping) -> None:
    """Apply {field: value} overrides; None values are skipped (reference
    semantics: only explicitly-passed CLI flags override, main.py:117-140)."""
    for name, value in overrides.items():
        if value is None:
            continue
        if name == "multihost":
            continue  # consumed by main.py before config handling
        if name == "wandb":
            if value:
                # explicit --wandb means "log online": enable AND go online
                # (reference configs ship enable=True so its flag only flips
                # offline, main.py:118; ours ship enable=False by default)
                cfg.log.wandb.enable = True
                cfg.log.wandb.offline = False
            continue
        if name in _REMOVED_CLI:
            raise removed_error(f"--{name}")
        if name not in _CLI_FIELDS:
            raise KeyError(f"unknown override {name!r}; valid: {sorted(_CLI_FIELDS)}")
        _set_path(cfg, _CLI_FIELDS[name][0], value)


def build_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="DistEGNN-TPU trainer")
    parser.add_argument("--config_path", type=str, required=True)
    parser.add_argument("--wandb", action="store_true")
    # multi-host pods: call jax.distributed.initialize() before any backend
    # use (replaces the reference's torchrun+NCCL process-group init,
    # main.py:159-163). See docs/MULTIHOST.md.
    parser.add_argument("--multihost", action="store_true")
    for name, (_, typ) in _CLI_FIELDS.items():
        parser.add_argument(f"--{name}", type=typ, default=None)
    for name in _REMOVED_CLI:   # parsed so that apply_overrides can say why
        parser.add_argument(f"--{name}", default=None, help=argparse.SUPPRESS)
    return parser


def validate_config(cfg: ConfigDict) -> None:
    _refuse_removed_keys(cfg)
    if cfg.data.accelerate_mode not in _VALID_ACCEL_MODES:
        raise ValueError(f"data.accelerate_mode must be one of {_VALID_ACCEL_MODES}")
    if cfg.data.accelerate_mode == "distribute":
        if cfg.data.split_mode not in _VALID_SPLIT_MODES:
            raise ValueError(f"data.split_mode must be one of {_VALID_SPLIT_MODES}")
        if cfg.data.outer_radius is None or cfg.data.inner_radius is None:
            raise ValueError("distribute mode requires data.outer_radius and data.inner_radius")
    if not 0.0 <= float(cfg.data.cutoff_rate) < 1.0:
        raise ValueError("data.cutoff_rate must be in [0, 1)")
    if int(cfg.data.get("prefetch_depth", 2)) < 0:
        raise ValueError("data.prefetch_depth must be >= 0 (0 = synchronous)")
    if int(cfg.data.get("stream_shard_cache", 4)) < 1:
        raise ValueError("data.stream_shard_cache must be >= 1")
    if cfg.train.accumulation_steps < 1:
        raise ValueError("train.accumulation_steps must be >= 1")
    resume = cfg.train.get("resume")
    if resume is not None and not isinstance(resume, str):
        raise ValueError("train.resume must be null, 'auto', or a checkpoint path")
    if float(cfg.train.get("checkpoint_interval_s", 0) or 0) < 0:
        raise ValueError("train.checkpoint_interval_s must be >= 0")
    if int(cfg.train.get("keep_checkpoints", 3)) < 1:
        raise ValueError("train.keep_checkpoints must be >= 1")
    if int(cfg.train.get("divergence_retries", 0) or 0) < 0:
        raise ValueError("train.divergence_retries must be >= 0")
    if not 0.0 < float(cfg.train.get("divergence_lr_decay", 0.5)) <= 1.0:
        raise ValueError("train.divergence_lr_decay must be in (0, 1]")
    if cfg.model.virtual_channels < 1:
        raise ValueError("model.virtual_channels must be >= 1")
    par = cfg.get("parallel")
    mesh = par.get("mesh") if par is not None else None
    if mesh is not None:
        if not isinstance(mesh, Mapping):
            raise ValueError("parallel.mesh must be a mapping with optional "
                             "keys data/graph/tensor")
        for key in mesh:
            if key not in ("data", "graph", "tensor"):
                raise ValueError(f"parallel.mesh: unknown key {key!r} "
                                 "(valid: data, graph, tensor)")
        for key in ("data", "graph", "tensor"):
            val = mesh.get(key, None if key != "tensor" else 1)
            if val is not None and int(val) < 1:
                raise ValueError(f"parallel.mesh.{key} must be >= 1")
        tensor = int(mesh.get("tensor", 1) or 1)
        if tensor > 1:
            hidden = int(cfg.model.hidden_nf)
            if hidden % tensor:
                raise ValueError(
                    f"parallel.mesh.tensor={tensor} must divide "
                    f"model.hidden_nf={hidden} (each chip owns a contiguous "
                    f"1/T hidden slice)")
            if cfg.model.model_name != "FastEGNN":
                raise ValueError(
                    f"parallel.mesh.tensor > 1 is only implemented for "
                    f"FastEGNN, not model.model_name="
                    f"{cfg.model.model_name!r}")
            if not bool(cfg.model.get("hoist_edge_mlp", True)):
                raise ValueError(
                    "parallel.mesh.tensor > 1 requires "
                    "model.hoist_edge_mlp=true (phi_e's tensor collective is "
                    "the node-level gather of the hoisted products)")
            if bool(cfg.model.get("tanh", False)):
                raise ValueError(
                    "parallel.mesh.tensor > 1 does not support model.tanh "
                    "(phi_x's psum is deferred through linear ops only)")
        mdata = mesh.get("data")
        dp = int(cfg.data.data_parallel)
        if mdata is not None and dp != 1 and int(mdata) != dp:
            raise ValueError(
                f"parallel.mesh.data={int(mdata)} conflicts with "
                f"data.data_parallel={dp} — set one of them")
    o = cfg.get("obs")
    if o is not None:
        for flag in ("enable", "per_host", "jax_probe", "step_events"):
            if not isinstance(o.get(flag, False), bool):
                raise ValueError(f"obs.{flag} must be a boolean")
        if int(o.get("buffer_events", 256)) < 1:
            raise ValueError("obs.buffer_events must be >= 1")
        if float(o.get("flush_interval_s", 2.0)) < 0:
            raise ValueError("obs.flush_interval_s must be >= 0")
    sl = cfg.get("slo")
    if sl is not None:
        if not isinstance(sl.get("enable", True), bool):
            raise ValueError("slo.enable must be a boolean")
        from distegnn_tpu.obs.slo import SLOSpec

        try:
            # SLOSpec.from_mapping owns the threshold/route validation;
            # surface its message under the config-section idiom
            SLOSpec.from_mapping(dict(sl))
        except ValueError as exc:
            raise ValueError(str(exc)) from None
    s = cfg.get("serve")
    if s is None:
        return  # hand-built config without the serving section
    if float(s.growth) <= 1.0:
        raise ValueError("serve.growth must be > 1")
    if int(s.max_batch) < 1 or int(s.cache_size) < 1:
        raise ValueError("serve.max_batch and serve.cache_size must be >= 1")
    if int(s.queue_capacity) < 1:
        raise ValueError("serve.queue_capacity must be >= 1")
    if float(s.batch_deadline_ms) < 0 or float(s.request_timeout_ms) <= 0:
        raise ValueError("serve.batch_deadline_ms must be >= 0 and "
                         "serve.request_timeout_ms > 0")
    if s.donate not in (True, False, "auto"):
        raise ValueError("serve.donate must be true, false, or 'auto'")
    if float(s.get("result_margin_s", 30.0)) <= 0:
        raise ValueError("serve.result_margin_s must be > 0")
    if int(s.get("session_cache", 0)) < 0:
        raise ValueError("serve.session_cache must be >= 0 (0 disables)")
    if int(s.get("session_cache_bytes", 0) or 0) < 0:
        raise ValueError("serve.session_cache_bytes must be >= 0 "
                         "(0 = unbounded by bytes)")
    t = s.get("tiled")
    if t is not None:
        if not isinstance(t, Mapping):
            raise ValueError("serve.tiled must be null or a mapping of "
                             "tiled-executor knobs")
        tknown = ("enable", "max_nodes", "tile_nodes", "halo_floor",
                  "edge_floor", "growth", "timeout_factor", "devices")
        for key in t:
            if key not in tknown:
                raise ValueError(f"serve.tiled: unknown key {key!r} "
                                 f"(accepted: {', '.join(tknown)})")
        if not isinstance(t.get("enable", False), bool):
            raise ValueError("serve.tiled.enable must be a boolean")
        for key in ("max_nodes", "tile_nodes", "halo_floor", "edge_floor"):
            if int(t.get(key, 1)) < 1:
                raise ValueError(f"serve.tiled.{key} must be >= 1")
        if int(t.get("tile_nodes", 65536)) > int(t.get("max_nodes",
                                                       4_194_304)):
            raise ValueError("serve.tiled.tile_nodes must be <= "
                             "serve.tiled.max_nodes")
        if float(t.get("growth", 2.0)) <= 1.0:
            raise ValueError("serve.tiled.growth must be > 1")
        if float(t.get("timeout_factor", 8.0)) < 1.0:
            raise ValueError("serve.tiled.timeout_factor must be >= 1")
        td = t.get("devices", 1)
        if td != "auto" and (isinstance(td, bool) or not isinstance(td, int)
                             or td < 1):
            raise ValueError("serve.tiled.devices must be 'auto' or an "
                             "int >= 1")
    r = s.get("rollout")
    if r is not None:
        if not isinstance(r, Mapping):
            raise ValueError("serve.rollout must be null or a mapping of "
                             "make_rollout_fn kwargs (radius, max_degree, ...)")
        if float(r.get("radius", 0.0)) <= 0:
            raise ValueError("serve.rollout.radius must be > 0")
        if int(r.get("max_degree", 0)) < 1:
            raise ValueError("serve.rollout.max_degree must be >= 1")
        if int(r.get("max_per_cell", 16)) < 1:
            raise ValueError("serve.rollout.max_per_cell must be >= 1")
        if (int(r.get("max_degree", 0))
                * int(r.get("edge_block", 256))) % 512:
            raise ValueError("serve.rollout: max_degree * edge_block must be "
                             "a multiple of 512 (the kernel edge tile)")
    if int(s.get("replicas", 1) or 1) < 1:
        raise ValueError("serve.replicas must be >= 1")
    if str(s.get("workers", "thread") or "thread") not in ("thread",
                                                           "process"):
        raise ValueError("serve.workers must be 'thread' or 'process'")
    w = s.get("worker")
    if w is not None:
        if not isinstance(w, Mapping):
            raise ValueError("serve.worker must be null or a mapping of "
                             "process-worker knobs")
        wknown = ("spawn_timeout_s", "heartbeat_s", "kill_grace_s")
        for key in w:
            if key not in wknown:
                raise ValueError(f"serve.worker: unknown key {key!r} "
                                 f"(accepted: {', '.join(wknown)})")
        for key in wknown:
            if key in w and float(w[key]) <= 0:
                raise ValueError(f"serve.worker.{key} must be > 0")
    sup = s.get("supervisor")
    if sup is not None:
        if not isinstance(sup, Mapping):
            raise ValueError("serve.supervisor must be null or a mapping of "
                             "ReplicaSupervisor kwargs")
        known = ("heartbeat_s", "wedge_timeout_s",
                 "worker_heartbeat_timeout_s", "backoff_base_s",
                 "backoff_max_s", "breaker_threshold", "breaker_cooldown_s",
                 "healthy_reset_s")
        for key in sup:
            if key not in known:
                raise ValueError(f"serve.supervisor: unknown key {key!r} "
                                 f"(accepted: {', '.join(known)})")
        for key in known:
            if key in sup and float(sup[key]) <= 0:
                raise ValueError(f"serve.supervisor.{key} must be > 0")
        if int(sup.get("breaker_threshold", 3)) < 1:
            raise ValueError("serve.supervisor.breaker_threshold must be >= 1")
    a = s.get("autoscale")
    if a is not None:
        if not isinstance(a, Mapping):
            raise ValueError("serve.autoscale must be null or a mapping of "
                             "ReplicaAutoscaler knobs")
        aknown = ("enable", "min_replicas", "max_replicas", "interval_s",
                  "scale_up_cooldown_s", "scale_down_cooldown_s", "step",
                  "queue_high", "shed_high", "p99_high_ms", "queue_low",
                  "idle_rounds", "drain_timeout_s")
        for key in a:
            if key not in aknown:
                raise ValueError(f"serve.autoscale: unknown key {key!r} "
                                 f"(accepted: {', '.join(aknown)})")
        if not isinstance(a.get("enable", False), bool):
            raise ValueError("serve.autoscale.enable must be a boolean")
        lo = int(a.get("min_replicas", 1))
        hi = int(a.get("max_replicas", 4))
        if lo < 1 or hi < lo:
            raise ValueError("serve.autoscale needs 1 <= min_replicas "
                             "<= max_replicas")
        if int(a.get("step", 1)) < 1 or int(a.get("idle_rounds", 3)) < 1:
            raise ValueError("serve.autoscale.step and "
                             "serve.autoscale.idle_rounds must be >= 1")
        for key in ("interval_s", "drain_timeout_s", "queue_high"):
            if float(a.get(key, 1.0)) <= 0:
                raise ValueError(f"serve.autoscale.{key} must be > 0")
        for key in ("scale_up_cooldown_s", "scale_down_cooldown_s",
                    "shed_high", "queue_low"):
            if float(a.get(key, 0.0)) < 0:
                raise ValueError(f"serve.autoscale.{key} must be >= 0")
        if a.get("p99_high_ms") is not None and float(a["p99_high_ms"]) <= 0:
            raise ValueError("serve.autoscale.p99_high_ms must be null "
                             "or > 0")
    p = s.get("priority")
    if p is not None:
        if not isinstance(p, Mapping):
            raise ValueError("serve.priority must be null or a mapping of "
                             "priority-admission knobs")
        pknown = ("enable", "header", "bulk_max_inflight_frac",
                  "degrade_shed_rate", "degrade_p99_ms", "bulk_retry_factor",
                  "bulk_content_bytes")
        for key in p:
            if key not in pknown:
                raise ValueError(f"serve.priority: unknown key {key!r} "
                                 f"(accepted: {', '.join(pknown)})")
        if not isinstance(p.get("enable", True), bool):
            raise ValueError("serve.priority.enable must be a boolean")
        if not str(p.get("header", "X-Priority")).strip():
            raise ValueError("serve.priority.header must be non-empty")
        frac = float(p.get("bulk_max_inflight_frac", 0.75))
        if not 0.0 < frac <= 1.0:
            raise ValueError("serve.priority.bulk_max_inflight_frac must be "
                             "in (0, 1]")
        if float(p.get("degrade_shed_rate", 0.05)) < 0:
            raise ValueError("serve.priority.degrade_shed_rate must be >= 0")
        if (p.get("degrade_p99_ms") is not None
                and float(p["degrade_p99_ms"]) <= 0):
            raise ValueError("serve.priority.degrade_p99_ms must be null "
                             "or > 0")
        if float(p.get("bulk_retry_factor", 4.0)) < 1:
            raise ValueError("serve.priority.bulk_retry_factor must be >= 1")
        if int(p.get("bulk_content_bytes", 0) or 0) < 0:
            raise ValueError("serve.priority.bulk_content_bytes must be "
                             ">= 0 (0 disables)")
    st = s.get("stream")
    if st is not None:
        if not isinstance(st, Mapping):
            raise ValueError("serve.stream must be null or a mapping of "
                             "streaming-rollout knobs")
        for key in st:
            if key not in ("chunk_steps",):
                raise ValueError(f"serve.stream: unknown key {key!r} "
                                 f"(accepted: chunk_steps)")
        if int(st.get("chunk_steps", 8)) < 1:
            raise ValueError("serve.stream.chunk_steps must be >= 1")
    models = s.get("models")
    if models is not None:
        if not isinstance(models, (list, tuple)) or not models:
            raise ValueError("serve.models must be null or a non-empty list "
                             "of {name, config_path?, overrides?} entries")
        seen = set()
        for item in models:
            if not isinstance(item, Mapping) or not item.get("name"):
                raise ValueError("each serve.models entry needs a 'name'")
            name = str(item["name"])
            if name in seen:
                raise ValueError(f"duplicate serve.models name {name!r}")
            seen.add(name)
            for key in item:
                if key not in ("name", "config_path", "overrides"):
                    raise ValueError(f"serve.models[{name!r}]: unknown key "
                                     f"{key!r}")
            if item.get("overrides") is not None and not isinstance(
                    item["overrides"], Mapping):
                raise ValueError(f"serve.models[{name!r}].overrides must be "
                                 "a mapping")
    g = s.get("gateway")
    if g is not None:
        if int(g.get("max_inflight", 64)) < 1:
            raise ValueError("serve.gateway.max_inflight must be >= 1")
        if not 0 <= int(g.get("port", 8008)) <= 65535:
            raise ValueError("serve.gateway.port must be in [0, 65535]")
        if float(g.get("drain_grace_s", 10.0)) < 0:
            raise ValueError("serve.gateway.drain_grace_s must be >= 0")
        nodes = g.get("warmup_nodes", [48, 96])
        if (not isinstance(nodes, (list, tuple)) or not nodes
                or any(int(n) < 2 for n in nodes)):
            raise ValueError("serve.gateway.warmup_nodes must be a "
                             "non-empty list of node counts >= 2")
    lg = cfg.get("log")
    if lg is not None:
        if not isinstance(lg.get("log_dir", ""), str):
            raise ValueError("log.log_dir must be a string path")
        if int(lg.get("test_interval", 2)) < 1:
            raise ValueError("log.test_interval must be >= 1")
        if not isinstance(lg.get("check_consistency", True), bool):
            raise ValueError("log.check_consistency must be a boolean")
        if int(lg.get("trace_epoch", 0) or 0) < 0:
            raise ValueError("log.trace_epoch must be >= 0")
    pm = cfg.get("promote")
    if pm is not None:
        if not isinstance(pm, Mapping):
            raise ValueError("promote must be null or a mapping of "
                             "promotion-conveyor knobs")
        pmknown = ("enable", "publish", "watch_dir", "model", "interval_s",
                   "history", "shadow_sample", "min_shadow",
                   "max_shadow_inflight", "gate_timeout_s", "drift_ceiling",
                   "max_error_rate")
        for key in pm:
            if key not in pmknown:
                raise ValueError(f"promote: unknown key {key!r} "
                                 f"(accepted: {', '.join(pmknown)})")
        for flag in ("enable", "publish"):
            if not isinstance(pm.get(flag, False), bool):
                raise ValueError(f"promote.{flag} must be a boolean")
        for skey in ("watch_dir", "model"):
            if not isinstance(pm.get(skey, ""), str):
                raise ValueError(f"promote.{skey} must be a string")
        for key in ("interval_s", "gate_timeout_s", "drift_ceiling"):
            if float(pm.get(key, 1.0)) <= 0:
                raise ValueError(f"promote.{key} must be > 0")
        for key in ("history", "min_shadow", "max_shadow_inflight"):
            if int(pm.get(key, 1)) < 1:
                raise ValueError(f"promote.{key} must be >= 1")
        if not 0.0 < float(pm.get("shadow_sample", 0.25)) <= 1.0:
            raise ValueError("promote.shadow_sample must be in (0, 1]")
        if float(pm.get("max_error_rate", 0.0)) < 0:
            raise ValueError("promote.max_error_rate must be >= 0")
        if ((pm.get("enable") or pm.get("publish"))
                and not str(pm.get("watch_dir", "")).strip()):
            raise ValueError("promote.watch_dir is required when "
                             "promote.enable or promote.publish is set")


def derive_runtime_fields(cfg: ConfigDict, world_size: Optional[int] = None) -> ConfigDict:
    """Inject data.world_size and log.exp_name (reference main.py:143-157).

    exp_name encodes dataset/split/model/radii/world_size/channels/timestamp —
    the same recipe, so runs are identifiable the same way.
    """
    if world_size is None:
        world_size = cfg.data.get("world_size")
    if world_size is None:
        import jax
        world_size = len(jax.devices())
    cfg.data.world_size = int(world_size)

    d = cfg.data
    if d.accelerate_mode == "distribute":
        geo = f"{d.split_mode}_o{d.outer_radius}_i{d.inner_radius}"
    else:
        geo = f"r{d.radius}_cut{d.cutoff_rate}"
    stamp = time.strftime("%Y%m%d_%H%M%S")
    cfg.log.exp_name = (
        f"{d.dataset_name}_{geo}_{cfg.model.model_name}"
        f"_ws{cfg.data.world_size}_C{cfg.model.virtual_channels}_{stamp}"
    )
    return cfg
