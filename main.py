"""DistEGNN-TPU training entry point (parity with reference main.py).

Usage:
  python main.py --config_path configs/nbody_fastegnn.yaml [--lr ... --seed ...]

Single program for single-chip and distributed runs: the reference launches one
OS process per GPU via torchrun and wires NCCL (main.py:159-163); here a single
process drives all local chips through one jitted step (shard_map over a
`graph` mesh axis when accelerate_mode == 'distribute'), and multi-host pods
need only `jax.distributed.initialize()` before the same code.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Callable, NamedTuple

import jax
import numpy as np

from distegnn_tpu import obs, runtime
from distegnn_tpu.config import build_arg_parser, derive_runtime_fields, load_config
from distegnn_tpu.data import (
    GraphDataset,
    GraphLoader,
    PrefetchLoader,
    process_nbody_cutoff,
)
from distegnn_tpu.models.registry import get_model
from distegnn_tpu.train import (
    TrainState,
    make_eval_step,
    make_optimizer,
    make_train_step,
    needs_grad_clip,
    restore_checkpoint,
    train,
)
from distegnn_tpu.train.checkpoint import adopt_resume_seed, resolve_resume
from distegnn_tpu.train.scan_epoch import ScanEpochRunner, dataset_nbytes, scan_enabled
from distegnn_tpu.utils.seed import fix_seed

# exit code of a preempted-but-resumable run (BSD EX_TEMPFAIL): a wrapper
# script can key retry-with-resume off it
EXIT_PREEMPTED = 75


def count_parameters(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def init_multihost():
    """Join the multi-host world BEFORE any backend use — the TPU replacement
    for the reference's NCCL process-group init (reference main.py:159-163).

    On TPU pods jax.distributed.initialize() auto-discovers coordinator, rank
    and world size from the pod metadata. Elsewhere (e.g. CPU test rigs) pass
    them via DISTEGNN_COORD / DISTEGNN_NPROC / DISTEGNN_PID env vars. After
    this, jax.devices() is the GLOBAL device list, jax.process_index() plays
    the reference's `rank`, and the same shard_map code spans all hosts."""
    coord = os.environ.get("DISTEGNN_COORD")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ["DISTEGNN_NPROC"]),
            process_id=int(os.environ["DISTEGNN_PID"]),
        )
    else:
        jax.distributed.initialize()
    obs.log(f"multihost: process {jax.process_index()}/{jax.process_count()}, "
            f"{len(jax.local_devices())} local / {len(jax.devices())} global devices")


def process_dataset_edge_cutoff(data_cfg, seed: int = 0):
    """Dispatch by dataset (reference process_dataset_edge_cutoff,
    datasets/process_dataset.py:32-45)."""
    name = data_cfg.dataset_name
    if name.startswith("nbody"):
        return process_nbody_cutoff(
            data_cfg.data_dir, name, data_cfg.max_samples, data_cfg.radius,
            data_cfg.frame_0, data_cfg.frame_T, data_cfg.cutoff_rate,
        )
    if name == "protein":
        try:
            from distegnn_tpu.data.protein import process_protein_cutoff
        except ImportError as e:
            raise NotImplementedError("protein pipeline not built yet (SURVEY.md §7.2 stage 8)") from e

        return process_protein_cutoff(
            data_cfg.data_dir, name, data_cfg.max_samples, data_cfg.radius,
            data_cfg.delta_t, data_cfg.cutoff_rate, backbone=data_cfg.backbone,
            test_rot=data_cfg.test_rot, test_trans=data_cfg.test_trans,
            seed=seed,
        )
    if name == "Water-3D":
        try:
            from distegnn_tpu.data.water3d import process_water3d_cutoff
        except ImportError as e:
            raise NotImplementedError("Water-3D pipeline not built yet (SURVEY.md §7.2 stage 8)") from e

        return process_water3d_cutoff(
            data_cfg.data_dir, name, data_cfg.max_samples, data_cfg.radius,
            data_cfg.delta_t, data_cfg.cutoff_rate, seed=seed,
        )
    raise NotImplementedError(f"{name} has no cutoff-mode processor")


class CutoffRun(NamedTuple):
    """What ``build_cutoff`` assembles and ``train()`` takes."""

    datasets: tuple          # GraphDataset of train, valid, test
    loaders: tuple           # their bare GraphLoaders (model.init's sample, the scan runner)
    feeds: tuple             # what train() iterates: the train loader behind PrefetchLoader, the other two bare
    model: Any
    tx: Any
    state: TrainState        # fresh from model.init; a resume replaces it
    step_factory: Callable
    train_step: Callable
    eval_step: Callable
    scan_runner: Any         # ScanEpochRunner, or None: the host loop over feeds[0]


def build_cutoff(config, files) -> CutoffRun:
    """Everything ``accelerate_mode: cutoff_edges`` trains with, from the
    config and the three processed split files: one assembly for ``main`` and
    for a benchmark driver that measures this path."""
    d = config.data
    datasets = tuple(GraphDataset(f, node_order=d.node_order) for f in files)
    obs.log("Data ready: " + "/".join(str(len(ds)) for ds in datasets) + " graphs")
    loaders = tuple(GraphLoader(
        ds, d.batch_size, shuffle=(i == 0), seed=config.seed,
        node_bucket=d.node_bucket, edge_bucket=d.edge_bucket,
        edge_block=d.edge_block,
        # cumsum aggregation wants the reverse-edge pairing for scatter-free
        # col-gather backwards (plain layout; ops/segment.py)
        pairing=(True if (not d.edge_block and
                          config.model.get("segment_impl") in ("cumsum", "ell")) else None),
    ) for i, ds in enumerate(datasets))
    # collate + put of training batch k+1 overlap step k on a background
    # thread, as in distribute mode (parallel/launch.py); depth 0 =
    # synchronous. The put is the placement on the one device that the jitted
    # step would do itself. The evaluation loaders stay bare
    feeds = (PrefetchLoader(loaders[0], jax.device_put, depth=int(d.get("prefetch_depth", 2))),
             *loaders[1:])

    # Model
    model = get_model(config.model, world_size=1, dataset_name=d.dataset_name)
    sample = next(iter(loaders[0]))
    params = model.init(jax.random.PRNGKey(config.seed), sample)
    obs.log(f"Model: {config.model.model_name}, {count_parameters(params)} parameters")

    # Optimizer (+ reference clip rule and cosine schedule option)
    total_steps = config.train.epochs * len(loaders[0]) // config.train.accumulation_steps

    def build_tx(lr_scale: float = 1.0):
        return make_optimizer(
            config.train.learning_rate * lr_scale,
            weight_decay=config.train.weight_decay,
            clip_norm=0.3 if needs_grad_clip(config) else None,
            accumulation_steps=config.train.accumulation_steps,
            total_steps=total_steps,
            scheduler=str(config.train.scheduler),
        )

    tx = build_tx()
    state = TrainState.create(params, tx)

    # MMD applies to Fast* (virtual-node) models only (utils/train.py:119)
    is_fast = config.model.model_name.startswith("Fast")
    mmd_w = config.train.mmd.weight if is_fast else 0.0

    def step_factory(lr_scale: float):
        """Jitted train step at a scaled LR — divergence recovery swaps it in
        after rolling back to the last finite state (the opt-state TREE is
        LR-independent, so the rolled-back state loads unchanged)."""
        return jax.jit(make_train_step(model, build_tx(lr_scale),
                                       mmd_weight=mmd_w,
                                       mmd_sigma=config.train.mmd.sigma,
                                       mmd_samples=config.train.mmd.samples))

    train_step = step_factory(1.0)
    eval_step = jax.jit(make_eval_step(model))

    # scan_epochs: fold the epoch loop into one on-device lax.scan program
    # (train/scan_epoch.py) when the dataset fits in HBM — kills the
    # per-minibatch dispatch latency that dominates small-graph training
    scan_runner = None
    total = sum(dataset_nbytes(l) for l in loaders)
    if scan_enabled(config.train.scan_epochs, total):
        scan_runner = ScanEpochRunner(
            train_step, eval_step, loaders[0], config.seed,
            loader_valid=loaders[1], loader_test=loaders[2])
        obs.log(f"scan_epochs: on ({total / 2**30:.2f} GiB device-resident)")
    return CutoffRun(datasets, loaders, feeds, model, tx, state, step_factory,
                     train_step, eval_step, scan_runner)


def main(argv=None):
    parser = build_arg_parser()
    args = parser.parse_args(argv)
    if getattr(args, "multihost", False):
        init_multihost()
    overrides = {k: v for k, v in vars(args).items() if k != "config_path"}
    config = load_config(args.config_path, overrides=overrides)

    if config.data.accelerate_mode == "distribute":
        try:
            from distegnn_tpu.parallel.launch import run_distributed
        except ImportError as e:
            raise NotImplementedError("distribute mode not built yet (SURVEY.md §7.2 stage 6)") from e

        best = run_distributed(config)
        _point_at_events()
        return best

    # cutoff_edges mode is single-device by contract (reference main.py:173
    # asserts world_size == 1); an explicit conflicting --world_size is an error
    ws = config.data.get("world_size")
    if ws not in (None, 1):
        raise ValueError(f"accelerate_mode=cutoff_edges is single-device; got --world_size {ws}")
    derive_runtime_fields(config, world_size=1)
    adopt_resume_seed(config)
    fix_seed(config.seed)

    files = process_dataset_edge_cutoff(config.data, seed=config.seed)
    run = build_cutoff(config, files)
    state = run.state

    start_epoch, start_step_in_epoch = 0, 0
    resumed = resolve_resume(config, state)
    if resumed is not None:
        state, start_epoch = resumed.state, resumed.epoch
        start_step_in_epoch = resumed.step_in_epoch
        obs.log(f"resume: restored {resumed.path} (epoch {start_epoch} + "
                f"{start_step_in_epoch} step(s) applied)")
    elif config.model.checkpoint:
        state, start_epoch, _ = restore_checkpoint(config.model.checkpoint, state)
        obs.log(f"Checkpoint loaded from {config.model.checkpoint} (epoch {start_epoch})")

    state, best_state, best, log_dict = train(
        state, run.train_step, run.eval_step, *run.feeds,
        config, start_epoch=start_epoch, scan_runner=run.scan_runner,
        start_step_in_epoch=start_step_in_epoch, step_factory=run.step_factory,
    )
    if best.get("preempted"):
        obs.log(f"Preempted (resumable). Best so far: {best}")
    else:
        obs.log(f"Done. Best: {best}")
    _point_at_events()
    return best


def _point_at_events():
    """Flush the event stream and tell the operator where it landed (and how
    to render it) — the obs analog of the log.json pointer."""
    tracer = obs.get_tracer()
    tracer.flush()
    w = getattr(tracer, "writer", None)
    if w is not None:
        obs.log(f"obs: events at {w.path}; render with "
                f"python scripts/obs_report.py {w.path}")


if __name__ == "__main__":
    runtime.configure_compile_cache()
    _best = main()
    if isinstance(_best, dict) and _best.get("preempted"):
        sys.exit(EXIT_PREEMPTED)
