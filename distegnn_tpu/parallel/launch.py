"""Distributed (DistEGNN) execution: one jitted shard_map'd train step over the
mesh's ``graph`` axis.

Replaces the reference's torchrun + NCCL + DDP stack (reference
main.py:159-229): there, one OS process per GPU runs the same Python loop and
synchronizes through process-group collectives; here ONE program traces the
step once, shard_map lays the partition axis over devices, and the three
per-layer virtual-node psums plus the node-count loss psum are XLA collectives
riding ICI. Gradient sync is an explicit psum of per-partition gradients
inside the step (see distegnn_tpu/train/step.py) — the DDP-sum pattern — so
every device applies the identical optimizer update and weights stay
replicated by construction (the invariant the reference checks with
broadcast+allclose at startup, main.py:40-55).

Multi-host: ``main.py --multihost`` calls ``jax.distributed.initialize()``;
``run_distributed`` then builds the mesh from the GLOBAL ``jax.devices()``
(all processes), host batches become global jax.Arrays via
``global_batch_putter`` (each host materializes only its addressable shards),
and the same shard_map spans the global mesh with XLA routing the collectives
over ICI/DCN. See docs/MULTIHOST.md for the pod launch recipe and
tests/test_multihost.py for a real two-process CPU test.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distegnn_tpu import obs
from distegnn_tpu.parallel.mesh import DATA_AXIS, GRAPH_AXIS, TENSOR_AXIS, make_mesh
from distegnn_tpu.train import (
    TrainState,
    make_eval_step,
    make_optimizer,
    make_train_step,
    needs_grad_clip,
    restore_checkpoint,
    train,
)
from distegnn_tpu.train.checkpoint import (
    adopt_resume_seed,
    resolve_resume,
    verify_resume_consensus,
)


def batch_layout(n_data: int):
    """The single source of truth for the batch array layout: (PartitionSpec
    for the leading shard axes, per-device strip function). 1-D mesh:
    [P, B, ...] sharded P(GRAPH_AXIS); 2-D: [D, P, B, ...] sharded
    P(DATA_AXIS, GRAPH_AXIS)."""
    if n_data > 1:
        return P(DATA_AXIS, GRAPH_AXIS), (lambda x: x[0, 0])
    return P(GRAPH_AXIS), (lambda x: x[0])


def make_device_steps(model, tx, mesh, mmd_weight: float, mmd_sigma: float,
                      mmd_samples: int):
    """The PER-DEVICE (axis-bound, un-shard_mapped) train/eval callables —
    the single source of step semantics for both distributed paths: the
    per-step loop (make_distributed_steps) and the scanned epoch
    (train.scan_epoch.DistributedScanRunner)."""
    n_data = mesh.shape[DATA_AXIS]
    data_axis = DATA_AXIS if n_data > 1 else None
    step = make_train_step(model, tx, mmd_weight=mmd_weight, mmd_sigma=mmd_sigma,
                           mmd_samples=mmd_samples, axis_name=GRAPH_AXIS,
                           data_axis_name=data_axis)
    ev = make_eval_step(model, axis_name=GRAPH_AXIS, data_axis_name=data_axis)
    return step, ev


def make_distributed_steps(model, tx, mesh, mmd_weight: float, mmd_sigma: float,
                           mmd_samples: int):
    """Build jitted (train_step, eval_step) running under shard_map.

    1-D mesh (data axis size 1): batch arrays arrive [P, B, ...]
    (ShardedGraphLoader layout); the leading axis shards over GRAPH_AXIS so
    each device sees its partition's [B, ...] slice.

    2-D mesh: batch arrives [D, P, B, ...]; the leading axes shard over
    (DATA_AXIS, GRAPH_AXIS). Loss node-weighting and the gradient psum span
    both axes; the model's virtual-node psums stay on GRAPH_AXIS (the data
    axis holds different graphs). State and PRNG key are replicated; outputs
    (replicated state, psum'd scalars) come back as single copies.
    """
    n_data = mesh.shape[DATA_AXIS]
    step, ev = make_device_steps(model, tx, mesh, mmd_weight, mmd_sigma,
                                 mmd_samples)
    batch_spec, strip = batch_layout(n_data)

    def _step_one(state, batch, key):
        # strip the leading shard axes (size 1 per device under shard_map)
        b = jax.tree.map(strip, batch)
        return step(state, b, key)

    def _eval_one(params, batch):
        return ev(params, jax.tree.map(strip, batch))

    train_step = jax.jit(jax.shard_map(
        _step_one, mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    ))
    eval_step = jax.jit(jax.shard_map(
        _eval_one, mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=P(),
        check_vma=False,
    ))
    return train_step, eval_step


def global_batch_putter(mesh):
    """Host numpy batch -> global jax.Array laid out for make_distributed_steps.

    Single-process this is equivalent to an implicit device_put; multi-host it
    is REQUIRED: each process holds the full logical batch in host RAM but
    materializes only its addressable shards (jax.make_array_from_callback
    invokes the callback per addressable shard index only) — the TPU analog of
    the reference's per-rank shard files (reference main.py:182-190)."""
    batch_spec, _ = batch_layout(mesh.shape[DATA_AXIS])

    def put(batch):
        def _mk(x):
            x = np.asarray(x)
            sharding = NamedSharding(mesh, batch_spec)
            return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

        return jax.tree.map(_mk, batch)

    return put


# the blocking put-wrapper (_PuttingLoader) lives on as
# data/stream.PrefetchLoader(depth=0); depth>0 (config data.prefetch_depth,
# default 2) overlaps collate + put with the previous step's compute


def _dispatch_preprocess(config, ws: int):
    """Per-dataset distribute-mode preprocessing (reference
    process_dataset_distribute, datasets/process_dataset.py:48-58). Idempotent:
    results are cached shard files keyed by config, so any process may call it
    and later callers hit the cache."""
    from distegnn_tpu.data.distribute import process_nbody_distribute

    d = config.data
    name = d.dataset_name
    if name.startswith("nbody"):
        return process_nbody_distribute(
            d.data_dir, name, ws, d.max_samples, d.inner_radius, d.outer_radius,
            d.split_mode, d.frame_0, d.frame_T, seed=config.seed,
        )
    if name == "Water-3D":
        try:
            from distegnn_tpu.data.water3d import process_water3d_distribute
        except ImportError as e:
            raise NotImplementedError("Water-3D pipeline not built yet (SURVEY.md §7.2 stage 8)") from e

        return process_water3d_distribute(
            d.data_dir, name, ws, d.max_samples, d.inner_radius, d.outer_radius,
            d.split_mode, d.delta_t, seed=config.seed,
        )
    if name in ("Fluid113K", "LargeFluid"):
        try:
            from distegnn_tpu.data.fluid113k import process_large_fluid_distribute
        except ImportError as e:
            raise NotImplementedError("Fluid113K pipeline not built yet (SURVEY.md §7.2 stage 8)") from e

        return process_large_fluid_distribute(
            d.data_dir, name, ws, d.max_samples, d.inner_radius, d.outer_radius,
            d.split_mode, d.delta_t, seed=config.seed,
        )
    raise NotImplementedError(f"{name} has no distribute-mode processor")


def run_distributed(config):
    """Distribute-mode entry (reference main.py distribute flow): partitioned
    shards -> ShardedGraphLoader -> shard_map'd jitted step -> shared outer
    training loop."""
    from distegnn_tpu.config import derive_runtime_fields
    from distegnn_tpu.data import PrefetchLoader, ShardedGraphLoader, open_dataset
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.utils.seed import fix_seed

    # world_size = graph partitions (reference semantics); data_parallel adds
    # the second mesh axis and parallel.mesh.tensor the third, so ws * dp * tp
    # devices are used. Multi-host: after jax.distributed.initialize()
    # (main.py --multihost) jax.devices() is the GLOBAL device list, so the
    # mesh spans all processes with no extra code.
    pmesh = (config.get("parallel") or {}).get("mesh") or {}
    tp = int(pmesh.get("tensor") or 1)
    dp = int(pmesh.get("data") or config.data.get("data_parallel") or 1)
    ws = (pmesh.get("graph") or config.data.get("world_size")
          or len(jax.devices()) // (dp * tp))
    ws = int(ws)
    if ws < 1 or ws * dp * tp > len(jax.devices()):
        raise ValueError(
            f"mesh data={dp} x graph={ws} x tensor={tp} does not fit the "
            f"{len(jax.devices())} available devices")
    derive_runtime_fields(config, world_size=ws)
    adopt_resume_seed(config)
    fix_seed(config.seed)
    mesh = make_mesh(n_graph=ws, n_data=dp, n_tensor=tp,
                     devices=jax.devices()[:ws * dp * tp])
    # record the resolved shape so downstream consumers (checkpoint metadata,
    # per-chip memory gauges) tag artifacts with the actual mesh
    config.parallel = {"mesh": {"data": dp, "graph": ws, "tensor": tp}}

    d = config.data
    name = d.dataset_name

    def preprocess():
        return _dispatch_preprocess(config, ws)

    if jax.process_count() > 1:
        # preprocessing runs on process 0 only, everyone else waits at a
        # barrier then reads the cache — the reference's rank-0 + dist.barrier
        # flow (reference main.py:171-177, process_dataset.py:462-463)
        from jax.experimental import multihost_utils

        if jax.process_index() == 0:
            split_paths = preprocess()
        multihost_utils.sync_global_devices("distegnn_preprocess")
        if jax.process_index() != 0:
            split_paths = preprocess()  # cache hit: shard files exist
    else:
        split_paths = preprocess()

    put = global_batch_putter(mesh)
    loaders = []
    for split_idx, paths in enumerate(split_paths):
        # open_dataset streams shard directories (scripts/shard_dataset.py
        # output) out-of-core and materializes pickle paths as before
        datasets = [open_dataset(p, node_order=d.node_order,
                                 cache_shards=int(d.get("stream_shard_cache", 4)))
                    for p in paths]
        loaders.append(PrefetchLoader(ShardedGraphLoader(
            datasets, d.batch_size, shuffle=(split_idx == 0), seed=config.seed,
            node_bucket=d.node_bucket, edge_bucket=d.edge_bucket,
            data_parallel=dp, edge_block=d.edge_block,
            # cumsum aggregation wants the reverse-edge pairing attached to
            # plain batches (scatter-free col-gather backward, ops/segment.py)
            pairing=(True if (not d.edge_block and
                              config.model.get("segment_impl") in ("cumsum", "ell"))
                     else None),
        ), put, depth=int(d.get("prefetch_depth", 2))))
    loader_train, loader_valid, loader_test = loaders
    obs.log(f"Data ready: {len(loader_train.loader.loaders[0].dataset)} graphs x "
            f"{ws} partitions x {dp} data shards")
    if d.split_mode == "metis":
        from distegnn_tpu.native import native_status

        # a missing compiler silently swaps the C++ partitioner for the slow
        # NumPy bisection; say which one cut the shards
        obs.log(f"partition: split_mode=metis via {native_status()}")

    model = get_model(config.model, world_size=ws, dataset_name=name,
                      axis_name=GRAPH_AXIS,
                      tensor_axis=(TENSOR_AXIS if tp > 1 else None))
    # init outside shard_map on the raw HOST batch (the axis names are unbound
    # there, and the param tree is identical either way — axis_name only
    # routes psums, and tensor_axis slices the SAME full params at compute
    # time); a global jax.Array can't be indexed on one host
    sample = next(iter(loader_train.loader))
    _, strip0 = batch_layout(dp)
    init_model = (model.copy(axis_name=None, tensor_axis=None) if tp > 1
                  else model.copy(axis_name=None))
    params = init_model.init(
        jax.random.PRNGKey(config.seed), jax.tree.map(strip0, sample))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    obs.log(f"Model: {config.model.model_name}, {n_params} parameters, "
            f"mesh data={dp} graph={ws} tensor={tp}")

    total_steps = config.train.epochs * len(loader_train) // config.train.accumulation_steps
    clip = 0.3 if needs_grad_clip(config) else None

    def build_tx(lr_scale: float = 1.0):
        return make_optimizer(
            config.train.learning_rate * lr_scale,
            weight_decay=config.train.weight_decay,
            clip_norm=clip, accumulation_steps=config.train.accumulation_steps,
            total_steps=total_steps, scheduler=str(config.train.scheduler),
        )

    tx = build_tx()
    state = TrainState.create(params, tx)
    start_epoch, start_step_in_epoch = 0, 0
    resumed = resolve_resume(config, state)
    if resumed is not None:
        state, start_epoch = resumed.state, resumed.epoch
        start_step_in_epoch = resumed.step_in_epoch
        obs.log(f"resume: restored {resumed.path} (epoch {start_epoch} + "
                f"{start_step_in_epoch} step(s) applied)")
    elif config.model.checkpoint:
        state, start_epoch, _ = restore_checkpoint(
            config.model.checkpoint, state, config=config)
        obs.log(f"Checkpoint loaded from {config.model.checkpoint} (epoch {start_epoch})")
    # coordinated-restore barrier: every host must have adopted the same
    # resume coordinates before any psum'd step runs (no-op single-process);
    # the local path rides the typed error so a consensus failure names a
    # concrete checkpoint to diff against the lagging hosts
    verify_resume_consensus(
        start_epoch, start_step_in_epoch,
        path=(resumed.path if resumed is not None
              else (config.model.checkpoint or None)))

    is_fast = config.model.model_name.startswith("Fast")
    mmd_w = config.train.mmd.weight if is_fast else 0.0

    def step_factory(lr_scale: float):
        """(shard_mapped step, per-device step) at a scaled LR — divergence
        recovery rolls back and retries at a decayed LR; the opt-state tree
        is LR-independent so the rolled-back state loads unchanged. The
        device step feeds DistributedScanRunner.with_train_step."""
        tx2 = build_tx(lr_scale)
        tstep, _ = make_distributed_steps(
            model, tx2, mesh, mmd_weight=mmd_w,
            mmd_sigma=config.train.mmd.sigma,
            mmd_samples=config.train.mmd.samples)
        dstep, _ = make_device_steps(
            model, tx2, mesh, mmd_weight=mmd_w,
            mmd_sigma=config.train.mmd.sigma,
            mmd_samples=config.train.mmd.samples)
        return tstep, dstep

    train_step, eval_step = make_distributed_steps(
        model, tx, mesh, mmd_weight=mmd_w,
        mmd_sigma=config.train.mmd.sigma, mmd_samples=config.train.mmd.samples,
    )

    # scan_epochs for the distribute path too: same flag + HBM-budget policy
    # as main.py; the per-DEVICE footprint is one partition's stacked dataset.
    scan_runner = None
    from distegnn_tpu.train.scan_epoch import (
        DistributedScanRunner,
        scan_enabled,
        sharded_dataset_nbytes,
    )

    total = sum(sharded_dataset_nbytes(l.loader) for l in loaders)
    if scan_enabled(config.train.scan_epochs, total):
        dstep, dev = make_device_steps(
            model, tx, mesh, mmd_weight=mmd_w,
            mmd_sigma=config.train.mmd.sigma,
            mmd_samples=config.train.mmd.samples)
        scan_runner = DistributedScanRunner(
            dstep, dev, mesh, loader_train.loader, config.seed,
            loader_valid=loader_valid.loader, loader_test=loader_test.loader)
        obs.log(f"scan_epochs: on ({total / 2**30:.2f} GiB device-resident "
                f"per chip)")

    state, best_state, best, log_dict = train(
        state, train_step, eval_step, loader_train, loader_valid, loader_test,
        config, start_epoch=start_epoch, scan_runner=scan_runner,
        start_step_in_epoch=start_step_in_epoch, step_factory=step_factory,
    )
    if best.get("preempted"):
        obs.log(f"Preempted (resumable). Best so far: {best}")
    else:
        obs.log(f"Done. Best: {best}")
    return best
