"""Device-resident epochs: ONE dispatch per epoch via lax.scan.

The reference's epoch loop dispatches one CUDA launch sequence per minibatch
(utils/train.py:83-117), and the host-driven loop here does the same: one
dispatch per micro-batch, which for small graphs leaves the device waiting
on the host. The scanned epoch
keeps the whole (uniformly padded) dataset in HBM as one stacked GraphBatch,
runs the epoch as a ``lax.scan`` over minibatch index slices, and the host
sees exactly one dispatch + one scalar fetch per epoch. How much that saves
on this machine: not measured. The permutation is still drawn on host from
(seed, epoch) — identical
to GraphLoader._order — and the per-step PRNG keys are fold_in(epoch, step),
identical to the host loop, so the scanned trajectory is step-for-step the
same training run (tests/test_scan_epoch.py proves parameter parity).

``ScanEpochRunner`` covers the single-process path (all four pipelines pad to
dataset-wide maxima already). ``DistributedScanRunner`` covers distribute
mode: the per-partition datasets live in HBM as ONE [P, G, ...] global array
sharded over the mesh's graph axis, and the epoch is a single
shard_map(lax.scan) dispatch — the per-layer virtual-node psums and the
gradient psum trace into the scan body as XLA collectives.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.data.loader import GraphLoader, ShardedGraphLoader
from distegnn_tpu.ops.graph import GraphBatch, pad_graphs
from distegnn_tpu.parallel.mesh import DATA_AXIS, GRAPH_AXIS


def scan_enabled(flag, total_nbytes: int) -> bool:
    """The scan_epochs policy, shared by main.py (single-process) and
    parallel.launch (distribute mode): 'auto' turns scan on when the backend
    has dispatch latency worth killing (i.e. not local CPU) AND the stacked
    dataset fits a conservative HBM budget; True forces it; False disables.

    ``total_nbytes`` is the PER-DEVICE resident footprint (all splits)."""
    if flag is True:
        return True
    if flag != "auto" or jax.default_backend() == "cpu":
        return False  # no dispatch latency locally; scan only adds compile
    # budget: ~40% of device memory (params/opt/activations need the rest)
    dev = jax.local_devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit is None:
        raise RuntimeError(
            f"train.scan_epochs: auto needs the device memory size, and "
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"memory_stats()['bytes_limit']; set train.scan_epochs to true "
            f"or false")
    return total_nbytes <= int(limit * 0.4)


def stack_dataset(loader: GraphLoader) -> GraphBatch:
    """Pad every graph of a loader's dataset to the loader's maxima and stack
    into one device-resident GraphBatch with leading axis [num_graphs].
    ``loader._graph`` (not ``loader.dataset[i]``) so edge_block loaders feed
    BLOCKIFIED graphs to pad_graphs, exactly as their __iter__ does."""
    batch = pad_graphs([loader._graph(i) for i in range(len(loader.dataset))],
                       **loader.pad_kwargs())
    return jax.device_put(batch)


def dataset_nbytes(loader: GraphLoader) -> int:
    """Rough device-memory footprint of stack_dataset (float32/int32 leaves)."""
    g0 = pad_graphs([loader._graph(0)], **loader.pad_kwargs())
    per = sum(np.asarray(x).nbytes for x in jax.tree.leaves(g0))
    return per * len(loader.dataset)


class ScanEpochRunner:
    """Scanned replacements for run_epoch_train / run_epoch_eval.

    train_step(state, batch, key) -> (state, metrics) and
    eval_step(params, batch) -> loss are the SAME jittable callables the host
    loop uses; here they are traced into one epoch-long XLA program.
    """

    def __init__(self, train_step: Callable, eval_step: Optional[Callable],
                 loader_train: GraphLoader, seed: int,
                 loader_valid: Optional[GraphLoader] = None,
                 loader_test: Optional[GraphLoader] = None):
        self.seed = seed
        self.loader = loader_train
        self.batch_size = loader_train.batch_size
        self.num_steps = len(loader_train)
        self.data_train = stack_dataset(loader_train)
        self.eval_sets = {}
        if eval_step is not None:
            for name, ld in (("valid", loader_valid), ("test", loader_test)):
                if ld is not None:
                    self.eval_sets[name] = (stack_dataset(ld), len(ld), ld.batch_size)

        self._compile(train_step, eval_step)

    def _compile(self, train_step: Callable, eval_step: Optional[Callable]):
        self._train_step, self._eval_step = train_step, eval_step

        def pick(data: GraphBatch, idx):
            return jax.tree.map(lambda a: a[idx], data)

        def run_train(state, data, perm, epoch_key):
            def body(st, inp):
                idx, k = inp
                st, metrics = train_step(st, pick(data, idx), k)
                return st, metrics["loss"]

            keys = jax.vmap(lambda i: jax.random.fold_in(epoch_key, i))(
                jnp.arange(self.num_steps))
            state, losses = jax.lax.scan(body, state, (perm, keys))
            # equal batch sizes (drop_last) -> plain mean == weighted average
            return state, jnp.mean(losses)

        def run_eval(params, data, perm):
            def body(_, idx):
                return None, eval_step(params, pick(data, idx))

            _, losses = jax.lax.scan(body, None, perm)
            return jnp.mean(losses)

        self._run_train = jax.jit(run_train)
        self._run_eval = jax.jit(run_eval) if eval_step is not None else None

    def with_train_step(self, train_step: Callable) -> "ScanEpochRunner":
        """A copy sharing the device-resident datasets but scanning a NEW
        train step — divergence recovery swaps in a decayed-LR step without
        re-staging HBM (trainer.py rollback path)."""
        import copy

        new = copy.copy(self)
        new._compile(train_step, self._eval_step)
        return new

    def _perm(self, loader: GraphLoader, epoch: int, steps: int, bsz: int):
        loader.set_epoch(epoch)
        order = loader._order()[: steps * bsz]
        return jnp.asarray(order.reshape(steps, bsz).astype(np.int32))

    def train_epoch(self, state, epoch: int):
        # spans as in run_epoch_train, with the epoch as the one "step": a
        # host annotation cannot go inside lax.scan. The sync makes the
        # train/epoch span end with the device's work, not with the enqueue
        with obs.span("train/epoch", epoch=epoch):
            with obs.span("train/step"):
                perm = self._perm(self.loader, epoch, self.num_steps, self.batch_size)
                epoch_key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
                with obs.span("train/dispatch"):
                    state, loss = self._run_train(state, self.data_train, perm,
                                                  epoch_key)
            with obs.span("train/epoch_sync"):
                jax.block_until_ready(loss)
        return state, loss  # loss: device scalar, ready

    def eval_epoch(self, params, split: str) -> float:
        data, steps, bsz = self.eval_sets[split]
        perm = jnp.arange(steps * bsz, dtype=jnp.int32).reshape(steps, bsz)
        return float(self._run_eval(params, data, perm))


_BATCH_ARRAY_FIELDS = ("node_feat", "node_attr", "loc", "vel", "target",
                       "loc_mean", "node_mask", "edge_index", "edge_attr",
                       "edge_mask", "edge_pair")


def stack_sharded_dataset(sharded: ShardedGraphLoader, mesh) -> GraphBatch:
    """All partitions' graphs, padded to the shared static layout and stacked
    into one global jax.Array tree with leaves [P, G, ...], sharded over
    GRAPH_AXIS (replicated over the data axis — the data axis picks different
    GRAPH INDICES per step, not different arrays).

    Streams ONE partition at a time: pad the partition's dataset in host RAM,
    device_put each field onto the devices holding that partition block, free
    the numpy, move on — peak host memory is one partition's padded dataset,
    not all of them (which is exactly the per-chip HBM budget the caller
    already checks). Multi-host: each process pads only its own partitions
    and contributes its addressable shards; a process owning no mesh devices
    contributes none.

    edge_pair is all-or-nothing ACROSS partitions (one pytree structure for
    the stack): if any partition's pairing failed (asymmetric edges — the
    same condition ShardedGraphLoader.__iter__ handles per step), the pair
    field is dropped from the whole stack instead of failing the run.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    loaders = sharded.loaders
    n_parts = len(loaders)
    n_graphs = len(loaders[0].dataset)
    sharding = NamedSharding(mesh, PartitionSpec(GRAPH_AXIS))
    proc = jax.process_index()
    # partition index -> the local devices holding its [1, G, ...] block
    part_devs: dict = {}
    for dev, idx in sharding.devices_indices_map((n_parts,)).items():
        if dev.process_index == proc:
            part_devs.setdefault(idx[0].indices(n_parts)[0], []).append(dev)

    # template (one padded graph): global leaf shapes + static fields, cheap
    # on every process including ones that own no partitions
    ld0 = loaders[0]
    template = pad_graphs([ld0._graph(0)], **ld0.pad_kwargs())

    shards: dict = {f: [] for f in _BATCH_ARRAY_FIELDS}
    all_have_pair = True
    for p, devs in sorted(part_devs.items()):
        ld = loaders[p]
        # ld._graph, not ld.dataset[i]: edge_block loaders blockify here
        batch = pad_graphs([ld._graph(i) for i in range(n_graphs)],
                           **ld.pad_kwargs())
        statics = (batch.edges_sorted, batch.edge_block, batch.edge_tile,
                   batch.max_in_degree)
        if statics != (template.edges_sorted, template.edge_block,
                       template.edge_tile, template.max_in_degree):
            raise ValueError(
                f"partition {p} static layout {statics} differs from the "
                "shared template — the loaders' dataset-stable scan failed")
        if batch.edge_pair is None:
            all_have_pair = False
        for f in _BATCH_ARRAY_FIELDS:
            leaf = getattr(batch, f)
            if leaf is None:
                continue
            piece = np.asarray(leaf)[None]  # [1, G, ...] partition block
            for dev in devs:
                shards[f].append((p, jax.device_put(piece, dev)))
        del batch  # free this partition's numpy before padding the next

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        all_have_pair = bool(np.all(multihost_utils.process_allgather(
            np.array(all_have_pair))))

    fields = {}
    for f in _BATCH_ARRAY_FIELDS:
        tmpl_leaf = getattr(template, f)
        if tmpl_leaf is None or (f == "edge_pair" and not all_have_pair):
            continue  # dropped pair shards are freed with the dict
        gshape = (n_parts, n_graphs) + np.asarray(tmpl_leaf).shape[1:]
        fields[f] = jax.make_array_from_single_device_arrays(
            gshape, sharding, [buf for _, buf in shards[f]])
    pair = fields.pop("edge_pair", None)
    return template.replace(**fields, edge_pair=pair)


def sharded_dataset_nbytes(sharded: ShardedGraphLoader) -> int:
    """PER-DEVICE footprint of stack_sharded_dataset: each device holds one
    partition's [G, ...] block (the partition axis is sharded; graphs within
    a partition share the static padded shape)."""
    ld = sharded.loaders[0]
    g0 = pad_graphs([ld._graph(0)], **ld.pad_kwargs())
    per = sum(np.asarray(x).nbytes for x in jax.tree.leaves(g0))
    return per * len(ld.dataset)


class DistributedScanRunner:
    """Scanned epochs over the distribute-mode mesh — same interface as
    ScanEpochRunner (train_epoch / eval_epoch), same permutation and PRNG
    discipline as the per-step path (tests/test_scan_epoch.py proves
    parameter parity for both runners).

    ``device_train_step`` / ``device_eval_step`` are the PER-DEVICE callables
    from parallel.launch.make_device_steps — axis-bound but not shard_mapped;
    here they trace into one shard_map(lax.scan) program per epoch.
    """

    def __init__(self, device_train_step: Callable,
                 device_eval_step: Optional[Callable], mesh,
                 loader_train: ShardedGraphLoader, seed: int,
                 loader_valid: Optional[ShardedGraphLoader] = None,
                 loader_test: Optional[ShardedGraphLoader] = None):
        self.seed = seed
        self.loader = loader_train
        self.dp = loader_train.data_parallel
        self.num_steps = len(loader_train)
        # per-partition graphs drawn per step (= batch_size * data_parallel)
        self.draw = loader_train.loaders[0].batch_size
        self.data_train = stack_sharded_dataset(loader_train, mesh)
        self.eval_sets = {}
        if device_eval_step is not None:
            for name, ld in (("valid", loader_valid), ("test", loader_test)):
                if ld is not None:
                    self.eval_sets[name] = (stack_sharded_dataset(ld, mesh),
                                            len(ld), ld.loaders[0].batch_size)
        self._mesh = mesh
        self._compile(device_train_step, device_eval_step)

    def _compile(self, device_train_step: Callable,
                 device_eval_step: Optional[Callable]):
        from jax.sharding import PartitionSpec as P

        self._device_train_step = device_train_step
        self._device_eval_step = device_eval_step
        mesh = self._mesh
        dp = self.dp
        data_spec = P(GRAPH_AXIS)
        # [S, B] replicated, or [S, D, B] with the D axis sharded over DATA:
        # each data shard picks ITS slice of the global batch's graph indices
        # (ShardedGraphLoader's [D, P, B] layout, loader.py)
        perm_spec = P(None, DATA_AXIS, None) if dp > 1 else P()

        def pick(data, idx):
            # local data leaves [1, G, ...] (this device's partition);
            # idx [B] (dp=1) or [1, B] (local slice of [S, D, B])
            return jax.tree.map(lambda a: a[0][idx.reshape(-1)], data)

        def run_train(state, data, perm, epoch_key):
            keys = jax.vmap(lambda i: jax.random.fold_in(epoch_key, i))(
                jnp.arange(perm.shape[0]))

            def body(st, inp):
                idx, k = inp
                st, metrics = device_train_step(st, pick(data, idx), k)
                return st, (metrics["loss"],
                            metrics.get("batch_consistency", jnp.float32(0)))

            state, (losses, cons) = jax.lax.scan(body, state, (perm, keys))
            # drop_last equal batch sizes -> plain mean == weighted average
            return state, jnp.mean(losses), jnp.max(cons)

        def run_eval(params, data, perm):
            def body(_, idx):
                return None, device_eval_step(params, pick(data, idx))

            _, losses = jax.lax.scan(body, None, perm)
            return jnp.mean(losses)

        self._run_train = jax.jit(jax.shard_map(
            run_train, mesh=mesh,
            in_specs=(P(), data_spec, perm_spec, P()),
            out_specs=(P(), P(), P()), check_vma=False))
        self._run_eval = None
        if device_eval_step is not None:
            self._run_eval = jax.jit(jax.shard_map(
                run_eval, mesh=mesh,
                in_specs=(P(), data_spec, perm_spec),
                out_specs=P(), check_vma=False))

    def with_train_step(self, device_train_step: Callable) -> "DistributedScanRunner":
        """A copy sharing the device-resident sharded datasets but scanning a
        NEW per-device train step — divergence recovery swaps in a decayed-LR
        step without re-staging HBM (trainer.py rollback path)."""
        import copy

        new = copy.copy(self)
        new._compile(device_train_step, self._device_eval_step)
        return new

    def _perm_array(self, order: np.ndarray, steps: int, draw: int):
        o = np.asarray(order[: steps * draw], dtype=np.int32)
        if self.dp > 1:
            # order[s*D*B + d*B + b] lands at [s, d, b] — exactly the
            # [P, D*B] -> [D, P, B] reshape ShardedGraphLoader applies
            return jnp.asarray(o.reshape(steps, self.dp, draw // self.dp))
        return jnp.asarray(o.reshape(steps, draw))

    def train_epoch(self, state, epoch: int):
        from distegnn_tpu.train.trainer import assert_batch_consistency

        # the same spans as ScanEpochRunner.train_epoch
        with obs.span("train/epoch", epoch=epoch):
            with obs.span("train/step"):
                self.loader.set_epoch(epoch)
                # all partition loaders share (seed, epoch) -> one common order
                perm = self._perm_array(self.loader.loaders[0]._order(),
                                        self.num_steps, self.draw)
                epoch_key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
                with obs.span("train/dispatch"):
                    state, loss, cons = self._run_train(state, self.data_train,
                                                        perm, epoch_key)
            with obs.span("train/epoch_sync"):
                assert_batch_consistency(cons, epoch)
        return state, loss  # loss: device scalar, ready

    def eval_epoch(self, params, split: str) -> float:
        data, steps, draw = self.eval_sets[split]
        perm = self._perm_array(np.arange(steps * draw), steps, draw)
        return float(self._run_eval(params, data, perm))
