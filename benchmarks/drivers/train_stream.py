"""Driver ``train_stream``: the host step loop of distribute mode, as
``parallel/launch.py:run_distributed`` assembles it (which cannot be called
itself: it trains to the end): one-device mesh, graphs partitioned by the
program's own splitter, ``PrefetchLoader(ShardedGraphLoader)``, the
shard-mapped step, ``train/trainer.py:run_epoch_train`` once per pass over
the pool. The same step object and state serve the first (compared) steps in
set-up and then the window.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

import jax
import numpy as np

from benchmarks.drivers import common
from benchmarks.reference import graphs as ref_graphs

WORK = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".work")


class _StopAfter:
    """Duck-typed ``PreemptionGuard``: ``run_epoch_train`` asks it after every
    dispatched step whether to stop, as it asks a preemption signal. Ends the
    first (compared) steps at their count; the window runs whole passes."""

    def __init__(self, driver):
        self.driver = driver
        self.count = None         # stop once this many steps are dispatched
        self.interrupted = False
        self.steps_done = 0

    def stop_agreed(self) -> bool:
        return self.count is not None and self.driver.count >= self.count


class _SpanLoader:
    """The loader, with each ``next`` under a host span for the trace."""

    def __init__(self, loader):
        self.loader = loader

    def set_epoch(self, epoch):
        self.loader.set_epoch(epoch)

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        it = iter(self.loader)
        while True:
            with common.span("loader_next"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch


class Driver:
    def __init__(self, config_file: str, mix: dict, seed: int, overrides=None):
        self.mix, self.seed = mix, int(seed)
        self.meta = common.load_meta(config_file)
        self.cfg = common.load_program_config(config_file, self.meta, seed, overrides)
        self.dims = common.model_dims(self.cfg)
        self.chips = 1
        self.first, self.losses = [], []
        self.count = 0
        self.epoch = 1
        self.prep_s = 0.0

    # ---------------------------------------------------------------- set-up
    def _pool(self, samples):
        """The pool's partitioned graphs, from the program's own splitter
        (``data/partition.py``), cached under the work directory by every
        parameter that shapes them."""
        from distegnn_tpu.data.fluid113k import build_fluid_graph
        from distegnn_tpu.data.partition import split_graph

        d = self.cfg.data
        key = json.dumps({"mix": self.mix, "split": d.split_mode,
                          "inner": d.inner_radius, "outer": d.outer_radius},
                         sort_keys=True)
        path = os.path.join(WORK, "pool_" + hashlib.sha256(key.encode()).hexdigest()[:16] + ".pkl")
        if os.path.exists(path):
            with open(path, "rb") as f:
                return pickle.load(f)
        pool = []
        for i, s in enumerate(samples):
            g = build_fluid_graph(s["loc"], s["vel"], s["viscosity"], s["mass"], s["target"])
            pool.append(split_graph(g, 1, d.split_mode, d.inner_radius,
                                    outer_radius=d.outer_radius, seed=i)[0])
        os.makedirs(WORK, exist_ok=True)
        with open(path + ".tmp", "wb") as f:
            pickle.dump(pool, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(path + ".tmp", path)
        return pool

    def setup(self, weights: dict) -> None:
        self.build()
        self.start(weights, self.seed)

    def build(self) -> None:
        """Everything but the state: data, loaders, model, the compiled step."""
        from benchmarks.traffic.generate import make_samples
        from distegnn_tpu.config import derive_runtime_fields
        from distegnn_tpu.data import PrefetchLoader, ShardedGraphLoader, open_dataset
        from distegnn_tpu.models.registry import get_model
        from distegnn_tpu.parallel.launch import global_batch_putter, make_distributed_steps
        from distegnn_tpu.parallel.mesh import GRAPH_AXIS, make_mesh
        from distegnn_tpu.train import make_optimizer, needs_grad_clip
        from distegnn_tpu.utils.seed import fix_seed

        cfg, d = self.cfg, self.cfg.data
        derive_runtime_fields(cfg, world_size=1)
        fix_seed(cfg.seed % (2 ** 32))
        mesh = make_mesh(n_graph=1, n_data=1, n_tensor=1, devices=jax.devices()[:1])

        t0 = time.perf_counter()
        self.samples = make_samples(self.mix)
        dataset = open_dataset(self._pool(self.samples), node_order=d.node_order)
        self.dataset = dataset
        inner = self.inner = ShardedGraphLoader(
            [dataset], d.batch_size, shuffle=True, seed=cfg.seed,
            node_bucket=d.node_bucket, edge_bucket=d.edge_bucket, data_parallel=1,
            edge_block=d.edge_block, split_remote=False, pairing=None)
        self.loader = _SpanLoader(PrefetchLoader(
            inner, global_batch_putter(mesh), depth=int(d.get("prefetch_depth", 2))))
        self.prep_s = time.perf_counter() - t0
        self.nodes_per_graph = int(dataset[0]["loc"].shape[0])
        self.edges_per_graph = int(np.mean([g["edge_index"].shape[1] for g in dataset.graphs]))
        self.padded = (inner.loaders[0].max_nodes, inner.loaders[0].max_edges)

        model = get_model(cfg.model, world_size=1, dataset_name=d.dataset_name,
                          axis_name=GRAPH_AXIS, tensor_axis=None)
        self.clip = 0.3 if needs_grad_clip(cfg) else None
        tx = self.tx = make_optimizer(
            cfg.train.learning_rate, weight_decay=cfg.train.weight_decay,
            clip_norm=self.clip, accumulation_steps=cfg.train.accumulation_steps,
            total_steps=cfg.train.epochs * len(self.loader) // cfg.train.accumulation_steps,
            scheduler=str(cfg.train.scheduler))
        self.step, _ = make_distributed_steps(
            model, tx, mesh, mmd_weight=cfg.train.mmd.weight,
            mmd_sigma=cfg.train.mmd.sigma, mmd_samples=cfg.train.mmd.samples)
        self.deadline = _StopAfter(self)
        self.n_first = int(self.mix["compare_steps"])

    def start(self, weights: dict, seed: int) -> None:
        """A fresh state from ``weights`` and the run's seed (loader order,
        step keys), driven through the first steps by the window's own call
        and feed."""
        from distegnn_tpu.train import TrainState

        self.cfg.seed = int(seed)
        for ld in self.inner.loaders:
            ld.seed = int(seed)
        self.names = list(weights)
        self.w0 = {k: np.asarray(v) for k, v in weights.items()}
        self.state = TrainState.create(common.to_tree(weights), self.tx)
        self.first, self.losses = [], []
        self.count = 0
        self.epoch = 1          # train() numbers its epochs from 1
        self.deadline.count = self.n_first
        while self.count < self.n_first:
            self._epoch()
        self.deadline.count = None
        jax.block_until_ready(self.state)

    def _recording_step(self, state, batch, key):
        with common.span("dispatch"):
            new_state, metrics = self.step(state, batch, key)
        i = self.count
        self.count += 1
        self.losses.append(metrics["loss"])
        if i < self.n_first:
            self.first.append({"key": key, "loc_mean": batch.loc_mean,
                               "loss": metrics["loss"],
                               "loss_total": metrics["loss_with_mmd"]})
            if i == 0:
                self.state_first = new_state
            if i == self.n_first - 1:
                self.state_last = new_state
        return new_state, metrics

    def _epoch(self):
        from distegnn_tpu.train.trainer import run_epoch_train

        guard = self.deadline
        guard.interrupted = False
        with common.span("epoch"):
            self.state, _ = run_epoch_train(
                self._recording_step, self.state, self.loader, self.cfg.seed,
                self.epoch, guard=guard)
        self.epoch += 1

    # ---------------------------------------------------------------- window
    def run_window(self, seconds: float) -> dict:
        from distegnn_tpu import obs

        stall = obs.get_registry().counter("data/stall_s")
        stall0, count0 = stall.value, self.count
        # whole passes over the pool: the window closes at the end of the
        # last pass that started inside it and is measured to that point, so
        # every run of the cell does the same amount of work
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._epoch()
        jax.block_until_ready(self.state)
        wall = time.perf_counter() - t0
        steps = self.count - count0
        losses = np.asarray(jax.device_get(self.losses[count0:]), np.float64)
        return {"wall_s": wall, "micro_steps": steps, "attempted": steps,
                "failed": int(np.sum(~np.isfinite(losses))),
                "nodes": steps * self.nodes_per_graph * int(self.cfg.data.batch_size),
                "launches_expected": steps,
                "counters": {"data_stall_s": stall.value - stall0}}

    # ------------------------------------------------------------ comparison
    def program_record(self) -> dict:
        """What the timed path produced in its first steps, under the
        benchmark's names (host arrays)."""
        first = jax.device_get([{k: f[k] for k in ("loss", "loss_total")} for f in self.first])
        w_last = common.to_plain(jax.device_get(self.state_last.params), self.names)
        grad = common.to_plain(jax.device_get(
            common.find_field(self.state_first.opt_state, "acc_grads")), self.names)
        inner_mu = common.to_plain(jax.device_get(
            common.find_field(self.state_last.opt_state, "mu")), self.names)
        return {"loss": np.asarray([f["loss"] for f in first], np.float64),
                "loss_total": np.asarray([f["loss_total"] for f in first], np.float64),
                "grad": grad, "mu": inner_mu, "w": w_last, "w0": self.w0}

    def reference_inputs(self) -> dict:
        """The raw batches of the first steps, in the order the loader fed
        them, with the MMD draw of each step's key mapped to raw nodes."""
        import jax.numpy as jnp

        raw_means = np.stack([s["loc"].mean(axis=0) for s in self.samples])
        C, S = self.dims["virtual_channels"], int(self.cfg.train.mmd.samples)
        n, N = self.nodes_per_graph, self.padded[0]
        built, batches = {}, []
        for f in self.first:
            mean = np.asarray(f["loc_mean"]).reshape(3)
            gi = int(np.argmin(np.sum((raw_means - mean) ** 2, axis=1)))
            if gi not in built:
                perm = common.node_perm(self.dataset[gi]["loc"], self.samples[gi]["loc"])
                built[gi] = (ref_graphs.fluid_graph(self.samples[gi], float(self.cfg.data.inner_radius)), perm)
            g, perm = built[gi]
            # the draw as the configuration runs it: the step key folded with
            # the partition's index on the graph axis (0), split per graph,
            # S*C uniform draws over the real nodes of the fed (reordered) graph
            key = jax.random.split(jax.random.fold_in(f["key"], 0), 1)[0]
            u = jax.random.uniform(key, (S * C,))
            idx = np.minimum(np.asarray((u * n).astype(jnp.int32)), N - 1)
            # for the planted fault "half of the rows left out": the rows the
            # loader put into the second half of its (reordered) node axis
            second = np.zeros(n, np.float32)
            second[perm[n // 2:]] = 1.0
            # edge lists padded to the fed batch's edge axis: one reference
            # program for the whole pool
            batches.append(ref_graphs.stack([dict(g, mmd_idx=perm[idx].astype(np.int32),
                                                  second_half=second)], edges=self.padded[1]))
        return {"batches": batches, "model": self.dims,
                "train": common.train_spec(self.cfg, self.clip),
                "block": int(self.mix["reference_block"]),
                "edge_block": self.mix.get("reference_edge_block")}

    def shapes(self) -> dict:
        return common.step_shapes(self, int(self.cfg.data.batch_size))

    def free(self) -> None:
        for name in ("state", "state_first", "state_last", "step", "loader", "dataset",
                     "first", "losses"):
            setattr(self, name, None)
