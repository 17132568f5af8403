"""Driver ``train_stream``: the host step loop of distribute mode, as
``parallel/launch.py:run_distributed`` assembles it (which cannot be called
itself: it trains to the end): a mesh of ``parallel.mesh.graph`` devices on
the graph axis (1 where the configuration names none), every graph cut into
that many partitions by the program's own splitter, one dataset a partition
into ``PrefetchLoader(ShardedGraphLoader)``, the shard-mapped step,
``train/trainer.py:run_epoch_train`` once per pass over the pool. The same
step object and state serve the first (compared) steps in set-up and then the
window.

The comparison sees the partitions through three things it hands the plain
reference (``reference/fastegnn.py``, "The partitioned loss"): the whole raw
graph's edge list with the edges between partitions taken out, each
partition's own MMD draw mapped to raw nodes and laid end to end, and the
weight ``P * n_p / n`` on partition ``p``'s draws. With one partition nothing
is taken out, the draw is the one draw and no weight is handed over.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

import jax
import numpy as np

from benchmarks import family
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


def _cached(kind: str, key: dict, make):
    """``make()``, kept under the work directory by every parameter that
    shapes it (``key``)."""
    digest = hashlib.sha256(json.dumps(key, sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(WORK, f"{kind}_{digest}.pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    made = make()
    os.makedirs(WORK, exist_ok=True)
    with open(path + ".tmp", "wb") as f:
        pickle.dump(made, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(path + ".tmp", path)
    return made


def _split_scene(sample: dict, split: dict, index: int) -> list:
    """One raw scene -> its partitions, as ``process_large_fluid_distribute``
    makes them: the whole graph without edges, then ``split_graph``."""
    from distegnn_tpu.data.fluid113k import build_fluid_graph
    from distegnn_tpu.data.partition import split_graph

    g = build_fluid_graph(sample["loc"], sample["vel"], sample["viscosity"], sample["mass"],
                          sample["target"])
    return split_graph(g, split["parts"], split["method"], split["inner"],
                       outer_radius=split["outer"], seed=index)


class Driver:
    def __init__(self, config_file: str, mix: dict, seed: int, overrides=None):
        self.mix, self.seed = mix, int(seed)
        self.meta = common.load_meta(config_file)
        self.cfg = common.load_program_config(config_file, self.meta, seed, overrides)
        self.dims = common.model_dims(self.cfg)
        self.family = family.of(self.dims)     # an unknown name ends the run here
        mesh = (self.cfg.get("parallel") or {}).get("mesh") or {}
        self.chips = int(mesh.get("graph") or 1)      # partitions = devices on the graph axis
        self.first, self.losses = [], []
        self.count = 0
        self.epoch = 1
        self.prep_s = 0.0

    # ---------------------------------------------------------------- set-up
    def _pool(self, samples):
        """The pool's graphs, each as its list of partitions from the
        program's own splitter (``data/partition.py``), cached."""
        d = self.cfg.data
        split = {"parts": self.chips, "method": d.split_mode,
                 "inner": d.inner_radius, "outer": d.outer_radius}
        return _cached("pool", {"mix": self.mix, "split": split},
                       lambda: [_split_scene(s, split, i) for i, s in enumerate(samples)])

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

        cfg, d, P = self.cfg, self.cfg.data, self.chips
        derive_runtime_fields(cfg, world_size=P)
        fix_seed(cfg.seed % (2 ** 32))
        mesh = make_mesh(n_graph=P, n_data=1, n_tensor=1, devices=jax.devices()[:P])

        t0 = time.perf_counter()
        self.samples = make_samples(self.mix)
        pool = self._pool(self.samples)
        # kept (local) edges of each pool graph, a partition at a time
        kept = np.asarray([[part["edge_index"].shape[1] for part in parts] for parts in pool])
        self.edge_imbalance = float(np.mean(kept.max(axis=1) / kept.mean(axis=1)))
        self.kept_edges_max = int(kept.sum(axis=1).max())
        repeats = int(self.mix.get("pass_repeats", 1))
        self.datasets, self.built = [], {}
        for p in range(P):
            ds = open_dataset([parts[p] for parts in pool], node_order=d.node_order)
            if repeats > 1:
                # a pass lists every pool graph ``repeats`` times (reordered once)
                ds = open_dataset([ds[i] for i in range(len(ds))] * repeats)
            self.datasets.append(ds)
        inner = self.inner = ShardedGraphLoader(
            self.datasets, d.batch_size, shuffle=True, seed=cfg.seed,
            node_bucket=d.node_bucket, edge_bucket=d.edge_bucket, data_parallel=1,
            edge_block=d.edge_block, split_remote=False, pairing=None)
        self.loader = _SpanLoader(PrefetchLoader(
            inner, global_batch_putter(mesh), depth=int(d.get("prefetch_depth", 2))))
        self.prep_s = time.perf_counter() - t0
        # of the whole graph: real nodes, and the edges its partitions keep
        self.nodes_per_graph = int(sum(ds[0]["loc"].shape[0] for ds in self.datasets))
        self.edges_per_graph = int(kept.sum(axis=1).mean())
        self.padded = (inner.loaders[0].max_nodes, inner.loaders[0].max_edges)   # a partition's

        model = get_model(cfg.model, world_size=P, dataset_name=d.dataset_name,
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
        self.state = TrainState.create(self.family.to_tree(weights), self.tx)
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
                "counters": {"data_stall_s": stall.value - stall0,
                             "partition_edge_imbalance": self.edge_imbalance}}

    # ------------------------------------------------------------ comparison
    def program_record(self) -> dict:
        """What the timed path produced in its first steps, under the
        benchmark's names (host arrays)."""
        first = jax.device_get([{k: f[k] for k in ("loss", "loss_total")} for f in self.first])
        w_last = self.family.to_plain(jax.device_get(self.state_last.params), self.names)
        grad = self.family.to_plain(jax.device_get(
            common.find_field(self.state_first.opt_state, "acc_grads")), self.names)
        inner_mu = self.family.to_plain(jax.device_get(
            common.find_field(self.state_last.opt_state, "mu")), self.names)
        return {"loss": np.asarray([f["loss"] for f in first], np.float64),
                "loss_total": np.asarray([f["loss_total"] for f in first], np.float64),
                "grad": grad, "mu": inner_mu, "w": w_last, "w0": self.w0}

    def _raw_graph(self, gi: int, radius: float) -> dict:
        """The reference's graph of pool scene ``gi`` (k-d tree radius search
        over the whole raw cloud: 9 s at 800,000 particles), which the mix
        alone shapes: cached like the pool."""
        return _cached("refgraph", {"mix": self.mix, "radius": radius, "graph": gi},
                       lambda: ref_graphs.fluid_graph(self.samples[gi], radius))

    def reference_inputs(self) -> dict:
        """The raw batches of the first steps, in the order the loader fed
        them: the whole raw graph without the edges between partitions, each
        partition's MMD draw of the step's key mapped to raw nodes."""
        import jax.numpy as jnp

        raw_means = np.stack([s["loc"].mean(axis=0) for s in self.samples])
        C, S = self.dims["virtual_channels"], int(self.cfg.train.mmd.samples)
        P, n, N = self.chips, self.nodes_per_graph, self.padded[0]
        radius = float(self.cfg.data.inner_radius)
        # edge lists padded to one length, so that one reference program
        # serves the whole pool: the longest kept list, rounded up as the fed
        # batch's edge axis is or to whole edge blocks
        edge_block = self.mix.get("reference_edge_block")
        unit = int(edge_block or self.cfg.data.edge_bucket)
        edges = -(-self.kept_edges_max // unit) * unit
        built, batches = self.built, []       # the pool's graphs serve every seed of one driver
        for f in self.first:
            mean = np.asarray(f["loc_mean"]).reshape(-1, 3)[0]     # every partition carries the graph's
            gi = int(np.argmin(np.sum((raw_means - mean) ** 2, axis=1)))
            if gi not in built:
                # where the loaders put each raw node: the partitions' fed
                # (reordered) rows, laid end to end, are a permutation of the
                # raw cloud; partition p's rows are perms[p]
                fed = [ds[gi]["loc"] for ds in self.datasets]
                perm = common.node_perm(np.concatenate(fed), self.samples[gi]["loc"])
                perms = np.split(perm, np.cumsum([len(x) for x in fed])[:-1])
                label = np.empty(n, np.int32)
                for p, rows in enumerate(perms):
                    label[rows] = p
                g = self._raw_graph(gi, radius)
                local = label[g["row"]] == label[g["col"]]           # cut edges are dropped
                built[gi] = (dict(g, **{k: g[k][local] for k in ("row", "col", "eattr")}), perms)
            g, perms = built[gi]
            # the draw as the configuration runs it: the step key folded with
            # the partition's index on the graph axis, split per graph, S*C
            # uniform draws over the real nodes of the partition's fed rows
            idx, weight = [], []
            # for the planted fault "half of the rows left out": the rows each
            # partition's loader put into the second half of its node axis
            second = np.zeros(n, np.float32)
            for p, rows in enumerate(perms):
                key = jax.random.split(jax.random.fold_in(f["key"], p), 1)[0]
                u = jax.random.uniform(key, (S * C,))
                drawn = np.minimum(np.asarray((u * len(rows)).astype(jnp.int32)), N - 1)
                idx.append(rows[drawn])
                weight.append(np.full(S * C, P * len(rows) / n, np.float32))
                second[rows[len(rows) // 2:]] = 1.0
            extra = {"mmd_w": np.concatenate(weight)} if P > 1 else {}
            batches.append(ref_graphs.stack(
                [dict(g, mmd_idx=np.concatenate(idx).astype(np.int32), second_half=second, **extra)],
                edges=edges))
        train = common.train_spec(self.cfg, self.clip)
        train["mmd"]["samples"] = P * S          # the P draws laid end to end
        return {"batches": batches, "model": self.dims, "train": train,
                "block": int(self.mix["reference_block"]), "edge_block": edge_block}

    def shapes(self) -> dict:
        return common.step_shapes(self, int(self.cfg.data.batch_size))

    def free(self) -> None:
        for name in ("state", "state_first", "state_last", "step", "loader", "datasets", "inner",
                     "first", "losses"):
            setattr(self, name, None)
