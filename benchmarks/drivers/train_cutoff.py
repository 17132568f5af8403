"""Driver ``train_cutoff``: single-device training through the host step loop
of ``accelerate_mode: cutoff_edges``, assembled by the program itself:
``main.build_cutoff`` (the function ``main.main`` trains with) hands over the
``GraphLoader`` behind ``PrefetchLoader``, the jitted ``make_train_step`` and
the optimizer, and ``train/trainer.py:run_epoch_train`` runs once per pass,
as ``train()`` calls it each epoch. The driver makes the data (every pool
graph by the program's ``build_water3d_graph``, the split files written as
``process_water3d_cutoff`` writes them), starts the state from the
benchmark's weights, and watches the feed. The same step object and state
serve the first (compared) steps in set-up and then the window. A program
without ``main.build_cutoff`` cannot run this driver: it assembles nothing
itself.
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
from benchmarks.drivers import common, train_stream
from benchmarks.reference import graphs as ref_graphs
from benchmarks.reference.water3d_graphs import water_graph

SPLITS = ("train", "valid", "test")


class Driver:
    def __init__(self, config_file: str, mix: dict, seed: int, overrides=None):
        self.mix, self.seed = mix, int(seed)
        self.meta = common.load_meta(config_file)
        self.cfg = common.load_program_config(config_file, self.meta, seed, overrides)
        self.dims = common.model_dims(self.cfg)
        self.family = family.of(self.dims)     # an unknown name ends the run here
        self.chips = 1
        self.first, self.losses = [], []
        self.count = 0
        self.epoch = 1
        self.prep_s = 0.0

    # ---------------------------------------------------------------- set-up
    def _split_files(self, samples: list) -> list:
        """The three processed split files, as ``process_water3d_cutoff``
        leaves them (a pickled list of graph dicts each), kept under the work
        directory by what shapes them. A pass lists every pool graph
        ``pass_repeats`` times (the same dicts again: pickle keeps them
        once); the evaluation splits, which no window runs, hold one batch."""
        from distegnn_tpu.data.water3d import build_water3d_graph

        d = self.cfg.data
        key = {"mix": self.mix, "radius": d.radius, "cutoff_rate": d.cutoff_rate,
               "batch": d.batch_size}
        digest = hashlib.sha256(json.dumps(key, sort_keys=True).encode()).hexdigest()[:16]
        work = train_stream.WORK
        paths = [os.path.join(work, f"water3d_{digest}_{split}.pkl") for split in SPLITS]
        if all(os.path.exists(p) for p in paths):
            return paths
        graphs = [build_water3d_graph(s["loc"], s["vel"], s["particle_type"], s["target"],
                                      d.radius, d.cutoff_rate) for s in samples]
        os.makedirs(work, exist_ok=True)
        one_batch = graphs[:int(d.batch_size)]
        for path, split in zip(paths, (graphs * int(self.mix.get("pass_repeats", 1)),
                                       one_batch, one_batch)):
            with open(path + ".tmp", "wb") as f:
                pickle.dump(split, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(path + ".tmp", path)
        return paths

    def setup(self, weights: dict) -> None:
        self.build()
        self.start(weights, self.seed)

    def build(self) -> None:
        """Everything but the state: data, and the program's own assembly."""
        from main import build_cutoff        # the checkout's main.py (run.py puts its root first)

        from benchmarks.traffic.generate_water3d import make_samples
        from distegnn_tpu.config import derive_runtime_fields
        from distegnn_tpu.train import needs_grad_clip
        from distegnn_tpu.utils.seed import fix_seed

        cfg = self.cfg
        derive_runtime_fields(cfg, world_size=1)
        fix_seed(cfg.seed % (2 ** 32))

        t0 = time.perf_counter()
        self.samples = make_samples(self.mix)
        self.raw_means = np.stack([s["loc"].mean(axis=0) for s in self.samples])
        if len(np.unique(self.raw_means, axis=0)) != len(self.samples):
            raise RuntimeError("two pool graphs share a mean position: the feed cannot be told apart")
        files = self._split_files(self.samples)
        run = self.run = build_cutoff(cfg, files)      # reads the files, model.init on one batch
        self.prep_s = time.perf_counter() - t0
        if run.scan_runner is not None:
            raise RuntimeError(
                f"train.scan_epochs={cfg.train.scan_epochs!r} resolves to the scanned "
                "epoch: this driver measures the host step loop")
        self.dataset, inner = run.datasets[0], run.loaders[0]
        self.loader = train_stream._SpanLoader(run.feeds[0])
        self.step, self.tx = run.train_step, run.tx
        self.built = {}
        self.batch_size = int(cfg.data.batch_size)
        self.nodes_per_graph = int(self.samples[0]["loc"].shape[0])
        self.edges_per_graph = int(np.mean([self.dataset[i]["edge_index"].shape[1]
                                            for i in range(len(self.samples))]))
        self.padded = (inner.max_nodes, inner.max_edges)
        self.clip = 0.3 if needs_grad_clip(cfg) else None          # build_cutoff's own rule
        if self.clip is not None or int(cfg.train.accumulation_steps) != 1:
            raise RuntimeError("program_record reads the first gradient off Adam's first moment: "
                               "that needs accumulation 1 and no clip")
        self.deadline = train_stream._StopAfter(self)
        self.n_first = int(self.mix["compare_steps"])

    def start(self, weights: dict, seed: int) -> None:
        """A fresh state from ``weights`` and the run's seed (loader order,
        step keys), driven through the first steps by the window's own call
        and feed."""
        from distegnn_tpu.train import TrainState

        self.cfg.seed = self.run.loaders[0].seed = int(seed)
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

    # the step under watch and one pass through ``run_epoch_train``: the
    # stream driver's own, which read nothing but what ``build`` and ``start``
    # set here too (step, loader, deadline, counters, the first steps' records)
    _recording_step = train_stream.Driver._recording_step
    _epoch = train_stream.Driver._epoch

    # ---------------------------------------------------------------- window
    def run_window(self, seconds: float) -> dict:
        from distegnn_tpu import obs

        reg = obs.get_registry()
        names = ("data/stall_s", "data/real_edges", "data/padded_edges")
        before = [reg.counter(n).value for n in names]
        count0 = self.count
        # whole passes: the window closes at the end of the last pass that
        # started inside it and is measured to that point, so every run of
        # the cell does the same amount of work
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._epoch()
        jax.block_until_ready(self.state)
        wall = time.perf_counter() - t0
        steps = self.count - count0
        losses = np.asarray(jax.device_get(self.losses[count0:]), np.float64)
        # the loader's counters over the window: a pass's producer starts with
        # the pass and every batch it makes is consumed before the pass ends
        counters = {n.replace("/", "_"): reg.counter(n).value - b for n, b in zip(names, before)}
        return {"wall_s": wall, "micro_steps": steps, "attempted": steps,
                "failed": int(np.sum(~np.isfinite(losses))),
                "nodes": steps * self.batch_size * self.nodes_per_graph,
                "launches_expected": steps, "counters": counters}

    # ------------------------------------------------------------ comparison
    def program_record(self) -> dict:
        """What the timed path produced in its first steps, under the
        benchmark's names (host arrays). Accumulation is 1 and nothing is
        clipped, so Adam's first moment after the first update is a tenth of
        the first gradient as the optimizer got it, weight decay folded in."""
        first = jax.device_get([{k: f[k] for k in ("loss", "loss_total")} for f in self.first])
        plain = lambda state, field: self.family.to_plain(jax.device_get(
            common.find_field(state.opt_state, field)), self.names)
        wd = float(self.cfg.train.weight_decay)
        grad = {k: 10.0 * v - wd * self.w0[k] for k, v in plain(self.state_first, "mu").items()}
        return {"loss": np.asarray([f["loss"] for f in first], np.float64),
                "loss_total": np.asarray([f["loss_total"] for f in first], np.float64),
                "grad": grad, "mu": plain(self.state_last, "mu"),
                "w": self.family.to_plain(jax.device_get(self.state_last.params), self.names),
                "w0": self.w0}

    def _raw_graph(self, k: int):
        """(the reference's graph of pool sample ``k``, where the program's
        loader put each raw node)."""
        if k not in self.built:
            g = water_graph(self.samples[k], float(self.cfg.data.radius))
            self.built[k] = (g, common.node_perm(self.dataset[k]["loc"], self.samples[k]["loc"]))
        return self.built[k]

    def reference_inputs(self) -> dict:
        """The raw batches of the first steps, in the order the loader fed
        them (each fed graph found by its mean position), each graph with its
        MMD draw (the step key split per graph, S*C uniform draws over the
        graph's real nodes) mapped to raw nodes."""
        import jax.numpy as jnp

        C, S = self.dims["virtual_channels"], int(self.cfg.train.mmd.samples)
        n, N, B = self.nodes_per_graph, self.padded[0], self.batch_size
        draw = jax.jit(lambda key: jax.vmap(
            lambda k: jnp.minimum((jax.random.uniform(k, (S * C,)) * n).astype(jnp.int32), N - 1)
        )(jax.random.split(key, B)))
        batches = []
        for f in self.first:
            means = np.asarray(f["loc_mean"]).reshape(B, 3)
            idx = np.asarray(draw(f["key"]))
            graphs = []
            for b in range(B):
                k = int(np.argmin(np.sum((self.raw_means - means[b]) ** 2, axis=1)))
                g, perm = self._raw_graph(k)
                graphs.append(dict(g, mmd_idx=perm[idx[b]].astype(np.int32)))
            # one edge length for every batch, the fed batch's, so that one
            # reference program serves the run
            batches.append(ref_graphs.stack(graphs, edges=self.padded[1]))
        return {"batches": batches, "model": self.dims,
                "train": common.train_spec(self.cfg, self.clip),
                "block": int(self.mix["reference_block"]),
                "edge_block": self.mix.get("reference_edge_block")}

    def shapes(self) -> dict:
        return common.step_shapes(self, self.batch_size)

    def free(self) -> None:
        for name in ("state", "state_first", "state_last", "step", "loader", "dataset", "run",
                     "first", "losses"):
            setattr(self, name, None)
