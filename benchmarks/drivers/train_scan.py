"""Driver ``train_scan``: single-device training with device-resident scanned
epochs, as ``main.main`` assembles it for ``accelerate_mode: cutoff_edges``:
graphs from the program's ``build_nbody_graph``, ``GraphLoader``, the jitted
``make_train_step`` inside ``train/scan_epoch.py:ScanEpochRunner``; one
``train_epoch`` call (one dispatch, one scalar fetch) per epoch, which is what
``train()`` does each epoch. Only the training split is made resident: no
evaluation runs in the window. The same runner and state serve the first
(compared) epoch in set-up and then the window.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import family
from benchmarks.drivers import common
from benchmarks.reference import graphs as ref_graphs


class Driver:
    def __init__(self, config_file: str, mix: dict, seed: int, overrides=None):
        self.mix, self.seed = mix, int(seed)
        self.meta = common.load_meta(config_file)
        self.cfg = common.load_program_config(config_file, self.meta, seed, overrides)
        self.dims = common.model_dims(self.cfg)
        self.family = family.of(self.dims)     # an unknown name ends the run here
        self.chips = 1
        self.fed = []          # (perm, epoch_key) of every dispatched epoch
        self.losses = []
        self.epoch = 1
        self.prep_s = 0.0

    def setup(self, weights: dict) -> None:
        self.build()
        self.start(weights, self.seed)

    def build(self) -> None:
        """Everything but the state: data resident on the device, model, the
        compiled epoch."""
        from benchmarks.traffic.generate import make_samples
        from distegnn_tpu.config import derive_runtime_fields
        from distegnn_tpu.data import GraphDataset, GraphLoader
        from distegnn_tpu.data.nbody import build_nbody_graph
        from distegnn_tpu.models.registry import get_model
        from distegnn_tpu.train import make_optimizer, make_train_step, needs_grad_clip
        from distegnn_tpu.train.scan_epoch import (ScanEpochRunner, dataset_nbytes,
                                                   scan_enabled)
        from distegnn_tpu.utils.seed import fix_seed

        cfg, d = self.cfg, self.cfg.data
        derive_runtime_fields(cfg, world_size=1)
        fix_seed(cfg.seed % (2 ** 32))

        t0 = time.perf_counter()
        s = self.samples = make_samples(self.mix)
        graphs = [build_nbody_graph(s["loc"][k], s["vel"][k], s["charges"][k],
                                    s["target"][k], radius=d.radius,
                                    cutoff_rate=d.cutoff_rate)
                  for k in range(s["loc"].shape[0])]
        self.dataset = GraphDataset(graphs, node_order=d.node_order)
        loader = GraphLoader(
            self.dataset, d.batch_size, shuffle=True, seed=cfg.seed,
            node_bucket=d.node_bucket, edge_bucket=d.edge_bucket,
            edge_block=d.edge_block, split_remote=False, pairing=None)
        self.nodes_per_graph = int(graphs[0]["loc"].shape[0])
        self.edges_per_graph = int(graphs[0]["edge_index"].shape[1])
        self.padded = (loader.max_nodes, loader.max_edges)

        model = get_model(cfg.model, world_size=1, dataset_name=d.dataset_name)
        self.clip = 0.3 if needs_grad_clip(cfg) else None
        tx = self.tx = make_optimizer(
            cfg.train.learning_rate, weight_decay=cfg.train.weight_decay,
            clip_norm=self.clip, accumulation_steps=cfg.train.accumulation_steps,
            total_steps=cfg.train.epochs * len(loader) // cfg.train.accumulation_steps,
            scheduler=str(cfg.train.scheduler))
        step = jax.jit(make_train_step(
            model, tx, mmd_weight=cfg.train.mmd.weight, mmd_sigma=cfg.train.mmd.sigma,
            mmd_samples=cfg.train.mmd.samples))
        total = dataset_nbytes(loader)
        if not scan_enabled(cfg.train.scan_epochs, total):
            raise RuntimeError(
                f"train.scan_epochs={cfg.train.scan_epochs!r} resolves to the host "
                f"loop for {total / 2**30:.2f} GiB: this driver measures the scanned epoch")
        self.runner = ScanEpochRunner(step, None, loader, cfg.seed)
        self.prep_s = time.perf_counter() - t0      # staging the set on the device included
        self.steps_per_epoch = self.runner.num_steps
        self.batch_size = int(d.batch_size)

        # observe the feed at the compiled program's boundary
        run_train = self.runner._run_train

        def observed(state, data, perm, epoch_key):
            self.fed.append((perm, epoch_key))
            with common.span("dispatch"):
                return run_train(state, data, perm, epoch_key)

        self.runner._run_train = observed

        if int(self.mix["compare_steps"]) != self.steps_per_epoch:
            raise ValueError("compare_steps must be one scanned epoch "
                             f"({self.steps_per_epoch} steps)")

    def start(self, weights: dict, seed: int) -> None:
        """A fresh state from ``weights`` and the run's seed (epoch
        permutation, step keys), driven through its first epoch by the
        window's own call."""
        from distegnn_tpu.train import TrainState

        self.cfg.seed = self.runner.seed = self.runner.loader.seed = int(seed)
        self.names = list(weights)
        self.w0 = {k: np.asarray(v) for k, v in weights.items()}
        self.state = TrainState.create(self.family.to_tree(weights), self.tx)
        self.fed, self.losses = [], []
        self.epoch = 1          # train() numbers its epochs from 1
        self._epoch()
        self.state_last = self.state
        jax.block_until_ready(self.state)

    def _epoch(self) -> float:
        with common.span("epoch"):
            self.state, loss = self.runner.train_epoch(self.state, self.epoch)
            with common.span("epoch_sync"):
                loss = float(loss)          # train() fetches once per epoch
        self.losses.append(loss)
        self.epoch += 1
        return loss

    def run_window(self, seconds: float) -> dict:
        e0 = len(self.losses)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._epoch()
        jax.block_until_ready(self.state)
        wall = time.perf_counter() - t0
        epochs = len(self.losses) - e0
        steps = epochs * self.steps_per_epoch
        bad = sum(1 for v in self.losses[e0:] if not np.isfinite(v))
        return {"wall_s": wall, "micro_steps": steps, "attempted": steps,
                "failed": bad * self.steps_per_epoch,
                "nodes": steps * self.batch_size * self.nodes_per_graph,
                "launches_expected": epochs, "counters": {}}

    def program_record(self) -> dict:
        st = jax.device_get(self.state_last)
        return {"loss": np.asarray([self.losses[0]], np.float64), "loss_total": None,
                "grad": None,
                "mu": self.family.to_plain(common.find_field(st.opt_state, "mu"), self.names),
                "w": self.family.to_plain(st.params, self.names), "w0": self.w0}

    def reference_inputs(self) -> dict:
        """The raw batches of the first epoch, as the runner's permutation
        fed them, each with its MMD draw (step key = fold_in(epoch key, i),
        split per graph, S*C uniform draws over the graph's real nodes)."""
        import jax.numpy as jnp

        perm, epoch_key = self.fed[0]
        perm = np.asarray(perm)
        s = self.samples
        C, S = self.dims["virtual_channels"], int(self.cfg.train.mmd.samples)
        n, N = self.nodes_per_graph, self.padded[0]
        B = perm.shape[1]
        draw = jax.jit(lambda key: jax.vmap(
            lambda k: jnp.minimum((jax.random.uniform(k, (S * C,)) * n).astype(jnp.int32), N - 1)
        )(jax.random.split(key, B)))
        batches = []
        for i in range(perm.shape[0]):
            idx = np.asarray(draw(jax.random.fold_in(epoch_key, i)))
            graphs = []
            for b, k in enumerate(perm[i]):
                node_perm = common.node_perm(self.dataset[int(k)]["loc"], s["loc"][k])
                g = ref_graphs.nbody_graph(s["loc"][k], s["vel"][k], s["charges"][k], s["target"][k])
                graphs.append(dict(g, mmd_idx=node_perm[idx[b]].astype(np.int32)))
            batches.append(ref_graphs.stack(graphs))
        return {"batches": batches, "model": self.dims,
                "train": common.train_spec(self.cfg, self.clip),
                "block": int(self.mix["reference_block"]),
                "edge_block": self.mix.get("reference_edge_block")}

    def shapes(self) -> dict:
        return common.step_shapes(self, self.batch_size)

    def free(self) -> None:
        for name in ("state", "state_last", "runner", "dataset", "fed"):
            setattr(self, name, None)
