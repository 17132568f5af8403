"""``main.build_cutoff``: the one assembly of ``accelerate_mode: cutoff_edges``
(datasets, loaders, prefetch, model, optimizer, steps, the scan decision),
which ``main.main`` trains with and the benchmark's ``train_cutoff`` driver
measures. Prefetch moves collate and put to a thread and the order of nothing:
Water-3D's yaml at a toy size trains bit for bit the same at
``data.prefetch_depth`` 0 and 2, resumes mid-epoch the same, and reports the
producer's spans and the batch's real and padded edge slots. The second half
ties the program's FastEGNN at Water-3D's dims (F=2, A=0, D=2, a batch axis)
to the benchmark's plain reference."""

from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest
import yaml

import main as main_mod
from distegnn_tpu import obs
from distegnn_tpu.config import derive_runtime_fields, load_config
from distegnn_tpu.data import GraphLoader, PrefetchLoader
from distegnn_tpu.train.trainer import run_epoch_train

CONFIG = os.path.join(os.path.dirname(__file__), "..", "configs", "water3d_fastegnn.yaml")
DEPTHS = (0, 2)
BATCH = 3
STEPS = 4                 # 12 graphs / batch 3


@pytest.fixture(scope="module")
def raw_dir(tmp_path_factory):
    from tests.conftest import make_water3d_h5

    return make_water3d_h5(tmp_path_factory.mktemp("w3d_cutoff"), 60, 40,
                           step_scale=0.003, seed=11)


def _yaml(tmp_path, raw_dir, depth, **train):
    with open(CONFIG) as f:
        cfg = yaml.safe_load(f)
    cfg["data"].update(data_dir=raw_dir, max_samples=12, radius=0.12, delta_t=5,
                       batch_size=BATCH, prefetch_depth=depth)
    cfg["train"].update(epochs=2, **train)
    cfg["log"].update(log_dir=str(tmp_path / f"logs{depth}"), test_interval=1)
    path = str(tmp_path / f"water3d_depth{depth}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def _assemble(path):
    config = load_config(path)
    derive_runtime_fields(config, world_size=1)
    files = main_mod.process_dataset_edge_cutoff(config.data, seed=config.seed)
    return config, main_mod.build_cutoff(config, files)


class _StopAfter:
    """Duck-typed PreemptionGuard: stop once ``count`` steps are dispatched."""

    def __init__(self, count):
        self.count, self.done = count, 0
        self.interrupted, self.steps_done = False, 0

    def stop_agreed(self):
        self.done += 1
        return self.done >= self.count


@pytest.fixture(scope="module")
def runs(raw_dir, tmp_path_factory):
    """``main.main`` over two epochs at each depth: the batches its step was
    fed, the logged losses, the final parameters."""
    out = {}
    real_train = main_mod.train
    for depth in DEPTHS:
        tmp = tmp_path_factory.mktemp(f"main{depth}")
        fed, kept = [], {}

        def spying_train(state, train_step, *rest, _fed=fed, _kept=kept, **kw):
            def step(s, batch, key):
                _fed.append(np.asarray(batch.loc_mean))
                return train_step(s, batch, key)

            _kept["loader"] = rest[1]
            result = real_train(state, step, *rest, **kw)
            _kept["state"] = result[0]
            return result

        main_mod.train = spying_train
        try:
            main_mod.main(["--config_path", _yaml(tmp, raw_dir, depth)])
        finally:
            main_mod.train = real_train
        logs = [os.path.join(r, f) for r, _, fs in os.walk(str(tmp)) for f in fs if f == "log.json"]
        assert len(logs) == 1, logs
        with open(logs[0]) as f:
            best, log, _ = json.load(f)
        best.pop("time_cost")
        out[depth] = {"fed": np.stack(fed), "log": log, "best": best, "loader": kept["loader"],
                      "params": jax.tree.map(np.asarray, kept["state"].params)}
    return out


def test_main_trains_through_the_prefetch_loader(runs):
    for depth in DEPTHS:
        loader = runs[depth]["loader"]
        assert isinstance(loader, PrefetchLoader) and loader.depth == depth
        assert isinstance(loader.loader, GraphLoader)
        assert runs[depth]["fed"].shape == (2 * STEPS, BATCH, 3)


@pytest.mark.parametrize("what", ["batch_order", "losses", "final_parameters"])
def test_prefetch_depth_moves_nothing(runs, what):
    a, b = runs[0], runs[2]
    if what == "batch_order":
        np.testing.assert_array_equal(a["fed"], b["fed"])
        # shuffled by epoch: the second pass is another order of the same graphs
        first, second = a["fed"][:STEPS].reshape(-1, 3), a["fed"][STEPS:].reshape(-1, 3)
        assert not np.array_equal(first, second)
        np.testing.assert_array_equal(np.sort(first, axis=0), np.sort(second, axis=0))
    elif what == "losses":
        for k in ("loss_train", "loss"):          # training, and the test split's of each evaluation
            assert len(a["log"][k]) == 2 and a["log"][k] == b["log"][k], k
        assert all(np.isfinite(a["log"]["loss_train"])) and a["best"] == b["best"]
    else:
        jax.tree.map(np.testing.assert_array_equal, a["params"], b["params"])


@pytest.mark.parametrize("depth", DEPTHS)
def test_mid_epoch_resume_replays_the_same_schedule(raw_dir, tmp_path, depth):
    """Two steps, then the rest of the epoch from ``start_step`` 2, leave the
    state of the uninterrupted epoch, bit for bit."""
    config, run = _assemble(_yaml(tmp_path, raw_dir, depth))
    whole, _ = run_epoch_train(run.train_step, run.state, run.feeds[0], config.seed, 1)
    part, _ = run_epoch_train(run.train_step, run.state, run.feeds[0], config.seed, 1,
                              guard=_StopAfter(2))
    rest, _ = run_epoch_train(run.train_step, part, run.feeds[0], config.seed, 1, start_step=2)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, whole.params), jax.tree.map(np.asarray, rest.params))
    assert not np.array_equal(jax.tree.leaves(part.params)[0], jax.tree.leaves(whole.params)[0])


def test_scan_runner_gets_the_bare_loader(raw_dir, tmp_path):
    config, run = _assemble(_yaml(tmp_path, raw_dir, 2, scan_epochs=True))
    assert run.scan_runner is not None and run.scan_runner.loader is run.loaders[0]
    assert all(isinstance(l, GraphLoader) for l in run.loaders)
    assert run.feeds[0].loader is run.loaders[0] and run.feeds[1:] == run.loaders[1:]
    _, off = _assemble(_yaml(tmp_path, raw_dir, 2, scan_epochs=False))
    assert off.scan_runner is None


@pytest.mark.parametrize("depth", DEPTHS)
def test_spans_and_edge_counters(raw_dir, tmp_path, depth):
    config, run = _assemble(_yaml(tmp_path, raw_dir, depth))
    reg = obs.get_registry()
    real, padded = reg.counter("data/real_edges"), reg.counter("data/padded_edges")
    r0, p0 = real.value, padded.value
    before = {s.id for s in obs.recent_spans()}
    run_epoch_train(run.train_step, run.state, run.feeds[0], config.seed, 1)
    names = [s.name for s in obs.recent_spans() if s.id not in before]
    for name in ("data/produce", "data/collate", "data/put", "data/next", "train/step"):
        assert names.count(name) >= STEPS, (name, names.count(name))
    assert reg.gauge("data/prefetch_depth").value == depth
    dr, dp = real.value - r0, padded.value - p0
    loader = run.loaders[0]
    assert dp == STEPS * BATCH * loader.max_edges and dp >= dr > 0
    edges = sum(g["edge_index"].shape[1] for g in run.datasets[0].graphs)
    assert dr == edges and float(dr).is_integer()      # every graph once a pass


# ---- the program's FastEGNN at Water-3D's dims against the plain reference

DIMS = {"hidden_nf": 64, "n_layers": 4, "virtual_channels": 3, "node_feat_nf": 2,
        "node_attr_nf": 0, "edge_attr_nf": 2, "normalize": False}
TRAIN = {"learning_rate": 5e-4, "weight_decay": 1e-12, "clip_norm": None,
         "accumulation_steps": 1, "mmd": {"sigma": 1.5, "weight": 0.01, "samples": 3}}


@pytest.fixture(scope="module")
def parity():
    """Two updates on one batch of 3 graphs of 150 particles: the program's
    jitted step on the padded batch, ``follow`` on the raw graphs."""
    import jax.numpy as jnp

    from benchmarks import weights
    from benchmarks.drivers import common
    from benchmarks.reference import fastegnn, graphs as ref_graphs
    from distegnn_tpu.data.water3d import build_water3d_graph
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.ops import pad_graphs
    from distegnn_tpu.train import TrainState, make_optimizer, make_train_step

    config = load_config(CONFIG)
    rng = np.random.default_rng(3)
    n, radius, B = 150, 0.2, 3
    graphs, raw = [], []
    for _ in range(B):
        loc = rng.uniform(0, 1, size=(n, 3)).astype(np.float32)
        vel = (rng.normal(size=(n, 3)) * 0.05 * (1 + 3 * loc[:, 2:3])).astype(np.float32)
        target = (loc + 5 * vel).astype(np.float32)
        g = build_water3d_graph(loc, vel, np.full(n, 5.0), target, radius)
        graphs.append(g)
        row, col = g["edge_index"]
        raw.append(ref_graphs._finish(loc, vel, target, g["node_feat"],
                                      np.zeros((n, 0), np.float32), row, col))
    batch = pad_graphs(graphs)
    N = batch.max_nodes
    model = get_model(config.model, world_size=1, dataset_name="Water-3D")
    tx = make_optimizer(TRAIN["learning_rate"], weight_decay=TRAIN["weight_decay"], clip_norm=None,
                        accumulation_steps=1, total_steps=100, scheduler="None")
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.01, mmd_sigma=1.5, mmd_samples=3))
    w0 = weights.make_weights(5, DIMS)
    names = list(w0)
    state = TrainState.create(common.to_tree(w0), tx)
    keys = [jax.random.PRNGKey(k) for k in (1, 2)]
    C, S = 3, 3
    prog, batches = {"loss": [], "total": []}, []
    for i, key in enumerate(keys):
        state, metrics = step(state, batch, key)
        prog["loss"].append(float(metrics["loss"]))
        prog["total"].append(float(metrics["loss_with_mmd"]))
        if i == 0:
            prog["mu1"] = common.to_plain(jax.device_get(
                common.find_field(state.opt_state, "mu")), names)
        idx = np.stack([np.minimum(np.asarray(
            (jax.random.uniform(k, (S * C,)) * n).astype(jnp.int32)), N - 1)
            for k in jax.random.split(key, B)])
        batches.append(ref_graphs.stack([dict(g, mmd_idx=idx[b].astype(np.int32))
                                         for b, g in enumerate(raw)]))
    prog["w"] = common.to_plain(jax.device_get(state.params), names)
    prog["mu"] = common.to_plain(jax.device_get(common.find_field(state.opt_state, "mu")), names)
    ref = fastegnn.follow({k: np.asarray(v) for k, v in w0.items()}, DIMS, TRAIN, batches, block=3)
    return prog, ref, {k: np.asarray(v) for k, v in w0.items()}


@pytest.mark.parametrize("what", ["loss", "first_gradient", "state_after_two_updates"])
def test_fastegnn_at_water3d_dims_matches_the_reference(parity, what):
    prog, ref, w0 = parity

    def worst(p, r):
        """Worst leaf's ||p - r|| over the leaf's own or the median leaf's
        norm, whichever is larger (a leaf of 1e-11 is rounding alone)."""
        norm = {k: np.linalg.norm(np.asarray(v, np.float64)) for k, v in r.items()}
        med = np.median(list(norm.values()))
        return max(np.linalg.norm(np.asarray(p[k], np.float64) - r[k]) / max(norm[k], med)
                   for k in r)

    if what == "loss":
        np.testing.assert_allclose(prog["loss"], ref["loss"], rtol=2e-5)
        np.testing.assert_allclose(prog["total"], ref["loss_total"], rtol=2e-5)
    elif what == "first_gradient":
        # accumulation is 1: after one update Adam's first moment is a tenth
        # of the first gradient
        assert worst({k: 10.0 * v for k, v in prog["mu1"].items()}, ref["grad_first"]) < 2e-4
    else:
        assert worst(prog["mu"], ref["mu"]) < 2e-4
        moved = lambda w: {k: np.asarray(w[k], np.float64) - w0[k] for k in w0}
        assert worst(moved(prog["w"]), moved(ref["w"])) < 5e-3
