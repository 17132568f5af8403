"""Training-runtime tests: loss parity vs dense numpy/torch references,
optimizer parity vs torch.Adam, grad accumulation, checkpoint roundtrip, and a
loss-goes-down smoke run (SURVEY.md §4: the test infrastructure the reference
lacks)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distegnn_tpu.data import GraphDataset, GraphLoader, build_nbody_graph
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.train import (
    TrainState,
    make_eval_step,
    make_optimizer,
    make_train_step,
    masked_mse,
    mmd_loss,
    restore_checkpoint,
    save_checkpoint,
)


def _tiny_dataset(rng, n_graphs=8, n=10):
    graphs = []
    for _ in range(n_graphs):
        loc = rng.normal(size=(n, 3))
        vel = rng.normal(size=(n, 3))
        charges = rng.choice([1.0, -1.0], size=(n, 1))
        target = loc + 0.1 * vel
        graphs.append(build_nbody_graph(loc, vel, charges, target, radius=-1.0, cutoff_rate=0.0))
    return graphs


def test_masked_mse_matches_numpy(rng):
    pred = rng.normal(size=(2, 6, 3)).astype(np.float32)
    target = rng.normal(size=(2, 6, 3)).astype(np.float32)
    mask = np.array([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1]], np.float32)
    got = float(masked_mse(jnp.asarray(pred), jnp.asarray(target), jnp.asarray(mask)))
    real = np.concatenate([(pred[0, :4] - target[0, :4]).ravel(), (pred[1] - target[1]).ravel()])
    np.testing.assert_allclose(got, np.mean(real**2), rtol=1e-5)


def test_mmd_loss_matches_dense_reference(rng):
    # With samples*C >= N every real node is drawn (Gumbel top-k over N nodes),
    # so the sampled set equals the node set and the loss is deterministic —
    # compare against a direct numpy transcription of reference kernel math
    # (utils/train.py:11-14,119-145).
    B, N, C, sigma, samples = 2, 4, 2, 1.5, 2  # num_sample = 4 = N
    V = rng.normal(size=(B, 3, C)).astype(np.float32)
    target = rng.normal(size=(B, N, 3)).astype(np.float32)
    mask = np.ones((B, N), np.float32)
    got = float(mmd_loss(jnp.asarray(V), jnp.asarray(target), jnp.asarray(mask),
                         jax.random.PRNGKey(0), sigma, samples))

    def k(x, y):
        d = np.linalg.norm(x[:, None] - y[None, :], axis=-1)
        return np.exp(-d / (2 * sigma * sigma))

    num_sample = samples * C
    l_vv = sum(k(V[b].T, V[b].T).sum() for b in range(B)) / B / C / C
    l_rv = 2 * sum(k(target[b], V[b].T).sum() for b in range(B)) / B / num_sample / C
    np.testing.assert_allclose(got, l_vv - l_rv, rtol=1e-4)


def test_optimizer_matches_torch_adam():
    # same quadratic, same init: optax chain must track torch.Adam(+wd) steps
    import torch

    w0 = np.array([1.0, -2.0, 3.0], np.float32)
    tw = torch.nn.Parameter(torch.tensor(w0))
    topt = torch.optim.Adam([tw], lr=1e-2, weight_decay=1e-2)
    for _ in range(5):
        topt.zero_grad()
        loss = (tw**2).sum()
        loss.backward()
        topt.step()

    tx = make_optimizer(1e-2, weight_decay=1e-2)
    params = jnp.asarray(w0)
    opt_state = tx.init(params)
    for _ in range(5):
        grads = jax.grad(lambda p: jnp.sum(p**2))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = params + updates["params"] if isinstance(updates, dict) else params + updates
    np.testing.assert_allclose(np.asarray(params), tw.detach().numpy(), rtol=1e-5, atol=1e-6)


def test_grad_accumulation_equals_mean():
    # MultiSteps(k=2) applied to two micro-grads == single step on their mean
    tx_acc = make_optimizer(1e-2, accumulation_steps=2)
    tx_ref = make_optimizer(1e-2)
    p = jnp.asarray([1.0, 2.0])
    g1, g2 = jnp.asarray([0.5, -1.0]), jnp.asarray([1.5, 3.0])

    s = tx_acc.init(p)
    pa = p
    for g in (g1, g2):
        u, s = tx_acc.update(g, s, pa)
        pa = pa + u
    sr = tx_ref.init(p)
    u, _ = tx_ref.update((g1 + g2) / 2, sr, p)
    pr = p + u
    np.testing.assert_allclose(np.asarray(pa), np.asarray(pr), rtol=1e-6)


@pytest.fixture(scope="module")
def tiny_setup():
    rng = np.random.default_rng(0)
    graphs = _tiny_dataset(rng)
    batch = pad_graphs(graphs[:4])
    model = FastEGNN(node_feat_nf=2, hidden_nf=16, virtual_channels=3, n_layers=2)
    params = model.init(jax.random.PRNGKey(0), batch)
    return model, params, graphs


def test_train_step_loss_decreases(tiny_setup):
    model, params, graphs = tiny_setup
    tx = make_optimizer(5e-3)
    state = TrainState.create(params, tx)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.03, mmd_sigma=1.5, mmd_samples=3))
    ds = GraphDataset(graphs)
    loader = GraphLoader(ds, batch_size=4, shuffle=True, seed=1)
    first = last = None
    for epoch in range(15):
        loader.set_epoch(epoch)
        for i, batch in enumerate(loader):
            state, m = step(state, batch, jax.random.PRNGKey(epoch * 100 + i))
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
    assert last < first * 0.5, f"loss did not decrease: {first} -> {last}"


def test_eval_step_runs(tiny_setup):
    model, params, graphs = tiny_setup
    ev = jax.jit(make_eval_step(model))
    batch = pad_graphs(graphs[:4])
    loss = float(ev(params, batch))
    assert np.isfinite(loss) and loss > 0


def test_early_stop_checked_every_epoch(tiny_setup, tmp_path):
    # reference checks the stop condition at the bottom of EVERY epoch
    # (utils/train.py:261-267), not only on eval epochs: with test_interval=10
    # and early_stop=3, the run must stop at epoch 3 before any eval happens.
    from distegnn_tpu.config import ConfigDict
    from distegnn_tpu.train.trainer import train

    model, params, graphs = tiny_setup
    tx = make_optimizer(1e-3)
    state = TrainState.create(params, tx)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.0, mmd_sigma=1.0, mmd_samples=1))
    ev = jax.jit(make_eval_step(model))
    loader = GraphLoader(GraphDataset(graphs), batch_size=4, shuffle=False, seed=0)
    config = ConfigDict({
        "seed": 0,
        "train": {"epochs": 50, "early_stop": 3},
        "log": {"test_interval": 10, "log_dir": str(tmp_path), "wandb": {"enable": False}},
    })
    _, _, best, log_dict = train(state, step, ev, loader, loader, loader, config, log=False)
    assert best["early_stop"] == 3
    assert len(log_dict["loss_train"]) == 3


def test_epoch_accumulates_on_device(tiny_setup):
    # run_epoch_train's average must equal the naive per-step float() average
    # (it now accumulates the scalar on device, one fetch per epoch)
    from distegnn_tpu.train.trainer import run_epoch_train

    model, params, graphs = tiny_setup
    tx = make_optimizer(1e-3)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.0, mmd_sigma=1.0, mmd_samples=1))
    loader = GraphLoader(GraphDataset(graphs), batch_size=4, shuffle=False, seed=0)

    state = TrainState.create(params, tx)
    _, avg = run_epoch_train(step, state, loader, seed=0, epoch=1)

    state2 = TrainState.create(params, tx)
    loader.set_epoch(1)
    total = cnt = 0.0
    for i, batch in enumerate(loader):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), 1), i)
        state2, m = step(state2, batch, key)
        total += float(m["loss"]) * batch.loc.shape[0]
        cnt += batch.loc.shape[0]
    np.testing.assert_allclose(avg, total / cnt, rtol=1e-6)


def test_checkpoint_roundtrip(tmp_path, tiny_setup):
    model, params, _ = tiny_setup
    tx = make_optimizer(1e-3, weight_decay=1e-8)
    state = TrainState.create(params, tx)
    path = str(tmp_path / "ckpt" / "best_model.ckpt")
    save_checkpoint(path, state, epoch=7, losses={"loss_valid": 0.5}, config={"a": 1})
    fresh = TrainState.create(params, tx)
    restored, epoch, losses = restore_checkpoint(path, fresh)
    assert epoch == 7 and losses["loss_valid"] == 0.5
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_rejects_mismatched_architecture(tmp_path, tiny_setup):
    """Restoring into a different param tree (e.g. hoist_edge_mlp flipped)
    must fail loudly, not zip mismatched leaves into garbage params."""
    import pytest

    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.ops.graph import pad_graphs

    model, params, graphs = tiny_setup
    batch = pad_graphs(graphs[:4])
    tx = make_optimizer(1e-3, weight_decay=1e-8)
    state = TrainState.create(params, tx)
    path = str(tmp_path / "ckpt" / "best_model.ckpt")
    save_checkpoint(path, state, epoch=1)

    other = FastEGNN(node_feat_nf=model.node_feat_nf,
                     edge_attr_nf=model.edge_attr_nf,
                     hidden_nf=model.hidden_nf,
                     virtual_channels=model.virtual_channels,
                     n_layers=model.n_layers,
                     hoist_edge_mlp=not model.hoist_edge_mlp)
    p2 = other.init(jax.random.PRNGKey(0), batch)
    fresh = TrainState.create(p2, tx)
    with pytest.raises(ValueError, match="checkpoint incompatible"):
        restore_checkpoint(path, fresh)


def test_trace_epoch_writes_profile(tiny_setup, tmp_path):
    """log.trace_epoch=N captures a jax.profiler trace of epoch N into
    <exp_dir>/trace/ (SURVEY §5.1 observability at the training surface)."""
    import os

    from distegnn_tpu.config import ConfigDict
    from distegnn_tpu.train.trainer import train

    model, params, graphs = tiny_setup
    tx = make_optimizer(1e-3)
    state = TrainState.create(params, tx)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.0, mmd_sigma=1.0, mmd_samples=1))
    ev = jax.jit(make_eval_step(model))
    loader = GraphLoader(GraphDataset(graphs), batch_size=4, shuffle=False, seed=0)
    config = ConfigDict({
        "seed": 0,
        "train": {"epochs": 2, "early_stop": 10},
        "log": {"test_interval": 10, "log_dir": str(tmp_path), "exp_name": "tr",
                "trace_epoch": 2, "wandb": {"enable": False}},
    })
    train(state, step, ev, loader, loader, loader, config, log=True)
    trace_dir = os.path.join(str(tmp_path), "tr", "trace")
    files = [os.path.join(r, f) for r, _, fs in os.walk(trace_dir) for f in fs]
    assert files, "no profiler trace written"


def test_restore_params_ignores_optimizer_wrapping(tmp_path, tiny_setup):
    """A checkpoint written with grad-accumulation (MultiSteps wraps extra
    opt-state arrays) must load into a bare model for evaluation/rollout —
    restore_params is params-only (restore_checkpoint correctly refuses)."""
    from distegnn_tpu.train.checkpoint import (restore_checkpoint,
                                               restore_params,
                                               save_checkpoint)

    model, params, graphs = tiny_setup
    tx_acc = make_optimizer(1e-3, accumulation_steps=4)
    state = TrainState.create(params, tx_acc)
    path = str(tmp_path / "acc.ckpt")
    save_checkpoint(path, state, epoch=3, config={"model": {"x": 1}})

    restored = restore_params(path, params)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    plain_state = TrainState.create(params, make_optimizer(1e-3))
    with pytest.raises(ValueError, match="incompatible"):
        restore_checkpoint(path, plain_state)


def test_resume_equals_uninterrupted(tiny_setup, tmp_path):
    """Interrupt-and-resume reproduces the uninterrupted run bitwise.

    Per-step PRNG keys derive from (seed, epoch, step) and the loader
    reshuffles from (seed, epoch) (trainer.run_epoch_train), so restoring
    last_model.ckpt at epoch k and continuing with start_epoch=k must yield
    the exact trajectory the unbroken run took — the property the reference's
    --checkpoint restart flow (main.py:208-220) provides and a resumed
    convergence run relies on after a mid-run abort."""
    from distegnn_tpu.config import ConfigDict
    from distegnn_tpu.train.trainer import train

    model, params, graphs = tiny_setup
    tx = make_optimizer(1e-3)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.03, mmd_sigma=1.5,
                                   mmd_samples=3))
    ev = jax.jit(make_eval_step(model))

    def mk_loader():
        return GraphLoader(GraphDataset(graphs), batch_size=4, shuffle=True, seed=0)

    def mk_config(dirname, epochs):
        return ConfigDict({
            "seed": 0,
            "train": {"epochs": epochs, "early_stop": 100},
            "log": {"test_interval": 2, "log_dir": str(tmp_path / dirname),
                    "exp_name": "run", "wandb": {"enable": False}},
        })

    # uninterrupted run: 6 epochs
    state_a = TrainState.create(params, tx)
    state_a, _, _, _ = train(state_a, step, ev, mk_loader(), mk_loader(),
                             mk_loader(), mk_config("full", 6))

    # interrupted at epoch 4 (last_model.ckpt written on eval epoch 4) ...
    state_b = TrainState.create(params, tx)
    train(state_b, step, ev, mk_loader(), mk_loader(), mk_loader(),
          mk_config("part", 4))
    ckpt = tmp_path / "part" / "run" / "state_dict" / "last_model.ckpt"
    fresh = TrainState.create(params, tx)
    restored, start_epoch, _ = restore_checkpoint(str(ckpt), fresh)
    assert start_epoch == 4

    # ... resumed for epochs 5..6
    state_c, _, _, _ = train(restored, step, ev, mk_loader(), mk_loader(),
                             mk_loader(), mk_config("resumed", 6),
                             start_epoch=start_epoch)

    for a, c in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_c.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_divergence_stops_training(tiny_setup, tmp_path):
    """SURVEY §5.3 failure detection: a non-finite train loss must stop the
    run immediately (unattended hardware sessions would otherwise burn the
    whole window training on NaN) and record the diagnosis in log.json."""
    import json
    import os

    from distegnn_tpu.config import ConfigDict
    from distegnn_tpu.train.trainer import train

    model, params, graphs = tiny_setup
    tx = make_optimizer(1e-3)

    calls = {"n": 0}

    def exploding_step(state, batch, key):
        calls["n"] += 1
        # diverge partway through epoch 2
        loss = jnp.float32(jnp.nan) if calls["n"] > 3 else jnp.float32(0.5)
        return state.replace(step=state.step + 1), {"loss": loss}

    config = ConfigDict({
        "seed": 0,
        "train": {"epochs": 10, "early_stop": 100},
        "log": {"test_interval": 2, "log_dir": str(tmp_path),
                "exp_name": "run", "wandb": {"enable": False}},
    })
    state = TrainState.create(params, tx)
    _, _, best, log_dict = train(
        state, exploding_step, lambda p, b: jnp.float32(0.1),
        GraphLoader(GraphDataset(graphs), batch_size=4, shuffle=True, seed=0),
        GraphLoader(GraphDataset(graphs), batch_size=4),
        GraphLoader(GraphDataset(graphs), batch_size=4),
        config)
    assert "diverged" in best
    assert len(log_dict["loss_train"]) < 10  # stopped early
    raw = open(os.path.join(tmp_path, "run", "log", "log.json")).read()
    logged = json.loads(raw, parse_constant=lambda c: pytest.fail(
        f"non-RFC-8259 token {c} in log.json"))  # strict: no bare NaN/Infinity
    assert "diverged" in logged[0]
    assert logged[1]["loss_train"][-1] is None  # NaN sanitized to null
