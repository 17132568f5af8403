"""FastTFN's plain reference (``benchmarks/reference/fasttfn.py``) on the
CPU: its kernels against the program's basis, symmetry, padding and blocks,
its gradient against float64 central differences, and its degree-0 input."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import fasttfn, train
from benchmarks.reference import graphs as ref_graphs
from benchmarks.tests.conftest import toy_mix
from benchmarks.traffic.generate import make_samples

DIMS = {"hidden_nf": 8, "n_layers": 2, "virtual_channels": 2, "node_feat_nf": 2,
        "node_attr_nf": 0, "edge_attr_nf": 2, "normalize": True, "model_name": "FastTFN"}
MMD = {"sigma": 1.5, "weight": 0.03, "samples": 3}


def _batch(G=4, n=5, edges=None, seed=0):
    """G complete graphs of n charged bodies, edge lists padded to ``edges``."""
    mix = dict(toy_mix("toy_nbody_mix"), samples_train=G, n_bodies=n)
    s = make_samples(mix)
    rng = np.random.default_rng(seed)
    graphs = [dict(ref_graphs.nbody_graph(s["loc"][k], s["vel"][k], s["charges"][k], s["target"][k]),
                   mmd_idx=rng.integers(0, n, MMD["samples"] * DIMS["virtual_channels"]).astype(np.int32))
              for k in range(G)]
    b = ref_graphs.stack(graphs, edges=edges)
    return dict(b, loss_rows=np.ones((G, n), np.float32))


def _weights(dims=DIMS, seed=3):
    """The layout's weights with the layer norms' affine parts moved off 1
    and 0, so that they take part."""
    w = {k: np.asarray(v) for k, v in weights.make_weights(seed, dims).items()}
    rng = np.random.default_rng(seed)
    for k in w:
        if ".ln" in k:
            w[k] = (w[k] + 0.3 * rng.normal(size=w[k].shape)).astype(np.float32)
    return w


def _grad(w, batch, block):
    """(mse, mse + weight x mmd, gradient) of the micro-batch, ``block``
    graphs at a time."""
    return train.micro_step(fasttfn._block_grad, w, DIMS, MMD, dict(batch), block)


def _forward(w, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(lambda g: fasttfn.forward(w, DIMS, g)))(batch)


def _rel(a, b):
    a, b = jax.tree.leaves(a), jax.tree.leaves(b)
    d = np.sqrt(sum(np.sum((np.asarray(x, np.float64) - np.asarray(y, np.float64)) ** 2)
                    for x, y in zip(a, b)))
    return d / np.sqrt(sum(np.sum(np.asarray(y, np.float64) ** 2) for y in b))


def test_kernels_are_the_programs_basis():
    """K01 and K11_J applied to the Cartesian unit vectors, against the
    program's ``compute_basis_and_r`` at random directions, read in the
    Cartesian order (the program keeps degree 1 as (y, z, x))."""
    from distegnn_tpu.models.se3.basis import compute_basis_and_r

    rng = np.random.default_rng(1)
    rel = rng.normal(size=(1, 16, 3)).astype(np.float32) * 3.0
    basis, _ = compute_basis_and_r(jnp.asarray(rel), 1)
    cart = [2, 0, 1]
    u = (rel[0] / np.linalg.norm(rel[0], axis=1, keepdims=True)).T          # [3,e]
    k01, _ = fasttfn.kernels(u, np.ones(16, np.float32), np.zeros((3, 16), np.float32))
    np.testing.assert_allclose(np.asarray(k01).T,
                               np.asarray(basis[(0, 1)])[0, :, :, 0, 0][:, cart], atol=2e-7)
    program = np.asarray(basis[(1, 1)])[0][:, cart][:, :, cart]              # [e, out, in, J]
    for b in range(3):
        v = np.zeros((3, 16), np.float32)
        v[b] = 1.0
        _, k11 = fasttfn.kernels(u, np.zeros(16, np.float32), v)
        for J in range(3):
            np.testing.assert_allclose(np.asarray(k11[J]).T, program[:, :, b, J], atol=2e-7)


def _rotation(seed):
    q, r = np.linalg.qr(np.random.default_rng(seed).normal(size=(3, 3)))
    q = q * np.sign(np.diag(r))
    return (q if np.linalg.det(q) > 0 else -q).astype(np.float32)


def test_forward_is_equivariant_and_invariant_to_node_order():
    batch, w = _batch(), _weights()
    pred, X = _forward(w, batch)
    R, t = _rotation(2), np.array([0.7, -1.3, 2.1], np.float32)
    moved = dict(batch, **{k: batch[k] @ R.T + t for k in ("loc", "target", "loc_mean")},
                 vel=batch["vel"] @ R.T)
    pred_m, X_m = _forward(w, moved)
    np.testing.assert_allclose(pred_m, np.asarray(pred) @ R.T + t, atol=2e-5)
    np.testing.assert_allclose(X_m, np.einsum("ij,gjc->gic", R, X) + t[:, None], atol=2e-5)

    perm = np.random.default_rng(3).permutation(batch["loc"].shape[1])
    inv = np.argsort(perm)
    shuffled = dict(batch, **{k: batch[k][:, perm] for k in ("feat", "attr", "loc", "vel", "target",
                                                             "charge", "loss_rows")},
                    row=inv[batch["row"]].astype(np.int32), col=inv[batch["col"]].astype(np.int32),
                    mmd_idx=inv[batch["mmd_idx"]].astype(np.int32))
    pred_p, X_p = _forward(w, shuffled)
    np.testing.assert_allclose(pred_p, np.asarray(pred)[:, perm], atol=2e-5)
    np.testing.assert_allclose(X_p, X, atol=2e-5)


@pytest.mark.parametrize("block, pad", [(1, 0), (2, 0), (4, 7), (2, 13)])
def test_padding_edges_and_blocks_leave_forward_and_gradient(block, pad):
    """Zero-weight edges appended to every graph's list and any ``block``:
    the same forward, loss and gradient as the whole batch at once unpadded,
    to rounding (float32 terms summed in another order); a padding edge
    takes no part in any normalization."""
    base, w = _batch(), _weights()
    E = base["row"].shape[1]
    batch = _batch(edges=E + pad)
    ref = _grad(w, base, 4)
    got = _grad(w, batch, block)
    assert abs(float(got[0]) / float(ref[0]) - 1.0) < 1e-6
    assert abs(float(got[1]) / float(ref[1]) - 1.0) < 1e-5
    assert _rel(got[2], ref[2]) < 1e-5
    assert _rel(_forward(w, batch), _forward(w, base)) < 1e-6


def test_a_graphs_prediction_is_its_own():
    """The layer norms normalize each edge over its own channels: a graph's
    prediction stays where it was when ANOTHER graph of its micro-batch
    changes, and moves when its own does."""
    batch, w = _batch(), _weights()
    other = dict(batch, loc=batch["loc"].copy())
    other["loc"][3] *= 1.5
    pred, pred_o = _forward(w, batch)[0], _forward(w, other)[0]
    np.testing.assert_allclose(pred_o[:3], pred[:3], rtol=0, atol=1e-6)
    assert np.max(np.abs(np.asarray(pred[3]) - np.asarray(pred_o[3]))) > 1e-4


def test_gradient_matches_float64_central_differences(monkeypatch):
    """In float64, the gradient along random directions against central
    differences of the loss. The published basis carries no gradient, so
    the loss's own derivative differs from it from the second layer on (the
    later layers' basis moves with the weights); with the basis's gradient
    let through (a test-only identity in ``_no_grad``'s place) the two agree,
    and without it the difference is there."""
    with jax.enable_x64(True):
        f64 = lambda a: np.asarray(a, np.float64) if np.asarray(a).dtype == np.float32 else a
        batch = {k: f64(v) for k, v in _batch().items()}
        w = {k: f64(v) for k, v in _weights().items()}
        G, n = batch["loc"].shape[:2]
        loss = lambda w: train.loss(fasttfn._block_terms, w, batch, float(G * n), DIMS, MMD, G)[0]
        published = jax.jit(jax.grad(loss))(w)
        monkeypatch.setattr(fasttfn, "_no_grad", lambda a: a)
        full, value = jax.jit(jax.grad(loss)), jax.jit(loss)
        g = full(w)
        rng = np.random.default_rng(5)
        for _ in range(3):
            # a unit direction and a small step: a ReLU's kink crossed inside
            # the step would break the difference, not the gradient
            d = {k: rng.normal(size=np.shape(v)) for k, v in w.items()}
            norm = np.sqrt(sum(np.sum(v * v) for v in d.values()))
            d = {k: v / norm for k, v in d.items()}
            eps = 1e-6
            fd = (value({k: w[k] + eps * d[k] for k in w})
                  - value({k: w[k] - eps * d[k] for k in w})) / (2 * eps)
            ad = sum(np.sum(np.asarray(g[k]) * d[k]) for k in w)
            assert abs(float(fd) - ad) < 1e-6 * abs(ad), (fd, ad)
        assert 1e-6 < _rel(published, g) < 0.5


def test_degree0_input_is_the_programs_charge():
    """The reference feeds the raw charge; the program feeds ``node_attr``
    (the raw charge) and stores q / max q as a feature: on the generator's
    charges (+-1, both signs in every system) all three are one number."""
    from distegnn_tpu.data.nbody import build_nbody_graph

    s = make_samples(toy_mix("toy_nbody_mix"))
    for k in range(s["loc"].shape[0]):
        ref = ref_graphs.nbody_graph(s["loc"][k], s["vel"][k], s["charges"][k], s["target"][k])
        prog = build_nbody_graph(s["loc"][k], s["vel"][k], s["charges"][k], s["target"][k])
        np.testing.assert_array_equal(ref["charge"], prog["node_attr"][:, 0])
        np.testing.assert_array_equal(ref["charge"], prog["node_feat"][:, -1])
        np.testing.assert_array_equal(ref["charge"], ref["feat"][:, -1])


def test_follow_takes_no_edge_block():
    with pytest.raises(ValueError, match="no edge_block"):
        fasttfn.follow(_weights(), DIMS, {}, [_batch()], 2, edge_block=8)
