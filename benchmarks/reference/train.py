"""The training the plain reference follows, whatever the model family: the
loss (MSE over the rows that count + weight x MMD between drawn targets and
the virtual nodes), its gradient summed over blocks of graphs, accumulation,
the global-norm clip and torch's Adam, in ``jax.numpy`` float32 at matmul
precision ``highest``.

A family's reference module (``fastegnn.py``, ``fasttfn.py``) brings its
forward as a jitted ``block_grad(w, blk, rows, *, model_key, mmd_key, G,
**options)``: (mse share, mmd share, gradient) of one block of graphs, made by
``loss_and_grad`` from the family's ``terms`` (the block's sums of
``graph_terms``). The loss is a sum over graphs, so the blocks' shares add
up.

Departures from the published training script, each because the
configuration as run states it: the MMD term draws its ``samples * C`` target
nodes with replacement (the drawn indices are an input here, so both sides
see the same nodes); MMD distances are floored at 1e-12 before the square
root. ``mmd_w``: the partitioned loss's weight on each drawn node
(``fastegnn.py``, "The partitioned loss"); absent, every draw has weight 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def kernel_sum(a, b, sigma, w=None):
    """sum_ij w_i k(a_i, b_j); ``w`` None: every row of ``a`` at weight 1."""
    d2 = jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    k = jnp.exp(-jnp.sqrt(jnp.maximum(d2, 1e-24)) / (2.0 * sigma * sigma))
    return jnp.sum(k if w is None else k * w[:, None])


def graph_terms(pred, X, g, mmd):
    """One graph's sums: squared error over the rows that count
    (``loss_rows``, all ones unless a fault is planted), k(V,V), k(samples,V),
    the last with each drawn node at its weight ``mmd_w`` where the batch
    carries one. ``pred`` [n,3], ``X`` [3,C] the virtual coordinates."""
    sse = jnp.sum((pred - g["target"]) ** 2 * g["loss_rows"][:, None])
    V = X.T
    k_vv = kernel_sum(V, V, mmd["sigma"])
    k_rv = kernel_sum(g["target"][g["mmd_idx"]], V, mmd["sigma"], g.get("mmd_w"))
    return sse, k_vv, k_rv


def loss(terms, w, blk, rows, model, mmd, G):
    """(mse share + weight * mmd share, (mse share, mmd share)) of one block
    of a batch of ``G`` graphs in which ``rows`` rows count towards the MSE;
    ``terms(w, model, mmd, blk)`` -> the block's (sse, k_vv, k_rv)."""
    C = model["virtual_channels"]
    S = mmd["samples"] * C
    sse, k_vv, k_rv = terms(w, model, mmd, blk)
    mse = sse / (rows * 3)
    mmd_l = k_vv / G / C / C - 2.0 * k_rv / G / S / C
    return mse + mmd["weight"] * mmd_l, (mse, mmd_l)


def loss_and_grad(terms, w, blk, rows, model, mmd, G):
    """(mse share, mmd share), gradient of their weighted sum: the body of a
    family's jitted ``block_grad``."""
    (_, (mse, mmd_l)), grads = jax.value_and_grad(
        lambda w: loss(terms, w, blk, rows, model, mmd, G), has_aux=True)(w)
    return mse, mmd_l, grads


def hashable(d):
    return tuple(sorted(d.items()))


def micro_step(block_grad, w, model, mmd, batch, block, half=False, **options):
    """Loss and gradient of one micro-batch (``G`` stacked graphs), summed
    over blocks of ``block`` graphs. Returns (mse, mse + weight*mmd, grads).
    ``options`` go to ``block_grad`` as they are (static arguments).

    ``half`` plants the fault "half of the batch left out, the mean taken
    over the rest": of several graphs the second half, of one graph the rows
    its ``second_half`` marks (the half the program's loader puts last)."""
    G, n = batch["loc"].shape[:2]
    second = batch.pop("second_half") if "second_half" in batch else jnp.zeros((G, n))
    if not half:
        keep = jnp.ones((G, n), jnp.float32)
    elif G > 1:
        keep = jnp.broadcast_to((jnp.arange(G) < (G + 1) // 2)[:, None], (G, n)).astype(jnp.float32)
    else:
        keep = 1.0 - second.astype(jnp.float32)
    batch = dict(batch, loss_rows=keep)
    rows = jnp.sum(keep)
    if G % block:
        raise ValueError(f"batch of {G} graphs is not a multiple of block {block}")
    mse = mm = 0.0
    grads = None
    with jax.default_matmul_precision("highest"):
        for s in range(0, G, block):
            blk = {k: v[s:s + block] for k, v in batch.items()}
            a, b, g = block_grad(w, blk, rows, model_key=hashable(model),
                                 mmd_key=hashable(mmd), G=G, **options)
            mse, mm = mse + a, mm + b
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return mse, mse + mmd["weight"] * mm, grads


@functools.partial(jax.jit, static_argnames=("lr", "wd", "clip"))
def _adam_update(w, g, mu, nu, t, *, lr, wd, clip):
    """torch.optim.Adam with L2 weight decay folded into the gradient, after
    an optional clip of the global norm; ``t`` counts updates from 1. Also
    returns each leaf's norm of the gradient as the moments get it."""
    if clip is not None:
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        scale = jnp.where(norm < clip, 1.0, clip / norm)
        g = {k: x * scale for k, x in g.items()}
    g = {k: g[k] + wd * w[k] for k in g}
    mu = {k: 0.9 * mu[k] + 0.1 * g[k] for k in g}
    nu = {k: 0.999 * nu[k] + 0.001 * g[k] * g[k] for k in g}
    c1, c2 = 1.0 - 0.9 ** t, 1.0 - 0.999 ** t
    w = {k: w[k] - lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + 1e-8) for k in g}
    return w, mu, nu, {k: jnp.sqrt(jnp.sum(x * x)) for k, x in g.items()}


def follow(block_grad, w0, model, train, batches, block, half=False, **options):
    """Follow the first ``len(batches)`` micro-steps of training from ``w0``.

    ``train``: learning_rate, weight_decay, clip_norm (or None),
    accumulation_steps, mmd {sigma, weight, samples}. Returns host numpy:
    ``loss`` [steps] (the logged MSE), ``loss_total`` [steps], ``grad_first``
    (the first micro-batch's gradient), ``mu`` (Adam's first moment after the
    last update), ``w`` (weights after the last micro-step), ``update_norms``
    (per leaf, [updates]: the norm of each accumulated, clipped gradient as
    Adam got it; ``mu`` is their decayed sum, so they bound it, and say how
    large it is when they do not cancel)."""
    acc_k = int(train["accumulation_steps"])
    w = dict(w0)
    mu = {k: jnp.zeros_like(v) for k, v in w.items()}
    nu = {k: jnp.zeros_like(v) for k, v in w.items()}
    acc, t = None, 0
    losses, totals, norms, grad_first = [], [], [], None
    for i, batch in enumerate(batches):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        mse, total, g = micro_step(block_grad, w, model, train["mmd"], batch, block, half=half,
                                   **options)
        losses.append(mse)
        totals.append(total)
        if i == 0:
            grad_first = g
        acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        if (i + 1) % acc_k == 0:
            t += 1
            mean = {k: v / acc_k for k, v in acc.items()}
            clip = train.get("clip_norm")
            w, mu, nu, norm = _adam_update(
                w, mean, mu, nu, float(t), lr=float(train["learning_rate"]),
                wd=float(train["weight_decay"]),
                clip=None if clip is None else float(clip))
            norms.append(norm)
            acc = None
    get = lambda tree: {k: np.asarray(v) for k, v in tree.items()}
    norms = jax.device_get(norms)
    return {"loss": np.asarray(jnp.stack(losses)),
            "loss_total": np.asarray(jnp.stack(totals)),
            "grad_first": get(grad_first), "mu": get(mu), "w": get(w),
            "update_norms": {k: np.asarray([n[k] for n in norms], np.float32) for k in w}}
