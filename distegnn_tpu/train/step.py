"""The jitted train/eval step — forward, loss (MSE + MMD), backward, clip,
optimizer, all in ONE traced program (SURVEY.md §7.1 item 2: the reference's
per-step Python work must become traced ops or disappear).

Distributed: the same step function runs under ``shard_map`` with
``axis_name='graph'``. Each device differentiates its OWN node-weighted loss
share (cross-partition terms arrive through the model's virtual-node psums),
then the step psums the parameter gradients across the axis — the DDP-sum
pattern (reference DDP allreduce + world_size rescale, main.py:196 +
utils/train.py:110). Do NOT seed the backward from the psum'd global loss
instead: psum's transpose is psum, which would scale every gradient by the
axis size.

Optimizer parity (reference main.py:197-202 + utils/train.py:150-158):
torch.Adam with L2 weight_decay folded into the gradient, optional
grad-clip-by-global-norm(0.3), loss/accumulation_steps with a step every k
micro-batches (optax.MultiSteps), optional cosine schedule over
epochs*len(loader)/accumulation_steps.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from distegnn_tpu import obs
from distegnn_tpu.ops.graph import GraphBatch
from distegnn_tpu.parallel.collectives import _psum
from distegnn_tpu.train.loss import (
    masked_mse,
    mmd_loss,
    weighted_global_loss,
    weighted_local_loss,
)


@struct.dataclass
class TrainState:
    params: dict
    opt_state: optax.OptState
    step: jnp.ndarray  # micro-batch counter

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TrainState":
        return cls(params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32))


def needs_grad_clip(config) -> bool:
    """Reference rule (utils/train.py:153-154): clip-by-norm 0.3 only when
    distributed or on the largest dataset, and only for FastEGNN."""
    dist = config.data.world_size > 1
    big = config.data.dataset_name in ("LargeFluid", "Fluid113K")
    return (dist or big) and config.model.model_name == "FastEGNN"


def make_optimizer(
    learning_rate: float,
    weight_decay: float = 0.0,
    clip_norm: Optional[float] = None,
    accumulation_steps: int = 1,
    total_steps: Optional[int] = None,
    scheduler: str = "None",
) -> optax.GradientTransformation:
    """torch-Adam-parity chain: [clip] -> +wd*p -> adam moments -> -lr [cosine]."""
    parts = []
    if clip_norm is not None:
        parts.append(optax.clip_by_global_norm(clip_norm))
    if weight_decay:
        # torch.Adam weight_decay: grad += wd * param BEFORE the moment update
        parts.append(optax.add_decayed_weights(weight_decay))
    parts.append(optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8))
    if scheduler == "cosine":
        assert total_steps is not None, "cosine scheduler needs total_steps"
        lr = optax.cosine_decay_schedule(learning_rate, total_steps)
    else:
        lr = learning_rate
    parts.append(optax.scale_by_learning_rate(lr))
    tx = optax.chain(*parts)
    if accumulation_steps > 1:
        # MultiSteps averages micro-grads — same math as the reference's
        # loss/accumulation_steps + step-every-k (utils/train.py:150-158)
        tx = optax.MultiSteps(tx, every_k_schedule=accumulation_steps)
    return tx


def _reduce_axes(axis_name, data_axis_name):
    """All mesh axes the LOSS/GRADIENT reduce over: graph partitions and (when
    2-D) data-parallel shards. The model's virtual-node psums stay on
    ``axis_name`` alone — virtual nodes are per-graph objects, and the data
    axis holds *different* graphs.

    The TENSOR axis is deliberately absent: the TP collectives' custom VJPs
    (parallel/collectives.py) already hand every tensor rank the FULL
    parameter cotangent (tensor-replicated, each loss term counted once), so
    the loss is replicated across tensor ranks and this psum over
    (data, graph) is exact unchanged for any tensor degree. Adding the tensor
    axis here would T-fold double-count gradients."""
    axes = tuple(a for a in (data_axis_name, axis_name) if a is not None)
    return axes if axes else None


def make_loss_fn(model, mmd_weight: float, mmd_sigma: float, mmd_samples: int,
                 axis_name: Optional[str] = None,
                 data_axis_name: Optional[str] = None) -> Callable:
    """loss(params, batch, key) -> (local_loss_for_grad, logged_global_mse).

    The grad path carries only THIS partition's weighted share; the train step
    psums the resulting parameter gradients across the mesh (DDP-sum pattern —
    differentiating the psum'd global loss instead would scale gradients by
    the axis size, since psum's transpose is psum). logged_global_mse is the
    node-weighted global MSE the reference logs (total_loss_loc).

    With a 2-D (data x graph) mesh the node-count weighting spans BOTH axes:
    every device holds a partition of some graph of the global batch, and the
    global loss is the node-weighted sum over all of them — the natural
    generalization of reference utils/train.py:100-110, where the data axis is
    degenerate (every rank sees the same graphs)."""
    axes = _reduce_axes(axis_name, data_axis_name)

    def loss_fn(params, batch: GraphBatch, key):
        loc_pred, virtual_loc = model.apply(params, batch)
        mse_local = masked_mse(loc_pred, batch.target, batch.node_mask)
        loss = weighted_local_loss(mse_local, batch.node_mask, axes)
        logged = _psum(loss, axes)
        if mmd_weight:
            for a in axes or ():
                # independent sample draw per device (each rank samples its
                # own local nodes, reference utils/train.py:124-139)
                key = jax.random.fold_in(key, jax.lax.axis_index(a))
            lm = mmd_loss(virtual_loc, batch.target, batch.node_mask, key, mmd_sigma, mmd_samples)
            loss = loss + mmd_weight * weighted_local_loss(lm, batch.node_mask, axes)
        return loss, logged

    return loss_fn


@obs.spanned("train/make_step")
def make_train_step(model, tx: optax.GradientTransformation, mmd_weight: float,
                    mmd_sigma: float, mmd_samples: int,
                    axis_name: Optional[str] = None,
                    data_axis_name: Optional[str] = None) -> Callable:
    """Returns step(state, batch, key) -> (state, metrics). Jit/shard_map it."""
    loss_fn = make_loss_fn(model, mmd_weight, mmd_sigma, mmd_samples,
                           axis_name, data_axis_name)
    axes = _reduce_axes(axis_name, data_axis_name)

    def step(state: TrainState, batch: GraphBatch, key):
        (loss, logged), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params, batch, key)
        if axes is not None:
            # DDP-style gradient sum over the WHOLE mesh: each device holds
            # the gradient of ITS shard's loss share (incl. cross-device terms
            # routed through the model's virtual-node psums); summing yields
            # the exact global gradient, identically on every device — weights
            # stay replicated.
            with jax.named_scope("grad_reduce"):
                grads = jax.lax.psum(grads, axes)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(params=params, opt_state=opt_state, step=state.step + 1)
        metrics = {"loss": logged, "loss_with_mmd": _psum(loss, axes)}
        if axis_name is not None:
            # In-step cross-rank data-consistency check (reference
            # utils/train.py:55-61 all_gathers loc_mean and asserts it EVERY
            # step): every partition of a graph carries the graph's GLOBAL
            # loc_mean, so across the graph axis the values must be bitwise
            # identical. max|m - pmin(m)| pmax'd over the axis is exactly 0
            # iff all ranks fed the same logical batch. Traced into the step:
            # one [B,3] collective — free next to the per-layer psums; the
            # trainer asserts the scalar host-side once per eval interval.
            # pmin spans the graph axis only (the data axis holds DIFFERENT
            # graphs); the final pmax spans the whole mesh so every process
            # sees a nonzero residual even when the drift is on another
            # host's data row.
            m = batch.loc_mean
            resid = jnp.max(jnp.abs(m - jax.lax.pmin(m, axis_name)))
            metrics["batch_consistency"] = jax.lax.pmax(resid, axes)
        return new_state, metrics

    return step


def make_eval_step(model, axis_name: Optional[str] = None,
                   data_axis_name: Optional[str] = None) -> Callable:
    """Returns eval(params, batch) -> node-weighted global MSE (no MMD —
    reference eval epochs compute only total_loss_loc)."""
    axes = _reduce_axes(axis_name, data_axis_name)

    def eval_step(params, batch: GraphBatch):
        loc_pred, _ = model.apply(params, batch)
        mse_local = masked_mse(loc_pred, batch.target, batch.node_mask)
        return weighted_global_loss(mse_local, batch.node_mask, axes)

    return eval_step
