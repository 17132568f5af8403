"""Differentiable cross-partition reductions — the TPU-native replacement for
the reference's custom NCCL autograd collective.

The reference hand-writes a differentiable all_reduce (``_AllReduce``,
reference models/FastEGNN.py:10-43: forward = all_reduce(SUM), backward =
all_reduce(grad)) and composes it into ``weighted_average_reduce`` (reference
models/FastEGNN.py:310-319: data*=w; allreduce(data); allreduce(w); data/=w)
to turn per-partition means into exact global means.

In JAX none of that machinery is needed: ``jax.lax.psum`` inside ``shard_map``
is differentiable by construction (its reverse-mode rule IS the
backward-allreduce the reference implements by hand), runs over ICI as an XLA
collective, and fuses into the surrounding jitted step. Per-graph node counts
come from mask sums as traced ops — replacing the reference's per-step Python
``.item()`` loops (models/FastEGNN.py:196,226,260), its known hot-loop wart.

Every function takes ``axis_name=None`` meaning "not distributed" so the same
model code runs single-chip and on a mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from distegnn_tpu.ops.segment import masked_sum


def _psum(x, axis_name):
    return jax.lax.psum(x, axis_name) if axis_name is not None else x


def pweighted_mean(data: jnp.ndarray, weight: jnp.ndarray, axis_name: Optional[str] = None):
    """Exact global weighted mean across mesh partitions.

    Parity with reference weighted_average_reduce (models/FastEGNN.py:310-319):
    multiply by weight, SUM-reduce data and weight across the axis, divide.
    ``weight`` broadcasts against ``data`` (e.g. [B,1,1] node counts vs [B,3,C]).
    """
    num = _psum(data * weight, axis_name)
    den = _psum(weight, axis_name)
    return num / jnp.maximum(den, 1e-30)


def global_node_sum(data: jnp.ndarray, mask: jnp.ndarray, axis_name: Optional[str] = None):
    """Masked sum over the node axis (axis=1 of [B, N, ...]), then summed across
    mesh partitions. Returns ([B, ...] sum, [B] count)."""
    s = _psum(masked_sum(data, mask, axis=1), axis_name)
    c = _psum(jnp.sum(mask.astype(data.dtype), axis=1), axis_name)
    return s, c


# ---------------------------------------------------------------------------
# Tensor-parallel collectives (hidden-dim sharding over TENSOR_AXIS).
#
# The EGCL MLPs are Megatron-split: the first dense is column-parallel (each
# chip computes a contiguous 1/T slice of the hidden dim), the second is
# row-parallel (each chip contracts its slice, then one psum restores the full
# output). Params stay FULL and replicated on every chip — slicing happens at
# compute time inside the model (see models/common.py) — so checkpoints,
# optimizer state, and the DDP gradient psum over (data, graph) are untouched.
#
# That replication makes the naive autodiff of psum/all_gather wrong: the loss
# is computed once per tensor rank, so transposed collectives double-count
# gradients by T. These custom VJPs implement the "loss counted once" rules
# (each is the transpose of its partner):
#
#   tp_copy    fwd identity          bwd psum      (entering a sharded region)
#   tp_reduce  fwd psum              bwd identity  (row-parallel contraction)
#   tp_gather  fwd all_gather(tiled) bwd slice     (column-parallel collection)
#   tp_slice   fwd slice             bwd all_gather(tiled)
#
# With these, every param gradient comes out tensor-replicated, so the train
# step's gradient psum over (data, graph) needs no change for T>1.
# ---------------------------------------------------------------------------


def _tp_slice_bounds(full_dim: int, axis_name: str):
    """(per-rank width, this rank's start offset) for a contiguous 1/T slice."""
    t = jax.lax.psum(1, axis_name)
    if full_dim % t != 0:
        raise ValueError(f"hidden dim {full_dim} not divisible by tensor size {t}")
    width = full_dim // t
    return width, jax.lax.axis_index(axis_name) * width


def tp_copy(x, axis_name: Optional[str] = None):
    """Identity forward; psums the cotangent over the tensor axis.

    Use where a tensor-replicated activation enters a sharded computation: the
    forward value is the same on every rank, but each rank contributes an
    independent gradient that must be summed.
    """
    if axis_name is None:
        return x

    @jax.custom_vjp
    def _copy(v):
        return v

    _copy.defvjp(lambda v: (v, None), lambda _, g: (jax.lax.psum(g, axis_name),))
    return _copy(x)


def tp_reduce(x, axis_name: Optional[str] = None):
    """psum forward (row-parallel contraction back to model dim); identity bwd."""
    if axis_name is None:
        return x

    @jax.custom_vjp
    def _reduce(v):
        return jax.lax.psum(v, axis_name)

    _reduce.defvjp(lambda v: (jax.lax.psum(v, axis_name), None), lambda _, g: (g,))
    return _reduce(x)


def tp_gather(x, axis_name: Optional[str] = None):
    """all_gather slices along the last dim forward; slice the cotangent bwd."""
    if axis_name is None:
        return x

    @jax.custom_vjp
    def _gather(v):
        return jax.lax.all_gather(v, axis_name, axis=v.ndim - 1, tiled=True)

    def _fwd(v):
        return _gather(v), v.shape[-1]

    def _bwd(width, g):
        start = jax.lax.axis_index(axis_name) * width
        return (jax.lax.dynamic_slice_in_dim(g, start, width, axis=g.ndim - 1),)

    _gather.defvjp(_fwd, _bwd)
    return _gather(x)


def tp_slice(x, axis_name: Optional[str] = None):
    """This rank's contiguous 1/T slice of the last dim fwd; all_gather bwd.

    Used to carve a rank-local column block out of a FULL replicated param at
    compute time (the param tree itself stays mesh-shape independent).
    """
    if axis_name is None:
        return x
    width, start = _tp_slice_bounds(x.shape[-1], axis_name)

    @jax.custom_vjp
    def _slice(v):
        return jax.lax.dynamic_slice_in_dim(v, start, width, axis=v.ndim - 1)

    def _bwd(_, g):
        return (jax.lax.all_gather(g, axis_name, axis=g.ndim - 1, tiled=True),)

    _slice.defvjp(lambda v: (_slice(v), None), _bwd)
    return _slice(x)


def tp_slice_rows(x, axis_name: Optional[str] = None):
    """Row-block analogue of tp_slice: 1/T slice of axis 0 (row-parallel W2)."""
    if axis_name is None:
        return x
    width, start = _tp_slice_bounds(x.shape[0], axis_name)

    @jax.custom_vjp
    def _slice(v):
        return jax.lax.dynamic_slice_in_dim(v, start, width, axis=0)

    def _bwd(_, g):
        return (jax.lax.all_gather(g, axis_name, axis=0, tiled=True),)

    _slice.defvjp(lambda v: (_slice(v), None), _bwd)
    return _slice(x)


def global_node_mean(data: jnp.ndarray, mask: jnp.ndarray, axis_name: Optional[str] = None):
    """Exact GLOBAL mean over real nodes of each graph, across all partitions.

    Single device: equals the reference's global_mean_pool. Distributed: equals
    global_mean_pool followed by weighted_average_reduce with per-partition
    node counts (reference models/FastEGNN.py:258-261) — computed here in one
    fused step: psum(masked node sum) / psum(node count).
    """
    s, c = global_node_sum(data, mask, axis_name)
    c = jnp.maximum(c, 1.0).reshape(c.shape + (1,) * (s.ndim - c.ndim))
    return s / c
