"""Shared building blocks for all model families.

Initializer parity notes (vs torch defaults used throughout the reference):
  - torch nn.Linear default: kaiming_uniform(a=sqrt(5)) == U(+-1/sqrt(fan_in));
    we match its variance with variance_scaling(1/3, fan_in, uniform).
  - coordinate heads: xavier_uniform with gain=0.001, no bias (reference
    models/FastEGNN.py:96-107) — variance_scaling(1e-6, fan_avg, uniform).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax.numpy as jnp
from flax import linen as nn

from distegnn_tpu.parallel.collectives import (
    tp_copy, tp_gather, tp_reduce, tp_slice, tp_slice_rows,
)

# torch nn.Linear default weight init (same variance): U(+-1/sqrt(fan_in))
torch_linear_init = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")
# xavier_uniform(gain=0.001): bound = gain*sqrt(6/(fan_in+fan_out)) -> scale = gain^2
coord_head_init = nn.initializers.variance_scaling(1e-6, "fan_avg", "uniform")


def _torch_bias_init(fan_in: int):
    """torch nn.Linear default bias init: U(+-1/sqrt(fan_in))."""
    bound = 1.0 / (fan_in ** 0.5)
    def init(key, shape, dtype=jnp.float32):
        import jax
        return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)
    return init


class TorchDense(nn.Module):
    """Dense with full torch nn.Linear default init parity (weight AND bias).

    ``dtype`` is the COMPUTE dtype (params stay float32): set jnp.bfloat16 to
    run the matmul on the MXU's native precision — TPU bf16 matmul throughput
    is ~2x fp32 (pallas_guide: MXU natively consumes bf16)."""

    features: int
    use_bias: bool = True
    kernel_init: Optional[Callable] = None
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        fan_in = x.shape[-1]
        return nn.Dense(
            self.features,
            use_bias=self.use_bias,
            kernel_init=self.kernel_init or torch_linear_init,
            bias_init=_torch_bias_init(fan_in),
            dtype=self.dtype,
        )(x)


class _DenseParams(nn.Module):
    """Shadow of nn.Dense's param subtree: declares the identical
    kernel/bias (same names, shapes, f32 param dtype, init functions) WITHOUT
    applying the matmul, and returns the full arrays. Instantiated with
    ``name='Dense_0'`` inside a ``name='TorchDense_i'`` shadow so the param
    path — and therefore flax's path-folded init RNG stream — is bitwise
    identical to the TorchDense it stands in for. This is how the
    tensor-parallel compute branches consume FULL replicated params (sliced at
    compute time via collectives.tp_slice*) while keeping the param tree
    invariant in the mesh shape, so checkpoints cross mesh layouts freely."""

    features: int
    use_bias: bool = True
    kernel_init: Optional[Callable] = None

    @nn.compact
    def __call__(self, fan_in):
        k = self.param("kernel", self.kernel_init or torch_linear_init,
                       (fan_in, self.features), jnp.float32)
        b = (self.param("bias", _torch_bias_init(fan_in), (self.features,), jnp.float32)
             if self.use_bias else None)
        return k, b


class _TorchDenseParams(nn.Module):
    """Shadow of TorchDense's param subtree (see :class:`_DenseParams`)."""

    features: int
    use_bias: bool = True
    kernel_init: Optional[Callable] = None

    @nn.compact
    def __call__(self, fan_in):
        return _DenseParams(self.features, use_bias=self.use_bias,
                            kernel_init=self.kernel_init, name="Dense_0")(fan_in)


class MLP(nn.Module):
    """Plain MLP: Dense(+act) stack; optionally activation after the last layer.

    ``tensor_axis`` enables Megatron-style tensor parallelism over the hidden
    dim (2-layer MLPs only): the first Dense is column-parallel (each tensor
    rank computes a contiguous 1/T hidden slice — exact, just fewer columns),
    the activation runs on the slice, and the second Dense is row-parallel.
    ``tensor_out='reduce'`` closes with ONE psum back to the full output
    (the per-MLP layer-boundary collective); ``tensor_out='partial'`` returns
    the rank-local partial sum so a linear consumer (phi_x's coordinate
    aggregation) can defer the psum to the node axis. Params stay full and
    replicated — the tree is identical to tensor_axis=None."""

    sizes: Sequence[int]
    act: Callable = nn.silu
    act_last: bool = False
    use_bias_last: bool = True
    kernel_init_last: Optional[Callable] = None
    dtype: Optional[Any] = None
    tensor_axis: Optional[str] = None
    tensor_out: str = "reduce"

    @nn.compact
    def __call__(self, x):
        n = len(self.sizes)
        if self.tensor_axis is not None:
            return self._tp_call(x)
        for i, size in enumerate(self.sizes):
            last = i == n - 1
            x = TorchDense(
                size,
                use_bias=self.use_bias_last if last else True,
                kernel_init=(self.kernel_init_last or torch_linear_init) if last else torch_linear_init,
                dtype=self.dtype,
            )(x)
            if not last or self.act_last:
                x = self.act(x)
        return x

    def _tp_call(self, x):
        ax = self.tensor_axis
        if len(self.sizes) != 2:
            raise ValueError(
                f"tensor-parallel MLP supports exactly 2 dense layers, got "
                f"sizes={list(self.sizes)}")
        if self.tensor_out not in ("reduce", "partial"):
            raise ValueError(f"unknown tensor_out {self.tensor_out!r}")
        if self.tensor_out == "partial" and self.use_bias_last:
            raise ValueError(
                "tensor_out='partial' requires use_bias_last=False (a bias "
                "on a partial sum would be counted T times)")
        fan0 = x.shape[-1]
        k0, b0 = _TorchDenseParams(self.sizes[0], name="TorchDense_0")(fan0)
        k1, b1 = _TorchDenseParams(
            self.sizes[1], use_bias=self.use_bias_last,
            kernel_init=self.kernel_init_last, name="TorchDense_1")(self.sizes[0])
        c = (lambda a: a.astype(self.dtype)) if self.dtype is not None else (lambda a: a)
        # column-parallel first Dense: exact 1/T column slice of the full
        # kernel; activation is elementwise so the slice stays exact
        h = self.act(tp_copy(c(x), ax) @ tp_slice(c(k0), ax) + tp_slice(c(b0), ax))
        # row-parallel second Dense: rank-local partial contraction
        y = h @ tp_slice_rows(c(k1), ax)
        if self.tensor_out == "partial":
            return y
        y = tp_reduce(y, ax)                 # the one psum at the MLP boundary
        if b1 is not None:
            y = y + c(b1)
        if self.act_last:
            y = self.act(y)
        return y


class CoordMLP(nn.Module):
    """Dense(H) -> act -> Dense(1, no bias, xavier gain 1e-3) [-> tanh].

    The scalar head that turns an invariant message into a displacement
    magnitude (reference get_coord_mlp, models/FastEGNN.py:96-107)."""

    hidden_nf: int
    act: Callable = nn.silu
    tanh: bool = False
    dtype: Optional[Any] = None
    # tensor-parallel hidden dim: the head returns a rank-local PARTIAL
    # scalar (row-parallel second Dense, psum deferred); the caller multiplies
    # it into coord_diff, segment-sums to the node axis, and closes with one
    # tp_reduce there — all linear ops, so deferring the psum is exact.
    # Incompatible with tanh (nonlinear in the partial sum).
    tensor_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        if self.tensor_axis is not None and self.tanh:
            raise ValueError(
                "CoordMLP: tanh=True cannot be tensor-parallel (the psum is "
                "deferred through linear ops only) — use tanh=False or T=1")
        x = MLP(
            [self.hidden_nf, 1],
            act=self.act,
            use_bias_last=False,
            kernel_init_last=coord_head_init,
            dtype=self.dtype,
            tensor_axis=self.tensor_axis,
            tensor_out="partial",
        )(x)
        # the scalar head feeds geometry (coord_diff multiplies it): return f32
        x = x.astype(jnp.float32)
        if self.tanh:
            x = jnp.tanh(x)
        return x


class HoistedEdgeMLP(nn.Module):
    """phi_e with its first Dense algebraically hoisted to the node axis, and
    the edge geometry riding the same gathers.

    The edge-message MLP's first layer is linear, and gathering commutes with
    a linear map, so

        concat([h_row, h_col, s]) @ W
            == gather_row(h @ W[:H]) + gather_col(h @ W[H:2H]) + s @ W[2H:]

    which (a) never materializes the [E, 2H+S] concat and (b) runs the big
    matmul over N rows instead of E (E/N = mean degree, ~15 at LargeFluid
    scale) — exactly the same math as MLP([H, H], act_last=True) on the
    concat, in a cheaper order.
    Parameters: one fused (2H+S, H) kernel + bias with torch nn.Linear
    defaults at the FULL fan-in, so init parity matches the fused Dense.

    The layer's other use of the same two index sets is ``coord_diff =
    x[row] - x[col]``, whose ``radial`` is phi_e's first scalar. A gather on
    the chip costs per row, not per byte, so the products and the coordinates
    share ONE gather per edge end (``EdgeOps.gather_sum_diff`` over
    ``[h @ wr | x]`` and ``[h @ wc | -x]``) and, from autodiff, one
    scatter-add per edge end in the backward. The pack is float32 because
    ``x`` is geometry and must not be rounded: bf16 products widen exactly,
    the pre-activation sum is rounded to the compute dtype once, and the
    products' cotangents accumulate in f32 where a bf16 scatter accumulated
    in bf16. The param tree is what it was before the pack.

    ``ops`` is the EdgeOps dispatch (any lowering; a blocked batch keeps
    separate gathers). Returns ``(edge_feat, coord_diff, radial)``:
    ``coord_diff`` un-normalized, ``radial`` its squared length [B, E, 1].
    """

    hidden_nf: int
    scalar_nf: int           # per-edge scalar features: radial (+ edge_attr)
    act: Callable = nn.silu
    dtype: Optional[Any] = None
    # tensor-parallel hidden dim: only the two hoisted NODE-axis matmuls
    # (h @ wr, h @ wc — the dominant cost) are column-sliced; ONE node-level
    # all-gather per product restores the full hidden dim before the pack and
    # the cheap per-edge work, so everything per-edge (and the second Dense)
    # stays replicated. Column slicing + tiled gather is bitwise-exact.
    tensor_axis: Optional[str] = None

    @nn.compact
    def __call__(self, h, x, edge_attr, ops):
        H = self.hidden_nf
        fan_in = 2 * H + self.scalar_nf
        w = self.param("kernel", torch_linear_init, (fan_in, H), jnp.float32)
        b = self.param("bias", _torch_bias_init(fan_in), (H,), jnp.float32)
        c = (lambda a: a.astype(self.dtype)) if self.dtype is not None else (lambda a: a)
        h, w, b = c(h), c(w), c(b)
        if self.tensor_axis is not None:
            ax = self.tensor_axis
            hin = tp_copy(h, ax)
            pr = tp_gather(hin @ tp_slice(w[:H], ax), ax)
            pc = tp_gather(hin @ tp_slice(w[H:2 * H], ax), ax)
        else:
            pr, pc = h @ w[:H], h @ w[H:2 * H]
        pre, coord_diff = ops.gather_sum_diff(pr, pc, x)
        radial = jnp.sum(coord_diff**2, axis=-1, keepdims=True)     # [B, E, 1]
        scalars = (radial if edge_attr is None
                   else jnp.concatenate([radial, edge_attr], axis=-1))
        y = self.act(pre + c(scalars) @ w[2 * H:] + b)
        return self.act(TorchDense(H, dtype=self.dtype)(y)), coord_diff, radial


class HoistedGate(nn.Module):
    """Single Dense over concat([scalars, h_row, h_col]) hoisted to the node
    axis (same algebra as :class:`HoistedEdgeMLP`, scalars-first concat order,
    no activation) — FastSchNet's coordinate gate. Init parity: fused kernel
    + bias with torch nn.Linear defaults at the full fan-in."""

    features: int
    scalar_nf: int
    hidden_nf: int
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, h, scalars, ops):
        S, H = self.scalar_nf, self.hidden_nf
        fan_in = S + 2 * H
        w = self.param("kernel", torch_linear_init, (fan_in, self.features), jnp.float32)
        b = self.param("bias", _torch_bias_init(fan_in), (self.features,), jnp.float32)
        if self.dtype is not None:
            h, scalars, w, b = (a.astype(self.dtype) for a in (h, scalars, w, b))
        ws, wr, wc = w[:S], w[S:S + H], w[S + H:]
        return ops.gather_rows(h @ wr) + ops.gather_cols(h @ wc) + scalars @ ws + b


def resolve_dtype(d):
    """Normalize a compute-dtype spec (None | 'bf16' | 'bfloat16' | dtype) to
    a jnp dtype or None (= float32 compute)."""
    if d is None or d in ("none", "None", "f32", "float32"):
        return None
    if d in ("bf16", "bfloat16") or d is jnp.bfloat16:
        return jnp.bfloat16
    return jnp.dtype(d)


def gather_nodes(data: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Batched node gather: data [B, N, F], idx [B, E] -> [B, E, F].

    One XLA gather per call — the TPU form of the reference's ``coord[row]``
    advanced indexing on flat arrays."""
    return jnp.take_along_axis(data, idx[..., None], axis=1)
