"""One-pass prefix sum along the leading axis (TPU Pallas, XLA fallback).

The cumsum segment lowering (ops/segment.py) stands or falls with the cost
of the prefix sum itself: XLA lowers a length-E cumsum into O(log E) shifted
adds — ~21 full-array passes at LargeFluid scale (E=1.6M), which can burn
more HBM traffic than the scatter it replaces. A sequential Pallas kernel
does it in ONE pass: the TPU grid executes in order, so a [1, F] VMEM
scratch carries the running total from tile to tile (read data once, write
prefix once). This is the *right* shape of Pallas kernel for this chip —
long streaming reduction — unlike the tiny-dot one-hot kernels that
hardware measurement refuted (docs/PERFORMANCE.md).

`prefix_sum(x)` always returns float32 prefix sums (accumulation precision —
see the segment lowering's accuracy note). `impl='auto'` (default) picks the
Pallas kernel on TPU for long axes and XLA elsewhere; the env var
``DISTEGNN_PREFIX_IMPL=xla|pallas`` overrides it for A/B measurement
(scripts/microbench_segsum.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distegnn_tpu import runtime

_TILE = 4096          # rows per grid step: [4096, 64] f32 = 1 MiB VMEM block
_MIN_PALLAS_ROWS = 32768  # below this the dispatch isn't worth it


def _prefix_kernel(x_ref, out_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    # In-tile inclusive scan by log-step doubling: the Pallas TPU lowering
    # has no cumsum primitive (hardware-discovered 2026-08-02: "Unimplemented
    # primitive ... KernelType.TC: cumsum"), so build it from roll + masked
    # add — log2(tile) VPU passes over a VMEM-resident block, preserving the
    # kernel's one-HBM-pass contract.
    x = x_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 1
    while k < x.shape[0]:
        shifted = pltpu.roll(x, k, axis=0)
        x = x + jnp.where(rows >= k, shifted, 0.0)
        k *= 2
    c = x + carry_ref[...]
    out_ref[...] = c
    carry_ref[...] = c[-1:]


def _suffix_kernel(x_ref, out_ref, carry_ref):
    # mirror of _prefix_kernel running the grid REVERSED (index_map maps
    # step i to tile n_tiles-1-i): in-tile suffix by doubling with upward
    # rolls; the carry flows from the last tile backwards. One HBM
    # read/write per element — no flip passes.
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    T = x.shape[0]
    k = 1
    while k < T:
        # upward roll by k == pltpu.roll by T-k (pltpu.roll rejects negative
        # shifts); the rows < T-k mask zeroes the wrapped-around rows either way
        shifted = pltpu.roll(x, T - k, axis=0)
        x = x + jnp.where(rows < T - k, shifted, 0.0)
        k *= 2
    c = x + carry_ref[...]
    out_ref[...] = c
    carry_ref[...] = c[:1]


@functools.partial(jax.jit, static_argnames=("tile", "reverse"))
def _prefix_pallas(x, tile: int = _TILE, reverse: bool = False):
    """Inclusive prefix sum along axis 0; ``reverse=True`` gives the inclusive
    SUFFIX sum (out[i] = sum_{j>=i} x[j]) in the same single pass."""
    E, F = x.shape
    n_tiles = -(-E // tile)
    pad = n_tiles * tile - E
    if pad:
        # zero padding is neutral for both directions (suffix pads at the
        # tail, which contributes 0 to every real row's suffix)
        x = jnp.concatenate([x, jnp.zeros((pad, F), x.dtype)], axis=0)
    if reverse:
        kernel, index_map = _suffix_kernel, lambda i: (n_tiles - 1 - i, 0)
    else:
        kernel, index_map = _prefix_kernel, lambda i: (i, 0)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile, F), index_map,
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tile, F), index_map,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, F), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, F), jnp.float32)],
        interpret=runtime.use_interpret(),
    )(x)
    return out[:E] if pad else out


@jax.custom_vjp
def _prefix_pallas_diff(x):
    return _prefix_pallas(x)


def _prefix_pallas_fwd(x):
    # residual: zero-size token carrying the primal dtype (a bare np.dtype is
    # not a JAX type, and the cotangent must match the primal's dtype)
    return _prefix_pallas(x), jnp.zeros((0,), x.dtype)


def _prefix_pallas_bwd(token, g):
    # out_i = sum_{j<=i} x_j  =>  d/dx_j = sum_{i>=j} g_i: the cotangent is
    # the SUFFIX sum of g — the same one-pass kernel with a reversed grid
    # (no flip passes; each flip would be a full extra HBM read+write at
    # [1.6M, 64] scale). The pallas_call itself has no JVP rule (hardware
    # run 2026-08-02: AssertionError in _pallas_call_jvp_rule), so these
    # custom rules are what make ``prefix_sum`` differentiable at all on the
    # pallas path. prefix and suffix are each other's VJPs, so the mutual
    # recursion supports arbitrary differentiation order.
    return (_suffix_pallas_diff(g).astype(token.dtype),)


@jax.custom_vjp
def _suffix_pallas_diff(x):
    return _prefix_pallas(x, reverse=True)


def _suffix_pallas_fwd(x):
    return _prefix_pallas(x, reverse=True), jnp.zeros((0,), x.dtype)


def _suffix_pallas_bwd(token, g):
    return (_prefix_pallas_diff(g).astype(token.dtype),)


_prefix_pallas_diff.defvjp(_prefix_pallas_fwd, _prefix_pallas_bwd)
_suffix_pallas_diff.defvjp(_suffix_pallas_fwd, _suffix_pallas_bwd)


def prefix_sum(x: jnp.ndarray, impl: str = "auto") -> jnp.ndarray:
    """float32 cumulative sum of ``x`` [E, F] along axis 0."""
    impl = os.environ.get("DISTEGNN_PREFIX_IMPL", impl)
    if impl == "auto":
        impl = ("pallas" if jax.default_backend() == "tpu"
                and x.shape[0] >= _MIN_PALLAS_ROWS else "xla")
    if impl == "pallas":
        return _prefix_pallas_diff(x)
    if impl == "xla":
        return jnp.cumsum(x.astype(jnp.float32), axis=0)
    raise ValueError(f"unknown prefix_sum impl {impl!r}")
