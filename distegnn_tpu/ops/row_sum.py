"""Sorted segment sum as ONE Pallas kernel: one-hot MXU tiles walked over a
row-sorted edge list (TPU; interpreted on the CPU, for the tests).

XLA's scatter-add with ``indices_are_sorted`` sums a row-sorted ``[1.64 M,
68]`` f32 stream into ``[113,144, 68]`` in 13.7 ms on a v5e, at about 10 ns a
row whatever the width: 4% of the HBM roofline (PERF.md section 6, PR 33).
Here the rows ride the MXU instead. Each graph's edge axis is cut into tiles
of ``TILE`` rows and the batch's flattened node axis (``b N + r``) into
blocks of ``BLOCK`` rows; because the ids ``b N + row`` ascend over the
batch (each graph's rows ascend, padding rows sit at slot N-1), a tile
touches only the blocks from its first id's to its last id's, and a grid
step (a VISIT) does

    out[:, block] += data[:, tile] @ onehot[BLOCK, TILE]^T

with the one-hot built in VMEM from an iota compare (as ``blocked.py``'s
``_seg_sum_kernel`` builds it), so the incidence never touches HBM. The
visits are listed on the device, sorted by block, from the tiles' first and
last ids (a strided slice, a max over each tile and a cumsum over ``E /
TILE`` entries), and go in as scalar prefetch: the out block stays resident
across a block's consecutive visits and is written once (the pattern of
megablox's ``make_group_metadata``). Every block is visited at least once, so
a block no id falls in is written as zeros. The list has a static length,
``n_tiles + n_blocks``; visits past the real ones repeat the last block and
add nothing.

The kernel reads a tile as ``[F, TILE]``, the edge axis minor: the layout
the TPU compiler gives these ``[B, E, F]`` arrays itself, so the operand
needs no relayout, and 67 or 68 columns pad to 72 sublanes where rows of 68
lanes would pad to 128 (PERF.md section 6, PR 36). Its out blocks are
``[F, BLOCK]`` for the same reason: ``[BLOCK, F]`` ones made XLA add
relayout copies around the call that grew the step's temporaries by 0.2 GiB
at the one-chip LargeFluid shape and 0.6 at a four-chip partition's
(compiled for a described v5e). An f32 operand is split
into three bf16 terms (``blocked._bf16_terms``) that sum to it, stacked, and
contracted in ONE single-pass matmul with an f32 accumulator: the one-hot
factor is 0/1 and exact in bf16, so no f32 operand is rounded to bf16 (bf16
data is one term). The sum's ORDER differs from the scatter's, so f32
results agree to a few ulp of a segment's sum of magnitudes, not bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distegnn_tpu import runtime
from distegnn_tpu.ops.blocked import _bf16_terms

# chosen by scripts/microbench_segsum.py on the chip (PERF.md section 6, PR 36)
TILE = 2048    # edge rows a visit: the one-hot's contraction dimension
BLOCK = 128    # node rows an out block: the one-hot's row dimension


def visits(first, last, num_segments: int, block: int = BLOCK):
    """``(tiles, blocks, valid)``, int32 ``[n_tiles + n_blocks]``: the grid's
    (edge tile, node block) pairs sorted by block, from each tile's first and
    last id (``[n_tiles]``, ascending from tile to tile). Tile ``t`` takes the
    blocks from one past the previous tile's last (or its first block again,
    when the two share it) through its own last; the last tile also takes
    every block after its last. So each block is visited, its visits are
    consecutive, and a block no id falls in gets one visit from a tile that
    adds nothing to it. ``valid`` is 0 on the padding visits past the real
    ones. Indices are clamped into range, so ids outside ``[0,
    num_segments)`` are dropped and no visit leaves the arrays."""
    n_tiles = first.shape[0]
    n_blocks = pl.cdiv(num_segments, block)
    first = jnp.clip(first // block, 0, n_blocks - 1)
    last = jnp.clip(last // block, 0, n_blocks - 1)
    prev = jnp.concatenate([jnp.full((1,), -1, last.dtype), last[:-1]])
    lo = jnp.where(first == prev, first, prev + 1)
    hi = last.at[-1].set(n_blocks - 1)
    count = jnp.maximum(hi - lo + 1, 1)
    ends = jnp.cumsum(count)
    v = jnp.arange(n_tiles + n_blocks, dtype=jnp.int32)
    tiles = jnp.minimum(jnp.searchsorted(ends, v, side="right"), n_tiles - 1)
    blocks = lo[tiles] + v - (ends[tiles] - count[tiles])
    valid = v < ends[-1]
    tiles = jnp.where(valid, tiles, n_tiles - 1).astype(jnp.int32)
    blocks = jnp.clip(jnp.where(valid, blocks, n_blocks - 1), 0,
                      n_blocks - 1).astype(jnp.int32)
    return tiles, blocks, valid.astype(jnp.int32)


def _kernel(tiles_ref, blocks_ref, valid_ref, ids_ref, data_ref, out_ref, *,
            edges: int, tiles_per_graph: int, tile: int, block: int):
    v = pl.program_id(0)
    b = blocks_ref[v]

    @pl.when((v == 0) | (b != blocks_ref[jnp.maximum(v - 1, 0)]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(valid_ref[v] != 0)
    def _():
        local = ids_ref[...] - b * block                            # [1, T]
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (block, tile), 0)
                  == local).astype(jnp.bfloat16)
        x = data_ref[...]                                           # [F, T]
        if edges % tile:
            # a graph's last tile reads past its edges: whatever is there
            # must not reach the MXU (0 x NaN is NaN); its ids are -1
            start = (tiles_ref[v] % tiles_per_graph) * tile
            e = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            x = jnp.where(e < edges - start, x, jnp.zeros_like(x))
        F = x.shape[0]
        terms = _bf16_terms(x)
        # the precision is said, or jax_default_matmul_precision 'highest'
        # (every cell's) asks Mosaic for an f32 pass of bf16 operands
        acc = jax.lax.dot_general(jnp.concatenate(terms, axis=0), onehot,
                                  (((1,), (1,)), ((), ())),
                                  precision=jax.lax.Precision.DEFAULT,
                                  preferred_element_type=jnp.float32)
        total = acc[:F]
        for k in range(1, len(terms)):
            total = total + acc[k * F:(k + 1) * F]
        out_ref[...] += total                                       # [F, R]


@functools.partial(jax.jit, static_argnames=("num_segments", "tile", "block"))
def row_sum(data, rows, num_segments: int, tile: int = TILE, block: int = BLOCK):
    """``out[b, n] = sum_{rows[b, e] == n} data[b, e]``: ``[B, E, F]`` data
    (f32 or bf16) and ``[B, E]`` ids ascending in each graph, so that ``b N +
    rows[b, e]`` ascends over the batch (padding rows at slot N-1 keep it so)
    -> ``[B, N, F]`` float32. Ids outside ``[0, N)`` are dropped."""
    B, E, F = data.shape
    N = num_segments
    tpg = pl.cdiv(E, tile)
    n_blocks = pl.cdiv(B * N, block)
    ids = rows.astype(jnp.int32) + (jnp.arange(B, dtype=jnp.int32) * N)[:, None]
    # one lane-dense [1, T] row a tile; a graph's tail matches no column
    ids_t = jnp.pad(ids, ((0, 0), (0, tpg * tile - E)),
                    constant_values=-1).reshape(B * tpg, 1, tile)
    tiles, blocks, valid = visits(ids_t[:, 0, 0], ids_t.max(axis=(1, 2)), B * N,
                                  block)
    kern = functools.partial(_kernel, edges=E, tiles_per_graph=tpg, tile=tile,
                             block=block)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B * tpg + n_blocks,),
            in_specs=[
                pl.BlockSpec((None, 1, tile), lambda v, t, b, ok: (t[v], 0, 0)),
                pl.BlockSpec((None, F, tile),
                             lambda v, t, b, ok: (t[v] // tpg, 0, t[v] % tpg)),
            ],
            out_specs=pl.BlockSpec((F, block), lambda v, t, b, ok: (0, b[v])),
        ),
        out_shape=jax.ShapeDtypeStruct((F, n_blocks * block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=runtime.use_interpret(),
    )(tiles, blocks, valid, ids_t, data.transpose(0, 2, 1))
    return out[:, :B * N].T.reshape(B, N, F)
