"""Masked segment ops — the TPU replacement for torch_scatter / scatter_add_.

The reference implements message aggregation with CUDA scatter kernels
(reference models/FastEGNN.py:322-337, unsorted_segment_{sum,mean} via
``scatter_add_`` with ``count.clamp(min=1)``). On TPU we use XLA's native
scatter-add (``jnp.zeros(...).at[ids].add(data)``), which lowers to an
efficient sorted-segment reduction, and carry explicit edge/node masks so all
shapes stay static under jit.

All functions are single-graph (leading axis = elements); batch them with
``jax.vmap`` — the model code does exactly that.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def segment_sum(data, segment_ids, num_segments, mask=None, indices_are_sorted=False):
    """Sum ``data`` rows into ``num_segments`` buckets.

    data: [E, ...]; segment_ids: [E] int; mask: optional [E] (0/1 or bool).
    Returns [num_segments, ...]. Masked-out rows contribute nothing (they may
    carry arbitrary ids, e.g. padding pointing at segment 0).

    ``indices_are_sorted=True`` (pad_graphs emits row-sorted edge lists —
    GraphBatch.edges_sorted) lets XLA use its sorted-scatter lowering.
    """
    if mask is not None:
        m = mask.astype(data.dtype).reshape(mask.shape + (1,) * (data.ndim - 1))
        data = data * m
    out_shape = (num_segments,) + data.shape[1:]
    return jnp.zeros(out_shape, dtype=data.dtype).at[segment_ids].add(
        data, indices_are_sorted=indices_are_sorted)


def segment_mean(data, segment_ids, num_segments, mask=None, indices_are_sorted=False):
    """Mean of ``data`` rows per segment; empty segments yield 0.

    Parity: reference clamps counts to >=1 (models/FastEGNN.py:337) — same
    behavior here via ``maximum(count, 1)``.
    """
    total = segment_sum(data, segment_ids, num_segments, mask=mask,
                        indices_are_sorted=indices_are_sorted)
    # counts accumulate in f32 regardless of data dtype: a bf16 accumulator
    # saturates at 256 (ulp 2), silently inflating means of degree>=256 nodes
    if mask is None:
        ones = jnp.ones(data.shape[:1], dtype=jnp.float32)
    else:
        ones = mask.astype(jnp.float32)
    count = jnp.zeros((num_segments,), dtype=jnp.float32).at[segment_ids].add(
        ones, indices_are_sorted=indices_are_sorted)
    count = jnp.maximum(count, 1.0).astype(data.dtype)
    return total / count.reshape((num_segments,) + (1,) * (data.ndim - 1))


# Below this many rows (B x E) the kernel's launch and visit list are not
# worth it: serving's small rungs keep the scatter-add.
_MIN_KERNEL_ROWS = 32768


def _row_sum_kernel_engages(rows: int) -> bool:
    """The Pallas kernel runs on a TPU for ``rows`` >= :data:`_MIN_KERNEL_ROWS`
    (the ``ops/cumsum.py`` idiom); elsewhere, the CPU included, the hinted
    scatter-add. Tests patch this to run the kernel interpreted."""
    return jax.default_backend() == "tpu" and rows >= _MIN_KERNEL_ROWS


def _scatter_row_sum(data, rows_sorted, num_segments, dtype):
    """The hinted scatter-add, graph by graph, into ``dtype`` (what the two
    call sites ran before the kernel)."""
    return jax.vmap(lambda t, r: jnp.zeros(
        (num_segments, t.shape[-1]), dtype).at[r].add(
            t.astype(dtype), indices_are_sorted=True))(data, rows_sorted)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _kernel_row_sum(data, rows_sorted, num_segments, dtype):
    from distegnn_tpu.ops import row_sum

    # the constants are read as this is traced, so that the micro-benchmark's
    # sweep (scripts/microbench_segsum.py) can set them
    return row_sum.row_sum(data, rows_sorted, num_segments, tile=row_sum.TILE,
                           block=row_sum.BLOCK).astype(dtype)


def _krs_fwd(data, rows_sorted, num_segments, dtype):
    token = jnp.zeros(data.shape[:2] + (0,), data.dtype)
    return _kernel_row_sum(data, rows_sorted, num_segments, dtype), (rows_sorted, token)


def _krs_bwd(num_segments, dtype, res, g):
    # the transpose of the scatter-add the kernel stands for: the same
    # per-graph gather of the cotangent autodiff makes of it, unchanged
    rows_sorted, token = res
    data = jax.ShapeDtypeStruct(token.shape[:2] + g.shape[2:], token.dtype)
    (ct,) = jax.linear_transpose(
        lambda d: _scatter_row_sum(d, rows_sorted, num_segments, dtype), data)(g)
    return ct, None


_kernel_row_sum.defvjp(_krs_fwd, _krs_bwd)


def sorted_row_sum(data, rows_sorted, num_segments, dtype=None):
    """BATCHED ``out[b, n] = sum_e data[b, e] [rows_sorted[b, e] == n]``
    (``[B, E, F]``, ``[B, E]`` -> ``[B, N, F]``) for ASCENDING ids
    (GraphBatch.edges_sorted; padding rows at slot N-1), in ``dtype``
    (default: the data's). On a TPU at ``B x E`` >= :data:`_MIN_KERNEL_ROWS`
    one Pallas kernel over the whole batch (``ops/row_sum.py``: ids ``b N +
    r`` ascend over it), accumulating in f32, f32 data contracted exactly;
    its backward is the scatter-add's own transpose, a gather. Elsewhere the
    scatter-add that says its ids are sorted, graph by graph, so the compiler
    neither sorts them nor permutes the rows first. Each traced call that
    takes the kernel adds one to the ``obs`` counter ``edge/row_sum_kernel``."""
    dtype = jnp.dtype(dtype or data.dtype)
    B, E = rows_sorted.shape
    if not _row_sum_kernel_engages(B * E):
        return _scatter_row_sum(data, rows_sorted, num_segments, dtype)
    from distegnn_tpu.ops.blocked import _count_row_sum_kernel

    _count_row_sum_kernel()
    return _kernel_row_sum(data, rows_sorted, num_segments, dtype)


@jax.custom_vjp
def gather_rows_sorted(h, rows_sorted):
    """BATCHED ``h[b, rows_sorted[b]]`` (``[B, N, F]``, ``[B, E]`` ->
    ``[B, E, F]``) for ASCENDING ids (GraphBatch.edges_sorted) whose BACKWARD
    is :func:`sorted_row_sum` by construction, not whatever autodiff makes of
    an unhinted gather (on the TPU: a sort of the ids, a permutation of the
    cotangent's rows and then the sorted scatter-add; PERF.md section 6, PR
    33). The forward is the unhinted ``jnp.take_along_axis`` over the whole
    batch, values bit for bit, and not a ``vmap`` of a per-graph gather: with
    one graph a batch ``take_along_axis`` drops the batch axis from the gather,
    which the TPU compiler then runs 2.6 to 5.7 times faster than the same
    gather with a batching dimension (same section). Padding rows point at
    slot N-1 and their cotangent lands there, as with the plain transpose."""
    return jnp.take_along_axis(h, rows_sorted[..., None], axis=1)


def _grs_fwd(h, rows_sorted):
    return gather_rows_sorted(h, rows_sorted), (rows_sorted, h.shape[1])


def _grs_bwd(res, g):
    rows_sorted, n = res
    return sorted_row_sum(g, rows_sorted, n), None


gather_rows_sorted.defvjp(_grs_fwd, _grs_bwd)


def segment_max(data, segment_ids, num_segments, mask=None, initial=-1e30):
    """Per-segment max; empty segments yield ``initial``. Masked rows are
    replaced by ``initial`` before the scatter so they never win."""
    if mask is not None:
        m = mask.reshape(mask.shape + (1,) * (data.ndim - 1)).astype(bool)
        data = jnp.where(m, data, initial)
    out_shape = (num_segments,) + data.shape[1:]
    return jnp.full(out_shape, initial, dtype=data.dtype).at[segment_ids].max(data)


def segment_softmax(scores, segment_ids, num_segments, mask=None):
    """Numerically-stable softmax over rows sharing a segment id (the TPU
    replacement for DGL's edge_softmax, reference modules.py:542). Masked rows
    get weight 0; segments with no rows produce all-zero weights."""
    if mask is not None:
        # mask BEFORE the exp: a masked row's raw score may exceed its
        # segment's real max, and exp(large) * 0 would be NaN
        m = mask.reshape(mask.shape + (1,) * (scores.ndim - 1)).astype(bool)
        scores = jnp.where(m, scores, -1e30)
    mx = segment_max(scores, segment_ids, num_segments)
    shifted = jnp.maximum(scores - mx[segment_ids], -80.0)
    e = jnp.where(scores > -1e29, jnp.exp(shifted), 0.0)
    denom = segment_sum(e, segment_ids, num_segments)
    return e / jnp.maximum(denom[segment_ids], 1e-30)


# --------------------------------------------------------------------------
# Scatter-free sorted-segment ops (``segment_impl='cumsum'``).
#
# XLA's TPU scatter-add ran far below HBM bandwidth at LargeFluid scale in a
# plug-in-era profile (22-33 ms per [1.6M, 64] aggregation, ~4% of peak; not
# measured on this machine), and both blocked one-hot MXU lowerings measured
# slower end to end there. This
# lowering uses only bandwidth-friendly primitives: for ascending segment ids
# (GraphBatch.edges_sorted), segment sums are exclusive-prefix differences
#
#     out[n] = cumsum(data)[end_n - 1] - cumsum(data)[start_n - 1]
#
# with the CSR bounds found by vectorized binary search. The accumulation runs
# in float32; the difference of two prefixes carries the rounding of the
# shared prefix (~|prefix| * eps), which is noise at bf16 compute precision
# but NOT bit-identical to the scatter path — strict-f32 parity paths should
# keep ``segment_impl='scatter'``.
#
# The custom VJP makes the backward exact and scatter-free: the cotangent of
# a segment sum is a plain row gather, so no transpose-of-scatter appears
# anywhere (the round-1 profile put ~2/3 of the step in those transposes).
# --------------------------------------------------------------------------

def _cs_bounds(segment_ids, num_segments):
    idx = jnp.arange(num_segments, dtype=segment_ids.dtype)
    starts = jnp.searchsorted(segment_ids, idx, side="left")
    ends = jnp.searchsorted(segment_ids, idx, side="right")
    return starts, ends


def _cs_sum_impl(data, segment_ids, num_segments):
    from distegnn_tpu.ops.cumsum import prefix_sum

    E = data.shape[0]
    c = prefix_sum(data.reshape(E, -1)).reshape((E,) + data.shape[1:])
    starts, ends = _cs_bounds(segment_ids, num_segments)
    tail = (1,) * (data.ndim - 1)
    hi = jnp.where((ends > 0).reshape((-1,) + tail),
                   jnp.take(c, jnp.maximum(ends - 1, 0), axis=0), 0.0)
    lo = jnp.where((starts > 0).reshape((-1,) + tail),
                   jnp.take(c, jnp.maximum(starts - 1, 0), axis=0), 0.0)
    return (hi - lo).astype(data.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def sorted_segment_sum_cs(data, segment_ids, num_segments):
    """Segment sum for ASCENDING ``segment_ids`` without any scatter.
    Rows to exclude must be zeroed by the caller (multiply by the mask
    before the call — that also routes the mask's gradient correctly)."""
    return _cs_sum_impl(data, segment_ids, num_segments)


def _cs_sum_fwd(data, segment_ids, num_segments):
    return _cs_sum_impl(data, segment_ids, num_segments), segment_ids


def _cs_sum_bwd(num_segments, segment_ids, g):
    # d out[n] / d data[e] = [segment_ids[e] == n]: the pull-back is a gather
    return jnp.take(g, segment_ids, axis=0), None


sorted_segment_sum_cs.defvjp(_cs_sum_fwd, _cs_sum_bwd)


def segment_sum_cs(data, segment_ids, num_segments, mask=None):
    """Drop-in for :func:`segment_sum` on sorted ids, cumsum lowering."""
    if mask is not None:
        m = mask.astype(data.dtype).reshape(mask.shape + (1,) * (data.ndim - 1))
        data = data * m
    return sorted_segment_sum_cs(data, segment_ids, num_segments)


def _packed_mean(sum_fn, data, segment_ids, num_segments, mask):
    """Segment mean as ONE packed call of ``sum_fn``: the count rides the
    same pass as the data (one extra column), clamp >= 1 (reference
    models/FastEGNN.py:337). Shared by the cumsum and ELL lowerings."""
    E = data.shape[0]
    flat = data.reshape(E, -1)
    if mask is not None:
        m = mask.astype(flat.dtype).reshape(E, 1)
        flat = flat * m
        ones = m
    else:
        ones = jnp.ones((E, 1), flat.dtype)
    packed = sum_fn(jnp.concatenate([flat, ones], axis=1), segment_ids,
                    num_segments)
    total, count = packed[:, :-1], packed[:, -1:]
    count = jnp.maximum(count.astype(jnp.float32), 1.0).astype(data.dtype)
    return (total / count).reshape((num_segments,) + data.shape[1:])


def segment_mean_cs(data, segment_ids, num_segments, mask=None):
    """Drop-in for :func:`segment_mean` on sorted ids, cumsum lowering."""
    return _packed_mean(sorted_segment_sum_cs, data, segment_ids,
                        num_segments, mask)


# --------------------------------------------------------------------------
# ELL lowering (``segment_impl='ell'``): fixed-degree gather + reduce.
#
# For ascending ids, segment n owns the contiguous slot range
# [start_n, end_n); padding every segment to the batch's max in-degree D
# turns the aggregation into D chained row gathers — no scatter, no prefix
# sum, read amplification N*D/E (~2.3x at radius-graph degree spread), and
# EXACT arithmetic (a plain <=D-term sum per node, same accuracy class as
# the scatter path — unlike the cumsum lowering's prefix cancellation).
# D comes from GraphBatch.max_in_degree (static; pad_graphs computes it).
# --------------------------------------------------------------------------

def _ell_sum_impl(data, segment_ids, num_segments, max_in_degree,
                  degree_chunk: int = 8):
    """Chunked over the degree axis: each chunk is ONE [N, K, F] gather +
    masked reduce (K = degree_chunk), bounding both the HLO count (D/K ops
    per aggregation instead of D) and the gathered intermediate (N*K*F)."""
    E = data.shape[0]
    starts, ends = _cs_bounds(segment_ids, num_segments)
    tail = (1,) * (data.ndim - 1)
    out = jnp.zeros((num_segments,) + data.shape[1:], jnp.float32)
    for d0 in range(0, max_in_degree, degree_chunk):
        k = min(degree_chunk, max_in_degree - d0)
        idx = starts[:, None] + jnp.arange(d0, d0 + k)          # [N, K]
        valid = (idx < ends[:, None]).reshape((-1, k) + tail)
        blk = jnp.take(data, jnp.minimum(idx, E - 1).reshape(-1), axis=0)
        blk = blk.reshape((num_segments, k) + data.shape[1:]).astype(jnp.float32)
        out = out + jnp.where(valid, blk, 0.0).sum(axis=1)
    return out.astype(data.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def sorted_segment_sum_ell(data, segment_ids, num_segments, max_in_degree):
    """Segment sum for ASCENDING ids via fixed-degree gathers. Rows to
    exclude must be zeroed by the caller (as with the cumsum lowering);
    ``max_in_degree`` must cover every segment's REAL row count — trailing
    same-id padding rows may overflow it only if their data is zeroed."""
    return _ell_sum_impl(data, segment_ids, num_segments, max_in_degree)


def _ell_sum_fwd(data, segment_ids, num_segments, max_in_degree):
    return _ell_sum_impl(data, segment_ids, num_segments, max_in_degree), segment_ids


def _ell_sum_bwd(num_segments, max_in_degree, segment_ids, g):
    return jnp.take(g, segment_ids, axis=0), None


sorted_segment_sum_ell.defvjp(_ell_sum_fwd, _ell_sum_bwd)


def segment_sum_ell(data, segment_ids, num_segments, max_in_degree, mask=None):
    if mask is not None:
        m = mask.astype(data.dtype).reshape(mask.shape + (1,) * (data.ndim - 1))
        data = data * m
    return sorted_segment_sum_ell(data, segment_ids, num_segments, max_in_degree)


def segment_mean_ell(data, segment_ids, num_segments, max_in_degree, mask=None):
    """Mean via one packed ELL pass (see :func:`_packed_mean`)."""
    return _packed_mean(
        lambda d, i, n: sorted_segment_sum_ell(d, i, n, max_in_degree),
        data, segment_ids, num_segments, mask)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def gather_rows_ell(h, rows_sorted, max_in_degree):
    """``h[rows_sorted]`` whose backward is the ELL segment sum."""
    return jnp.take(h, rows_sorted, axis=0)


def _gre_fwd(h, rows_sorted, max_in_degree):
    return jnp.take(h, rows_sorted, axis=0), (rows_sorted, h.shape[0])


def _gre_bwd(max_in_degree, res, g):
    rows_sorted, n = res
    return _ell_sum_impl(g, rows_sorted, n, max_in_degree), None


gather_rows_ell.defvjp(_gre_fwd, _gre_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def paired_gather_cols_ell(h, cols, pair, rows_sorted, edge_mask, max_in_degree):
    """``h[cols]`` whose backward rides the reverse-edge involution + ELL
    segment sum (see :func:`paired_gather_cols_cs`)."""
    del pair, rows_sorted, edge_mask
    return jnp.take(h, cols, axis=0)


def _pge_fwd(h, cols, pair, rows_sorted, edge_mask, max_in_degree):
    return jnp.take(h, cols, axis=0), (pair, rows_sorted, edge_mask, h.shape[0])


def _pge_bwd(max_in_degree, res, g):
    return (_paired_bwd(
        lambda d, i, n: _ell_sum_impl(d, i, n, max_in_degree), res, g),
        None, None, None, None)


paired_gather_cols_ell.defvjp(_pge_fwd, _pge_bwd)


@jax.custom_vjp
def gather_rows_cs(h, rows_sorted):
    """``h[rows_sorted]`` whose BACKWARD is the cumsum segment sum instead of
    the transpose-of-gather scatter (ids ascending, so the pull-back
    ``sum_e g[e] -> node rows[e]`` is exactly :func:`sorted_segment_sum_cs`).
    Padding rows may point at any node slot; as with the plain gather, their
    cotangent lands on that slot — callers zero masked cotangents upstream
    (identical semantics to ``jnp.take``'s transpose)."""
    return jnp.take(h, rows_sorted, axis=0)


def _gr_fwd(h, rows_sorted):
    return jnp.take(h, rows_sorted, axis=0), (rows_sorted, h.shape[0])


def _gr_bwd(res, g):
    rows_sorted, n = res
    return _cs_sum_impl(g, rows_sorted, n), None


gather_rows_cs.defvjp(_gr_fwd, _gr_bwd)


@jax.custom_vjp
def paired_gather_cols_cs(h, cols, pair, rows_sorted, edge_mask):
    """``h[cols]`` for a symmetric edge list whose BACKWARD rides the sorted
    row axis: the transpose of the col-incidence is the reverse-edge
    permutation ``pair`` (ops/blocked.pairing_perm), so
    grad_h = sorted_segment_sum(g[pair] * mask, rows). Scatter-free in both
    directions."""
    del pair, rows_sorted, edge_mask
    return jnp.take(h, cols, axis=0)


def _pgc_fwd(h, cols, pair, rows_sorted, edge_mask):
    return jnp.take(h, cols, axis=0), (pair, rows_sorted, edge_mask, h.shape[0])


def _paired_bwd(sum_impl, res, g):
    """Shared backward of the paired col gathers: pull the cotangent through
    the reverse-edge involution, mask padding, then sorted segment sum."""
    pair, rows_sorted, edge_mask, n = res
    gp = jnp.take(g, pair, axis=0)
    m = edge_mask.astype(gp.dtype).reshape(edge_mask.shape + (1,) * (gp.ndim - 1))
    return sum_impl(gp * m, rows_sorted, n)


def _pgc_bwd(res, g):
    return (_paired_bwd(_cs_sum_impl, res, g), None, None, None, None)


paired_gather_cols_cs.defvjp(_pgc_fwd, _pgc_bwd)


def masked_sum(data, mask, axis):
    """Sum over ``axis`` counting only mask==1 elements. mask broadcasts from the left."""
    m = mask.astype(data.dtype).reshape(mask.shape + (1,) * (data.ndim - mask.ndim))
    return jnp.sum(data * m, axis=axis)


def masked_mean(data, mask, axis, eps_count: float = 1.0):
    """Mean over ``axis`` counting only mask==1 elements (count clamped >= eps_count)."""
    m = mask.astype(data.dtype).reshape(mask.shape + (1,) * (data.ndim - mask.ndim))
    total = jnp.sum(data * m, axis=axis)
    count = jnp.sum(m, axis=axis)
    return total / jnp.maximum(count, eps_count)
