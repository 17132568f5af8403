"""Micro-benchmarks for the blocked one-hot MXU kernels (ops/blocked.py) vs
the XLA sorted-scatter path, at LargeFluid shape.

The round-2 prediction (docs/PERFORMANCE.md) was that the blocked kernels
bound the hot aggregations near HBM bandwidth; the first hardware run of the
full step measured SLOWER than the plain path (plug-in era; not measured on
this machine). This isolates
the primitives to find out which one lies: times blocked_segment_sum /
blocked_gather across (dtype, tile) against scatter/segment-sum/gather on the
same data, plus the paired backward-gather path.

Usage: python scripts/microbench_blocked.py [--quick]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 113_152          # 442 blocks of 256
BLOCK = 256
H = 64
AVG_DEG = 14.5       # bench workload: E ~ 1.64M


def timed(fn, *args, warmup=2, steps=10):
    import jax.numpy as jnp

    def sync(o):
        np.asarray(jnp.ravel(o)[0])

    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps * 1e3


def main():
    import jax
    import jax.numpy as jnp

    from distegnn_tpu.ops.blocked import (
        blockify_edges, pairing_perm, slot_ids, _gather, _seg_sum,
    )

    quick = "--quick" in sys.argv
    rng = np.random.default_rng(0)

    # synthetic symmetric radius-like graph: undirected pairs, both directions
    E_half = int(N * AVG_DEG) // 2
    src = rng.integers(0, N, size=E_half)
    dst = (src + rng.integers(1, 200, size=E_half)) % N   # mild locality
    ei = np.concatenate([np.stack([src, dst]), np.stack([dst, src])], axis=1)
    order = np.argsort(ei[0], kind="stable")
    ei = ei[:, order].astype(np.int64)
    E_real = ei.shape[1]

    results = {}
    for tile in (512,) if quick else (512, 1024, 2048):
        epb_raw = -(-int(np.diff(np.searchsorted(ei[0], np.arange(0, N + 1, BLOCK))).max()) // tile) * tile
        bei, _, bmask = blockify_edges(ei, None, N, epb_raw, BLOCK)
        E_blk = bei.shape[1]
        slot = np.asarray(slot_ids(jnp.asarray(bei[0]), jnp.asarray(bmask), BLOCK, epb_raw))
        pair = pairing_perm(bei)
        slot_j = jnp.asarray(slot)
        for dt in (jnp.float32, jnp.bfloat16):
            x = jnp.asarray(rng.normal(size=(E_blk, H)).astype(np.float32)).astype(dt)
            h = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32)).astype(dt)
            f_seg = jax.jit(lambda d, s, t=tile: _seg_sum(d, s, N, BLOCK, t))
            f_gat = jax.jit(lambda hh, s, t=tile: _gather(hh, s, BLOCK, t))
            key = f"tile{tile}_{dt.__name__}"
            results[f"blocked_seg_{key}"] = timed(f_seg, x, slot_j)
            results[f"blocked_gather_{key}"] = timed(f_gat, h, slot_j)
        if pair is not None:
            g32 = jnp.asarray(rng.normal(size=(E_blk, H)).astype(np.float32))
            pair_j = jnp.asarray(pair)
            f_pb = jax.jit(lambda g, p, s, t=tile: _seg_sum(jnp.take(g, p, axis=0), s, N, BLOCK, t))
            results[f"paired_bwd_tile{tile}_f32"] = timed(f_pb, g32, pair_j, slot_j)
        print(f"# tile={tile}: E_real={E_real} E_blocked={E_blk} "
              f"(pad waste {(E_blk / E_real - 1) * 100:.0f}%)", flush=True)

        # einsum lowering on the same layout (tile-independent; once is enough)
        if tile == 512:
            from distegnn_tpu.ops.blocked import (
                _ein_gather_raw, _ein_seg_sum_raw, onehot_blocks,
            )

            f_oh = jax.jit(lambda s: onehot_blocks(s, epb_raw, BLOCK))
            oh = f_oh(slot_j)
            results["einsum_onehot_build"] = timed(f_oh, slot_j)
            f_eseg = jax.jit(_ein_seg_sum_raw)
            f_egat = jax.jit(_ein_gather_raw)
            for dt in (jnp.float32, jnp.bfloat16):
                x = jnp.asarray(rng.normal(size=(E_blk, H)).astype(np.float32)).astype(dt)
                h = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32)).astype(dt)
                nm = dt.__name__
                results[f"einsum_seg_{nm}"] = timed(f_eseg, x, oh)
                results[f"einsum_gather_{nm}"] = timed(f_egat, h, oh)

    # XLA reference points on the same (unblocked) sorted edge list
    ids = jnp.asarray(ei[0].astype(np.int32))
    for dt in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(rng.normal(size=(E_real, H)).astype(np.float32)).astype(dt)
        h = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32)).astype(dt)
        nm = dt.__name__
        results[f"xla_scatter_sorted_{nm}"] = timed(
            jax.jit(lambda d, i: jnp.zeros((N, H), d.dtype).at[i].add(d)), x, ids)
        results[f"xla_segsum_flag_{nm}"] = timed(
            jax.jit(lambda d, i: jax.ops.segment_sum(d, i, num_segments=N,
                                                     indices_are_sorted=True)), x, ids)
        results[f"xla_gather_{nm}"] = timed(jax.jit(lambda hh, i: hh[i]), h, ids)

    for k, v in results.items():
        print(f"{k:36s} {v:8.2f} ms")


if __name__ == "__main__":
    main()
