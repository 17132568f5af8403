"""Micro-benchmarks for the hot aggregation/matmul primitives at LargeFluid
shape — decides which segment-op lowering and compute dtype the model uses.

Variants:
  scatter_unsorted   zeros.at[ids].add(x) with shuffled ids (round-1 behavior)
  scatter_sorted     same op, ids sorted ascending (what pad_graphs now emits)
  segsum_flag        jax.ops.segment_sum(indices_are_sorted=True)
  gather             the read side (x[ids]) for comparison
  matmul_f32 / bf16  the edge-MLP matmul [E,128]x[128,64]
"""

from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")

E, N, H = 1_639_080, 113_140, 64


def timed(fn, *args, warmup=2, steps=10):
    """Sync by a 1-element device->host fetch of the final result, inside
    the timed region."""
    import jax.numpy as jnp
    import numpy as np

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

    rng = np.random.default_rng(0)
    ids_sorted = np.sort(rng.integers(0, N, size=E)).astype(np.int32)
    ids_shuf = rng.permutation(ids_sorted).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(E, H)).astype(np.float32))
    a = jnp.asarray(rng.normal(size=(E, 2 * H)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(2 * H, H)).astype(np.float32))
    ids_s = jnp.asarray(ids_sorted)
    ids_u = jnp.asarray(ids_shuf)

    f_scatter = jax.jit(lambda d, i: jnp.zeros((N, H), d.dtype).at[i].add(d))
    f_segsum_flag = jax.jit(lambda d, i: jax.ops.segment_sum(
        d, i, num_segments=N, indices_are_sorted=True))
    f_gather = jax.jit(lambda d, i: d[i[:N]])
    f_mm = jax.jit(lambda d, k: d @ k)
    f_mm_bf16 = jax.jit(lambda d, k: (d.astype(jnp.bfloat16) @ k.astype(jnp.bfloat16)).astype(jnp.float32))

    print(f"scatter_unsorted   {timed(f_scatter, x, ids_u):8.2f} ms")
    print(f"scatter_sorted     {timed(f_scatter, x, ids_s):8.2f} ms")
    print(f"segsum_flag_sorted {timed(f_segsum_flag, x, ids_s):8.2f} ms")
    print(f"gather             {timed(f_gather, x, ids_s):8.2f} ms")
    print(f"matmul_f32         {timed(f_mm, a, w):8.2f} ms")
    print(f"matmul_bf16        {timed(f_mm_bf16, a, w):8.2f} ms")
    tiled_exec_bench(rng)


def tiled_exec_bench(rng):
    """Tile-executor unit (serve/tiled.py): plan cost, per-(tile, layer)
    invocation time, and the measured H2D-overlap stall fraction at a small
    multi-tile shape. The per-invocation number is the one that multiplies
    by tiles x layers for a giant scene; the plan cost is the host-side
    prep a session-cache hit amortizes away."""
    import jax

    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.ops.tiling import plan_tiles
    from distegnn_tpu.serve.buckets import synthetic_graph
    from distegnn_tpu.serve.engine import InferenceEngine
    from distegnn_tpu.serve.tiled import TiledExecutor

    on_tpu = jax.default_backend() == "tpu"
    n, tile = (65_536, 16_384) if on_tpu else (1_500, 512)
    g = synthetic_graph(n, radius=0.35 * (1_500 / n) ** (1 / 3), seed=0)

    t0 = time.perf_counter()
    plan = plan_tiles(g["edge_index"], g["loc"], g["edge_attr"],
                      tile_nodes=tile)
    plan_ms = (time.perf_counter() - t0) * 1e3

    model = FastEGNN(node_feat_nf=1, edge_attr_nf=2, hidden_nf=H,
                     virtual_channels=3, n_layers=4)
    params = model.init(jax.random.PRNGKey(0),
                        pad_graphs([{k: v[:32] if v.ndim and v.shape[0] == n
                                     else v for k, v in g.items()
                                     if k != "edge_index"}
                                    | {"edge_index": np.array([[0, 1],
                                                               [1, 0]],
                                                              np.int32),
                                       "edge_attr": g["edge_attr"][:2]}],
                                   node_bucket=1, edge_bucket=1))
    tx = TiledExecutor(InferenceEngine(model, params),
                       {"tile_nodes": tile})
    out = tx.predict(dict(g))               # warmup: compiles + first pass
    t0 = time.perf_counter()
    out = tx.predict(dict(g), plan=plan)
    pass_ms = (time.perf_counter() - t0) * 1e3
    per_inv = pass_ms / (out["tiles"] * out["layers"])
    print(f"tiled_plan         {plan_ms:8.2f} ms  "
          f"[N={n}, tiles={out['tiles']}, halo={out['halo_fraction']:.3f}]")
    print(f"tiled_tile_layer   {per_inv:8.2f} ms  "
          f"[pass={pass_ms:.1f} ms over {out['tiles']}x{out['layers']} "
          f"invocations, h2d_stall={out['stall_fraction']:.3f}]")


if __name__ == "__main__":
    main()
