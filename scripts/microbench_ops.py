"""Micro-benchmarks for the hot aggregation/matmul primitives at LargeFluid
shape — decides which segment-op lowering and compute dtype the model uses.

Variants:
  scatter_unsorted   zeros.at[ids].add(x) with shuffled ids (round-1 behavior)
  scatter_sorted     same op, ids sorted ascending (what pad_graphs now emits)
  segsum_flag        jax.ops.segment_sum(indices_are_sorted=True)
  gather             the read side (x[ids]) for comparison
  matmul_f32 / bf16  the edge-MLP matmul [E,128]x[128,64]
  fused_edge_layer   the whole per-layer edge pipeline in ONE Pallas pass
                     (ops/edge_pipeline.py) — geometry + phi_e + coord gate +
                     all three aggregations; compare against the SUM of the
                     unfused primitives above to see the traffic it removes.
                     Off-TPU it runs interpret mode at a toy shape (the full
                     shape would take hours interpreted).
  fused_egnn_stack   the cross-layer megakernel (ops/layer_pipeline.py): ALL
                     L layers in one Pallas grid with the graph VMEM-resident.
                     Runs at the VMEM-capped shape (the stack must fit the 16
                     MiB budget), and prints the analytic HBM-bytes-per-step
                     model for plain / fused / fused_stack at both the capped
                     and flagship shapes — the traffic ratio is the claim the
                     megakernel makes, so the numbers and their assumptions
                     are emitted next to the timing.
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
    fused_edge_bench(rng)
    fused_stack_bench(rng)
    tiled_exec_bench(rng)


def fused_edge_bench(rng):
    import jax
    import jax.numpy as jnp

    from distegnn_tpu.ops.edge_pipeline import (EdgeWeights, build_edge_blocks,
                                                fused_edge_layer)

    block = 512
    on_tpu = jax.default_backend() == "tpu"
    n_pad = (-(-N // block) * block) if on_tpu else 3 * block
    nb = n_pad // block
    per_block = -(-E // nb)  # ceil: worst block's share of the edges
    epb = (-(-per_block // block) * block) if on_tpu else 3 * block
    # blocked layout built directly: block b owns epb row-local edge slots,
    # cols within one block of the row (always inside the 3-block window)
    rows, cols = [], []
    for b in range(nb):
        r = np.sort(rng.integers(b * block, (b + 1) * block, size=epb))
        c = np.clip(r + rng.integers(-block, block, size=epb), 0, n_pad - 1)
        rows.append(r)
        cols.append(c)
    row = jnp.asarray(np.concatenate(rows).astype(np.int32))
    col = jnp.asarray(np.concatenate(cols).astype(np.int32))
    e_tot = int(row.shape[0])
    attr = jnp.asarray(rng.normal(size=(e_tot, 2)).astype(np.float32))
    mask = jnp.ones((e_tot,), jnp.float32)
    row_t, col_l, kblk, scal = jax.jit(
        lambda r, c, a, m: build_edge_blocks(r, c, a, m, block=block,
                                             n_nodes=n_pad))(row, col, attr, mask)
    xc = jnp.asarray(rng.normal(size=(n_pad, 3)).astype(np.float32))
    hr = jnp.asarray(rng.normal(size=(n_pad, H)).astype(np.float32))
    hc = jnp.asarray(rng.normal(size=(n_pad, H)).astype(np.float32))
    wts = EdgeWeights(
        ws=jnp.asarray(rng.normal(size=(3, H)).astype(np.float32)),
        b1=jnp.zeros((1, H)), w2=jnp.asarray(rng.normal(size=(H, H)).astype(np.float32)),
        b2=jnp.zeros((1, H)), w3=jnp.asarray(rng.normal(size=(H, H)).astype(np.float32)),
        b3=jnp.zeros((1, H)), w4=jnp.asarray(rng.normal(size=(1, H)).astype(np.float32)))
    def run(*args):
        # scalar touching all three accumulators so none is DCE'd and the
        # timed() sync fetch stays 1 element
        t, cnt, ef = fused_edge_layer(*args, wts, block, "bf16")
        return t[0, 0] + cnt[0] + ef[0, 0]

    f = jax.jit(run)
    ms = timed(f, xc, hr, hc, row_t, col_l, kblk, scal)
    tag = "" if on_tpu else " (interpret, toy shape)"
    print(f"fused_edge_layer   {ms:8.2f} ms  [N={n_pad}, E={e_tot}]{tag}")


def fused_stack_bench(rng):
    import jax
    import jax.numpy as jnp

    from distegnn_tpu.ops.edge_pipeline import build_edge_blocks
    from distegnn_tpu.ops.layer_pipeline import (StackConfig,
                                                 fused_egnn_stack,
                                                 hbm_bytes_per_step,
                                                 stack_weight_shapes)

    block, L, C = 512, 4, 3
    # VMEM-capped shape on EVERY backend: the whole stack must be resident,
    # and the flagship shape exceeds the 16 MiB budget by design.
    n_pad = 3 * block
    nb = n_pad // block
    epb = 3 * block
    rows, cols = [], []
    for b in range(nb):
        r = np.sort(rng.integers(b * block, (b + 1) * block, size=epb))
        c = np.clip(r + rng.integers(-block, block, size=epb), 0, n_pad - 1)
        rows.append(r)
        cols.append(c)
    row = jnp.asarray(np.concatenate(rows).astype(np.int32))
    col = jnp.asarray(np.concatenate(cols).astype(np.int32))
    e_tot = int(row.shape[0])
    attr = jnp.asarray(rng.normal(size=(e_tot, 2)).astype(np.float32))
    mask = jnp.ones((e_tot,), jnp.float32)
    edge_arrs = jax.jit(
        lambda r, c, a, m: build_edge_blocks(r, c, a, m, block=block,
                                             n_nodes=n_pad))(row, col, attr,
                                                             mask)
    R = 128  # masked-off remote tail: the pad path, zero live remote edges
    remote_arrs = (jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.int32),
                   jnp.zeros((R, 2), jnp.float32), jnp.zeros((R,), jnp.float32))
    cfg = StackConfig(n_layers=L, block=block, hidden=H, channels=C,
                      dtype_name="bf16")
    wstack = {k: jnp.asarray(
        rng.normal(size=(L,) + s).astype(np.float32) * 0.05)
        for k, s in stack_weight_shapes(cfg).items()}
    h0 = jnp.asarray(rng.normal(size=(n_pad, H)).astype(np.float32))
    x0 = jnp.asarray(rng.normal(size=(n_pad, 3)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(n_pad, 3)).astype(np.float32) * 0.01)
    X0 = jnp.asarray(rng.normal(size=(3, C)).astype(np.float32))
    Hv0 = jnp.asarray(rng.normal(size=(H, C)).astype(np.float32))
    nmask = jnp.ones((n_pad,), jnp.float32)

    def run(*args):
        h, x, X, Hv = fused_egnn_stack(cfg, *args, None, None, edge_arrs,
                                       remote_arrs, wstack)
        return h[0, 0] + x[0, 0] + X[0, 0] + Hv[0, 0]

    f = jax.jit(run)
    on_tpu = jax.default_backend() == "tpu"
    ms = timed(f, h0, x0, v, X0, Hv0, nmask)
    tag = "" if on_tpu else " (interpret, VMEM-capped shape)"
    print(f"fused_egnn_stack   {ms:8.2f} ms  [N={n_pad}, E={e_tot}, L={L}]{tag}")

    # Analytic HBM-bytes-per-step model (ops/layer_pipeline.hbm_bytes_per_step)
    # — CPU-evidence-only until a hardware profile confirms it. Assumptions:
    # bf16 compute streams, f32 state/checkpoints, remote tail at the padded
    # width, every array read/written exactly as many times as the lowering's
    # dataflow implies (no cache modeling).
    print("hbm_bytes_per_step model (analytic; CPU evidence only):")
    for label, (n, e, rp) in (
            (f"capped  N={n_pad} E={e_tot}", (n_pad, e_tot, R)),
            ("flagship N=113152 E=1639424", (113_152, 1_639_424, 8192))):
        per = {impl: hbm_bytes_per_step(
            impl, n_nodes=n, n_edges=e, hidden=H, channels=C, n_layers=L,
            remote_pad=rp, node_attr_nf=2, dtype_name="bf16")["total"]
            for impl in ("plain", "fused", "fused_stack")}
        ratio = per["fused"] / per["fused_stack"]
        print(f"  {label}: plain {per['plain'] / 1e9:7.3f} GB | "
              f"fused {per['fused'] / 1e9:7.3f} GB | "
              f"fused_stack {per['fused_stack'] / 1e9:7.3f} GB | "
              f"fused/fused_stack = {ratio:.2f}x")


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
