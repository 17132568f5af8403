"""Benchmark: LargeFluid-scale training-step throughput, nodes/sec/chip.

Prints one JSON line per finished leg, the best training leg last:
{"metric", "value", "unit", "device", ...}. A leg that fails, or a race in
which no training leg produced a number, exits non-zero; nothing is ever
printed as 0.0 under a metric's name. The measure legs look the device up in
DEVICE_PEAKS and refuse one that is not there, so they run on the chip only.

Workload: Fluid113K shape — 113,140 nodes, ~1.7M radius-0.075 edges, batch 1,
FastEGNN hidden 64 / 4 layers / C=3 with MMD (sigma 3, w 0.01, n 50) and grad
clip 0.3 — the largefluid_distegnn.yaml configuration on one chip.

Layouts (docs/PERFORMANCE.md):
  plain        — row-sorted padded edge list, XLA scatter/gather aggregation
  plain-cumsum — same layout, --seg cumsum: scatter-free prefix-sum
                 aggregations with gather-only VJPs (ops/segment.py)
  plain-ell    — same layout, --seg ell: scatter-free fixed-degree chained
                 gathers, exact arithmetic (ops/segment.py ELL block)
  blocked      — blocked-CSR layout, one-hot contraction ops (ops/blocked.py;
                 --impl einsum|pallas selects the lowering); explicit runs only
  fused        — blocked layout consumed by the fused edge-pipeline Pallas
                 kernel (model.edge_impl='fused', ops/edge_pipeline.py).
                 Mosaic refuses the kernel on jax 0.9.0 (ROADMAP S2), so this
                 leg fails with the compiler's error until that is decided.
  fused_stack  — the cross-layer megakernel (model.edge_impl='fused_stack',
                 ops/layer_pipeline.py) at a bounded node count
                 (BENCH_STACK_NODES, default 1536: the VMEM-resident stack
                 cannot hold the 113k shape). Same refusal as fused.
Default is auto: run the candidates in RACE_ORDER, each in a child process,
and report the fastest training leg. The parent never initializes a JAX
backend — the chip belongs to one process at a time, and a parent that held
it would starve its children.

Timing: a non-donated jit, synced by fetching the loss scalar to the host
(which drains the device queue) inside the timed region.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

from distegnn_tpu import runtime   # imports jax, initializes no backend


def _emit_bench(rec, flush: bool = False) -> None:
    """Print the BENCH contract line AND mirror it as a structured
    ``bench/result`` obs event (logs/bench/obs/events.jsonl), binding a
    sink on first use when no run has configured one. The stdout contract
    must survive a broken obs import, so the mirror is best-effort. For the
    measuring process only: binding the obs sink asks JAX for the process
    index, which initializes the backend — the race's parent prints its
    lines itself."""
    print(json.dumps(rec), flush=flush)
    try:
        from distegnn_tpu import obs

        if not obs.get_tracer().enabled:
            obs.configure(log_dir=os.path.join("logs", "bench", "obs"),
                          tags={"run": "bench"})
        obs.event("bench/result", **rec)
        obs.flush()
    except Exception as e:
        print(f"bench: obs mirror failed ({e!r})", file=sys.stderr)


def _env_int(name: str, default: int) -> int:
    """Defensive env override parse: a malformed BENCH_* var degrades to the
    default with a warning instead of crashing at import."""
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        print(f"bench: malformed {name}={os.environ.get(name)!r}; "
              f"using default {default}", file=sys.stderr)
        return default


N_NODES = _env_int("BENCH_NODES", 113_140)  # override for smoke tests
RADIUS = 0.075
TARGET_EDGES_PER_NODE = 15.0
HIDDEN, LAYERS, CHANNELS = 64, 4, 3
WARMUP, STEPS = 3, 10
# bound on one race child: compile plus 13 steps of the slowest lowering
CHILD_TIMEOUT_S = _env_int("BENCH_CHILD_TIMEOUT_S", 1200)

# Published peaks of one chip, keyed by the device_kind JAX reports. Source:
# Google Cloud documentation "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM); the
# f32 rate is taken as half the bf16 rate. A device that is not here is an
# error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"f32_flops": 98.5e12, "hbm_gbps": 819.0},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench: no published peaks for device_kind {device_kind!r}; "
            f"add it to DEVICE_PEAKS with its source") from None


# Auto-race order, one (child argv, extra env) tuple per leg: the cumsum /
# remat / agg-dtype stacks, plain, and the unfused, unreordered scatter
# control. The fused and fused_stack legs are NOT raced: Mosaic refuses both
# kernels under jax 0.9.0 (ROADMAP S2), so they would only fail the race;
# `--layout fused|fused_stack` still runs them explicitly and fails with the
# compiler's own error. ELL, the blocked lowerings and `--mesh DxGxT` (which
# needs D*G*T devices) are explicit-only too.
RACE_ORDER = (
    (["--layout", "plain", "--seg", "cumsum"],
     {"BENCH_AGG_DTYPE": "bf16", "BENCH_REMAT": "1"}),
    (["--layout", "plain", "--seg", "cumsum"], {"BENCH_AGG_DTYPE": "bf16"}),
    (["--layout", "plain"], {"BENCH_REMAT": "1"}),
    (["--layout", "plain"], None),
    (["--layout", "plain", "--fuse", "0"], {"BENCH_REORDER": "0"}),
    # Tiled-serving leg (serve/tiled.py): inference nodes/sec through the
    # giant-scene tile executor. Its metric is tiled_serve_nodes_per_sec,
    # which never contends for the race's training headline.
    # BENCH_TILED_DEVICES=8 adds the device sweep (D=1 anchor +
    # D=min(8, devices, tiles) mesh rounds + scaling_efficiency).
    (["--layout", "tiled"], {"BENCH_TILED_DEVICES": "8"}),
    # Input-pipeline leg last (host-side graphs/s + stall fractions for the
    # streamed-shard prefetch A/B, data/stream.py): its metric is
    # io_pipeline_graphs_per_sec, on another scale than the headline.
    (["--layout", "io"], None),
)


def make_fluid_cloud(rng):
    """Synthetic fluid-like particle cloud at Fluid113K density, as a raw
    graph dict (pre-padding) — shared by the single-chip measure() path and
    the 3D-mesh leg (which partitions it before padding)."""
    from distegnn_tpu.ops.radius import radius_graph_np

    vol = N_NODES * (4.0 / 3.0) * np.pi * RADIUS**3 / TARGET_EDGES_PER_NODE
    side = max(vol ** (1.0 / 3.0), 2.0 * RADIUS)
    loc = rng.uniform(0, side, size=(N_NODES, 3)).astype(np.float32)
    vel = rng.normal(size=(N_NODES, 3)).astype(np.float32) * 0.01
    if _env_int("BENCH_REORDER", 1):
        # Z-curve node relabeling (ops/order.py): same cloud, same graph,
        # locality-friendly indices — the production loaders offer the same
        # via data.node_order. BENCH_REORDER=0 restores the random labeling
        # for anchor-comparable A/B runs.
        from distegnn_tpu.ops.order import morton_perm

        p = morton_perm(loc)
        loc, vel = loc[p], vel[p]
    edge_index = radius_graph_np(loc, RADIUS)
    n_edges = edge_index.shape[1]
    dist = np.linalg.norm(loc[edge_index[0]] - loc[edge_index[1]], axis=1)
    graph = {
        "node_feat": np.concatenate(
            [np.linalg.norm(vel, axis=1, keepdims=True), vel[:, :2]], axis=1
        ).astype(np.float32),                       # 3 features (largefluid config)
        "node_attr": np.ones((N_NODES, 2), np.float32),  # viscosity, mass
        "loc": loc,
        "vel": vel,
        "target": loc + vel * 0.05,
        "loc_mean": loc.mean(axis=0),
        "edge_index": edge_index,
        "edge_attr": np.repeat(dist[:, None], 2, axis=1).astype(np.float32),
    }
    return graph, n_edges


def make_fluid_batch(rng, edge_block: int = 0, pairing: bool = False,
                     edge_tile: int = 512, split_remote: bool = False):
    """Padded single-chip batch of one fluid cloud (see make_fluid_cloud)."""
    from distegnn_tpu.ops.graph import pad_graphs

    graph, n_edges = make_fluid_cloud(rng)
    kw = ({"edge_block": edge_block, "edge_tile": edge_tile,
           "split_remote": split_remote}
          if edge_block else {"compute_pair": pairing})
    return pad_graphs([graph], **kw), n_edges


def layout_tag(edge_block: int, impl: str, seg: str = "scatter",
               edge_impl: str = "plain") -> str:
    """The machine-read layout label shared by bench.py and profile_step.py
    outputs."""
    if edge_impl == "fused_stack":
        return f"fused_stack{edge_block}"
    if edge_impl == "fused":
        return f"fused{edge_block}"
    if edge_block:
        return f"blocked{edge_block}-{impl}"
    return "plain" if seg == "scatter" else f"plain-{seg}"


def measure(edge_block: int, impl: str = "einsum", seg: str = "scatter",
            fuse: bool = True, edge_impl: str = "plain"):
    import jax

    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.train import TrainState, make_optimizer, make_train_step

    device = runtime.device_summary()
    peaks = device_peaks(device["kind"])   # before any compile: fail early
    rng = np.random.default_rng(0)
    edge_tile = _env_int("BENCH_EDGE_TILE", 512)
    batch, n_edges = make_fluid_batch(rng, edge_block,
                                      pairing=(seg in ("cumsum", "ell")),
                                      edge_tile=edge_tile,
                                      split_remote=(edge_impl in
                                                    ("fused", "fused_stack")))

    model = FastEGNN(node_feat_nf=3, node_attr_nf=2, edge_attr_nf=2,
                     hidden_nf=HIDDEN, virtual_channels=CHANNELS, n_layers=LAYERS,
                     compute_dtype="bf16", blocked_impl=impl, segment_impl=seg,
                     fuse_agg=fuse, edge_impl=edge_impl,
                     agg_dtype=os.environ.get("BENCH_AGG_DTYPE") or None,
                     # racing knob: without remat the backward re-reads ~10
                     # GiB of saved [E,.] activations, which remat trades
                     # for recompute. Default off.
                     remat=bool(_env_int("BENCH_REMAT", 0)))
    params = model.init(jax.random.PRNGKey(0), batch)
    tx = make_optimizer(5e-4, weight_decay=1e-12, clip_norm=0.3)
    state = TrainState.create(params, tx)
    # no donate_argnums: the effect of donation on this step is not measured
    # on this machine (ROADMAP S4)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.01, mmd_sigma=3.0,
                                   mmd_samples=50))

    for i in range(WARMUP):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
    float(metrics["loss"])  # hard sync: drain the device queue

    t0 = time.perf_counter()
    for i in range(STEPS):
        state, metrics = step(state, batch, jax.random.PRNGKey(100 + i))
    float(metrics["loss"])  # hard sync
    dt = time.perf_counter() - t0

    # analytic FLOPs + bytes from XLA cost analysis: MFU for the compute
    # ceiling, achieved HBM GB/s for the (binding) memory ceiling
    try:
        an = step.lower(state, batch, jax.random.PRNGKey(0)).compile().cost_analysis()
        if isinstance(an, list):
            an = an[0]
        flops = float(an.get("flops", float("nan")))
        bytes_moved = float(an.get("bytes accessed", float("nan")))
    except Exception:
        flops = bytes_moved = float("nan")
    mfu = flops / (dt / STEPS) / peaks["f32_flops"]
    hbm_gbps = bytes_moved / (dt / STEPS) / 1e9

    nodes_per_sec = N_NODES * STEPS / dt
    layout = layout_tag(edge_block, impl, seg, edge_impl)
    # self-describing record: the locality / fusion / stream-dtype knobs are
    # part of the measured configuration
    if edge_block and edge_tile != 512:
        layout += f"+t{edge_tile}"
    if not fuse:
        layout += "+nofuse"
    if not _env_int("BENCH_REORDER", 1):
        layout += "+noreorder"
    if os.environ.get("BENCH_AGG_DTYPE"):
        layout += f"+agg{os.environ['BENCH_AGG_DTYPE']}"
    if _env_int("BENCH_REMAT", 0):
        layout += "+remat"
    return {
        "metric": "largefluid_train_nodes_per_sec_per_chip",
        "value": round(nodes_per_sec, 1),
        "unit": (f"nodes/sec/chip (N={N_NODES}, E={n_edges}, step={dt / STEPS * 1e3:.1f}ms, "
                 f"platform={device['platform']}, layout={layout}, mfu_f32={mfu:.3f}, "
                 f"hbm_gbps={hbm_gbps:.0f} ({hbm_gbps / peaks['hbm_gbps']:.0%} of peak), "
                 f"sync=fetch)"),
        "device": device,
    }


def measure_mesh(mesh_str: str, seg: str = "scatter", fuse: bool = True):
    """3D-mesh distributed step timing (``--mesh DxGxT``): the shard_mapped
    train step from parallel/launch over a (data, graph, tensor) mesh. Data
    shards hold DIFFERENT clouds; graph>1 splits each cloud with the random
    partitioner (metis at bench node counts would dominate setup time);
    tensor>1 slices the EGCL hidden dims per chip (parallel/collectives.py TP
    ops — docs/PERFORMANCE.md "3D mesh" has the memory/comm model). Plain
    edge layout + scatter aggregation only: the fused kernel's TP dispatch is
    parity-proven in the dryrun (__graft_entry__._tensor_parity); this leg
    answers step-time-vs-mesh-shape: per-chip throughput across mesh shapes
    is the comparison."""
    import jax

    from distegnn_tpu.data.partition import split_graph
    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.parallel.launch import (
        batch_layout,
        global_batch_putter,
        make_distributed_steps,
    )
    from distegnn_tpu.parallel.mesh import GRAPH_AXIS, TENSOR_AXIS, make_mesh
    from distegnn_tpu.train import TrainState, make_optimizer

    if seg != "scatter":
        sys.exit(f"--mesh supports --seg scatter only (got {seg})")
    D, G, T = (int(v) for v in mesh_str.lower().split("x"))
    need = D * G * T
    if len(jax.devices()) < need:
        sys.exit(f"--mesh {mesh_str}: needs {need} devices, "
                 f"have {len(jax.devices())}")
    if HIDDEN % T:
        sys.exit(f"--mesh {mesh_str}: hidden {HIDDEN} not divisible by "
                 f"tensor={T}")
    mesh = make_mesh(n_graph=G, n_data=D, n_tensor=T,
                     devices=jax.devices()[:need])

    clouds, n_edges_total = [], 0
    for s in range(D):
        cloud, n_edges = make_fluid_cloud(np.random.default_rng(s))
        n_edges_total += n_edges
        clouds.append(split_graph(cloud, G, "random", inner_radius=RADIUS,
                                  outer_radius=1.5 * RADIUS, seed=s)
                      if G > 1 else [cloud])
    mn = max(p["loc"].shape[0] for parts in clouds for p in parts) + 8
    me = max(p["edge_index"].shape[1] for parts in clouds for p in parts) + 64

    def stack(xs):
        return jax.tree.map(lambda *a: np.stack(a, axis=0), *xs)

    shard_stacks = [stack([pad_graphs([p], max_nodes=mn, max_edges=me)
                           for p in parts]) for parts in clouds]
    host_batch = stack(shard_stacks) if D > 1 else shard_stacks[0]

    model = FastEGNN(
        node_feat_nf=3, node_attr_nf=2, edge_attr_nf=2, hidden_nf=HIDDEN,
        virtual_channels=CHANNELS, n_layers=LAYERS, compute_dtype="bf16",
        fuse_agg=fuse, axis_name=GRAPH_AXIS,
        tensor_axis=(TENSOR_AXIS if T > 1 else None),
        agg_dtype=os.environ.get("BENCH_AGG_DTYPE") or None,
        remat=bool(_env_int("BENCH_REMAT", 0)))
    _, strip = batch_layout(D)
    init_model = (model.copy(axis_name=None, tensor_axis=None) if T > 1
                  else model.copy(axis_name=None))
    params = init_model.init(jax.random.PRNGKey(0),
                             jax.tree.map(strip, host_batch))
    tx = make_optimizer(5e-4, weight_decay=1e-12, clip_norm=0.3)
    state = TrainState.create(params, tx)
    step, _ = make_distributed_steps(model, tx, mesh, mmd_weight=0.01,
                                     mmd_sigma=3.0, mmd_samples=50)
    gb = global_batch_putter(mesh)(host_batch)

    for i in range(WARMUP):
        state, metrics = step(state, gb, jax.random.PRNGKey(i))
    float(metrics["loss"])  # hard sync: drain the device queue

    t0 = time.perf_counter()
    for i in range(STEPS):
        state, metrics = step(state, gb, jax.random.PRNGKey(100 + i))
    float(metrics["loss"])  # hard sync
    dt = time.perf_counter() - t0

    nodes_per_sec = D * N_NODES * STEPS / dt
    device = runtime.device_summary()
    platform = device["platform"]
    layout = f"mesh{D}x{G}x{T}"
    if _env_int("BENCH_REMAT", 0):
        layout += "+remat"
    if os.environ.get("BENCH_AGG_DTYPE"):
        layout += f"+agg{os.environ['BENCH_AGG_DTYPE']}"
    return {
        "metric": "largefluid_train_nodes_per_sec_per_chip",
        "value": round(nodes_per_sec / need, 1),
        "unit": (f"nodes/sec/chip (N={N_NODES} x D={D}, E={n_edges_total}, "
                 f"step={dt / STEPS * 1e3:.1f}ms, platform={platform}, "
                 f"layout={layout}, devices={need}, sync=fetch)"),
        "device": device,
    }


def measure_io():
    """Input-pipeline leg: graphs/s through load -> collate -> device_put
    over the out-of-core shard pipeline (data/stream.py), prefetch ON vs the
    blocking put, with per-mode ``data/stall_s`` deltas. The number is a
    HOST-side throughput (not a training headline): each consumed batch
    sleeps BENCH_IO_COMPUTE_MS to stand in for a device step, so the A/B
    isolates exactly what PrefetchLoader hides — disk read + collate + put
    overlapping compute. Self-caps N to BENCH_IO_NODES (the pipeline cost is
    per-graph collate, not model FLOPs; the flagship 113k cloud would just
    make shard writes slow without changing the ratio)."""
    import tempfile

    import jax

    from distegnn_tpu import obs
    from distegnn_tpu.data import (
        GraphLoader, PrefetchLoader, StreamedGraphDataset, write_shards,
    )

    global N_NODES
    cap = _env_int("BENCH_IO_NODES", 2048)
    if N_NODES > cap:
        print(f"bench: io leg capped at N={cap} (host-pipeline leg; model "
              f"FLOPs are simulated)", file=sys.stderr)
        N_NODES = cap
    n_graphs = _env_int("BENCH_IO_GRAPHS", 24)
    depth = _env_int("BENCH_IO_DEPTH", 2)
    compute_s = _env_int("BENCH_IO_COMPUTE_MS", 25) / 1e3

    graphs, n_edges = [], 0
    for s in range(n_graphs):
        g, e = make_fluid_cloud(np.random.default_rng(s))
        graphs.append(g)
        n_edges = max(n_edges, e)
    reg = obs.get_registry()

    def run_epoch(pf):
        stall = reg.counter("data/stall_s")
        pf.set_epoch(0)
        for batch in pf:  # warm epoch: shard cache, page cache, device path
            jax.block_until_ready(batch)
        pf.set_epoch(1)
        s0, n = stall.value, 0
        t0 = time.perf_counter()
        for batch in pf:
            jax.block_until_ready(batch)
            time.sleep(compute_s)  # simulated device step
            n += 1
        wall = time.perf_counter() - t0
        return {"graphs_per_s": n / wall, "stall_s": stall.value - s0,
                "wall_s": wall, "batches": n}

    with tempfile.TemporaryDirectory() as td:
        write_shards(graphs, td, shard_size=max(1, n_graphs // 6))
        ds = StreamedGraphDataset(td, cache_shards=2)
        loader = GraphLoader(ds, 1, shuffle=True, seed=0)
        blocking = run_epoch(PrefetchLoader(loader, put=jax.device_put,
                                            depth=0))
        prefetch = run_epoch(PrefetchLoader(loader, put=jax.device_put,
                                            depth=depth))

    device = runtime.device_summary()
    platform = device["platform"]
    return {
        "metric": "io_pipeline_graphs_per_sec",
        "value": round(prefetch["graphs_per_s"], 2),
        "unit": (f"graphs/s through load->collate->put (streamed shards, "
                 f"prefetch depth={depth}, N={N_NODES}, E<={n_edges}, "
                 f"simulated compute {compute_s * 1e3:.0f}ms/step, "
                 f"platform={platform}; host pipeline, not a training "
                 f"headline)"),
        "device": device,
        "vs_blocking": round(prefetch["graphs_per_s"]
                             / blocking["graphs_per_s"], 3),
        "stall_s": round(prefetch["stall_s"], 4),
        "stall_s_blocking": round(blocking["stall_s"], 4),
        "stall_fraction": round(prefetch["stall_s"] / prefetch["wall_s"], 4),
        "stall_fraction_blocking": round(
            blocking["stall_s"] / blocking["wall_s"], 4),
        "prefetch_depth": depth,
        "batches_per_epoch": blocking["batches"],
    }


def measure_tiled():
    """Tiled-serving leg: inference nodes/sec for ONE giant scene through
    the fixed-shape tile executor (serve/tiled.py) — the million-node
    serving path's throughput plus its three health gauges (tile count,
    halo fraction, H2D-overlap stall fraction). An INFERENCE number, never
    the training headline. Self-caps via BENCH_TILED_NODES; tile size via
    BENCH_TILE_NODES (default N/6 so the leg always actually tiles);
    BENCH_TILED_IMPL=fused runs the halo-aware fused edge pipeline;
    BENCH_TILED_DEVICES>1 adds the device sweep — the same scene rerun
    through D device-parallel rounds (serve/mesh_tiled.py) with the D=1
    number kept as seq_nodes_per_sec and scaling_efficiency =
    (mesh/seq)/D."""
    import jax

    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.serve.engine import InferenceEngine
    from distegnn_tpu.serve.tiled import TiledExecutor

    global N_NODES
    cap = _env_int("BENCH_TILED_NODES", N_NODES)
    if N_NODES > cap:
        print(f"bench: tiled leg capped at N={cap}", file=sys.stderr)
        N_NODES = cap
    impl = os.environ.get("BENCH_TILED_IMPL", "plain")
    if impl not in ("plain", "fused"):
        impl = "plain"
    tile_nodes = _env_int("BENCH_TILE_NODES", 0)
    if tile_nodes <= 0:
        tile_nodes = max(512, (N_NODES // 6 // 512) * 512)
    steps = max(1, _env_int("BENCH_TILED_STEPS", 2))

    cloud, n_edges = make_fluid_cloud(np.random.default_rng(0))
    model = FastEGNN(node_feat_nf=3, node_attr_nf=2, edge_attr_nf=2,
                     hidden_nf=HIDDEN, virtual_channels=CHANNELS,
                     n_layers=LAYERS, edge_impl=impl)
    # params from a tiny same-featured batch (shapes are size-independent)
    small = {k: (v[:64] if k in ("node_feat", "node_attr", "loc", "vel",
                                 "target") else v) for k, v in cloud.items()}
    ei = cloud["edge_index"]
    sel = (ei[0] < 64) & (ei[1] < 64)
    small["edge_index"] = (ei[:, sel] if sel.any()
                           else np.array([[0, 1], [1, 0]], np.int32))
    small["edge_attr"] = (cloud["edge_attr"][sel] if sel.any()
                          else cloud["edge_attr"][:2])
    if impl == "fused":
        init_batch = pad_graphs([small], max_nodes=1536, edge_block=512,
                                edge_tile=512, split_remote=True,
                                compute_pair=False)
        layout = {"edge_block": 512, "split_remote": True}
    else:
        init_batch = pad_graphs([small], node_bucket=1, edge_bucket=1)
        layout = None
    params = model.init(jax.random.PRNGKey(0), init_batch)
    engine = InferenceEngine(model, params, layout_opts=layout)
    tx = TiledExecutor(engine, {"tile_nodes": tile_nodes,
                                "max_nodes": max(N_NODES, 4_194_304)})

    out = tx.predict(dict(cloud))            # warmup: compiles + first pass
    t0 = time.perf_counter()
    for _ in range(steps):
        out = tx.predict(dict(cloud))
    dt = time.perf_counter() - t0

    nodes_per_sec = N_NODES * steps / dt
    device = runtime.device_summary()
    platform = device["platform"]
    rec = {
        "metric": "tiled_serve_nodes_per_sec",
        "value": round(nodes_per_sec, 1),
        "unit": (f"inference nodes/sec through the tiled executor "
                 f"(N={N_NODES}, E={n_edges}, tiles={out['tiles']} x "
                 f"{tile_nodes} own nodes (padded {out['padded_nodes']}), "
                 f"impl={impl}, layers={LAYERS}, platform={platform}; "
                 f"serving leg, not a training headline)"),
        "device": device,
        "tiles": out["tiles"],
        "tile_nodes": tile_nodes,
        "padded_nodes": out["padded_nodes"],
        "halo_fraction": round(out["halo_fraction"], 4),
        "h2d_stall_fraction": round(out["stall_fraction"], 4),
        "work_imbalance": round(out["work_imbalance"], 4),
        "pass_ms": round(dt / steps * 1e3, 1),
        "devices": 1,
        "tiled_rounds": out["rounds"],
        "scaling_efficiency": None,
    }

    # device sweep (serve/mesh_tiled.py): rerun the SAME scene and plan at
    # D = min(BENCH_TILED_DEVICES, local devices, tiles). The headline value
    # becomes the D-device number; seq_nodes_per_sec keeps the D=1 anchor and
    # scaling_efficiency = (mesh/seq)/D. On CPU this traces the mesh path
    # only — virtual devices share one host, so the ratio there is plumbing
    # proof, never a speedup claim.
    req = _env_int("BENCH_TILED_DEVICES", 0)
    D = min(req, jax.local_device_count(), out["tiles"])
    if D > 1:
        tx.devices = D
        mout = tx.predict(dict(cloud))       # warmup: pmap compile
        t0 = time.perf_counter()
        for _ in range(steps):
            mout = tx.predict(dict(cloud))
        mdt = time.perf_counter() - t0
        mesh_nps = N_NODES * steps / mdt
        rec.update({
            "value": round(mesh_nps, 1),
            "unit": (f"inference nodes/sec through the tiled executor at "
                     f"D={D} device-parallel rounds (N={N_NODES}, "
                     f"E={n_edges}, tiles={out['tiles']} -> "
                     f"{mout['rounds']} rounds, impl={impl}, "
                     f"layers={LAYERS}, platform={platform}; serving leg; "
                     f"CPU sweep is plumbing evidence, not a speedup claim)"),
            "devices": D,
            "tiled_rounds": mout["rounds"],
            "seq_nodes_per_sec": round(nodes_per_sec, 1),
            "scaling_efficiency": round((mesh_nps / nodes_per_sec) / D, 4),
            "round_ms": round(mout["round_ms"], 2),
            "halo_gather_ms": round(mout["halo_gather_ms"], 2),
            "h2d_stall_fraction": round(mout["stall_fraction"], 4),
            "pass_ms": round(mdt / steps * 1e3, 1),
        })
    return rec


def main():
    args = sys.argv[1:]
    layout, impl, seg, fuse, mesh_str = "auto", "einsum", "scatter", True, None
    usage = ("usage: bench.py [--layout plain|blocked|fused|fused_stack|"
             "tiled|io|auto] "
             "[--impl pallas|einsum] [--seg scatter|cumsum|ell] "
             "[--fuse 0|1] [--mesh DxGxT]  "
             "(env: BENCH_REORDER, BENCH_AGG_DTYPE, BENCH_STACK_NODES, "
             "BENCH_IO_NODES, BENCH_IO_DEPTH)")
    if "--mesh" in args:
        i = args.index("--mesh")
        if i + 1 >= len(args) or not re.fullmatch(r"\d+x\d+x\d+",
                                                  args[i + 1].lower()):
            sys.exit(usage)
        mesh_str = args[i + 1].lower()
    if "--layout" in args:
        i = args.index("--layout")
        if i + 1 >= len(args) or args[i + 1] not in ("plain", "blocked", "fused",
                                                     "fused_stack", "tiled",
                                                     "io", "auto"):
            sys.exit(usage)
        layout = args[i + 1]
    if "--impl" in args:
        i = args.index("--impl")
        if i + 1 >= len(args) or args[i + 1] not in ("pallas", "einsum"):
            sys.exit(usage)
        impl = args[i + 1]
    if "--seg" in args:
        i = args.index("--seg")
        if i + 1 >= len(args) or args[i + 1] not in ("scatter", "cumsum", "ell"):
            sys.exit(usage)
        seg = args[i + 1]
    if "--fuse" in args:
        i = args.index("--fuse")
        if i + 1 >= len(args) or args[i + 1] not in ("0", "1"):
            sys.exit(usage)
        fuse = args[i + 1] == "1"

    if mesh_str is not None:
        runtime.provision_cpu_devices(
            int(np.prod([int(v) for v in mesh_str.split("x")])))
        _emit_bench(measure_mesh(mesh_str, seg, fuse))
        return

    edge_block = _env_int("BENCH_EDGE_BLOCK", 256)
    if layout == "fused":
        # fused edge pipeline: kernel constraints pin the block (>= 512 and a
        # multiple of it); BENCH_FUSED_BLOCK overrides for VMEM-window sweeps
        fb = _env_int("BENCH_FUSED_BLOCK", 512)
        _emit_bench(measure(fb, impl, seg, fuse, edge_impl="fused"))
        return
    if layout == "fused_stack":
        # Cross-layer megakernel: the whole L-layer stack must be VMEM-
        # resident, and the flagship 113k shape exceeds the 16 MiB budget by
        # design (ops/layer_pipeline.check_stack_vmem would raise its typed
        # error at trace time). Self-cap to the largest padded shape that
        # fits at Fluid113K density; the resulting number is an A/B vs
        # --layout fused at the SAME node count.
        global N_NODES
        cap = _env_int("BENCH_STACK_NODES", 1536)
        if N_NODES > cap:
            print(f"bench: fused_stack leg capped at N={cap} "
                  f"(VMEM-resident stack; N={N_NODES} exceeds the "
                  f"default 16 MiB budget)", file=sys.stderr)
            N_NODES = cap
        fb = _env_int("BENCH_FUSED_BLOCK", 512)
        _emit_bench(measure(fb, impl, seg, fuse, edge_impl="fused_stack"))
        return
    if layout == "tiled":
        # giant-scene serving leg (tile executor nodes/sec + halo/stall
        # gauges); an inference number, never the training headline
        runtime.provision_cpu_devices(_env_int("BENCH_TILED_DEVICES", 0))
        _emit_bench(measure_tiled())
        return
    if layout == "io":
        # input-pipeline A/B (prefetch vs blocking put over streamed shards);
        # reports graphs/s + stall fractions, never the training headline
        _emit_bench(measure_io())
        return
    if layout in ("plain", "blocked"):
        _emit_bench(measure(edge_block if layout == "blocked" else 0,
                            impl, seg, fuse))
        return

    # auto: every RACE_ORDER leg in a child process of its own, one at a time
    # (one process per chip; this parent never touches a backend). Each
    # finished leg prints its record at once; the fastest training leg is
    # printed again last. Any failed leg, or no training number at all, makes
    # the exit code non-zero.
    self_path = os.path.abspath(__file__)
    best, fails = None, []
    for child_args, child_env in RACE_ORDER:
        leg = " ".join(child_args) + (
            " " + " ".join(f"{k}={v}" for k, v in child_env.items())
            if child_env else "")
        try:
            out = subprocess.run(
                [sys.executable, self_path] + child_args,
                capture_output=True, text=True, timeout=CHILD_TIMEOUT_S,
                cwd=os.path.dirname(self_path),
                env=(dict(os.environ, **child_env) if child_env else None))
        except subprocess.TimeoutExpired:
            fails.append(f"{leg}: timed out after {CHILD_TIMEOUT_S}s")
            continue
        rec = None
        if out.returncode == 0:
            for line in out.stdout.strip().splitlines():
                try:
                    parsed = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(parsed, dict) and parsed.get("metric"):
                    rec = parsed
        if rec is None:
            fails.append(f"{leg}: rc={out.returncode}, "
                         f"stderr tail: {out.stderr[-300:]}")
            continue
        # print, not _emit_bench: the child mirrored its record into obs,
        # and this process must not touch a backend while legs remain
        print(json.dumps(dict(rec, leg=leg)), flush=True)
        # only the training headline contends for best: the io and tiled
        # legs' metrics live on other scales
        if rec["metric"] == "largefluid_train_nodes_per_sec_per_chip" and (
                best is None or rec["value"] > best["value"]):
            best = dict(rec, leg=leg)
    for f in fails:
        print(f"bench: leg failed ({f})", file=sys.stderr)
    if best is not None:
        print(json.dumps(dict(best, legs_failed=[f.split(":", 1)[0]
                                                 for f in fails])))
    else:
        print("bench: no training leg produced a measurement",
              file=sys.stderr)
    if fails or best is None:
        sys.exit(1)


if __name__ == "__main__":
    # config only: the race's parent still initializes no backend
    runtime.configure_compile_cache()
    main()
