"""Micro-benchmark: what the BACKWARD of the row-end gather costs on the chip,
at the three cells' own shapes and on row indices from the benchmark's own
generator (``benchmarks/traffic/generate.py``), one candidate lowering beside
the other. A hand tool: no cell runs it, a CPU run of it times nothing.

    python3 scripts/microbench_segsum.py [--shapes fluid113k,fluid800k,nbody,water3d]
        [--candidates today,hint,rule,cumsum,parts,kernel,col] [--steps 10] [--no-layer]
        [--profile]
    python3 scripts/microbench_segsum.py --count [--shapes ...]

Shapes (``--shapes``): ``fluid113k`` one Fluid113K-format cloud (113,140
particles, about 1.64 M edges: ``largefluid_train_g1``), ``fluid800k`` one
cloud of 202,000 particles at the same density (about 2.95 M edges: a chip's
partition of ``largefluid800k_train_g4``, without the METIS cut), ``nbody``
250 complete graphs of 100 bodies (``nbody_train_b250``), ``water3d`` 15 frames
of 7,806 particles within 0.035 (``water3d_train_b15``: 15 x 108,672 edge slots
into 7,808 rows). The cotangent is ``f32[B, E, 67]``, a layer's packed width.

Candidates (``--candidates``), of ``sum_e ct[e] -> node row[e]`` over ascending
``row``:

  today    the transpose autodiff makes of ``jnp.take_along_axis`` with no
           hint (the parent's ``EdgeOps.gather_rows``)
  hint     the same gather carrying ``indices_are_sorted=True``: JAX hands
           the hint to the scatter-add, forward and transpose both know
  rule     ``ops/segment.py:gather_rows_sorted`` with the kernel held off: the
           forward is today's unhinted gather, the backward by a rule of its
           own the scatter-add that carries the hint (PR 33's lowering)
  cumsum   ``gather_rows_cs`` as the tree has it (``segment_impl: cumsum``): a
           GLOBAL f32 prefix, bounds by binary search, two gathers. Timed for
           the record; its differences carry the whole prefix's rounding
  parts    what block-local prefix differences would be made of, each alone:
           the prefix pass (Pallas one-pass kernel, XLA cumsum, a [256, 256]
           lower-triangular matmul a block at ``highest``), the CSR bounds
           (two searchsorted), three N-row gathers out of the [E, 67] table.
           Not below 32,768 rows a graph
  kernel   the tree on a TPU: ``sorted_row_sum`` as the Pallas kernel of
           ``ops/row_sum.py``, for the transpose and the aggregation. First
           its (TILE, BLOCK) variants with the operand's write, each with its
           HBM roofline share (least bytes: the operand, the ids and the
           output, once, at 819 GB/s); the fastest is set for the rest.
           Then, beside ``rule``'s scatter, the aggregation as
           ``EdgeOps.agg_rows_pair`` runs it (``[B, E, 3]`` and ``[B, E,
           64]`` streams, the mask and the count column packed, then
           summed: the packed operand the kernel makes XLA write is in
           the time)
  col      the COL-end transpose, ``sum_e ct[e] -> node col[e]`` over the
           unsorted ``col``: autodiff's transpose of the unhinted
           ``jnp.take_along_axis`` (``col_transpose/today``, the parent's)
           against the col-sorted view (``ops/segment.py`` ``sort_cols`` +
           ``gather_cols_sorted``: one sort, one flat gather of the
           cotangent into col order, ``sorted_row_sum``) with the kernel
           (``view_kernel``) and with the hinted scatter (``view_rule``);
           the view's parts alone (``col_parts/sort``, ``perm_gather``,
           ``kernel``); and the layer gradient with the view
           (``layer_grad/col_view``) and with it held off
           (``layer_grad/col_off``), the kernel on in both

For each: the forward gather, the transpose alone, and (unless ``--no-layer``)
``jax.grad`` of one FastEGNN layer of the cell's configuration with that row
pass. Prints ms a call and ns a row (B x E rows); ``--profile`` traces three
calls of each layer gradient and lists its dearest device ops; the JSON goes
to ``chiprun_out/microbench_segsum.json``. ``--count`` times nothing: it
traces each shape's cell configuration at the cell's step batch and prints
the ``edge/row_sum_kernel`` count (12 on the chip, 0 on the CPU).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WIDTH = 67                      # a layer's packed width: H = 64 products + 3 coordinates
SHAPES = ("fluid113k", "fluid800k", "nbody", "water3d")
CANDIDATES = ("today", "hint", "rule", "cumsum", "parts", "kernel", "col")
# (TILE, BLOCK) of ops/row_sum.py tried alone; the fastest is kept. PR 36's
# first sweep also tried the operand as [E, F] rows and the three bf16 terms
# as three passes or at 'highest': slower everywhere (PERF.md section 6)
KERNEL_VARIANTS = ((512, 128), (1024, 128), (2048, 128), (1024, 256), (2048, 256))
HBM_BYTES_PER_S = 819e9         # v5e (benchmarks/peaks.json)


def timed(fn, *args, warmup=2, steps=10):
    """ms a call, the device's work waited for inside the timed region."""
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3


# a chip's step batch in each cell: graphs, padded nodes, padded edge slots
CELL_SHAPES = {"fluid113k": (1, 113_144, 1_639_040), "fluid800k": (1, 202_000, 2_936_576),
               "nbody": (250, 104, 9_984), "water3d": (15, 7_808, 108_672)}


def make_batch(shape: str, small: bool, cell_shape: bool = False):
    """(GraphBatch, the cell's config file): graphs from the benchmark's
    generator, built, ordered and padded as the cell's loader does. With
    ``cell_shape`` (and ``small``) the few small graphs are repeated and
    padded to the cell's step batch, :data:`CELL_SHAPES`: shapes that trace
    as the cell's do, for ``--count``."""
    from benchmarks.traffic.generate import make_samples
    from distegnn_tpu.ops import graph

    def pad_graphs(graphs, **kw):
        if cell_shape:
            B, N, E = CELL_SHAPES[shape]
            graphs = [graphs[i % len(graphs)] for i in range(B)]
            kw.update(max_nodes=N, max_edges=E)
        return graph.pad_graphs(graphs, **kw)

    traffic = os.path.join(ROOT, "benchmarks", "traffic")
    if shape == "nbody":
        from distegnn_tpu.data import build_nbody_graph

        with open(os.path.join(traffic, "nbody_b250.json")) as f:
            mix = json.load(f)
        mix["samples_train"] = 8 if small else 250
        if small:
            mix["n_bodies"] = 12
        s = make_samples(mix)
        graphs = [build_nbody_graph(s["loc"][i], s["vel"][i], s["charges"][i], s["target"][i])
                  for i in range(mix["samples_train"])]
        return (pad_graphs(graphs, node_bucket=8, edge_bucket=128),
                "benchmarks/configs/nbody_fastegnn.yaml")
    if shape == "water3d":
        from benchmarks.reference.water3d_graphs import water_graph
        from benchmarks.traffic.generate_water3d import make_samples as water_samples

        with open(os.path.join(traffic, "water3d_b15.json")) as f:
            mix = json.load(f)
        mix["trajectories"] = 1
        if small:
            mix["particles"], mix["frames_per_trajectory"] = 600, 3
        graphs = []
        for sample in water_samples(mix):
            r = water_graph(sample, mix["radius"])
            order = np.lexsort((r["col"], r["row"]))          # row-sorted, as the loader's
            graphs.append({"node_feat": r["feat"], "node_attr": r["attr"], "loc": r["loc"],
                           "vel": r["vel"], "target": r["target"],
                           "edge_index": np.stack([r["row"], r["col"]])[:, order],
                           "edge_attr": r["eattr"][order]})
        return (pad_graphs(graphs, node_bucket=8, edge_bucket=128,
                           max_edges=None if small else CELL_SHAPES[shape][2]),
                "benchmarks/configs/water3d_fastegnn.yaml")
    from distegnn_tpu.data.fluid113k import build_fluid_graph
    from distegnn_tpu.data.partition import split_graph
    from distegnn_tpu.ops.order import morton_reorder_graph

    with open(os.path.join(traffic, "largefluid_pool8.json")) as f:
        mix = json.load(f)
    mix["graphs_pool"] = 1
    mix["particles"] = 3000 if small else {"fluid113k": 113140, "fluid800k": 202000}[shape]
    s = make_samples(mix)[0]
    whole = build_fluid_graph(s["loc"], s["vel"], s["viscosity"], s["mass"], s["target"])
    part = split_graph(whole, 1, "metis", mix["radius"], outer_radius=mix["radius"])[0]
    return (pad_graphs([morton_reorder_graph(part)], node_bucket=8, edge_bucket=128),
            "benchmarks/configs/largefluid_distegnn.yaml")


def row_gathers() -> dict:
    """``(table [B, N, F], row [B, E]) -> [B, E, F]`` of each candidate."""
    import jax
    import jax.numpy as jnp
    from distegnn_tpu.ops.segment import gather_rows_cs, gather_rows_sorted

    return {
        "today": lambda t, r: jnp.take_along_axis(t, r[..., None], axis=1),
        "hint": lambda t, r: jax.vmap(
            lambda h, i: h.at[i].get(indices_are_sorted=True, mode="fill"))(t, r),
        "rule": gather_rows_sorted,
        "cumsum": lambda t, r: jax.vmap(gather_rows_cs)(t, r),
        "kernel": gather_rows_sorted,
    }


@contextlib.contextmanager
def kernel_engaged(on: bool):
    """``ops/segment.py``'s sorted sums as the Pallas kernel (``on``) or as
    the hinted scatter-add, whatever the backend and size."""
    from distegnn_tpu.ops import segment

    kept = segment._row_sum_kernel_engages
    segment._row_sum_kernel_engages = lambda rows: on
    try:
        yield
    finally:
        segment._row_sum_kernel_engages = kept


@contextlib.contextmanager
def row_pass(candidate: str):
    """``EdgeOps.gather_rows`` of a plain row-sorted batch as ``candidate``
    lowers it: ``rule``, ``cumsum`` and ``kernel`` are the tree's own branches
    (``rule`` with the kernel held off), ``today`` (the parent's expression)
    and ``hint`` are put in its place. The aggregation is the kernel under
    ``kernel`` and the hinted scatter-add under every other."""
    from distegnn_tpu.ops.blocked import EdgeOps

    with kernel_engaged(candidate == "kernel"):
        if candidate in ("rule", "cumsum", "kernel"):
            yield
            return
        kept, gather = EdgeOps.gather_rows, row_gathers()[candidate]
        EdgeOps.gather_rows = lambda self, data: gather(data, self.g.row)
        try:
            yield
        finally:
            EdgeOps.gather_rows = kept


@contextlib.contextmanager
def col_view_engaged(on: bool):
    """FastEGNN's col-sorted view (``ops/blocked.py`` ``col_sorted_view``) as
    the tree makes it (``on``) or held off, so that the col gather transposes
    as autodiff's unsorted scatter-add."""
    from distegnn_tpu.models import fast_egnn

    kept = fast_egnn.col_sorted_view
    if not on:
        fast_egnn.col_sorted_view = lambda g: None
    try:
        yield
    finally:
        fast_egnn.col_sorted_view = kept


def dearest_ops(fn, args, calls: int = 3, top: int = 8) -> list:
    """``[(op, ms a call)]`` of the device ops that took longest in ``calls``
    traced calls of ``fn``."""
    import collections
    import shutil
    import tempfile

    import jax
    from benchmarks import tracing

    trace_dir = tempfile.mkdtemp(prefix="microbench_trace_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        devices, _ = tracing.read_planes(tracing.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    total = collections.Counter()
    for plane in devices.values():
        for name, start, end in plane["ops"]:
            total[name] += end - start
    return [(name, ns / calls / 1e6) for name, ns in total.most_common(top)]


def kernel_variants(ct, row, N: int, steps: int, report) -> dict:
    """Time ``ops/row_sum.row_sum`` over the batch, its operand written by a
    producer in the same program (``ct * s``: in the layout the kernel asks
    for, as a step's producer writes it), with its HBM roofline share (least
    bytes: the operand, the ids and the output, once) and its worst segment
    against the sum in float64: at the first shape each of
    :data:`KERNEL_VARIANTS`, the fastest then set as ``row_sum``'s module
    constants for everything after; at later shapes the kept one alone. The
    kept one's dearest device ops are listed (the kernel alone)."""
    import jax
    import jax.numpy as jnp
    from distegnn_tpu.ops import row_sum

    B, E, F = ct.shape
    least = (ct.size * 4 + row.size * 4 + B * N * F * 4) / HBM_BYTES_PER_S * 1e3
    ids = np.asarray(row + (jnp.arange(B, dtype=jnp.int32) * N)[:, None]).reshape(-1)
    d64 = np.asarray(ct, np.float64).reshape(B * E, F)
    want = np.zeros((B * N, F)); np.add.at(want, ids, d64)
    scale = np.zeros((B * N, F)); np.add.at(scale, ids, np.abs(d64))
    one = jnp.float32(1.0)
    found = {}
    swept = getattr(row_sum, "_microbench_kept", None)
    variants = [(row_sum.TILE, row_sum.BLOCK)] if swept else KERNEL_VARIANTS
    fns = {}
    for tile, block in variants:
        name = f"kernel/T{tile}_R{block}"
        fns[name] = fn = jax.jit(lambda d, s, r, tile=tile, block=block: row_sum.row_sum(
            d * s, r, N, tile=tile, block=block))
        try:
            ms = timed(fn, ct, one, row, steps=steps)
        except Exception as e:                  # a variant Mosaic refuses is a finding
            print(f"{name:28s} refused: {str(e)[:300]}", flush=True)
            found[name] = {"error": str(e)[:2000]}
            continue
        report(name, ms)
        got = np.asarray(fn(ct, one, row)).reshape(B * N, F)
        err = float((np.abs(got - want) / np.maximum(scale, 1e-30)).max())
        found[name] = {"ms": ms, "hbm_roofline_pct": 100 * least / ms,
                       "worst_segment_ulp": err / 2.0 ** -24}
        print(f"{'':28s} HBM roofline {100 * least / ms:6.2f}%  worst segment "
              f"{err / 2.0 ** -24:.2f} ulp of sum|ct|", flush=True)
    timed_ok = {k: v for k, v in found.items() if "ms" in v}
    if not timed_ok:
        return found
    best = min(timed_ok, key=lambda k: timed_ok[k]["ms"])
    if not swept:
        t, r = best.split("/")[1].split("_")
        row_sum.TILE, row_sum.BLOCK = int(t[1:]), int(r[1:])
        print(f"kept {best}: TILE={row_sum.TILE} BLOCK={row_sum.BLOCK}", flush=True)
        found["kept"] = row_sum._microbench_kept = best
    found["dearest_ops"] = ops = dearest_ops(fns[best], (ct, one, row))
    for name, ms in ops[:4]:
        print(f"    {ms:8.3f} ms  {name[:200]}", flush=True)
    return found


def aggregate(g, candidate: str):
    """jitted ``EdgeOps.agg_rows_pair`` of one layer (``a`` = the packed
    cotangent's first 3 columns, ``b`` the next 64, as streams a layer
    makes) under ``candidate``'s lowering."""
    import jax
    from distegnn_tpu.ops.blocked import EdgeOps

    ops = EdgeOps(g)

    def agg(ct, mask):
        with kernel_engaged(candidate == "kernel"):
            a, b = ct[..., :3] * mask[..., None], ct[..., 3:]
            return ops.agg_rows_pair(a, b, a_mean=False)

    return jax.jit(agg)


def layer_grad(config_file: str, g, candidate: str, col_view: bool = True):
    """jitted ``params, g -> grad`` of one FastEGNN layer of the cell's
    configuration (its dtype, remat and matmul precision), and the params;
    ``col_view`` False holds the col-sorted view off."""
    import jax
    import jax.numpy as jnp
    from benchmarks.drivers import common
    from distegnn_tpu.models.registry import get_model

    path = os.path.join(ROOT, config_file)
    cfg = common.load_program_config(path, common.load_meta(path), 0)
    cfg.model.n_layers = 1
    cfg.model.segment_impl = "cumsum" if candidate == "cumsum" else "scatter"
    model = get_model(cfg.model, dataset_name=cfg.data.dataset_name)

    def loss(params, g):
        x, _ = model.apply(params, g)
        return jnp.sum((x - g.target) ** 2 * g.node_mask[..., None])

    with row_pass(candidate), col_view_engaged(col_view):
        params = model.init(jax.random.PRNGKey(0), g)
        fn = jax.jit(jax.grad(loss))
        fn.lower(params, g)            # traced here, under the candidate's row pass
        jax.block_until_ready(fn(params, g))
    return fn, params


def col_pass(g, ct, steps: int, report) -> dict:
    """The ``col`` candidate's transposes and parts (see the module's
    docstring), each with its worst segment against autodiff's transpose,
    relative to the segment's sum of absolute values."""
    import jax
    import jax.numpy as jnp
    from distegnn_tpu.ops.segment import gather_cols_sorted, sort_cols, sorted_row_sum

    B, E = g.col.shape
    N = g.node_mask.shape[1]
    col, one = g.col, jnp.float32(1.0)
    zeros = jnp.zeros((B, N, WIDTH), jnp.float32)

    def today(ct, s, c):
        return jax.vjp(lambda t: jnp.take_along_axis(t, c[..., None], axis=1),
                       zeros)[1](ct * s)[0]

    def view(ct, s, c):
        cs, co = sort_cols(c)
        return jax.vjp(lambda t: gather_cols_sorted(t, c, cs, co), zeros)[1](ct * s)[0]

    fn = jax.jit(today)
    report("col_transpose/today", timed(fn, ct, one, col, steps=steps))
    want = np.asarray(fn(ct, one, col))
    scale = np.asarray(jax.jit(lambda ct, c: jax.vmap(
        lambda d, i: jnp.zeros((N, WIDTH), jnp.float32).at[i].add(jnp.abs(d)))(ct, c))(
            ct, col))
    found = {}
    for on, name in ((True, "view_kernel"), (False, "view_rule")):
        with kernel_engaged(on):
            # a fresh function each: jit keys its cache on the function
            fn = jax.jit(lambda ct, s, c: view(ct, s, c))
            report(f"col_transpose/{name}", timed(fn, ct, one, col, steps=steps))
            err = float((np.abs(np.asarray(fn(ct, one, col)) - want)
                         / np.maximum(scale, 1e-30)).max())
        found[f"rel_err/col_{name}"] = err
        print(f"worst segment |col {name} - today| / sum|ct| = {err:.3e}", flush=True)
    sort = jax.jit(sort_cols)
    report("col_parts/sort", timed(sort, col, steps=steps))
    cs, co = sort(col)
    perm = jax.jit(lambda ct, s, co: (ct * s).reshape(B * E, WIDTH).at[
        (co + (jnp.arange(B, dtype=co.dtype) * E)[:, None]).reshape(-1)].get(
            mode="promise_in_bounds", unique_indices=True).reshape(B, E, WIDTH))
    report("col_parts/perm_gather", timed(perm, ct, one, co, steps=steps))
    with kernel_engaged(True):
        fn = jax.jit(lambda d, s, r: sorted_row_sum(d * s, r, N))
        report("col_parts/kernel", timed(fn, perm(ct, one, co), one, cs, steps=steps))
    return found


def count_kernel_calls(shape: str) -> float:
    """``edge/row_sum_kernel`` over ONE traced gradient of the cell's model
    configuration at the cell's step-batch shapes (:data:`CELL_SHAPES`;
    traced only, nothing compiled or run): ``3 x L`` where the kernel engages
    (a TPU, ``B x E`` over the row minimum), 0 elsewhere."""
    import jax
    import jax.numpy as jnp
    from benchmarks.drivers import common
    from distegnn_tpu import obs
    from distegnn_tpu.models.registry import get_model

    g, config_file = make_batch(shape, small=True, cell_shape=True)
    path = os.path.join(ROOT, config_file)
    cfg = common.load_program_config(path, common.load_meta(path), 0)
    model = get_model(cfg.model, dataset_name=cfg.data.dataset_name)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), g)

    def loss(params, g):
        x, _ = model.apply(params, g)
        return jnp.sum((x - g.target) ** 2 * g.node_mask[..., None])

    counter = obs.get_registry().counter("edge/row_sum_kernel")
    before = counter.value
    jax.make_jaxpr(jax.grad(loss))(params, g)
    n = counter.value - before
    print(f"row_sum_kernel/{shape} B,N,E={g.row.shape[0]},{g.node_mask.shape[1]},"
          f"{g.row.shape[1]} layers={cfg.model.n_layers}: {n:g} kernel calls a traced "
          f"step", flush=True)
    return n


def bench_shape(shape: str, candidates, steps: int, layer: bool, small: bool,
                profile: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from distegnn_tpu.ops.cumsum import _MIN_PALLAS_ROWS, prefix_sum
    from distegnn_tpu.ops.segment import _cs_bounds

    g, config_file = make_batch(shape, small)
    g = jax.tree_util.tree_map(jnp.asarray, g)
    B, E = g.row.shape
    N = g.node_mask.shape[1]
    rows = B * E
    out = {"B": B, "E": E, "N": N, "width": WIDTH, "edges_sorted": bool(g.edges_sorted),
           "real_edges": int(np.asarray(g.edge_mask).sum())}
    print(f"== {shape}: B={B} E={E} N={N} width={WIDTH} real edges {out['real_edges']} "
          f"edges_sorted={g.edges_sorted}", flush=True)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((B, N, WIDTH)), jnp.float32)
    # mean 1, unit variance: the case a prefix's rounding shows on (ISSUE 33)
    ct = jnp.asarray(1.0 + rng.standard_normal((B, E, WIDTH)), jnp.float32)

    gathers = row_gathers()

    def report(name, ms):
        out[name] = {"ms": ms, "ns_per_row": ms * 1e6 / rows}
        print(f"{name:28s} {ms:9.3f} ms  {ms * 1e6 / rows:7.2f} ns/row", flush=True)

    if "kernel" in candidates:
        out["kernel_variants"] = kernel_variants(ct, g.row, N, steps, report)

    if "col" in candidates:
        out.update(col_pass(g, ct, steps, report))
        for view in (True, False) if layer else ():
            name = f"layer_grad/col_{'view' if view else 'off'}"
            fn, params = layer_grad(config_file, g, "kernel", col_view=view)
            report(name, timed(fn, params, g, steps=steps))
            if profile:
                out[name]["dearest_ops"] = ops = dearest_ops(fn, (params, g))
                for op, ms in ops:
                    print(f"    {ms:8.3f} ms  {op[:230]}", flush=True)

    sums = {}
    for c in candidates:
        if c not in gathers:
            continue
        gather = gathers[c]
        with row_pass(c):
            if c not in ("cumsum", "kernel"):
                report(f"forward_gather/{c}", timed(jax.jit(gather), table, g.row,
                                                    steps=steps))
            # the cotangent made inside the program, as a step makes it: a
            # scatter takes its producer in, a kernel's operand is written
            transpose = jax.jit(lambda ct, s, r, gather=gather: jax.vjp(
                lambda t: gather(t, r), jnp.zeros((B, N, WIDTH), jnp.float32))[1](ct * s)[0])
            scale = jnp.float32(1.0)
            report(f"transpose/{c}", timed(transpose, ct, scale, g.row, steps=steps))
            sums[c] = np.asarray(transpose(ct, scale, g.row))
        if c in ("rule", "kernel"):
            report(f"aggregate/{c}", timed(aggregate(g, c), ct, g.edge_mask, steps=steps))
        if layer:
            fn, params = layer_grad(config_file, g, c)
            report(f"layer_grad/{c}", timed(fn, params, g, steps=steps))
            if profile:
                out[f"layer_grad/{c}"]["dearest_ops"] = ops = dearest_ops(fn, (params, g))
                for name, ms in ops:
                    print(f"    {ms:8.3f} ms  {name[:230]}", flush=True)
    base = "today" if "today" in sums else "rule" if "rule" in sums else None
    if base:
        # worst segment, relative to the segment's sum of absolute values
        scale = np.asarray(jax.jit(lambda ct, r: jax.vmap(
            lambda c, i: jnp.zeros((N, WIDTH), jnp.float32).at[i].add(jnp.abs(c)))(ct, r))(
                ct, g.row))
        for c, s in sums.items():
            if c != base:
                err = float((np.abs(s - sums[base]) / np.maximum(scale, 1e-30)).max())
                out[f"rel_err/{c}"] = err
                print(f"worst segment |{c} - {base}| / sum|ct| = {err:.3e}", flush=True)

    if "parts" in candidates and E >= _MIN_PALLAS_ROWS and B == 1:
        x, r = ct[0], g.row[0]
        tri = jnp.tril(jnp.ones((256, 256), jnp.float32))

        def prefix_tri(x):
            xb = jnp.pad(x, ((0, -E % 256), (0, 0))).reshape(-1, 256, WIDTH)
            return jnp.einsum("ij,bjf->bif", tri, xb,
                              precision=jax.lax.Precision.HIGHEST).reshape(-1, WIDTH)[:E]

        impls = {"parts/prefix_xla_cumsum": lambda x: prefix_sum(x, impl="xla"),
                 "parts/prefix_tri256_highest": prefix_tri}
        if jax.default_backend() == "tpu" or small:
            impls["parts/prefix_pallas_one_pass"] = lambda x: prefix_sum(x, impl="pallas")
        for name, f in impls.items():
            report(name, timed(jax.jit(f), x, steps=steps))
        bounds = jax.jit(lambda r: _cs_bounds(r, N))
        report("parts/bounds_2_searchsorted", timed(bounds, r, steps=steps))
        starts, ends = bounds(r)
        take3 = jax.jit(lambda c, s, e: (jnp.take(c, jnp.maximum(e - 1, 0), axis=0)
                                         - jnp.take(c, jnp.maximum(s - 1, 0), axis=0)
                                         + jnp.take(c, jnp.minimum(e, E - 1), axis=0)))
        report("parts/3_takes_of_N_rows", timed(take3, x, starts, ends, steps=steps))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--candidates", default=",".join(CANDIDATES))
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--no-layer", action="store_true", help="skip jax.grad of one layer")
    ap.add_argument("--profile", action="store_true",
                    help="trace each layer gradient and list its dearest device ops")
    ap.add_argument("--count", action="store_true",
                    help="only count edge/row_sum_kernel in one traced gradient a shape "
                         "at the cell's step-batch shapes (nothing compiled)")
    ap.add_argument("--small", action="store_true",
                    help="toy sizes, to try the tool's paths on the CPU (times mean nothing)")
    args = ap.parse_args(argv)
    shapes, candidates = args.shapes.split(","), args.candidates.split(",")
    for name, known in ((shapes, SHAPES), (candidates, CANDIDATES)):
        bad = [n for n in name if n not in known]
        if bad:
            ap.error(f"unknown {bad}; known: {', '.join(known)}")

    import jax

    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "steps": args.steps, "shapes": {}}
    print(f"device: {dev.platform} ({dev.device_kind}) x {jax.device_count()}", flush=True)
    if args.count:
        result["row_sum_kernel"] = {shape: count_kernel_calls(shape) for shape in shapes}
        shapes = []
    for shape in shapes:
        result["shapes"][shape] = bench_shape(shape, candidates, args.steps,
                                              not args.no_layer, args.small, args.profile)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = "microbench_segsum_count.json" if args.count else "microbench_segsum.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
