"""Micro-benchmark: what the BACKWARD of the row-end gather costs on the chip,
at the three cells' own shapes and on row indices from the benchmark's own
generator (``benchmarks/traffic/generate.py``), one candidate lowering beside
the other. A hand tool: no cell runs it, a CPU run of it times nothing.

    python3 scripts/microbench_segsum.py [--shapes fluid113k,fluid800k,nbody]
        [--candidates today,hint,rule,cumsum,parts] [--steps 10] [--no-layer]
        [--profile]

Shapes (``--shapes``): ``fluid113k`` one Fluid113K-format cloud (113,140
particles, about 1.64 M edges: ``largefluid_train_g1``), ``fluid800k`` one
cloud of 202,000 particles at the same density (about 2.95 M edges: a chip's
partition of ``largefluid800k_train_g4``, without the METIS cut), ``nbody``
250 complete graphs of 100 bodies (``nbody_train_b250``). The cotangent is
``f32[B, E, 67]``, a layer's packed width.

Candidates (``--candidates``), of ``sum_e ct[e] -> node row[e]`` over ascending
``row``:

  today    the transpose autodiff makes of ``jnp.take_along_axis`` with no
           hint (the parent's ``EdgeOps.gather_rows``)
  hint     the same gather carrying ``indices_are_sorted=True``: JAX hands
           the hint to the scatter-add, forward and transpose both know
  rule     the tree's ``ops/segment.py:gather_rows_sorted``: the forward is
           today's unhinted gather, the backward by a rule of its own the
           scatter-add that carries the hint (``sorted_row_sum``)
  cumsum   ``gather_rows_cs`` as the tree has it (``segment_impl: cumsum``): a
           GLOBAL f32 prefix, bounds by binary search, two gathers. Timed for
           the record; its differences carry the whole prefix's rounding
  parts    what block-local prefix differences would be made of, each alone:
           the prefix pass (Pallas one-pass kernel, XLA cumsum, a [256, 256]
           lower-triangular matmul a block at ``highest``), the CSR bounds
           (two searchsorted), three N-row gathers out of the [E, 67] table.
           Not below 32,768 rows a graph

For each: the forward gather, the transpose alone, and (unless ``--no-layer``)
``jax.grad`` of one FastEGNN layer of the cell's configuration with that row
pass. Prints ms a call and ns a row (B x E rows); ``--profile`` traces three
calls of each layer gradient and lists its dearest device ops; the JSON goes
to ``chiprun_out/microbench_segsum.json``.
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
SHAPES = ("fluid113k", "fluid800k", "nbody")
CANDIDATES = ("today", "hint", "rule", "cumsum", "parts")


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


def make_batch(shape: str, small: bool):
    """(GraphBatch, the cell's config file): graphs from the benchmark's
    generator, built, ordered and padded as the cell's loader does."""
    from benchmarks.traffic.generate import make_samples
    from distegnn_tpu.ops.graph import pad_graphs

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
    }


@contextlib.contextmanager
def row_pass(candidate: str):
    """``EdgeOps.gather_rows`` of a plain row-sorted batch as ``candidate``
    lowers it: ``rule`` and ``cumsum`` are the tree's own branches, ``today``
    (the parent's expression) and ``hint`` are put in its place."""
    from distegnn_tpu.ops.blocked import EdgeOps

    if candidate in ("rule", "cumsum"):
        yield
        return
    kept, gather = EdgeOps.gather_rows, row_gathers()[candidate]
    EdgeOps.gather_rows = lambda self, data: gather(data, self.g.row)
    try:
        yield
    finally:
        EdgeOps.gather_rows = kept


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


def layer_grad(config_file: str, g, candidate: str):
    """jitted ``params, g -> grad`` of one FastEGNN layer of the cell's
    configuration (its dtype, remat and matmul precision), and the params."""
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

    with row_pass(candidate):
        params = model.init(jax.random.PRNGKey(0), g)
        fn = jax.jit(jax.grad(loss))
        fn.lower(params, g)            # traced here, under the candidate's row pass
        jax.block_until_ready(fn(params, g))
    return fn, params


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

    sums = {}
    for c in candidates:
        if c not in gathers:
            continue
        gather = gathers[c]
        if c != "cumsum":
            report(f"forward_gather/{c}", timed(jax.jit(gather), table, g.row, steps=steps))
        transpose = jax.jit(lambda ct, r, gather=gather: jax.vjp(
            lambda t: gather(t, r), jnp.zeros((B, N, WIDTH), jnp.float32))[1](ct)[0])
        report(f"transpose/{c}", timed(transpose, ct, g.row, steps=steps))
        sums[c] = np.asarray(transpose(ct, g.row))
        if layer:
            fn, params = layer_grad(config_file, g, c)
            report(f"layer_grad/{c}", timed(fn, params, g, steps=steps))
            if profile:
                out[f"layer_grad/{c}"]["dearest_ops"] = ops = dearest_ops(fn, (params, g))
                for name, ms in ops:
                    print(f"    {ms:8.3f} ms  {name[:230]}", flush=True)
    if "today" in sums:
        # worst segment, relative to the segment's sum of absolute values
        scale = np.asarray(jax.jit(lambda ct, r: jax.vmap(
            lambda c, i: jnp.zeros((N, WIDTH), jnp.float32).at[i].add(jnp.abs(c)))(ct, r))(
                ct, g.row))
        for c, s in sums.items():
            if c != "today":
                err = float((np.abs(s - sums["today"]) / np.maximum(scale, 1e-30)).max())
                out[f"rel_err/{c}"] = err
                print(f"worst segment |{c} - today| / sum|ct| = {err:.3e}", flush=True)

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
    for shape in shapes:
        result["shapes"][shape] = bench_shape(shape, candidates, args.steps,
                                              not args.no_layer, args.small, args.profile)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "microbench_segsum.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
