"""Remat memory-scaling evidence.

FastEGNN's ``remat`` flag claims to trade recompute FLOPs for the O(E*H)
per-layer activation memory that bounds nodes/chip
(distegnn_tpu/models/fast_egnn.py). Two measurements:

1. PRIMARY (backend-independent, runs anywhere): the byte total of the
   ``jax.vjp`` closure — exactly the residual arrays autodiff saves between
   forward and backward. This is the memory rematerialization eliminates.
2. ``--xla-temp``: ``compiled.memory_analysis().temp_size_in_bytes`` of the
   jitted grad. CAVEAT, measured 2026-08-01: **XLA:CPU's buffer assignment
   reports identical temp with and without remat** (a minimal
   checkpoint-layer repro shows byte-identical arenas, i.e. the CPU
   pipeline undoes or ignores the rematerialization), so this mode is only
   meaningful on TPU, where it has not been run yet.

Usage:
  python scripts/measure_remat_memory.py [--nodes 20000 50000] [--json out]
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def _model_and_loss(n_nodes: int, remat: bool, seg: str):
    import jax

    import bench
    from distegnn_tpu.models.fast_egnn import FastEGNN

    bench.N_NODES = n_nodes
    rng = np.random.default_rng(0)
    batch, n_edges = bench.make_fluid_batch(rng)
    model = FastEGNN(node_feat_nf=3, node_attr_nf=2, edge_attr_nf=2,
                     hidden_nf=64, virtual_channels=3, n_layers=4,
                     compute_dtype="bf16", segment_impl=seg, remat=remat)
    params = model.init(jax.random.PRNGKey(0), batch)

    def loss(p):
        loc, X = model.apply(p, batch)
        return ((loc - batch.target) ** 2 * batch.node_mask[..., None]).sum()

    return params, loss, n_edges


def vjp_residual_bytes(n_nodes: int, remat: bool, seg: str = "scatter") -> dict:
    import jax

    params, loss, n_edges = _model_and_loss(n_nodes, remat, seg)
    _, f_vjp = jax.vjp(loss, params)
    leaves = [x for x in jax.tree.leaves(f_vjp) if hasattr(x, "nbytes")]
    return {"n_nodes": n_nodes, "n_edges": n_edges, "remat": remat,
            "residual_bytes": int(sum(x.nbytes for x in leaves)),
            "residual_arrays": len(leaves)}


def xla_temp_bytes(n_nodes: int, remat: bool, seg: str = "scatter") -> dict:
    import jax

    params, loss, n_edges = _model_and_loss(n_nodes, remat, seg)
    ma = jax.jit(jax.grad(loss)).lower(params).compile().memory_analysis()
    return {"n_nodes": n_nodes, "n_edges": n_edges, "remat": remat,
            "temp_bytes": int(ma.temp_size_in_bytes)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, nargs="+", default=[20000, 50000])
    ap.add_argument("--seg", default="scatter")
    ap.add_argument("--xla-temp", action="store_true",
                    help="also report jitted-grad XLA temp (TPU-meaningful)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax

    rows = []
    for n in args.nodes:
        for remat in (False, True):
            r = vjp_residual_bytes(n, remat, args.seg)
            if args.xla_temp:
                r.update(xla_temp_bytes(n, remat, args.seg))
            rows.append(r)
            print(f"N={n:>7} remat={str(remat):5} "
                  f"residuals={r['residual_bytes'] / 2**30:.3f} GiB "
                  f"({r['residual_arrays']} arrays)"
                  + (f" xla_temp={r['temp_bytes'] / 2**30:.3f} GiB"
                     if args.xla_temp else ""))
        off, on = rows[-2]["residual_bytes"], rows[-1]["residual_bytes"]
        print(f"          -> remat residual reduction {off / max(on, 1):.1f}x")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"backend": jax.default_backend(),
                       "method": "jax.vjp closure bytes (saved residuals); "
                                 "xla temp only meaningful on TPU (see "
                                 "module docstring)",
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
