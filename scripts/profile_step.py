"""Step-time breakdown + MFU for the bench workload.

Times the LargeFluid-shape FastEGNN train step end-to-end and in pieces
(forward, forward+loss, grad, MMD on/off), reports XLA cost-analysis FLOPs and
an MFU estimate, and optionally captures a jax.profiler trace.

Usage:
  python scripts/profile_step.py [--trace DIR] [--steps 10]

Prints a JSON breakdown.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

# TPU v5e (v5 lite) peak: 197 TFLOP/s bf16, ~98.5 TFLOP/s fp32 (public spec).
PEAK_FLOPS = {"bf16": 197e12, "f32": 98.5e12}


def timed(fn, *args, warmup=3, steps=10):
    """Sync via a 1-element device->host fetch inside the timed region."""
    import jax
    import jax.numpy as jnp

    def sync(o):
        leaf = jax.tree.leaves(o)[0]
        np.asarray(jnp.ravel(leaf)[0])

    out = None
    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps


def cost_flops(jitted, *args):
    try:
        an = jitted.lower(*args).compile().cost_analysis()
        if isinstance(an, list):
            an = an[0]
        return float(an.get("flops", float("nan")))
    except Exception:
        return float("nan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None, help="dir for jax.profiler trace")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--nodes", type=int, default=113_140)
    ap.add_argument("--bf16", action="store_true", help="compute_dtype='bf16'")
    ap.add_argument("--edge-block", type=int, default=0,
                    help="blocked edge layout (0 = plain)")
    ap.add_argument("--impl", default="einsum", choices=["einsum", "pallas"],
                    help="blocked-op lowering (with --edge-block)")
    ap.add_argument("--seg", default="scatter", choices=["scatter", "cumsum", "ell"],
                    help="plain-layout aggregation lowering")
    args = ap.parse_args()

    import jax

    from bench import HIDDEN, LAYERS, CHANNELS, make_fluid_batch
    import bench as bench_mod

    bench_mod.N_NODES = args.nodes
    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.train import TrainState, make_optimizer, make_train_step
    from distegnn_tpu.train.loss import masked_mse, mmd_loss

    rng = np.random.default_rng(0)
    batch, n_edges = make_fluid_batch(rng, edge_block=args.edge_block,
                                      pairing=(args.seg in ("cumsum", "ell")))
    dev = jax.devices()[0]
    batch = jax.device_put(batch, dev)

    model = FastEGNN(node_feat_nf=3, node_attr_nf=2, edge_attr_nf=2,
                     hidden_nf=HIDDEN, virtual_channels=CHANNELS, n_layers=LAYERS,
                     compute_dtype="bf16" if args.bf16 else None,
                     blocked_impl=args.impl, segment_impl=args.seg)
    params = model.init(jax.random.PRNGKey(0), batch)
    tx = make_optimizer(5e-4, weight_decay=1e-12, clip_norm=0.3)
    state = TrainState.create(params, tx)
    key = jax.random.PRNGKey(7)

    fwd = jax.jit(model.apply)
    step_mmd = jax.jit(make_train_step(model, tx, mmd_weight=0.01, mmd_sigma=3.0,
                                       mmd_samples=50))
    step_nommd = jax.jit(make_train_step(model, tx, mmd_weight=0.0, mmd_sigma=3.0,
                                         mmd_samples=50))

    def loss_only(p, b, k):
        pred, vloc = model.apply(p, b)
        return masked_mse(pred, b.target, b.node_mask) + 0.01 * mmd_loss(
            vloc, b.target, b.node_mask, k, 3.0, 50)

    grad_fn = jax.jit(jax.grad(loss_only))
    mmd_only = jax.jit(lambda v, b, k: mmd_loss(v, b.target, b.node_mask, k, 3.0, 50))

    vloc = jnp_zeros = None
    import jax.numpy as jnp
    vloc = jnp.zeros((1, 3, CHANNELS))

    from bench import layout_tag

    res = {"n_nodes": args.nodes, "n_edges": int(n_edges),
           "platform": dev.platform, "device": str(dev.device_kind),
           "layout": layout_tag(args.edge_block, args.impl, args.seg)}
    res["t_forward_ms"] = timed(fwd, params, batch, steps=args.steps) * 1e3
    res["t_grad_ms"] = timed(grad_fn, params, batch, key, steps=args.steps) * 1e3
    res["t_step_full_ms"] = timed(step_mmd, state, batch, key, steps=args.steps) * 1e3
    res["t_step_nommd_ms"] = timed(step_nommd, state, batch, key, steps=args.steps) * 1e3
    res["t_mmd_only_ms"] = timed(mmd_only, vloc, batch, key, steps=args.steps) * 1e3
    res["t_optimizer_ms"] = res["t_step_full_ms"] - res["t_grad_ms"] - res["t_mmd_only_ms"]

    res["flops_forward"] = cost_flops(fwd, params, batch)
    res["flops_step"] = cost_flops(step_mmd, state, batch, key)
    step_s = res["t_step_full_ms"] / 1e3
    res["achieved_tflops"] = res["flops_step"] / step_s / 1e12
    res["mfu_vs_f32_peak"] = res["flops_step"] / step_s / PEAK_FLOPS["f32"]
    res["mfu_vs_bf16_peak"] = res["flops_step"] / step_s / PEAK_FLOPS["bf16"]
    res["nodes_per_sec"] = args.nodes / step_s

    if args.trace:
        with jax.profiler.trace(args.trace):
            for i in range(3):
                state, m = step_mmd(state, batch, jax.random.PRNGKey(i))
            jax.block_until_ready(m["loss"])
        res["trace_dir"] = args.trace

    print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in res.items()}, indent=2))


if __name__ == "__main__":
    main()
