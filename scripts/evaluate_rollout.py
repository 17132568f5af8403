"""Autoregressive rollout MSE evaluation (the BASELINE.json "rollout MSE"
surface).

The reference evaluates one-step MSE only; this drives the framework's
on-device rollout (distegnn_tpu/rollout.py: predict -> rebuild the radius
graph on device -> next step, all inside one lax.scan) against ground-truth
trajectory frames and reports MSE per horizon.

Wired datasets (dispatch on config data.dataset_name):
  nbody*   — raw loc/vel/charges .npy trajectories; full graph emulated with
             a radius larger than the system; horizons keyed by FRAME index.
  Water-3D — h5 trajectories, multi-step (--max-steps) radius-graph rollout;
             horizons keyed by rollout STEP (each spanning delta_t frames);
             rollout displacement rescaled to the pipeline's one-frame
             velocity convention.
  Fluid113K — zstd/msgpack simulations (the headline dataset);
             horizons keyed by rollout STEP; velocity convention converted
             with a data-estimated frame duration.

Usage:
  python scripts/evaluate_rollout.py --config_path configs/nbody_fastegnn.yaml \
      [--checkpoint logs/.../best_model.ckpt] [--samples 50] [--split test]

Prints one JSON line: {"metric": "rollout_mse", "horizons": {frame: mse}, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def evaluate_nbody_rollout(config, checkpoint=None, samples=50, split="test",
                           edge_block=256, seed=0):
    """Rollout the n-body test trajectories; returns {horizon_frame: mse}."""
    import jax
    import jax.numpy as jnp

    from distegnn_tpu.data.nbody import _find_tag
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.ops.graph import _round_up
    from distegnn_tpu.rollout import make_rollout_fn

    base = os.path.join(config.data.data_dir, config.data.dataset_name)
    tag = _find_tag(base, split)
    loc = np.load(os.path.join(base, f"loc_{split}_{tag}.npy"))[:samples]
    vel = np.load(os.path.join(base, f"vel_{split}_{tag}.npy"))[:samples]
    charges = np.load(os.path.join(base, f"charges_{split}_{tag}.npy"))[:samples]
    num, T, n, _ = loc.shape
    f0, fT = config.data.frame_0, config.data.frame_T
    delta = fT - f0
    steps = max((T - 1 - f0) // delta, 1)
    horizons = [f0 + (k + 1) * delta for k in range(steps) if f0 + (k + 1) * delta < T]
    if not horizons:
        raise ValueError(
            f"trajectory too short to evaluate: T={T} frames, first horizon "
            f"would be frame {f0 + delta} (frame_0={f0}, delta={delta})")

    N = _round_up(n, edge_block)
    node_mask = np.zeros((N,), np.float32)
    node_mask[:n] = 1.0

    # full graph (radius -1) emulated with a radius larger than any system
    # extent; real radius configs pass through unchanged
    radius = float(config.data.radius)
    if radius <= 0:
        radius = float(np.abs(loc).max()) * 2.0 + 1.0
    max_degree = max(_round_up(n - 1, 2), 2)
    while (max_degree * edge_block) % 512:
        max_degree += 2

    model = get_model(config.model, dataset_name=config.data.dataset_name)
    rollout = jax.jit(
        make_rollout_fn(model, radius=radius, max_degree=max_degree,
                        max_per_cell=N,
                        feature_fn=_speed_plus_static_feature,
                        edge_block=edge_block),
        static_argnums=(4,))

    mask_j = jnp.asarray(node_mask)
    mse_acc = {h: 0.0 for h in horizons}
    params = _init_params(model, checkpoint, config, seed)
    for k in range(num):
        # charges passed per-sample as a rollout ARGUMENT (not a closure), so
        # the jitted rollout is compiled once and reused across samples;
        # normalization matches the training pipeline (build_nbody_graph:
        # charges / charges.max(), no abs)
        qn_pad = np.zeros((N, 1), np.float32)
        qn_pad[:n] = (charges[k] / charges[k].max()).astype(np.float32).reshape(n, 1)
        loc0 = np.zeros((N, 3), np.float32)
        vel0 = np.zeros((N, 3), np.float32)
        loc0[:n], vel0[:n] = loc[k, f0], vel[k, f0]

        traj, overflow = rollout(params, jnp.asarray(loc0), jnp.asarray(vel0),
                                 mask_j, steps, (jnp.asarray(qn_pad),))
        if bool(np.asarray(overflow).any()):
            raise RuntimeError(
                f"radius-graph capacity overflow on sample {k} — raise "
                "max_degree/max_per_cell; MSE from a truncated graph is invalid")
        for i, h in enumerate(horizons):
            pred = np.asarray(traj[i])[:n]
            mse_acc[h] += float(np.mean((pred - loc[k, h]) ** 2))
    return {h: mse_acc[h] / num for h in horizons}, steps, num


def _speed_plus_static_feature(v, static):
    """The shared rollout feature_fn: [|v|, static channel] — the canonical
    conventions live in the training pipelines (nbody.py build_nbody_graph:
    [|v|, q/q.max]; water3d.py build_water3d_graph: [|v|, type/type.max]);
    the static channel is precomputed per sample with exactly those
    normalizations and passed as a rollout feat_arg."""
    import jax.numpy as jnp

    speed = jnp.linalg.norm(v, axis=-1, keepdims=True)
    return jnp.concatenate([speed, static], axis=-1)


def evaluate_water3d_rollout(config, checkpoint=None, samples=4, split="test",
                             edge_block=256, seed=0, max_steps=5,
                             degree_margin=2.0):
    """Multi-step rollout over Water-3D h5 trajectories; returns
    ({step_index: mse}, steps, num_trajectories). Each rollout step spans
    ``delta_t`` frames starting at frame 0; velocities follow the training
    convention (one-frame position delta), so the rollout's displacement is
    rescaled by 1/delta_t."""
    import h5py
    import jax
    import jax.numpy as jnp

    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.ops.graph import _round_up
    from distegnn_tpu.rollout import make_rollout_fn

    radius = float(config.data.radius)
    delta = int(config.data.delta_t)
    trajs, t_min = [], None
    with h5py.File(os.path.join(config.data.data_dir, config.data.dataset_name,
                                f"{split}.h5"), "r") as f:
        for key in sorted(f.keys())[:samples]:
            T = f[key]["position"].shape[0]
            t_min = T if t_min is None else min(t_min, T)
            # partial read: a rollout of max_steps only touches the first
            # max_steps*delta + 1 frames (vel0 needs frame 1)
            pos = np.asarray(f[key]["position"][:max_steps * delta + 1], np.float32)
            trajs.append((pos, np.asarray(f[key]["particle_type"], np.float32)))
    if not trajs:
        raise ValueError("no trajectories in the h5 file")

    # a step k needs target frame k*delta and vel0 needs frame 1 (T >= 2)
    steps = min(max_steps, (t_min - 1) // delta)
    if steps < 1 or t_min < 2:
        raise ValueError(
            f"trajectories too short for one rollout step of delta_t={delta} "
            f"(shortest has {t_min} frames)")
    n_max = max(p.shape[1] for p, _ in trajs)
    N = _round_up(n_max, edge_block)

    max_degree, max_per_cell = _calibrate_degree(
        (pos[0] for pos, _ in trajs), radius, edge_block, degree_margin)

    model = get_model(config.model, dataset_name=config.data.dataset_name)
    rollout = jax.jit(
        make_rollout_fn(model, radius=radius, max_degree=max_degree,
                        max_per_cell=max_per_cell,
                        feature_fn=_speed_plus_static_feature,
                        edge_block=edge_block,
                        velocity_scale=1.0 / delta),
        static_argnums=(4,))

    params = _init_params(model, checkpoint, config, seed)
    mse_acc = {k: 0.0 for k in range(1, steps + 1)}
    for pos, ptype in trajs:
        n = pos.shape[1]
        mask = np.zeros((N,), np.float32)
        mask[:n] = 1.0
        tn = np.zeros((N, 1), np.float32)
        tn[:n, 0] = ptype / max(float(ptype.max()), 1e-12)
        loc0 = np.zeros((N, 3), np.float32)
        vel0 = np.zeros((N, 3), np.float32)
        loc0[:n] = pos[0]
        vel0[:n] = pos[1] - pos[0]
        traj, overflow = rollout(params, jnp.asarray(loc0), jnp.asarray(vel0),
                                 jnp.asarray(mask), steps, (jnp.asarray(tn),))
        if bool(np.asarray(overflow).any()):
            raise RuntimeError(_OVERFLOW_MSG)
        for k in range(1, steps + 1):
            pred = np.asarray(traj[k - 1])[:n]
            mse_acc[k] += float(np.mean((pred - pos[k * delta]) ** 2))
    num = len(trajs)
    return {k: v / num for k, v in mse_acc.items()}, steps, num


def _calibrate_degree(first_frames, radius, edge_block, margin):
    """(max_degree, max_per_cell) for the on-device radius graph, from the
    max observed first-frame degree x safety margin, 512-aligned for the
    blocked layout."""
    from distegnn_tpu.ops.graph import _round_up
    from distegnn_tpu.ops.radius import radius_graph_np

    deg0 = 1
    for pos0 in first_frames:
        ei = radius_graph_np(pos0, radius)
        deg = np.bincount(ei[0], minlength=pos0.shape[0]).max() if ei.size else 1
        deg0 = max(deg0, int(deg))
    max_degree = _round_up(int(deg0 * margin) + 1, 2)
    while (max_degree * edge_block) % 512:
        max_degree += 2
    return max_degree, max(int(deg0 * margin), 32)


_OVERFLOW_MSG = ("radius-graph capacity overflow — re-run with a larger "
                 "--degree-margin; MSE from a truncated graph is invalid")


def _static_plus_speed_feature(v, static):
    """Fluid113K's rollout feature_fn: [viscosity, mass, |v|] — static
    channels FIRST, matching build_fluid_graph (data/fluid113k.py:118-119)."""
    import jax.numpy as jnp

    speed = jnp.linalg.norm(v, axis=-1, keepdims=True)
    return jnp.concatenate([static, speed], axis=-1)


def evaluate_fluid113k_rollout(config, checkpoint=None, samples=2, split="test",
                               edge_block=256, seed=0, max_steps=5,
                               degree_margin=2.0):
    """Multi-step rollout over Fluid113K (LargeFluid) simulations — the
    headline dataset. Horizons keyed by rollout step (delta_t
    frames each, starting at frame 0). The sim's own velocity field is the
    model input; the rollout's delta_t-frame displacement is converted back
    to that convention with a data-estimated frame duration."""
    import jax
    import jax.numpy as jnp

    from distegnn_tpu.data.fluid113k import SIM_SPLITS, read_sim
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.ops.graph import _round_up
    from distegnn_tpu.rollout import make_rollout_fn

    delta = int(config.data.delta_t)
    radius = float(config.data.inner_radius or config.data.radius)
    lo, hi = SIM_SPLITS[split]
    sims = []
    for idx in range(lo, min(lo + samples, hi)):
        try:
            pos, vel, visc, mass = read_sim(config.data.data_dir,
                                            config.data.dataset_name, idx)
        except FileNotFoundError:
            break
        # keep only the frames a rollout touches (read_sim has no partial
        # read — shards are whole-file zstd — but the stacked tail can be
        # dropped immediately: frames 0..max_steps*delta)
        keep = max_steps * int(config.data.delta_t) + 1
        sims.append((pos[:keep] if pos.shape[0] > keep else pos,
                     vel[:1], visc, mass))
    if not sims:
        raise ValueError(f"no {split} simulations found under "
                         f"{config.data.data_dir}/{config.data.dataset_name}")

    t_min = min(pos.shape[0] for pos, _, _, _ in sims)
    steps = min(max_steps, (t_min - 1) // delta)
    if steps < 1:
        raise ValueError(
            f"simulations too short for one rollout step of delta_t={delta} "
            f"(shortest has {t_min} frames)")
    n_max = max(pos.shape[1] for pos, _, _, _ in sims)
    N = _round_up(n_max, edge_block)

    # frame duration estimated from the data: |pos[1]-pos[0]| ~ |vel[0]|*dt.
    # A degenerate estimate means the velocity convention cannot be recovered
    # and any MSE would be silently wrong — refuse, like the overflow path.
    dts = []
    for pos, vel, _, _ in sims:
        dx = np.linalg.norm(pos[1] - pos[0], axis=1)
        v0 = np.linalg.norm(vel[0], axis=1)
        ok = v0 > 1e-8
        if ok.any():
            dts.append(float(np.median(dx[ok] / v0[ok])))
    frame_dt = float(np.median(dts)) if dts else 0.0
    if not np.isfinite(frame_dt) or frame_dt <= 0:
        raise ValueError(
            "cannot estimate the frame duration from the data (static first "
            "frames or zero velocities) — the rollout velocity convention "
            "would be wrong; check the simulation dump")

    max_degree, max_per_cell = _calibrate_degree(
        (pos[0] for pos, _, _, _ in sims), radius, edge_block, degree_margin)

    model = get_model(config.model, dataset_name=config.data.dataset_name)
    rollout = jax.jit(
        make_rollout_fn(model, radius=radius, max_degree=max_degree,
                        max_per_cell=max_per_cell,
                        feature_fn=_static_plus_speed_feature,
                        edge_block=edge_block,
                        velocity_scale=1.0 / (delta * frame_dt)),
        static_argnums=(4,))

    params = _init_params(model, checkpoint, config, seed)
    mse_acc = {k: 0.0 for k in range(1, steps + 1)}
    for pos, vel, viscosity, mass in sims:
        n = pos.shape[1]
        mask = np.zeros((N,), np.float32)
        mask[:n] = 1.0
        attr = np.zeros((N, 2), np.float32)
        attr[:n, 0] = viscosity
        attr[:n, 1] = mass
        loc0 = np.zeros((N, 3), np.float32)
        vel0 = np.zeros((N, 3), np.float32)
        loc0[:n], vel0[:n] = pos[0], vel[0]
        attr_j = jnp.asarray(attr)
        # attr enters BOTH as node_feat channels (feature_fn) and as the
        # model's node_attr input (node_attr_nf=2 in the largefluid config)
        traj, overflow = rollout(params, jnp.asarray(loc0), jnp.asarray(vel0),
                                 jnp.asarray(mask), steps, (attr_j,),
                                 node_attr_now=attr_j)
        if bool(np.asarray(overflow).any()):
            raise RuntimeError(_OVERFLOW_MSG)
        for k in range(1, steps + 1):
            pred = np.asarray(traj[k - 1])[:n]
            mse_acc[k] += float(np.mean((pred - pos[k * delta]) ** 2))
    num = len(sims)
    return {k: v / num for k, v in mse_acc.items()}, steps, num


def _init_params(model, checkpoint, config, seed):
    """Params from a checkpoint when given, else fresh init (smoke mode)."""
    import jax

    # init on a minimal batch of the right feature widths (shape-polymorphic
    # flax init; the rollout batch differs only in N/E)
    from distegnn_tpu.ops.graph import pad_graphs

    rng = np.random.default_rng(seed)
    n = 4
    g = {
        "node_feat": rng.normal(size=(n, config.model.node_feat_nf)).astype(np.float32),
        "node_attr": np.ones((n, int(config.model.get("node_attr_nf", 0))), np.float32),
        "loc": rng.normal(size=(n, 3)).astype(np.float32),
        "vel": rng.normal(size=(n, 3)).astype(np.float32),
        "target": np.zeros((n, 3), np.float32),
        "edge_index": np.stack([np.arange(n), np.roll(np.arange(n), 1)]).astype(np.int64),
        "edge_attr": np.ones((n, config.model.edge_attr_nf), np.float32),
    }
    params = model.init(jax.random.PRNGKey(seed), pad_graphs([g]))
    if checkpoint:
        # params-only: evaluation must load checkpoints written with ANY
        # optimizer wrapping (grad accumulation changes the opt-state tree)
        from distegnn_tpu.train.checkpoint import restore_params

        params = restore_params(checkpoint, params)
    return params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config_path", required=True)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--samples", type=int, default=50)
    ap.add_argument("--split", default="test")
    ap.add_argument("--max-steps", type=int, default=5,
                    help="rollout horizon cap (trajectory datasets)")
    ap.add_argument("--degree-margin", type=float, default=2.0,
                    help="radius-graph capacity = observed degree x margin")
    ap.add_argument("--platform", default=None,
                    help="pin a jax platform (e.g. cpu) before backend init")
    args = ap.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from distegnn_tpu.config import load_config

    config = load_config(args.config_path)
    name = config.data.dataset_name
    if name.startswith("nbody"):
        horizons, steps, num = evaluate_nbody_rollout(
            config, checkpoint=args.checkpoint, samples=args.samples,
            split=args.split)
    elif name == "Water-3D":
        horizons, steps, num = evaluate_water3d_rollout(
            config, checkpoint=args.checkpoint, samples=args.samples,
            split=args.split, max_steps=args.max_steps,
            degree_margin=args.degree_margin)
    elif name == "Fluid113K":
        horizons, steps, num = evaluate_fluid113k_rollout(
            config, checkpoint=args.checkpoint, samples=args.samples,
            split=args.split, max_steps=args.max_steps,
            degree_margin=args.degree_margin)
    else:
        raise SystemExit(f"no rollout evaluator wired for dataset {name!r} "
                         "(supported: nbody*, Water-3D, Fluid113K)")
    print(json.dumps({
        "metric": "rollout_mse",
        "dataset": name,
        "split": args.split,
        "samples": num,
        "steps": steps,
        "checkpoint": args.checkpoint,
        # significant figures, not fixed decimals: fluid displacement targets
        # give MSEs of 1e-9 scale, which round(_, 6) flattened to 0.0
        "horizons": {str(k): float(f"{v:.4g}") for k, v in horizons.items()},
    }))


if __name__ == "__main__":
    main()
