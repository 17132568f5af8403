"""Traffic generator for the Water-3D mixes (``scene`` ``water3d_frames``):
synthetic particle trajectories in the record layout of DeepMind's
``learning_to_simulate`` Water-3D set as upstream converts and reads it
(GLAD-RUC/DistEGNN ``datasets/process_dataset.py:225-297``: per trajectory a
``position [T, N, 3]`` and a ``particle_type [N]``; a sample is a frame, its
velocity the one-frame difference, its target the position ``delta_t`` frames
on; ``frames_per_trajectory`` samples a trajectory, 15 upstream).

An adapted copy of the program's zero-egress generator
(``scripts/generate_water3d_synthetic.py::synth_traj``: a damped falling cloud
in a cube sized for ``neighbours`` particles within ``radius``), kept here so
that a later PR cannot change the yardstick's inputs. What differs, and why:
initial speeds grow with height by ``1 + shear * z / side`` and each
trajectory has a speed scale of its own, uniform in ``speed_range``, so that
the rows of a graph and the graphs of a batch differ in how far they move and
a loss taken over part of them is a different loss (PERF.md, PR 24: with
exchangeable rows no number tells a fault from rounding); the frames of a
trajectory are taken every ``frame_stride`` frames from the first, where
upstream draws them at random from the first 250 (unseeded there).

Like ``generate.py``, the samples depend on the mix alone (``data_seed``):
a training job's data set is fixed while its initialization and order vary,
and ``--seed`` seeds weights, order and step keys (the driver).
"""

from __future__ import annotations

import numpy as np


def water3d_frames(mix: dict) -> list:
    """``[{loc, vel, target, particle_type}]``: ``trajectories`` x
    ``frames_per_trajectory`` samples of ``particles`` particles each, one
    particle type (water, 5, as in the published set)."""
    n, radius = int(mix["particles"]), float(mix["radius"])
    delta_t, stride = int(mix["delta_t"]), int(mix["frame_stride"])
    per_traj = int(mix["frames_per_trajectory"])
    side = (n * (4.0 / 3.0) * np.pi * radius ** 3 / float(mix["neighbours"])) ** (1.0 / 3.0)
    gravity = np.array([0.0, 0.0, -0.05], np.float32)
    lo, hi = (float(a) for a in mix["speed_range"])
    ptype = np.full(n, 5.0, np.float32)
    out = []
    for t in range(int(mix["trajectories"])):
        rng = np.random.default_rng([int(mix["data_seed"]), t])
        pos = rng.uniform(0, side, size=(n, 3)).astype(np.float32)
        vel = rng.normal(size=(n, 3)).astype(np.float32) * np.float32(0.02 * rng.uniform(lo, hi))
        vel *= (1.0 + float(mix["shear"]) * pos[:, 2:3] / side).astype(np.float32)
        frames = [pos]
        for _ in range((per_traj - 1) * stride + delta_t):
            vel = (0.99 * vel + gravity * 0.01
                   + rng.normal(size=(n, 3)).astype(np.float32) * 2e-3)
            pos = pos + vel * 0.02
            out_of_box = (pos < 0) | (pos > side)
            vel = np.where(out_of_box, -0.5 * vel, vel)
            pos = np.clip(pos, 0, side)
            frames.append(pos)
        for k in range(per_traj):
            f = k * stride
            out.append({"loc": frames[f], "vel": frames[f + 1] - frames[f],
                        "target": frames[f + delta_t], "particle_type": ptype})
    return out


SCENES = {"water3d_frames": water3d_frames}


def make_samples(mix: dict) -> list:
    return SCENES[mix["scene"]](mix)
