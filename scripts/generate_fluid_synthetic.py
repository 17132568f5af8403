"""Synthetic Fluid113K-format data generator (pipeline validation at any
scale).

The reference produces Fluid113K by driving the external SPlisHSPlasH C++
simulator (dataset_generation/Fluid113K/create_physics_scenes.py:1-497 +
create_physics_records.py:1-148, ~930 LoC of scene synthesis around two
native binaries). That physics pipeline stays OFFLINE and out of the training
path; real data is downloadable (reference README.md:21, docs/DATASETS.md).

This script covers the other need those files served: producing data in the
exact on-disk format at a chosen scale, so the full distribute pipeline
(read_sim -> build_fluid_graph -> METIS partitioning -> ShardedGraphLoader ->
shard_map training) can be exercised end-to-end without the native simulator.
Particles follow a cheap damped pseudo-SPH dynamic (gravity + box bounce +
velocity noise) — NOT physical fluid; use it for plumbing and performance
work, never for accuracy claims.

  python scripts/generate_fluid_synthetic.py --out data/LargeFluid \
      --particles 113140 --sims-train 2 --sims-valid 1 --sims-test 1
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distegnn_tpu.data.fluid113k import SIM_SPLITS, write_fluid_sim  # noqa: E402


def synth_sim(rng: np.random.Generator, n: int, frames: int, radius: float):
    """Damped falling-particle cloud in a unit-ish box at a density giving
    ~15 neighbors within ``radius`` (the Fluid113K edge density)."""
    vol = n * (4.0 / 3.0) * np.pi * radius**3 / 15.0
    side = vol ** (1.0 / 3.0)
    pos = rng.uniform(0, side, size=(n, 3)).astype(np.float32)
    vel = rng.normal(size=(n, 3)).astype(np.float32) * 0.01
    g = np.array([0.0, 0.0, -0.05], np.float32)
    poss, vels = [], []
    for _ in range(frames):
        vel = 0.99 * vel + g * 0.01 + rng.normal(size=(n, 3)).astype(np.float32) * 1e-3
        pos = pos + vel * 0.01
        # bounce off the box walls
        under, over = pos < 0, pos > side
        vel = np.where(under | over, -0.5 * vel, vel)
        pos = np.clip(pos, 0, side)
        poss.append(pos.copy())
        vels.append(vel.copy())
    return np.stack(poss), np.stack(vels)


def generate(out: str, dataset_name: str = "Fluid113K", particles: int = 113_140,
             frames: int = 48, radius: float = 0.075, sims_train: int = 2,
             sims_valid: int = 1, sims_test: int = 1, seed: int = 0) -> None:
    """Write ``sims_*`` synthetic simulations per split under
    ``out/dataset_name`` (importable: a caller that already holds an
    accelerator must not shell out to a second JAX process)."""
    rng = np.random.default_rng(seed)
    counts = {"train": sims_train, "valid": sims_valid, "test": sims_test}
    for split, (lo, _) in SIM_SPLITS.items():
        for k in range(counts[split]):
            pos, vel = synth_sim(rng, particles, frames, radius)
            visc = np.full((particles,), 0.01, np.float32)
            mass = np.full((particles,), 0.1, np.float32)
            write_fluid_sim(out, dataset_name, lo + k, pos, vel, visc, mass)
            print(f"wrote sim {lo + k} ({split}): {particles} particles x "
                  f"{frames} frames", flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--out", type=str, required=True)
    p.add_argument("--dataset_name", type=str, default="Fluid113K")
    p.add_argument("--particles", type=int, default=113_140)
    p.add_argument("--frames", type=int, default=48)
    p.add_argument("--radius", type=float, default=0.075)
    p.add_argument("--sims-train", type=int, default=2)
    p.add_argument("--sims-valid", type=int, default=1)
    p.add_argument("--sims-test", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    generate(**vars(p.parse_args()))


if __name__ == "__main__":
    main()
