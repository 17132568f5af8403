"""Resumable chunked n-body generation (single-core hosts, bounded runtime).

Writes chunks of trajectories to <path>/chunks/{split}_{i:04d}.npz, skipping
chunks that already exist, and exits cleanly after --budget seconds. When all
chunks are present it merges them into the reference .npy layout
(generate_dataset.py:86-118) and removes the chunk dir. Re-invoke until it
prints DONE. Same physics as scripts/generate_nbody.py (batched integrator,
distegnn_tpu/data/nbody_sim.py); each chunk seeds its own RNG from
(seed, split, chunk index) so resumption is deterministic.

Deliberate delta from generate_nbody_files: integrates and stores float32
(half the time and disk on a bandwidth-starved host; the training pipeline
casts to f32 at graph build anyway). For reference-dtype (float64) output use
scripts/generate_nbody.py.

  python scripts/generate_nbody_chunked.py --path data/n_body_system/nbody_100 \
      --n_isolated 100 --num-train 5000 --num-valid 2000 --num-test 2000 \
      --seed 43 --budget 480
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distegnn_tpu.data.nbody_sim import simulate_trajectories_batched  # noqa: E402

CHUNK = 256


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--path", type=str, required=True)
    p.add_argument("--num-train", type=int, default=5000)
    p.add_argument("--num-valid", type=int, default=2000)
    p.add_argument("--num-test", type=int, default=2000)
    p.add_argument("--length", type=int, default=5000)
    p.add_argument("--sample-freq", type=int, default=100)
    p.add_argument("--n_isolated", type=int, default=100)
    p.add_argument("--clusters", type=int, default=1)
    p.add_argument("--seed", type=int, default=43)
    p.add_argument("--budget", type=float, default=480.0)
    p.add_argument("--platform", type=str, default="auto",
                   choices=("auto", "cpu", "tpu"),
                   help="jax backend: auto (default: JAX's own choice, which "
                        "honours JAX_PLATFORMS) | cpu | tpu (pin it; a pinned "
                        "backend that is missing fails at start-up)")
    args = p.parse_args()
    if args.platform != "auto":
        jax.config.update("jax_platforms", args.platform)
    dev = jax.devices()[0]
    print(f"generate_nbody_chunked: backend={dev.platform} "
          f"({dev.device_kind})", flush=True)

    tag = f"charged{args.n_isolated}_0_0_{args.clusters}"
    chunk_dir = os.path.join(args.path, "chunks")
    os.makedirs(chunk_dir, exist_ok=True)
    t0 = time.perf_counter()

    splits = [("train", args.num_train), ("valid", args.num_valid), ("test", args.num_test)]
    todo = done = 0
    for split, num in splits:
        n_chunks = (num + CHUNK - 1) // CHUNK
        for ci in range(n_chunks):
            f = os.path.join(chunk_dir, f"{split}_{ci:04d}.npz")
            if os.path.exists(f):
                done += 1
                continue
            if time.perf_counter() - t0 > args.budget:
                todo += 1
                continue
            n = min(CHUNK, num - ci * CHUNK)
            split_id = {"train": 0, "valid": 1, "test": 2}[split]
            rng = np.random.default_rng([args.seed, split_id, ci])
            loc, vel, ch, ed = simulate_trajectories_batched(
                rng, n, args.length, args.sample_freq,
                n_isolated=args.n_isolated, clusters=args.clusters,
                dtype="float32")
            np.savez(f + ".tmp.npz", loc=loc, vel=vel, charges=ch, edges=ed)
            os.replace(f + ".tmp.npz", f)
            done += 1
            print(f"chunk {split}/{ci} ({n} traj) done "
                  f"[{time.perf_counter() - t0:.0f}s]", flush=True)

    if todo:
        print(f"PARTIAL: {done} chunks done, {todo} remaining — re-invoke to continue")
        return

    for split, num in splits:
        n_chunks = (num + CHUNK - 1) // CHUNK
        parts = [np.load(os.path.join(chunk_dir, f"{split}_{ci:04d}.npz"))
                 for ci in range(n_chunks)]
        for key, name in (("loc", "loc"), ("vel", "vel"),
                          ("charges", "charges"), ("edges", "edges")):
            arr = np.concatenate([p[key] for p in parts])[:num]
            np.save(os.path.join(args.path, f"{name}_{split}_{tag}.npy"), arr)
        print(f"merged {split}: {num} trajectories", flush=True)
    for f in os.listdir(chunk_dir):
        os.remove(os.path.join(chunk_dir, f))
    os.rmdir(chunk_dir)
    print("DONE")


if __name__ == "__main__":
    main()
