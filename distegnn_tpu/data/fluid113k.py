"""Fluid113K (LargeFluid) pipeline (reference process_large_fluid_dist,
datasets/process_dataset.py:441-578).

Input: SPlisHSPlasH scenes packed as 16 zstd+msgpack shards per simulation
(``sim_XXXX_YY.msgpack.zst``; each frame dict has 'pos', 'vel', and scene
constants 'viscosity', 'm' — written by
dataset_generation/Fluid113K/create_physics_records.py with msgpack-numpy).
Simulation splits: train 1-100, valid 101-120, test 121-140; 16 random frames
from the first 50 per sim; node_attr = [viscosity, mass],
node_feat = [viscosity, mass, |v|] (3 features — largefluid config's
node_feat_nf=3/node_attr_nf=2).

msgpack-numpy's array encoding is decoded with a local hook (the library
isn't in this image): {b'nd': True, b'type': .., b'shape': .., b'data': ..}
-> np.ndarray."""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.data.distribute import write_partitioned_split
from distegnn_tpu.data.water3d import _split_seed

SIM_SPLITS = {"train": (1, 101), "valid": (101, 121), "test": (121, 141)}
SHARDS_PER_SIM = 16
FRAMES_PER_SIM = 16
FRAME_RANGE = 50


def _mn_decode(obj):
    """msgpack-numpy decode hook (format of msgpack_numpy.encode)."""
    if isinstance(obj, dict):
        if obj.get(b"nd") is True:
            return np.frombuffer(obj[b"data"], dtype=np.dtype(obj[b"type"].decode())
                                 ).reshape(obj[b"shape"])
        if obj.get("nd") is True:
            return np.frombuffer(obj["data"], dtype=np.dtype(obj["type"])
                                 ).reshape(obj["shape"])
    return obj


def read_sim(data_dir: str, dataset_name: str, idx: int):
    """Read one simulation's 16 shards -> (pos [T,N,3], vel [T,N,3],
    viscosity [N], mass [N]) (reference process_key, process_dataset.py:480-498)."""
    import msgpack
    import zstandard as zstd

    position, vel = [], []
    viscosity = mass = None
    dctx = zstd.ZstdDecompressor()
    for i in range(SHARDS_PER_SIM):
        path = os.path.join(data_dir, dataset_name, f"sim_{idx:04d}_{i:02d}.msgpack.zst")
        with open(path, "rb") as f:
            raw = msgpack.unpackb(dctx.decompress(f.read()), raw=False,
                                  object_hook=_mn_decode, strict_map_key=False)
        for frame in raw:
            position.append(np.asarray(frame["pos"]))
            vel.append(np.asarray(frame["vel"]))
        if raw:  # tolerate empty shards (short simulations)
            viscosity = np.asarray(raw[0]["viscosity"])
            mass = np.asarray(raw[0]["m"])
    return (np.stack(position).astype(np.float32), np.stack(vel).astype(np.float32),
            viscosity.astype(np.float32), mass.astype(np.float32))


def write_fluid_sim(data_dir: str, dataset_name: str, idx: int,
                    pos: np.ndarray, vel: np.ndarray,
                    viscosity: np.ndarray, mass: np.ndarray) -> None:
    """Write one simulation in the exact on-disk format ``read_sim`` consumes
    (16 zstd+msgpack shards with msgpack-numpy array encoding — the layout of
    reference dataset_generation/Fluid113K/create_physics_records.py:1-148).

    pos/vel: [T, N, 3]; T frames are split evenly over the 16 shards. Used by
    scripts/generate_fluid_synthetic.py (format-identical synthetic data for
    pipeline validation at any scale) and the end-to-end tests; real
    SPlisHSPlasH data is the supported production path (docs/DATASETS.md)."""
    import msgpack
    import zstandard as zstd

    def encode_np(o):
        if isinstance(o, np.ndarray):
            return {b"nd": True, b"type": o.dtype.str.encode(),
                    b"shape": list(o.shape), b"data": o.tobytes()}
        return o

    base = os.path.join(data_dir, dataset_name)
    os.makedirs(base, exist_ok=True)
    T = pos.shape[0]
    # np.array_split balance: every shard non-empty for T >= SHARDS_PER_SIM
    bounds = np.linspace(0, T, SHARDS_PER_SIM + 1).astype(int)
    cctx = zstd.ZstdCompressor()
    viscosity = np.asarray(viscosity, np.float32)
    mass = np.asarray(mass, np.float32)
    for s in range(SHARDS_PER_SIM):
        frames = [
            {"pos": np.asarray(pos[t], np.float32),
             "vel": np.asarray(vel[t], np.float32),
             "viscosity": viscosity, "m": mass}
            for t in range(bounds[s], bounds[s + 1])
        ]
        packed = msgpack.packb(frames, default=encode_np)
        with open(os.path.join(base, f"sim_{idx:04d}_{s:02d}.msgpack.zst"), "wb") as f:
            f.write(cctx.compress(packed))


def build_fluid_graph(loc_0, vel_0, viscosity, mass, target) -> dict:
    """Whole-graph dict, no edges — Fluid113K runs distribute-mode only and
    partitions rebuild inner_radius edges (reference builds edges only inside
    split_large_graph_*)."""
    loc_0 = np.asarray(loc_0, np.float32)
    vel_0 = np.asarray(vel_0, np.float32)
    node_attr = np.stack([np.broadcast_to(viscosity, loc_0[:, 0].shape),
                          np.broadcast_to(mass, loc_0[:, 0].shape)], axis=-1)
    speed = np.linalg.norm(vel_0, axis=1, keepdims=True)
    node_feat = np.concatenate([node_attr, speed], axis=1)
    return {
        "node_feat": node_feat.astype(np.float32),
        "node_attr": node_attr.astype(np.float32),
        "loc": loc_0,
        "vel": vel_0,
        "target": np.asarray(target, np.float32),
        "loc_mean": loc_0.mean(axis=0),
        "edge_index": np.zeros((2, 0), np.int32),
        "edge_attr": np.zeros((0, 2), np.float32),
    }


def process_large_fluid_distribute(data_dir: str, dataset_name: str, world_size: int,
                                   max_samples: int, inner_radius: float,
                                   outer_radius: Optional[float], split_mode: str,
                                   delta_t: int, seed: int = 0) -> List[List[str]]:
    base = os.path.join(data_dir, dataset_name)
    processed_dir = os.path.join(base, "processed")
    os.makedirs(processed_dir, exist_ok=True)
    out = []
    for split, (lo, hi) in SIM_SPLITS.items():
        key = (f"{dataset_name}_{split_mode}_{split}_o{outer_radius}_i{inner_radius}"
               f"_{max_samples}_{delta_t}_s{seed}")
        shard_paths = [os.path.join(processed_dir, f"{key}_{p}-{world_size}.pkl")
                       for p in range(world_size)]
        out.append(shard_paths)
        if all(os.path.exists(p) for p in shard_paths):
            continue
        rng = np.random.default_rng(_split_seed(seed, split))
        graphs = []
        with obs.span("data/build_graph", split=split):
            for idx in range(lo, hi):
                if len(graphs) >= max_samples:
                    break
                pos, vel, viscosity, mass = read_sim(data_dir, dataset_name, idx)
                n = min(FRAMES_PER_SIM, max_samples - len(graphs))
                hi_f = min(FRAME_RANGE, pos.shape[0] - delta_t - 1)
                if hi_f <= 0:
                    continue  # simulation too short for this delta_t
                for frame in rng.integers(0, hi_f, size=n):
                    graphs.append(build_fluid_graph(pos[frame], vel[frame], viscosity,
                                                    mass, pos[frame + delta_t]))
        write_partitioned_split(graphs, processed_dir, key, world_size,
                                split_mode, inner_radius, outer_radius, seed=seed)
    return out
