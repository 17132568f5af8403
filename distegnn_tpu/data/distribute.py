"""Distribute-mode preprocessing: partition every graph into world_size shards
and cache one file per (split, partition-rank) — the reference's rank-0
preprocessing + per-rank shard files flow (reference
datasets/process_dataset.py:308-578: rank 0 partitions all frames, writes
``..._{rank}-{world_size}.pt``, other ranks wait at a barrier).

Here one host process drives all chips, so "rank 0 does the work" is simply
the only code path; multi-host pods reuse the same cache through a shared
filesystem exactly like the reference.

The reference wires this mode only for Water-3D / Fluid113K; the n-body
variant below exists because it makes the distributed path testable and
benchmarkable from generated data alone (same partition+shard flow)."""

from __future__ import annotations

import os
import pickle
from typing import List, Optional

import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.data.nbody import _find_tag, build_nbody_graph
from distegnn_tpu.data.partition import split_graph


def _shard_paths(processed_dir: str, key: str, world_size: int) -> List[str]:
    return [os.path.join(processed_dir, f"{key}_{p}-{world_size}.pkl") for p in range(world_size)]


def write_partitioned_split(
    graphs: List[dict],
    processed_dir: str,
    key: str,
    world_size: int,
    split_mode: str,
    inner_radius: float,
    outer_radius: Optional[float],
    seed: int = 0,
) -> List[str]:
    """Partition each graph into world_size parts; write shard p's list of
    partition-p dicts to its own file. Asserts equal shard lengths (reference
    process_dataset.py:430-431,570-571)."""
    paths = _shard_paths(processed_dir, key, world_size)
    if all(os.path.exists(p) for p in paths):
        return paths
    shards: List[List[dict]] = [[] for _ in range(world_size)]
    with obs.span("data/partition", graphs=len(graphs), parts=world_size):
        for i, g in enumerate(graphs):
            parts = split_graph(
                g, world_size, split_mode, inner_radius,
                outer_radius=outer_radius, seed=seed + i,
            )
            for p in range(world_size):
                shards[p].append(parts[p])
    assert len({len(s) for s in shards}) == 1, "unequal shard lengths"
    os.makedirs(processed_dir, exist_ok=True)
    for p, path in enumerate(paths):
        # tmp + atomic rename: a reader (another host on shared storage, or a
        # crashed run's leftovers) never sees a truncated pickle
        with open(path + ".tmp", "wb") as f:
            pickle.dump(shards[p], f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(path + ".tmp", path)
    return paths


def process_nbody_distribute(
    data_dir: str,
    dataset_name: str,
    world_size: int,
    max_samples: int,
    inner_radius: float,
    outer_radius: Optional[float],
    split_mode: str,
    frame_0: int,
    frame_T: int,
    seed: int = 0,
    tag: Optional[str] = None,
) -> List[List[str]]:
    """N-body distribute mode: whole graphs (full connectivity dropped — each
    partition rebuilds inner_radius edges) split into world_size shards.
    Returns [train_paths, valid_paths, test_paths], each world_size long."""
    base = os.path.join(data_dir, dataset_name)
    processed_dir = os.path.join(base, "processed")
    os.makedirs(processed_dir, exist_ok=True)

    out = []
    for split in ("train", "valid", "test"):
        key = (
            f"{dataset_name}_{split}_dist_{split_mode}_o{outer_radius}_i{inner_radius}"
            f"_{max_samples}_{frame_0}_{frame_T}_s{seed}"
        )
        paths = _shard_paths(processed_dir, key, world_size)
        if not all(os.path.exists(p) for p in paths):
            t = tag if tag is not None else _find_tag(base, split)
            loc = np.load(os.path.join(base, f"loc_{split}_{t}.npy"))[:max_samples]
            vel = np.load(os.path.join(base, f"vel_{split}_{t}.npy"))[:max_samples]
            charges = np.load(os.path.join(base, f"charges_{split}_{t}.npy"))[:max_samples]
            with obs.span("data/build_graph", graphs=loc.shape[0]):
                graphs = [
                    build_nbody_graph(loc[k, frame_0], vel[k, frame_0], charges[k],
                                      loc[k, frame_T], with_edges=False)
                    for k in range(loc.shape[0])
                ]
            write_partitioned_split(
                graphs, processed_dir, key, world_size, split_mode,
                inner_radius, outer_radius, seed=seed,
            )
        out.append(paths)
    return out
