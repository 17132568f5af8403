"""N-body preprocessing pipeline (reference process_nbody_cutoff,
datasets/process_dataset.py:61-125): load raw trajectory .npy files, pick
(frame_0 -> frame_T) prediction pairs, build (radius or full) graphs with the
edge cutoff, cache to disk keyed by every parameter.

Graphs are plain numpy dicts (the schema pad_graphs consumes); serialized
lists are pickled (the reference torch.save()s PyG Data lists,
process_dataset.py:114-115)."""

from __future__ import annotations

import glob
import os
import pickle
from typing import List, Optional

import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.ops.radius import cutoff_edges_np, full_graph_np, radius_graph_np


def build_nbody_graph(
    loc: np.ndarray,
    vel: np.ndarray,
    charges: np.ndarray,
    target: Optional[np.ndarray],
    radius: float = -1.0,
    cutoff_rate: float = 0.0,
    with_edges: bool = True,
) -> dict:
    """One sample -> graph dict (reference process_key,
    process_dataset.py:90-115): full graph when radius == -1 else radius
    graph; drop the longest cutoff_rate fraction; edge_attr = distance
    duplicated to 2 channels; node_feat = [|v|, q / max q]; node_attr = q;
    loc_mean = mean position (the virtual-node seed).

    with_edges=False skips edge construction (empty edge list) — for
    distribute mode, which drops whole-graph edges and rebuilds per-partition
    inner_radius edges anyway (building the O(n^2) full set would be waste)."""
    loc = np.asarray(loc, np.float32)
    vel = np.asarray(vel, np.float32)
    charges = np.asarray(charges, np.float32)
    n = loc.shape[0]

    if with_edges:
        edge_index = full_graph_np(n) if radius == -1 else radius_graph_np(loc, radius)
        edge_index = cutoff_edges_np(edge_index, loc, cutoff_rate)
    else:
        edge_index = np.zeros((2, 0), np.int64)
    dist = np.linalg.norm(loc[edge_index[0]] - loc[edge_index[1]], axis=1)
    edge_attr = np.repeat(dist[:, None], 2, axis=1).astype(np.float32)

    speed = np.linalg.norm(vel, axis=1, keepdims=True)
    node_feat = np.concatenate([speed, charges / charges.max()], axis=1).astype(np.float32)

    return {
        "node_feat": node_feat,
        "node_attr": charges,
        "loc": loc,
        "vel": vel,
        "target": None if target is None else np.asarray(target, np.float32),
        "loc_mean": loc.mean(axis=0),
        "edge_index": edge_index.astype(np.int32),
        "edge_attr": edge_attr,
    }


def _find_tag(base: str, split: str) -> str:
    hits = sorted(glob.glob(os.path.join(base, f"loc_{split}_*.npy")))
    if not hits:
        raise FileNotFoundError(f"no loc_{split}_*.npy under {base} — run scripts/generate_nbody.py first")
    name = os.path.basename(hits[0])
    return name[len(f"loc_{split}_"):-len(".npy")]


def process_nbody_cutoff(
    data_dir: str,
    dataset_name: str,
    max_samples: int,
    radius: float,
    frame_0: int,
    frame_T: int,
    cutoff_rate: float,
    tag: Optional[str] = None,
) -> List[str]:
    """Process train/valid/test splits; returns the three processed file paths.
    Cached: an existing file (same parameter key in its name) is reused
    untouched (reference process_dataset.py:66-72)."""
    base = os.path.join(data_dir, dataset_name)
    processed_dir = os.path.join(base, "processed")
    os.makedirs(processed_dir, exist_ok=True)

    paths = []
    for split in ("train", "valid", "test"):
        out = os.path.join(
            processed_dir,
            f"{dataset_name}_{split}_{radius}_{cutoff_rate:.3f}_{max_samples}_{frame_0}_{frame_T}.pkl",
        )
        paths.append(out)
        if os.path.exists(out):
            continue

        t = tag if tag is not None else _find_tag(base, split)
        loc = np.load(os.path.join(base, f"loc_{split}_{t}.npy"))[:max_samples]
        vel = np.load(os.path.join(base, f"vel_{split}_{t}.npy"))[:max_samples]
        charges = np.load(os.path.join(base, f"charges_{split}_{t}.npy"))[:max_samples]

        with obs.span("data/build_graph", graphs=loc.shape[0]):
            graphs = [
                build_nbody_graph(
                    loc[k, frame_0], vel[k, frame_0], charges[k], loc[k, frame_T],
                    radius=radius, cutoff_rate=cutoff_rate,
                )
                for k in range(loc.shape[0])
            ]
        with open(out, "wb") as f:
            pickle.dump(graphs, f, protocol=pickle.HIGHEST_PROTOCOL)
    return paths
