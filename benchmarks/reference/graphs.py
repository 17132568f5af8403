"""Plain graph construction for the reference, from raw samples, as the two
published pipelines define it (GLAD-RUC/DistEGNN ``datasets/process_dataset.py``):

Fluid113K   nodes carry features [viscosity, mass, |v|] and attributes
            [viscosity, mass]; a directed edge for every ordered pair closer
            than ``radius``; edge attributes [distance, distance].
nbody_100   nodes carry features [|v|, charge / max charge], no attributes,
            and the raw charge (``charge``, FastTFN's degree-0 input); all
            ordered pairs; edge attributes [distance, distance].

Independent of ``distegnn_tpu``: the neighbour search is scipy's k-d tree,
nodes keep their raw order and edges come in the tree's order.
"""

from __future__ import annotations

import numpy as np


def _finish(loc, vel, target, feat, attr, row, col):
    dist = np.linalg.norm(loc[row] - loc[col], axis=1).astype(np.float32)
    return {"feat": feat.astype(np.float32), "attr": attr.astype(np.float32),
            "loc": loc, "vel": vel, "target": target,
            "loc_mean": loc.mean(axis=0).astype(np.float32),
            "row": row.astype(np.int32), "col": col.astype(np.int32),
            "eattr": np.stack([dist, dist], axis=1)}


def fluid_graph(sample: dict, radius: float) -> dict:
    from scipy.spatial import cKDTree

    loc = np.asarray(sample["loc"], np.float32)
    vel = np.asarray(sample["vel"], np.float32)
    n = loc.shape[0]
    pairs = cKDTree(loc.astype(np.float64)).query_pairs(radius, output_type="ndarray")
    d = loc[pairs[:, 0]].astype(np.float64) - loc[pairs[:, 1]].astype(np.float64)
    pairs = pairs[np.sum(d * d, axis=1) < radius * radius]      # strict, as published
    row = np.concatenate([pairs[:, 0], pairs[:, 1]])
    col = np.concatenate([pairs[:, 1], pairs[:, 0]])
    attr = np.stack([np.full(n, sample["viscosity"], np.float32),
                     np.full(n, sample["mass"], np.float32)], axis=1)
    feat = np.concatenate([attr, np.linalg.norm(vel, axis=1, keepdims=True)], axis=1)
    return _finish(loc, vel, np.asarray(sample["target"], np.float32), feat, attr,
                   row, col)


def nbody_graph(loc, vel, charges, target) -> dict:
    n = loc.shape[0]
    row, col = np.nonzero(~np.eye(n, dtype=bool))
    feat = np.concatenate([np.linalg.norm(vel, axis=1, keepdims=True),
                           charges / charges.max()], axis=1)
    g = _finish(np.asarray(loc, np.float32), np.asarray(vel, np.float32),
                np.asarray(target, np.float32), feat,
                np.zeros((n, 0), np.float32), row, col)
    return dict(g, charge=np.asarray(charges, np.float32).reshape(n))


def stack(graphs: list, edges: int = None) -> dict:
    """Graphs of equal node count -> one batch with a leading graph axis.
    Edge lists are padded to ``edges`` entries (default: the longest) with
    edges of weight 0 (``ew``), so that graphs of slightly different edge
    counts share one compiled reference program."""
    E = max(g["row"].shape[0] for g in graphs) if edges is None else int(edges)
    out = []
    for g in graphs:
        e = g["row"].shape[0]
        if e > E:
            raise ValueError(f"graph has {e} edges, more than the {E} to pad to")
        pad = lambda a: np.concatenate([a, np.zeros((E - e,) + a.shape[1:], a.dtype)])
        out.append(dict(g, row=pad(g["row"]), col=pad(g["col"]), eattr=pad(g["eattr"]),
                        ew=pad(np.ones(e, np.float32))))
    return {k: np.stack([g[k] for g in out]) for k in out[0]}
