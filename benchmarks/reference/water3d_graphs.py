"""Plain graph construction for the reference, Water-3D, from raw samples, as
the published pipeline defines it (GLAD-RUC/DistEGNN
``datasets/process_dataset.py:258-277``):

Water-3D    nodes carry features [|v|, type / max type] and, for the model
            (``node_attr_nf`` 0), no attributes; a directed edge for every
            ordered pair closer than ``radius`` (strict); edge attributes
            [distance, distance].

Independent of ``distegnn_tpu``, like ``graphs.py``, whose ``_finish`` and
``stack`` it shares: scipy's k-d tree, raw node order, edges in the tree's
order.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference.graphs import _finish


def water_graph(sample: dict, radius: float) -> dict:
    from scipy.spatial import cKDTree

    loc = np.asarray(sample["loc"], np.float32)
    vel = np.asarray(sample["vel"], np.float32)
    ptype = np.asarray(sample["particle_type"], np.float32).reshape(-1, 1)
    pairs = cKDTree(loc.astype(np.float64)).query_pairs(radius, output_type="ndarray")
    d = loc[pairs[:, 0]].astype(np.float64) - loc[pairs[:, 1]].astype(np.float64)
    pairs = pairs[np.sum(d * d, axis=1) < radius * radius]      # strict, as published
    row = np.concatenate([pairs[:, 0], pairs[:, 1]])
    col = np.concatenate([pairs[:, 1], pairs[:, 0]])
    feat = np.concatenate([np.linalg.norm(vel, axis=1, keepdims=True),
                           ptype / ptype.max()], axis=1)
    return _finish(loc, vel, np.asarray(sample["target"], np.float32), feat,
                   np.zeros((loc.shape[0], 0), np.float32), row, col)
