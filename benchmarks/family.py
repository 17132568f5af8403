"""The model family of a configuration, chosen by its ``model.model_name`` in
this one place. A family is four things: the layout of the benchmark's
weights (``weights.py``), the mapping between those flat weights and the
program's parameter tree (``drivers/common.py``), the plain reference's
forward (``reference/<family>.py``, trained by ``reference/train.py``), and
the operations and bytes a micro-step requires (``counts.py``).

The name travels in a driver's ``dims`` and, with them, in ``shapes``. Code
that holds them calls ``of(dims)`` and the family's own functions; a ``dims``
without a name, or with one that has no family here, ends the run before
set-up. ``weights.make_weights``, ``weights.layout`` and ``counts``'s entry
points are thin wrappers for callers that hold sizes alone (the metric
readers, tests written before a second family came): they read a ``dims``
without a name as FastEGNN's.
"""

from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Callable

KNOWN = ("FastEGNN", "FastTFN")


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    layout: Callable          # dims -> [(flat name, shape, kind, bound)]
    tree_path: Callable       # flat name -> key path in the program's ``params``
    reference: ModuleType     # its ``follow(w0, model, train, batches, block, ...)``
    forward_matmul_flops: Callable    # shapes -> FLOPs of one forward pass
    agg_bytes: Callable               # shapes -> least bytes of its gathers and sums

    def make_weights(self, seed: int, dims: dict) -> dict:
        from benchmarks import weights

        return weights.make(seed, self.layout(dims))

    def to_tree(self, weights: dict) -> dict:
        from benchmarks.drivers import common

        return common.to_tree(weights, self.tree_path)

    def to_plain(self, tree: dict, names) -> dict:
        from benchmarks.drivers import common

        return common.to_plain(tree, names, self.tree_path)


def name_of(dims: dict, default: str = None) -> str:
    name = dims.get("model_name", default)
    if name not in KNOWN:
        raise SystemExit(f"model_name {name!r} has no family in the benchmark; "
                         f"it knows {', '.join(KNOWN)}")
    return name


def of(dims: dict, default: str = None) -> Family:
    from benchmarks import counts, weights
    from benchmarks.drivers import common
    from benchmarks.reference import fastegnn, fasttfn

    return {
        "FastEGNN": Family("FastEGNN", weights.fastegnn_layout, common.fastegnn_tree_path,
                           fastegnn, counts.fastegnn_forward_matmul_flops,
                           counts.fastegnn_agg_bytes),
        "FastTFN": Family("FastTFN", weights.fasttfn_layout, common.fasttfn_tree_path,
                          fasttfn, counts.fasttfn_forward_matmul_flops, counts.fasttfn_agg_bytes),
    }[name_of(dims, default)]
