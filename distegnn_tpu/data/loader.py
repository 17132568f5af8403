"""Static-shape graph loaders.

The reference wraps processed lists in a PyG DataLoader with drop_last=True and
a seeded RandomSampler so every rank draws the same graph order
(reference main.py:184-190, datasets/process_dataset.py:582-596). Here loaders
collate into padded ``GraphBatch``es with dataset-wide N/E maxima fixed at
construction, so every batch of an epoch shares ONE compiled XLA program —
the TPU-first replacement for ragged PyG batching.
"""

from __future__ import annotations

import contextlib
import pickle
import threading
import time
import zipfile
from typing import Callable, List, Optional, Sequence, Union

import numpy as np
import jax

from distegnn_tpu import obs
from distegnn_tpu.config import removed_error
from distegnn_tpu.ops.graph import GraphBatch, _round_up, pad_graphs

# module-level open hook: the fault-injection harness (testing/faults.py
# flaky_open / truncated_read) swaps this to exercise the retry path without
# touching a real filesystem fault
_file_open = open

# bounded retry around dataset file reads: epoch-start reads off NFS/GCS see
# transient ESTALE/EIO-style hiccups, and a multi-hour unattended run must
# not die to one
_OPEN_ATTEMPTS = 3
_OPEN_BACKOFF_S = 0.1

# What a transiently-broken read surfaces as: open/read syscall errors
# (OSError), a pickle cut mid-payload (EOFError / UnpicklingError), a
# truncated .npz (BadZipFile), and numpy's header parse on garbage bytes
# (ValueError). A file broken the same way on every attempt still fails
# hard after the last retry.
_READ_ERRORS = (OSError, EOFError, pickle.UnpicklingError,
                zipfile.BadZipFile, ValueError)


def _open_with_retry(path: str, mode: str = "rb"):
    """``open`` with ``_OPEN_ATTEMPTS`` tries and exponential backoff
    (0.1s, 0.2s, ...); each retry is logged. The final failure propagates —
    a genuinely missing/unreadable file is still a hard error.

    NOTE: this only guards the ``open()`` syscall. Dataset loads must use
    :func:`_read_with_retry`, which covers the FULL payload read — a
    truncated NFS read succeeds at open() and dies inside ``pickle.load``."""
    for attempt in range(_OPEN_ATTEMPTS):
        try:
            return _file_open(path, mode)
        except OSError as e:
            if attempt == _OPEN_ATTEMPTS - 1:
                raise
            delay = _OPEN_BACKOFF_S * (2 ** attempt)
            obs.log(f"loader: open {path} failed ({e!r}); retry "
                    f"{attempt + 1}/{_OPEN_ATTEMPTS - 1} in {delay:.1f}s")
            time.sleep(delay)


def _read_with_retry(path: str, reader: Callable, what: str = "dataset",
                     retry_on: tuple = ()):
    """Open ``path`` and run ``reader(file)`` with the bounded retry covering
    the WHOLE read, not just ``open()``: a truncated NFS read hands back a
    short payload that only explodes inside ``pickle.load``/``np.load``, and
    before this existed such a failure escaped the retry and killed a
    multi-hour convergence session. ``retry_on`` adds caller-typed errors
    (e.g. a shard checksum mismatch) to the retryable set; the final failure
    always propagates."""
    errors = _READ_ERRORS + tuple(retry_on)
    for attempt in range(_OPEN_ATTEMPTS):
        try:
            with _file_open(path, "rb") as f:
                return reader(f)
        except errors as e:
            if attempt == _OPEN_ATTEMPTS - 1:
                raise
            delay = _OPEN_BACKOFF_S * (2 ** attempt)
            obs.log(f"loader: {what} read {path} failed ({e!r}); retry "
                    f"{attempt + 1}/{_OPEN_ATTEMPTS - 1} in {delay:.1f}s")
            time.sleep(delay)


# Stall attribution: the trainer reads per-step deltas of ``data/stall_s``,
# so that counter must mean "time the TRAINER was blocked on data". When the
# prefetch producer (data/stream.PrefetchLoader) drives a loader from its
# background thread, the collate/put work overlaps compute and is NOT a
# stall — the producer redirects its thread's accounting to
# ``data/produce_s`` via this thread-local, and only the consumer's real
# wait lands on ``data/stall_s``.
_STALL_TLS = threading.local()


def _stall_counter():
    name = getattr(_STALL_TLS, "name", None) or "data/stall_s"
    return obs.get_registry().counter(name)


@contextlib.contextmanager
def stall_attribution(name: str):
    """Redirect this THREAD's loader stall accounting to ``name``."""
    prev = getattr(_STALL_TLS, "name", None)
    _STALL_TLS.name = name
    try:
        yield
    finally:
        _STALL_TLS.name = prev


def graphs_nbytes(graphs: Sequence[dict]) -> int:
    """Resident bytes of a list of graph dicts (numpy payload only)."""
    total = 0
    for g in graphs:
        for v in g.values():
            if isinstance(v, np.ndarray):
                total += v.nbytes
    return total


def _log_host_bytes(nbytes: int, what: str) -> None:
    """Account dataset host residency on the ``data/host_bytes`` gauge (the
    RSS a training process pays to hold its datasets — the number the
    out-of-core streamed loader exists to bound)."""
    obs.get_registry().gauge("data/host_bytes").add(nbytes)
    obs.log(f"loader: {what} resident {nbytes / 2**20:.1f} MiB "
            f"(data/host_bytes)")


class GraphDataset:
    """A list of graph dicts, from a processed pickle file or in memory
    (reference DatasetWrapper, datasets/process_dataset.py:582-596)."""

    def __init__(self, source: Union[str, Sequence[dict]],
                 node_order: str = "none"):
        if isinstance(source, str):
            # retry covers the FULL pickle read: a truncated NFS payload dies
            # inside pickle.load, not at open()
            self.graphs: List[dict] = _read_with_retry(
                source, pickle.load, what="pickle")
        elif isinstance(source, list):
            # already-materialized list: adopt it as-is. list(source) here
            # used to double the transient footprint of the outer container
            # for zero benefit (the graph dicts were shared either way).
            self.graphs = source
        else:
            self.graphs = list(source)
        # 'morton': relabel nodes along a Z curve of their positions — static
        # locality preprocessing for the gather/aggregation hot loop
        # (ops/order.py). Permutation-equivariant models see
        # an identical problem with cache-friendly edge indices.
        if node_order == "morton":
            from distegnn_tpu.ops.order import morton_reorder_graph

            if self.graphs is source:
                # shallow outer copy (pointers only) so the caller's list is
                # never mutated by the per-slot reorder below
                self.graphs = list(self.graphs)
            # per-slot replacement so peak payload residency stays one
            # dataset + one graph, not two full array sets
            with obs.span("data/reorder", graphs=len(self.graphs)):
                for i in range(len(self.graphs)):
                    self.graphs[i] = morton_reorder_graph(self.graphs[i])
        elif node_order not in ("none", None):
            raise ValueError(f"GraphDataset: unknown node_order {node_order!r}")
        _log_host_bytes(graphs_nbytes(self.graphs),
                        f"GraphDataset[{len(self.graphs)} graphs]")

    def __len__(self) -> int:
        return len(self.graphs)

    def __getitem__(self, i: int) -> dict:
        return self.graphs[i]

    def size_maxima(self):
        n = max(g["loc"].shape[0] for g in self.graphs)
        e = max(g["edge_index"].shape[1] for g in self.graphs)
        return n, e


class GraphLoader:
    """Deterministic batching: permutation from (seed, epoch) only, so every
    host draws identical order (the invariant the reference checks per step
    with an all_gather, utils/train.py:55-61 — here it holds by construction).
    drop_last always (reference main.py:186)."""

    def __init__(
        self,
        dataset: GraphDataset,
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        node_bucket: int = 8,
        edge_bucket: int = 128,
        max_nodes: int = None,
        max_edges: int = None,
        edge_block: int = 0,
        edges_per_block: int = None,
        edge_tile: int = 512,
        pairing: Optional[bool] = None,  # None=auto (blocked: symmetry scan; plain: off)
        cache_bytes: int = 2 << 30,
        max_in_degree: Optional[int] = None,  # plain+pairing: dataset-stable ELL D
        # False only; benchmarks/drivers/train_scan.py and train_stream.py pass it (ROADMAP D15)
        split_remote: bool = False,
    ):
        if split_remote:
            raise removed_error("GraphLoader(split_remote=True)")
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.edge_block, self.edge_tile = edge_block, edge_tile
        self.pairing = False
        self._prepared_cache = None
        if edge_block:
            # dataset-stable blocked layout: ONE edges_per_block and ONE
            # pairing decision for every batch (single scan up front), so the
            # whole run keeps a single pytree structure / compiled program
            from distegnn_tpu.ops.blocked import scan_dataset_for_blocking

            if max_edges is not None:
                raise ValueError("GraphLoader: max_edges is unsupported with "
                                 "edge_block; pass edges_per_block instead")
            n, _ = dataset.size_maxima()
            self.max_nodes = _round_up(max(max_nodes or 0, n, 1), edge_block)
            if edges_per_block is None or pairing is None:
                deg, sym = scan_dataset_for_blocking(
                    dataset, self.max_nodes, edge_block)
                if edges_per_block is None:
                    edges_per_block = _round_up(deg, edge_tile)
                pairing = sym if pairing is None else pairing
            self.pairing = pairing
            self.edges_per_block = edges_per_block
            self.max_edges = (self.max_nodes // edge_block) * edges_per_block
            # cache prepared (blockified) graphs across epochs when affordable:
            # per-graph blocked edge payload ~ E * (2 idx + attrs + mask + pair)
            d0 = dataset[0].get("edge_attr")
            per = self.max_edges * (8 + 4 + 8 + (d0.shape[1] * 4 if d0 is not None else 0))
            if per * len(dataset) <= cache_bytes:
                self._prepared_cache = {}
            else:
                obs.log(f"GraphLoader: blockify cache OFF "
                        f"({per * len(dataset) / 2**30:.1f} GiB > "
                        f"{cache_bytes / 2**30:.1f} GiB budget) — every epoch re-lays "
                        f"edges on host; raise cache_bytes if RAM allows")
        else:
            self.edges_per_block = None
            # plain layout: pairing=True attaches the reverse-edge involution
            # to every batch (segment_impl='cumsum' uses it for scatter-free
            # col-gather backwards). In-tree edge builders emit symmetric
            # radius/full graphs, so the all-or-nothing per-batch pairing
            # stays structurally stable across the run.
            self.pairing = bool(pairing)
            if max_nodes is None or max_edges is None:
                n, e = dataset.size_maxima()
                max_nodes = max_nodes if max_nodes is not None else _round_up(n, node_bucket)
                max_edges = max_edges if max_edges is not None else _round_up(e, edge_bucket)
            self.max_nodes, self.max_edges = max_nodes, max_edges
            # GraphBatch.max_in_degree is STATIC: a per-batch value would
            # retrace the jitted step whenever it crossed a bucket boundary,
            # so scan the dataset once for a run-stable D (same rationale as
            # the blocked path's edges_per_block scan above)
            if self.pairing and max_in_degree is None:
                deg = max(int(np.bincount(dataset[i]["edge_index"][0],
                                          minlength=1).max())
                          for i in range(len(dataset)))
                max_in_degree = _round_up(max(deg, 1), 8)
            self.max_in_degree = max_in_degree
        if len(self) == 0:
            raise ValueError(
                f"batch_size {batch_size} > dataset size {len(dataset)}: "
                "drop_last leaves zero batches"
            )

    def pad_kwargs(self) -> dict:
        """kwargs that make pad_graphs emit this loader's (stable) layout."""
        if self.edge_block:
            return dict(edge_block=self.edge_block, edge_tile=self.edge_tile,
                        edges_per_block=self.edges_per_block,
                        max_nodes=self.max_nodes, compute_pair=self.pairing)
        return dict(max_nodes=self.max_nodes, max_edges=self.max_edges,
                    compute_pair=self.pairing, max_in_degree=self.max_in_degree)

    def _graph(self, i: int) -> dict:
        """Fetch graph i, blockified (and cached) when edge_block is on."""
        if not self.edge_block:
            return self.dataset[i]
        if self._prepared_cache is not None and i in self._prepared_cache:
            return self._prepared_cache[i]
        from distegnn_tpu.ops.blocked import prepare_blocked_graph

        g = prepare_blocked_graph(self.dataset[i], self.max_nodes,
                                  self.edges_per_block, self.edge_block,
                                  compute_pair=self.pairing)
        if self._prepared_cache is not None:
            self._prepared_cache[i] = g
        return g

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        return len(self.dataset) // self.batch_size

    def _order(self) -> np.ndarray:
        if not self.shuffle:
            return np.arange(len(self.dataset))
        return np.random.default_rng([self.seed, self.epoch]).permutation(len(self.dataset))

    def __iter__(self):
        order = self._order()
        # collation time is data-stall by definition (iteration is
        # synchronous: the trainer blocks on this generator) — unless this
        # thread runs under stall_attribution (prefetch producer), in which
        # case the same work overlaps compute and lands on data/produce_s
        stall = _stall_counter()
        reg = obs.get_registry()
        real_c, padded_c = reg.counter("data/real_edges"), reg.counter("data/padded_edges")
        for b in range(len(self)):
            t0 = time.perf_counter()
            idx = order[b * self.batch_size:(b + 1) * self.batch_size]
            batch = pad_graphs(
                [self._graph(int(i)) for i in idx], **self.pad_kwargs(),
            )
            # edge slots of the batch the step will compute on, and how many
            # of them hold an edge (the rest is padding to the dataset's
            # largest graph, or a blocked layout's interior padding)
            real_c.add(int(np.count_nonzero(batch.edge_mask)))
            padded_c.add(batch.edge_mask.size)
            stall.add(time.perf_counter() - t0)
            yield batch


class ShardedGraphLoader:
    """Lockstep loaders over per-partition shards, stacked on a leading
    partition axis [P, B, ...] — the layout shard_map consumes with the P axis
    sharded over the mesh's ``graph`` axis. Mirrors the reference's per-rank
    shard files + identical seeded order (main.py:182-190); shards share one
    N/E maximum so the stack is rectangular.

    ``data_parallel=D`` activates the mesh's second axis: each step draws
    D*batch_size graphs per partition shard and emits [D, P, B, ...], the D
    axis sharding over DATA_AXIS (different graphs per data shard — true data
    parallelism, which the reference lacks: its ranks all see the same batch,
    SURVEY.md §2.10)."""

    @obs.spanned("data/loader_init")
    def __init__(
        self,
        datasets: Sequence[GraphDataset],
        batch_size: int,
        shuffle: bool = False,
        seed: int = 0,
        node_bucket: int = 8,
        edge_bucket: int = 128,
        data_parallel: int = 1,
        edge_block: int = 0,
        edge_tile: int = 512,
        pairing: Optional[bool] = None,  # None=auto (blocked: AND over shard scans; plain: off)
        # False only; benchmarks/drivers/train_scan.py and train_stream.py pass it (ROADMAP D15)
        split_remote: bool = False,
    ):
        if split_remote:
            raise removed_error("ShardedGraphLoader(split_remote=True)")
        sizes = {len(d) for d in datasets}
        if len(sizes) != 1:
            raise ValueError(f"shards must be equal length, got {sorted(sizes)}")
        maxima = [d.size_maxima() for d in datasets]
        n = max(m[0] for m in maxima)
        e = max(m[1] for m in maxima)
        self.data_parallel = data_parallel
        if edge_block:
            # one blocked layout across ALL shards so the [P, B, ...] stack is
            # rectangular: common N, common edges_per_block, and ONE pairing
            # decision (max/AND over shards)
            from distegnn_tpu.ops.blocked import scan_dataset_for_blocking

            N = _round_up(n, edge_block)
            scans = [scan_dataset_for_blocking(d, N, edge_block) for d in datasets]
            epb = _round_up(max(s[0] for s in scans), edge_tile)
            if pairing is None:
                pairing = all(s[1] for s in scans)
            self.loaders = [
                GraphLoader(
                    d, batch_size * data_parallel, shuffle=shuffle, seed=seed,
                    max_nodes=N, edge_block=edge_block, edge_tile=edge_tile,
                    edges_per_block=epb, pairing=pairing,
                )
                for d in datasets
            ]
        else:
            # one static max_in_degree across ALL shards so the stacked
            # [P, B, ...] batches share a single pytree identity
            mid = None
            if pairing:
                deg = max(int(np.bincount(d[i]["edge_index"][0], minlength=1).max())
                          for d in datasets for i in range(len(d)))
                mid = _round_up(max(deg, 1), 8)
            self.loaders = [
                GraphLoader(
                    d, batch_size * data_parallel, shuffle=shuffle, seed=seed,
                    max_nodes=_round_up(n, node_bucket), max_edges=_round_up(e, edge_bucket),
                    pairing=pairing, max_in_degree=mid,
                )
                for d in datasets
            ]

    @property
    def num_partitions(self) -> int:
        return len(self.loaders)

    def set_epoch(self, epoch: int) -> None:
        for l in self.loaders:
            l.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loaders[0])

    def __iter__(self):
        D = self.data_parallel
        # the per-shard loaders already count their collation time; only the
        # stack/reshape work on top of them is added here (same thread-local
        # attribution as GraphLoader.__iter__)
        stall = _stall_counter()
        for parts in zip(*self.loaders):
            t0 = time.perf_counter()
            if any(p.edge_pair is None for p in parts):
                # pairing must be all-or-nothing for a rectangular stack
                parts = [p.replace(edge_pair=None) for p in parts]
            stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *parts)
            if D > 1:
                # [P, D*B, ...] -> [D, P, B, ...]
                stacked = jax.tree.map(
                    lambda x: x.reshape(x.shape[0], D, x.shape[1] // D,
                                        *x.shape[2:]).swapaxes(0, 1),
                    stacked,
                )
            stall.add(time.perf_counter() - t0)
            yield stacked
