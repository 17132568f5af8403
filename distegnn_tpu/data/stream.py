"""Out-of-core streamed graph pipeline: sharded on-disk datasets + async
double-buffered prefetch.

Two independent single-host ceilings fall here (ROADMAP "million-node
graphs" item, streaming rationale per arXiv:1906.11786):

1. **Residency** — ``GraphDataset`` pickles the whole dataset into host RAM.
   :func:`write_shards` lays a processed dataset out as a directory of
   fixed-schema ``.npz`` shards plus a JSON manifest (per-shard N/E maxima,
   dataset maxima, CRC32 checksums), and :class:`StreamedGraphDataset` serves
   the same ``__getitem__``/``size_maxima`` protocol while holding only a
   bounded LRU of decoded shards — host RSS is O(cache_shards · shard_bytes),
   not O(dataset).

2. **Stall** — the old ``_PuttingLoader`` blocked the trainer on every
   synchronous collate + host→device put. :class:`PrefetchLoader` moves that
   work to a bounded background thread (``data.prefetch_depth`` deep, default
   2) so disk read + collate + put overlap the previous step's compute;
   ``data/stall_s`` then measures only true starvation, with the overlapped
   producer time visible separately as ``data/produce_s`` and, batch by
   batch, as ``data/produce`` spans on the producer's thread.

Determinism is untouched: epoch order lives entirely in
``GraphLoader._order()`` (seeded permutation), the shard format round-trips
arrays bitwise (npz is lossless), and the prefetch queue is strictly FIFO —
so a streamed, prefetched epoch is bitwise-identical to the in-memory
blocking epoch (tests/test_stream.py asserts this end to end).
"""

from __future__ import annotations

import collections
import json
import os
import queue
import threading
import time
import zlib
from typing import Callable, Optional, Sequence

import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.data.loader import (
    GraphDataset, _read_with_retry, stall_attribution,
)
from distegnn_tpu.obs.jaxprobe import TransferMeter

FORMAT = "distegnn-shards-v1"
MANIFEST = "manifest.json"

# graph-dict fields along the node axis / edge axis / per-graph, in the order
# they are concatenated into a shard. Optional fields must be uniformly
# present or absent across the WHOLE dataset (the loaders' static-shape
# contract: one pytree structure per run).
_NODE_FIELDS = ("node_feat", "node_attr", "loc", "vel", "target")
_EDGE_FIELDS = ("edge_attr",)
_OPTIONAL = frozenset({"node_attr", "target", "edge_attr"})


class ShardChecksumError(RuntimeError):
    """A shard's bytes do not match the manifest CRC32 (bit rot, torn write,
    or a partially-synced copy). Retried a bounded number of times — a
    transient short read off NFS heals; persistent corruption propagates."""


class PrefetchCrashError(RuntimeError):
    """The prefetch producer thread died. The original exception is chained
    as ``__cause__`` — the trainer gets a typed, immediate failure instead of
    a silent hang on an empty queue."""


def _crc32(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF


def _check_uniform_fields(graphs: Sequence[dict]):
    """Which optional fields are present — uniformly, or it's an error."""
    present = {}
    for name in _NODE_FIELDS + _EDGE_FIELDS:
        if name in _OPTIONAL:
            have = [g.get(name) is not None for g in graphs]
            if any(have) and not all(have):
                raise ValueError(
                    f"write_shards: field {name!r} present in some graphs but "
                    "not others; the static-shape loaders need one schema for "
                    "the whole dataset")
            present[name] = bool(have and have[0])
        else:
            present[name] = True
    return present


def write_shards(graphs: Sequence[dict], out_dir: str, shard_size: int = 64,
                 node_order: str = "none") -> dict:
    """Write ``graphs`` as ``out_dir/shard_%05d.npz`` + ``manifest.json``.

    Shard schema (fixed): ``node_ptr``/``edge_ptr`` int64 prefix offsets over
    the shard's graphs, node-axis fields concatenated on axis 0, edge fields
    on their edge axis (``edge_index`` is [2, Etot] with LOCAL per-graph node
    ids — slicing by ``edge_ptr`` recovers each graph exactly), ``loc_mean``
    stacked [g, 3]. Writes are atomic (tmp + rename) and each shard's CRC32
    goes in the manifest so a torn read is detected at load, not at loss=NaN.

    Returns the manifest dict.
    """
    if shard_size < 1:
        raise ValueError(f"write_shards: shard_size must be >= 1, got {shard_size}")
    graphs = list(graphs)
    if not graphs:
        raise ValueError("write_shards: empty dataset")
    if node_order == "morton":
        from distegnn_tpu.ops.order import morton_reorder_graph

        graphs = [morton_reorder_graph(g) for g in graphs]
    elif node_order not in ("none", None):
        raise ValueError(f"write_shards: unknown node_order {node_order!r}")
    present = _check_uniform_fields(graphs)
    os.makedirs(out_dir, exist_ok=True)

    shards = []
    for s0 in range(0, len(graphs), shard_size):
        chunk = graphs[s0:s0 + shard_size]
        arrays = {
            "node_ptr": np.cumsum(
                [0] + [g["loc"].shape[0] for g in chunk], dtype=np.int64),
            "edge_ptr": np.cumsum(
                [0] + [g["edge_index"].shape[1] for g in chunk], dtype=np.int64),
            "edge_index": np.concatenate(
                [g["edge_index"] for g in chunk], axis=1),
            "loc_mean": np.stack(
                [g["loc_mean"] if g.get("loc_mean") is not None
                 else g["loc"].mean(axis=0) for g in chunk], axis=0),
        }
        for name in _NODE_FIELDS:
            if present[name]:
                arrays[name] = np.concatenate([g[name] for g in chunk], axis=0)
        for name in _EDGE_FIELDS:
            if present[name]:
                arrays[name] = np.concatenate([g[name] for g in chunk], axis=0)
        import io

        buf = io.BytesIO()
        np.savez(buf, **arrays)
        payload = buf.getvalue()
        fname = f"shard_{len(shards):05d}.npz"
        tmp = os.path.join(out_dir, fname + ".tmp")
        with open(tmp, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(out_dir, fname))
        shards.append({
            "file": fname,
            "n_graphs": len(chunk),
            "max_nodes": max(g["loc"].shape[0] for g in chunk),
            "max_edges": max(g["edge_index"].shape[1] for g in chunk),
            "crc32": _crc32(payload),
            "bytes": len(payload),
        })

    manifest = {
        "format": FORMAT,
        "n_graphs": len(graphs),
        "shard_size": shard_size,
        "node_order": node_order or "none",
        "fields": present,
        "max_nodes": max(s["max_nodes"] for s in shards),
        "max_edges": max(s["max_edges"] for s in shards),
        "shards": shards,
    }
    tmp = os.path.join(out_dir, MANIFEST + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(out_dir, MANIFEST))
    obs.log(f"write_shards: {len(graphs)} graphs -> {len(shards)} shards in "
            f"{out_dir} ({sum(s['bytes'] for s in shards) / 2**20:.1f} MiB)")
    return manifest


def is_shard_dir(path) -> bool:
    return (isinstance(path, str) and os.path.isdir(path)
            and os.path.exists(os.path.join(path, MANIFEST)))


class StreamedGraphDataset:
    """Out-of-core ``GraphDataset`` drop-in over a :func:`write_shards`
    directory: same ``__len__``/``__getitem__``/``size_maxima`` protocol, so
    ``GraphLoader``/``ShardedGraphLoader`` (and their dataset-wide blocking /
    degree scans) work unchanged — but only ``cache_shards`` decoded shards
    are resident at any time (LRU), keeping host RSS bounded regardless of
    dataset size.

    Honest residency note: npz members are zip-compressed streams, so shards
    cannot be OS-mmapped page-by-page; a shard's arrays are materialized when
    it enters the cache (one sequential read + CRC32 verify, O(shard) not
    O(dataset)) and every ``__getitem__`` serves zero-copy views into those
    arrays. The LRU bound — not mmap — is what keeps RSS flat.
    """

    def __init__(self, shard_dir: str, node_order: str = "none",
                 cache_shards: int = 4, verify: bool = True):
        if cache_shards < 1:
            raise ValueError(
                f"StreamedGraphDataset: cache_shards must be >= 1, got {cache_shards}")
        self.shard_dir = shard_dir
        self.cache_shards = cache_shards
        self.verify = verify
        self.manifest = _read_with_retry(
            os.path.join(shard_dir, MANIFEST),
            lambda f: json.loads(f.read().decode("utf-8")),
            what="manifest")
        if self.manifest.get("format") != FORMAT:
            raise ValueError(
                f"StreamedGraphDataset: {shard_dir} manifest format "
                f"{self.manifest.get('format')!r} != {FORMAT!r}")
        if node_order in ("none", None):
            self._reorder = None
        elif node_order == "morton":
            if self.manifest.get("node_order") == "morton":
                # already baked into the shards at write time — don't pay a
                # per-access reorder for an identity permutation
                self._reorder = None
            else:
                from distegnn_tpu.ops.order import morton_reorder_graph

                self._reorder = morton_reorder_graph
        else:
            raise ValueError(
                f"StreamedGraphDataset: unknown node_order {node_order!r}")
        self._starts = np.cumsum(
            [0] + [s["n_graphs"] for s in self.manifest["shards"]])
        self._cache = collections.OrderedDict()  # shard idx -> dict of arrays
        self._cache_bytes = 0
        self._host_gauge = obs.get_registry().gauge("data/host_bytes")

    def __len__(self) -> int:
        return int(self.manifest["n_graphs"])

    @property
    def open_shards(self) -> int:
        """Decoded shards currently resident (the RSS proxy tests bound)."""
        return len(self._cache)

    def size_maxima(self):
        return int(self.manifest["max_nodes"]), int(self.manifest["max_edges"])

    def _load_shard(self, si: int) -> dict:
        meta = self.manifest["shards"][si]
        path = os.path.join(self.shard_dir, meta["file"])

        def _reader(f):
            payload = f.read()
            if self.verify and _crc32(payload) != meta["crc32"]:
                raise ShardChecksumError(
                    f"{path}: crc32 {_crc32(payload):#010x} != manifest "
                    f"{meta['crc32']:#010x} ({len(payload)} bytes read, "
                    f"{meta['bytes']} expected)")
            import io

            with np.load(io.BytesIO(payload)) as z:
                return {k: z[k] for k in z.files}

        # a short/torn read shows up as a CRC mismatch — retryable; a shard
        # corrupted the same way on every attempt still fails hard
        return _read_with_retry(path, _reader, what="shard",
                                retry_on=(ShardChecksumError,))

    def _shard(self, si: int) -> dict:
        hit = self._cache.get(si)
        if hit is not None:
            self._cache.move_to_end(si)
            return hit
        arrays = self._load_shard(si)
        nbytes = sum(a.nbytes for a in arrays.values())
        self._cache[si] = arrays
        self._cache_bytes += nbytes
        while len(self._cache) > self.cache_shards:
            _, old = self._cache.popitem(last=False)
            self._cache_bytes -= sum(a.nbytes for a in old.values())
        self._host_gauge.set(self._cache_bytes)
        return arrays

    def __getitem__(self, i: int) -> dict:
        if i < 0:
            i += len(self)
        if not 0 <= i < len(self):
            raise IndexError(f"graph index {i} out of range [0, {len(self)})")
        si = int(np.searchsorted(self._starts, i, side="right")) - 1
        l = i - int(self._starts[si])
        sh = self._shard(si)
        n0, n1 = int(sh["node_ptr"][l]), int(sh["node_ptr"][l + 1])
        e0, e1 = int(sh["edge_ptr"][l]), int(sh["edge_ptr"][l + 1])
        fields = self.manifest["fields"]
        g = {
            "edge_index": sh["edge_index"][:, e0:e1],
            "loc_mean": sh["loc_mean"][l],
        }
        for name in _NODE_FIELDS:
            g[name] = sh[name][n0:n1] if fields.get(name) else None
        for name in _EDGE_FIELDS:
            g[name] = sh[name][e0:e1] if fields.get(name) else None
        if self._reorder is not None:
            g = self._reorder(g)
        return g


def open_dataset(source, node_order: str = "none", cache_shards: int = 4):
    """One constructor for both residency models: a :func:`write_shards`
    directory streams (:class:`StreamedGraphDataset`); a pickle path or
    in-memory list materializes (:class:`GraphDataset`). launch.py routes
    every dataset path through here, so switching a run out-of-core is a
    data-path change, not a code change."""
    if is_shard_dir(source):
        return StreamedGraphDataset(source, node_order=node_order,
                                    cache_shards=cache_shards)
    return GraphDataset(source, node_order=node_order)


class PrefetchLoader:
    """Async replacement for the blocking put-wrapper (`_PuttingLoader`): a
    bounded background thread runs the inner loader's disk read + collate +
    host→device ``put`` up to ``depth`` batches ahead, overlapping the
    previous step's compute.

    Accounting contract (trainer reads per-step deltas of ``data/stall_s``):
    the producer thread runs under ``stall_attribution("data/produce_s")`` so
    the overlapped collate work no longer pollutes the stall counter; only
    the consumer's real wait on the queue lands on ``data/stall_s``.
    ``data/prefetch_depth`` gauge reports the configured depth. ``depth=0``
    degrades to the old fully synchronous behavior.

    Spans: each batch is built under ``data/produce`` > ``data/collate`` (the
    inner loader's ``next``), ``data/put``. The producer's wait on a full
    queue lies outside them.

    Failure contract: a producer crash propagates as
    :class:`PrefetchCrashError` (original chained as ``__cause__``) on the
    consumer's next ``__next__`` — never a hang. Abandoning iteration
    mid-epoch stops and joins the thread (generator ``finally``).
    """

    def __init__(self, loader, put: Optional[Callable] = None, depth: int = 2):
        if depth < 0:
            raise ValueError(f"PrefetchLoader: depth must be >= 0, got {depth}")
        self.loader, self.put, self.depth = loader, put, depth
        self._meter = TransferMeter()

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def _produce(self, it, stall=None):
        """The next batch of ``it``, collated and put, or None at its end;
        with ``stall``, the put's time is added to that counter."""
        with obs.span("data/produce"):
            with obs.span("data/collate"):
                batch = next(it, None)
            if batch is None:
                return None
            with obs.span("data/put") as put:
                self._meter.h2d(batch)
                out = self.put(batch) if self.put is not None else batch
            if stall is not None:
                stall.add((put.end_ns - put.start_ns) / 1e9)
            return out

    def __iter__(self):
        reg = obs.get_registry()
        reg.gauge("data/prefetch_depth").set(self.depth)
        if self.depth == 0:
            # synchronous path: put time is trainer stall by definition
            stall = reg.counter("data/stall_s")
            it = iter(self.loader)
            while (out := self._produce(it, stall)) is not None:
                yield out
            return

        q = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def _offer(msg) -> bool:
            # bounded-queue put that never deadlocks a dead consumer: give up
            # as soon as the consumer signalled stop
            while not stop.is_set():
                try:
                    q.put(msg, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def _producer():
            try:
                with stall_attribution("data/produce_s"):
                    it = iter(self.loader)
                    while (out := self._produce(it)) is not None:
                        if not _offer(("item", out)):
                            return
                _offer(("done", None))
            except BaseException as e:  # must reach the consumer, whatever it is
                _offer(("err", e))

        t = threading.Thread(target=_producer, daemon=True,
                             name="distegnn-prefetch")
        t.start()
        stall = reg.counter("data/stall_s")
        try:
            while True:
                t0 = time.perf_counter()
                while True:
                    try:
                        kind, val = q.get(timeout=1.0)
                        break
                    except queue.Empty:
                        if not t.is_alive():
                            raise PrefetchCrashError(
                                "prefetch producer thread died without "
                                "reporting (queue empty, thread dead)")
                stall.add(time.perf_counter() - t0)
                if kind == "done":
                    return
                if kind == "err":
                    raise PrefetchCrashError(
                        f"prefetch producer crashed: {val!r}") from val
                yield val
        finally:
            stop.set()
            while True:  # unblock a producer parked on a full queue
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=10.0)
