"""JAX-runtime probes: compile (recompile!) spans and watcher, device memory,
transfers.

The #1 silent perf bug on a shape-laddered TPU stack is a recompile after
warmup — a shape drifting past its bucket, a weak_type flip, a donated buffer
changing layout — which shows up only as a mysteriously slow step. XLA's
compiles are invisible to user code EXCEPT through ``jax.monitoring``: every
backend compile, and every retrieval from the persistent cache in its place,
records a ``/jax/core/compile/backend_compile_duration`` event with the
``fun_name`` of the program. One listener, registered when this module is
imported (``distegnn_tpu.obs`` imports it), turns each into a ``jax/compile``
span (``fun_name``) in the ring: ``obs.recent_spans()`` then answers "which
program compiled how often" whether or not a run configured anything.
:class:`CompileWatcher` (``obs.jax_probe``) adds, on top, the phase the
runtime declared (``warmup``, ``epoch<N>``, ``serve``, ...), counts
compiles-after-warmup so ``scripts/obs_report.py --check`` can fail a run on
them, and is what sends the span to ``events.jsonl``: without a watcher the
file holds no ``jax/compile`` record.

Listener lifetime: ``jax.monitoring`` listeners cannot portably be removed,
so the one listener dispatches to the currently-active watcher —
re-configuring a run (or running many tests in one process) swaps the
watcher, never stacks listeners.

Also here: ``device_memory_stats()`` (``memory_stats()`` of local device 0,
when the backend exposes it — TPU/GPU yes, CPU None) and
:class:`TransferMeter` host->device byte accounting for the loaders.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

import jax.monitoring

from distegnn_tpu.obs import metrics as _metrics
from distegnn_tpu.obs import trace as _trace

# one per program handed to the backend: a real XLA compile or, with the
# persistent cache on, the retrieval that replaced it
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active: Optional["CompileWatcher"] = None


def _on_duration_event(event: str, duration_secs: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    end_ns = time.perf_counter_ns()
    attrs: Dict[str, Any] = {"fun_name": kwargs.get("fun_name")}
    w = _active
    if w is not None:
        attrs.update(w._record_compile(duration_secs))
    _trace.record_span("jax/compile", end_ns - int(duration_secs * 1e9),
                       end_ns, sink=w is not None, **attrs)


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


class CompileWatcher:
    """Attributes compiles to runtime-declared phases and counts them.

    Counters (global registry): ``jax/compiles`` (total),
    ``jax/compiles_after_warmup`` (the alarm), ``jax/compile_s`` (time spent
    compiling). While a watcher is active every ``jax/compile`` span carries
    its ``phase`` and ``after_warmup``, so the report can render a recompile
    table.
    """

    def __init__(self, registry: Optional[_metrics.MetricsRegistry] = None):
        self.registry = registry or _metrics.get_registry()
        self._lock = threading.Lock()
        self.phase = "warmup"
        self.warmup_done = False
        self.compiles = 0
        self.compiles_after_warmup = 0

    def set_phase(self, phase: str) -> None:
        with self._lock:
            self.phase = phase

    def mark_warmup_done(self) -> None:
        """Declare steady state: every compile from here on is a recompile —
        the silent perf bug obs_report's --check gate exists to catch."""
        with self._lock:
            self.warmup_done = True

    def _record_compile(self, duration_secs: float) -> Dict[str, Any]:
        with self._lock:
            self.compiles += 1
            after = self.warmup_done
            if after:
                self.compiles_after_warmup += 1
            phase = self.phase
        self.registry.counter("jax/compiles").add(1)
        self.registry.counter("jax/compile_s").add(duration_secs)
        if after:
            self.registry.counter("jax/compiles_after_warmup").add(1)
        return {"phase": phase, "after_warmup": after}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"compiles": self.compiles,
                    "compiles_after_warmup": self.compiles_after_warmup,
                    "phase": self.phase, "warmup_done": self.warmup_done}


def install_compile_watcher(registry: Optional[_metrics.MetricsRegistry] = None
                            ) -> CompileWatcher:
    """Install (or re-target) THE process compile watcher: the one the
    module's listener hands every compile to."""
    global _active
    _active = CompileWatcher(registry)
    return _active


def get_compile_watcher() -> Optional[CompileWatcher]:
    return _active


def deactivate_compile_watcher() -> None:
    """Stop counting (``jax/compile`` spans go on, without phases)."""
    global _active
    _active = None


def set_phase(phase: str) -> None:
    """Phase declaration on the active watcher; no-op when none is live, so
    runtimes can declare phases unconditionally."""
    w = _active
    if w is not None:
        w.set_phase(phase)


def mark_warmup_done() -> None:
    w = _active
    if w is not None:
        w.mark_warmup_done()


# ---- device memory ---------------------------------------------------------

def device_memory_stats() -> Dict[str, Any]:
    """``memory_stats()`` of local device 0 when the backend exposes it
    (TPU/GPU); {} on CPU or pre-initialization failure. Keys are
    backend-defined (e.g. ``bytes_in_use``, ``peak_bytes_in_use``)."""
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
        return dict(stats) if stats else {}
    except Exception:
        return {}


def emit_memory_event(tracer: Optional[_trace.Tracer] = None,
                      name: str = "jax/memory", **attrs) -> Dict[str, Any]:
    """Snapshot device memory into the event stream (no-op payload on CPU —
    the event still lands, so the report can say 'no memory stats here')."""
    t = tracer or _trace.get_tracer()
    stats = device_memory_stats()
    t.event(name, **{**attrs, **{k: stats[k] for k in
                                 ("bytes_in_use", "peak_bytes_in_use",
                                  "largest_alloc_size")
                                 if k in stats}})
    return stats


def record_memory_gauges(tag: str,
                         registry: Optional[_metrics.MetricsRegistry] = None,
                         ) -> Dict[str, Any]:
    """Per-chip HBM footprint as registry gauges (``mem/<tag>/<key>``) —
    the obs_report/snapshot view that pairs with :func:`emit_memory_event`'s
    events.jsonl view. The 3D-mesh sizing question this answers: does the
    T-way hidden-dim shard actually shrink ``peak_bytes_in_use`` per chip?
    Empty dict (no gauges) on CPU, where the backend reports no stats."""
    reg = registry or _metrics.get_registry()
    stats = device_memory_stats()
    for k in ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size"):
        if k in stats:
            reg.gauge(f"mem/{tag}/{k}").set(float(stats[k]))
    return stats


# ---- host<->device transfer accounting -------------------------------------

def tree_nbytes(tree) -> int:
    """Total nbytes of the array leaves of a pytree (numpy or jax arrays)."""
    try:
        import jax

        leaves = jax.tree.leaves(tree)
    except Exception:
        leaves = [tree]
    return sum(int(getattr(l, "nbytes", 0)) for l in leaves)


class TransferMeter:
    """Byte counter at the host->device boundary. The loaders/putters call
    ``h2d(batch)`` on everything they hand to the device. The counter lives
    in the global registry (``xfer/h2d_bytes``) so it appears in every
    snapshot without plumbing."""

    def __init__(self, registry: Optional[_metrics.MetricsRegistry] = None):
        reg = registry or _metrics.get_registry()
        self._h2d = reg.counter("xfer/h2d_bytes")

    def h2d(self, tree) -> int:
        n = tree_nbytes(tree)
        self._h2d.add(n)
        return n
