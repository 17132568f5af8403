"""Low-overhead structured tracing: spans + events -> ring, profiler, JSONL.

One process-global :class:`Tracer` (swap it with :func:`configure`) serves
every runtime — trainer, serve stack, loaders, checkpointing. ``span()`` is
the one instrumentation call and has three sinks:

1. a ``jax.profiler.TraceAnnotation`` of the same name, so whenever anyone
   records a profiler trace the span lies in the xplane's host plane, on the
   device trace's clock;
2. a bounded in-memory ring of :class:`SpanRecord` (:func:`recent_spans`,
   the newest ``RING_SIZE``), on ``time.perf_counter_ns``, each with the CPU
   time its thread spent inside it (``time.thread_time_ns``: a span that
   blocks in a call, on a queue or on the device's backpressure, is long but
   cheap), its own id and the id of the span that enclosed it on its thread;
3. ``events.jsonl``, when :func:`configure` has bound a sink.

Ring and annotation do not depend on the sink: ``obs.enable: false`` and
``train(log=False)`` mean "no file, no watcher events", not "no spans".
``event()`` has the JSONL sink alone and returns at once without one. A span
costs four clock reads, one annotation object and one ring append
(docs/OBSERVABILITY.md has the micro-timing).

Event schema (docs/OBSERVABILITY.md): one JSON object per line,
  {"ts": <unix seconds>, "kind": "span"|"event"|"log", "name": str,
   "proc": <process_index>, "host": <hostname>, ["dur_s": float], ...attrs}

Writing is buffered (``buffer_events`` lines or ``flush_interval_s`` seconds,
whichever first) behind one lock, appended to ``<dir>/events.jsonl``. By
default only process 0 writes (params/metrics are replicated, and one file
per run is what the report tooling wants); ``per_host=True`` gives every
process its own ``events_p<i>.jsonl`` for load-imbalance hunts.

``log()`` is the host-prefixed structured logger replacing bare ``print``:
stdout stays line-compatible (the message text is unchanged; a ``[p<i>] ``
prefix appears only on processes > 0), always flushed, and — when a sink is
live — the same message lands in events.jsonl as a ``log`` event.
"""

from __future__ import annotations

import atexit
import collections
import functools
import itertools
import json
import os
import socket
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

RING_SIZE = 8192


def _process_index() -> int:
    """jax.process_index() if the backend is importable, else 0. Kept lazy so
    importing obs never forces backend initialization."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


class EventWriter:
    """Thread-safe buffered JSONL appender with time/size-based flushing."""

    def __init__(self, path: str, buffer_events: int = 256,
                 flush_interval_s: float = 2.0):
        self.path = path
        self.buffer_events = max(int(buffer_events), 1)
        self.flush_interval_s = float(flush_interval_s)
        self._lock = threading.Lock()
        self._buf: list[str] = []
        self._last_flush = time.monotonic()
        self._closed = False
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # truncate: one writer per run dir, and a re-configured run (tests,
        # resumed processes reusing a dir) must not interleave with old events
        with open(path, "w"):
            pass
        # every writer flushes at interpreter exit, not just the one the
        # global tracer happens to hold — a bench that buffers its tail and
        # calls sys.exit must still leave a parseable file behind
        atexit.register(self.close)

    def write(self, record: Dict[str, Any]) -> None:
        line = json.dumps(record, separators=(",", ":"), default=repr)
        with self._lock:
            if self._closed:
                return
            self._buf.append(line)
            if (len(self._buf) >= self.buffer_events
                    or time.monotonic() - self._last_flush >= self.flush_interval_s):
                self._flush_locked()

    def _flush_locked(self) -> None:
        if self._buf:
            with open(self.path, "a") as f:
                f.write("\n".join(self._buf) + "\n")
            self._buf.clear()
        self._last_flush = time.monotonic()

    def flush(self) -> None:
        with self._lock:
            if not self._closed:
                self._flush_locked()

    def close(self) -> None:
        with self._lock:
            if not self._closed:
                self._flush_locked()
                self._closed = True
        atexit.unregister(self.close)


class SpanRecord(NamedTuple):
    """One finished span as the ring keeps it. Times are
    ``time.perf_counter_ns``; ``cpu_ns`` is the thread's CPU time inside the
    span (0 for a span recorded after the fact); ``parent`` is 0 for a span
    with none open around it on its thread."""

    name: str
    thread: str
    start_ns: int
    end_ns: int
    cpu_ns: int
    id: int
    parent: int
    attrs: Dict[str, Any]


_ring: "collections.deque[SpanRecord]" = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_open = threading.local()       # .stack: this thread's open spans, outermost first


def recent_spans() -> List[SpanRecord]:
    """The newest ``RING_SIZE`` finished spans of this process, in the order
    they ended."""
    return list(_ring.copy())   # copy() is one C call: no append can interleave


def clear_spans() -> None:
    _ring.clear()


def record_span(name: str, start_ns: int, end_ns: int, sink: bool = True,
                **attrs) -> None:
    """A span learned of only when it was over (a ``jax.monitoring``
    duration): the ring and, with ``sink``, JSONL; no profiler annotation.
    Its parent is the span open on this thread now."""
    stack = getattr(_open, "stack", None)
    _ring.append(SpanRecord(name, threading.current_thread().name, start_ns,
                            end_ns, 0, next(_ids),
                            stack[-1].id if stack else 0, attrs))
    if sink:
        _tracer._emit("span", name, dur_s=round((end_ns - start_ns) / 1e9, 6),
                      **attrs)


class _Span:
    """Times a with-block; at exit appends one :class:`SpanRecord` to the
    ring and, with a sink, writes one ``span`` record. Extra attributes can
    be attached mid-flight via ``set(**attrs)``; ``start_ns``/``end_ns`` stay
    readable after the block."""

    __slots__ = ("_tracer", "name", "attrs", "id", "parent",
                 "start_ns", "end_ns", "_cpu_ns", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> "_Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._cpu_ns = time.thread_time_ns()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end_ns = time.perf_counter_ns()
        cpu_ns = time.thread_time_ns() - self._cpu_ns
        self._annotation.__exit__(exc_type, exc, tb)
        _open.stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _ring.append(SpanRecord(self.name, threading.current_thread().name,
                                self.start_ns, self.end_ns, cpu_ns, self.id,
                                self.parent, self.attrs))
        if self._tracer.writer is not None:
            self._tracer._emit(
                "span", self.name,
                dur_s=round((self.end_ns - self.start_ns) / 1e9, 6),
                **self.attrs)
        return False


class Tracer:
    """Span/event/log emitter over an optional :class:`EventWriter` sink."""

    def __init__(self, writer: Optional[EventWriter] = None,
                 tags: Optional[Dict[str, Any]] = None,
                 process_index: int = 0):
        self.writer = writer
        self.tags = dict(tags or {})
        self.process_index = int(process_index)

    @property
    def enabled(self) -> bool:
        return self.writer is not None

    def _emit(self, kind: str, name: str, **attrs) -> None:
        w = self.writer
        if w is None:
            return
        rec = {"ts": round(time.time(), 6), "kind": kind, "name": name}
        rec.update(self.tags)
        rec.update(attrs)
        w.write(rec)

    def span(self, name: str, **attrs) -> _Span:
        """Context manager timing a block into the profiler's trace and the
        ring, and into the sink when one is live."""
        return _Span(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        self._emit("event", name, **attrs)

    def log(self, msg: str, **attrs) -> None:
        """Structured logger replacing bare ``print``: stdout-line-compatible
        (identical text on process 0 / single-process; ``[p<i>] `` prefix on
        other processes), always flushed, mirrored into the event stream."""
        prefix = f"[p{self.process_index}] " if self.process_index else ""
        print(prefix + msg, flush=True)  # noqa: obs-print (the logger itself)
        self._emit("log", "log", msg=msg, **attrs)

    def flush(self) -> None:
        if self.writer is not None:
            self.writer.flush()

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()


# ---- process-global tracer --------------------------------------------------

_tracer = Tracer()          # disabled until configure() runs
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    return _tracer


def configure(log_dir: Optional[str] = None, enable: bool = True,
              per_host: bool = False, buffer_events: int = 256,
              flush_interval_s: float = 2.0,
              tags: Optional[Dict[str, Any]] = None,
              filename: Optional[str] = None) -> Tracer:
    """(Re)bind the global tracer.

    ``enable=False`` or ``log_dir=None`` installs a sinkless tracer: events
    become no-ops, spans keep to the ring and the profiler, and NO file is
    created (the ``obs.enable: false`` kill switch); ``log()`` keeps printing
    either way. Default sink layout:
    process 0 writes ``<log_dir>/events.jsonl``; with ``per_host`` every
    process writes ``<log_dir>/events_p<i>.jsonl``. Every record is tagged
    ``proc``/``host`` (plus any extra ``tags``) so multi-host streams merge
    unambiguously.

    ``filename`` overrides the sink file name outright and always writes
    (no process-0 gating) — the serving worker children reuse this per-host
    machinery with worker-scoped names (``events_worker_<model>_<idx>.jsonl``
    next to the parent's ``events.jsonl``), so obs_report can stitch one
    request waterfall across the process boundary.
    """
    global _tracer
    pidx = _process_index()
    writer = None
    if enable and log_dir is not None and (per_host or filename is not None
                                           or pidx == 0):
        name = filename or (f"events_p{pidx}.jsonl" if per_host
                            else "events.jsonl")
        writer = EventWriter(os.path.join(log_dir, name),
                             buffer_events=buffer_events,
                             flush_interval_s=flush_interval_s)
    all_tags = {"proc": pidx, "host": socket.gethostname()}
    all_tags.update(tags or {})
    with _tracer_lock:
        old, _tracer = _tracer, Tracer(writer, tags=all_tags,
                                       process_index=pidx)
        old.close()
    return _tracer


def configure_from_config(config, exp_dir: str, enabled_here: bool = True,
                          tags: Optional[Dict[str, Any]] = None) -> Tracer:
    """Wire the tracer from a run config's ``obs:`` section (absent section =
    defaults = on). ``enabled_here`` gates non-logging invocations (e.g.
    ``train(log=False)`` test runs must not leave event files around).
    Returns the tracer; also installs the compile watcher when
    ``obs.jax_probe`` is on."""
    o = config.get("obs") if hasattr(config, "get") else None
    get = (lambda k, d: o.get(k, d) if o is not None else d)
    enable = bool(get("enable", True)) and enabled_here
    tracer = configure(
        log_dir=os.path.join(exp_dir, "obs") if enable else None,
        enable=enable,
        per_host=bool(get("per_host", False)),
        buffer_events=int(get("buffer_events", 256)),
        flush_interval_s=float(get("flush_interval_s", 2.0)),
        tags=tags)
    if enable and bool(get("jax_probe", True)):
        from distegnn_tpu.obs.jaxprobe import install_compile_watcher

        install_compile_watcher()
    return tracer


# module-level conveniences — stable call sites that always hit the CURRENT
# global tracer (configure() may rebind it mid-process, e.g. across tests)

def span(name: str, **attrs):
    return _tracer.span(name, **attrs)


def spanned(name: str):
    """Decorator form of :func:`span`: the whole call is the span."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _tracer.span(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate


def event(name: str, **attrs) -> None:
    _tracer.event(name, **attrs)


def log(msg: str, **attrs) -> None:
    _tracer.log(msg, **attrs)


def flush() -> None:
    _tracer.flush()


@atexit.register
def _flush_at_exit() -> None:
    try:
        _tracer.close()
    except Exception:
        pass
