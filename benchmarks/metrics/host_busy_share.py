"""Share of the window the step loop's thread spent WORKING on micro-steps:
the CPU time (``cpu_ns``, the thread's own clock) of the program's
``train/step`` spans (batch in hand to ready for the next: key folding, the
dispatch call, loss accumulation, cadence and guard) over the window. Their
wall time would not do: once the host is a few programs ahead every enqueue
blocks on the runtime's backpressure, and a ``train/step`` lasts as long as
the device's step. The two named waits, ``data/next`` (the loader) and
``train/epoch_sync`` (the device), are spans of their own. A span counts
whole if it ended inside the window and not at all otherwise: where inside a
mostly blocked span its CPU time fell is not known. The line carries it only
at ``--trace 1``, where the profiler's Python tracer inflates the thread's
CPU time: it is what a traced run's host pays (PERF.md section 3 has an
untraced reading beside it)."""

from benchmarks import span_window


def read(ctx):
    w = span_window.window(ctx)
    if w is None:
        return None
    spans, lo, hi = w
    busy = sum(s.cpu_ns for s in spans if s.name == "train/step" and lo < s.end_ns <= hi)
    return 100.0 * busy / (hi - lo)
