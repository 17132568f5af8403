"""How often set-up handed one program of the step loop to the backend: the
most ``jax/compile`` spans under one ``fun_name``, among those that ended
before the window and lie in a ``train/dispatch`` span (the programs the step
loop itself dispatches; a compile, or a retrieval from the persistent cache in
its place, so the count is the same warm and cold). 1 is the least; 2 is the
double compile of PERF.md section 5. JAX's own one-op programs are left out:
``jit(broadcast_in_dim)`` compiles once per shape under one name and says
nothing about the program. A full ring may have lost set-up's oldest spans:
the count would be too low, so nothing is reported."""

import collections

from benchmarks import span_window


def read(ctx):
    w = span_window.window(ctx)
    if w is None:
        return None
    spans, lo, _ = w
    if span_window.ring_is_full(spans):
        return None
    dispatch = {s.id for s in spans if s.name == "train/dispatch"}
    per = collections.Counter(
        s.attrs.get("fun_name") for s in spans
        if s.name == "jax/compile" and s.end_ns <= lo and s.parent in dispatch)
    return max(per.values()) if per else None
