"""The program's own spans over the measured window, for the per-layer
metrics that read them (``host_busy_share``, ``loader_produce_share``,
``max_compiles_per_program``).

The program keeps its newest spans in a ring (``distegnn_tpu.obs``,
``recent_spans()``), on ``time.perf_counter_ns``. The window is the
``ctx["window"]["wall_s"]`` seconds that end with the last ``train/epoch``
span; spans are clipped to it. A program without the ring (a commit before
it), a ring with no ``train/epoch`` span, or a full ring that no longer
reaches back to the window's start, gives ``None`` and the metric is left out
of the line.
"""

from __future__ import annotations


def window(ctx: dict):
    """(spans, lo_ns, hi_ns) or None."""
    from distegnn_tpu import obs

    recent = getattr(obs, "recent_spans", None)
    if recent is None:
        return None
    return bounds(recent(), ctx["window"]["wall_s"])


def ring_is_full(spans: list) -> bool:
    """The ring drops its oldest span for each new one once it is full: what
    lies before the window may then be gone."""
    from distegnn_tpu import obs

    return len(spans) >= obs.RING_SIZE


def bounds(spans: list, wall_s: float):
    ends = [s.end_ns for s in spans if s.name == "train/epoch"]
    if not ends:
        return None
    hi = max(ends)
    lo = hi - int(wall_s * 1e9)
    if ring_is_full(spans) and min(s.start_ns for s in spans) > lo:
        return None
    return spans, lo, hi


def clipped(spans: list, name: str, lo: int, hi: int) -> list:
    """[(start, end)] of the spans called ``name``, cut to [lo, hi]."""
    out = []
    for s in spans:
        if s.name == name and s.end_ns > lo and s.start_ns < hi:
            out.append((max(s.start_ns, lo), min(s.end_ns, hi)))
    return out
