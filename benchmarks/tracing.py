"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time (union of op intervals), time per class of
op, program launches, and the idle gaps named by the harness's own host span
that covered them. Kept with the benchmark so that every PR computes the same
numbers the same way.
"""

from __future__ import annotations

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
GAP_FLOOR_NS = 20_000        # shorter holes between ops are launch latency, not a gap
SPAN_PREFIX = "bench/"


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def load_classes(sizes=None) -> dict:
    """The class map of ``op_classes.json``; ``sizes`` = {"N": [...], "E":
    [...]}, the extents the cell's node and edge axes can show (padded per
    graph, and flattened over the graphs of a batch), switches its shape
    rules on."""
    with open(os.path.join(HERE, "op_classes.json")) as f:
        raw = json.load(f)
    return {"prefixes": [(c, tuple(p)) for c, p in raw["classes"]],
            "shape_rules": raw.get("shape_rules", []) if sizes else [],
            "sizes": {k: {str(x) for x in v} for k, v in (sizes or {}).items()}}


def op_label(name: str) -> str:
    """``%fusion.46 = bf16[...] fusion(...)`` -> ``fusion.46``."""
    return re.sub(r"^%", "", name).split(" ")[0].split("(")[0]


def _by_shape(name: str, classes: dict):
    if " = " not in name:
        return None
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    shapes = [(t, d.split(",") if d else []) for t, d in _SHAPE.findall(rest)]
    if not shapes:
        return None
    (_, out), operands = shapes[0], shapes[1:]
    size = classes["sizes"]
    is_int = lambda t: t.startswith(("s", "u")) and not t.startswith("u8")
    for rule in classes["shape_rules"]:
        if opcode not in rule["opcodes"]:
            continue
        has = lambda dims, axis: bool(size[axis] & set(dims))
        if not has(out, rule["out_has"]):
            continue
        if "out_lacks" in rule and has(out, rule["out_lacks"]):
            continue
        if not any(is_int(t) and has(d, rule["int_operand_has"]) for t, d in operands):
            continue
        if not any(not is_int(t) and t != "pred" and has(d, rule["float_operand_has"])
                   for t, d in operands):
            continue
        return rule["class"]
    return None


def classify(name: str, classes: dict) -> str:
    by_shape = _by_shape(name, classes) if classes["shape_rules"] else None
    if by_shape:
        return by_shape
    base = op_label(name)
    for cls, prefixes in classes["prefixes"]:
        if base.startswith(prefixes):
            return cls
    return "other"


def union_ns(intervals: list) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events: list) -> list:
    """[(name, start, end)] possibly nested (a ``while`` spans its body) ->
    [(name, self_ns)]: each event's duration less its children's."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][2] <= s:
            out.append((stack[-1][0], stack[-1][3]))
            stack.pop()
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    while stack:
        out.append((stack[-1][0], stack[-1][3]))
        stack.pop()
    return out


def gaps_ns(intervals: list, lo: int, hi: int) -> list:
    """Idle holes of at least GAP_FLOOR_NS inside [lo, hi]."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s - end >= GAP_FLOOR_NS:
            out.append((end, s))
        end = max(end, e)
    if hi - end >= GAP_FLOOR_NS:
        out.append((end, hi))
    return out


def attribute(gaps: list, spans: list) -> dict:
    """Seconds of idle per host span name: a gap goes to the shortest
    harness span that covers its midpoint, else to ``outside_spans``."""
    by = {}
    for s, e in gaps:
        mid = (s + e) // 2
        cover = [(se - ss, n) for n, ss, se in spans if ss <= mid <= se]
        name = min(cover)[1] if cover else "outside_spans"
        by[name] = by.get(name, 0.0) + (e - s) / 1e9
    return by


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    modules = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                               for ev in line.events]
            devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    return devices, spans


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def reduce_planes(devices: dict, spans: list, chips: int, sizes=None) -> dict:
    """The numbers the readers use, averaged over the ``chips`` busiest
    devices (a one-chip cell on a four-chip host leaves three silent)."""
    classes = load_classes(sizes)
    per = []
    for name, d in devices.items():
        if not d["ops"]:
            continue
        iv = [(s, e) for _, s, e in d["ops"]]
        per.append((union_ns(iv), name, d, iv))
    per.sort(reverse=True)
    per = per[:chips]
    if not per:
        return {"busy_s": 0.0, "class_s": {}, "launches": 0, "ops": [], "gaps": []}
    class_s, op_s = {}, {}
    for _, _, d, _ in per:
        for name, self_ns in self_times(d["ops"]):
            cls = classify(name, classes)
            if cls == "control":
                continue          # a while's own time is its body's bookkeeping
            class_s[cls] = class_s.get(cls, 0.0) + self_ns / 1e9 / len(per)
            label = cls + ":" + op_label(name)
            op_s[label] = op_s.get(label, 0.0) + self_ns / 1e9 / len(per)
    busy = sum(b for b, _, _, _ in per) / 1e9 / len(per)
    _, _, d0, iv0 = per[0]
    lo, hi = min(s for s, _ in iv0), max(e for _, e in iv0)
    gap_by = attribute(gaps_ns(iv0, lo, hi), spans)
    top = lambda m: [[k, v] for k, v in sorted(m.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy, "class_s": class_s,
            "launches": sum(len(d["modules"]) for _, _, d, _ in per) / len(per),
            "ops": top(op_s), "gaps": top(gap_by)}


def reduce_trace(trace_dir: str, chips: int, sizes=None) -> dict:
    devices, spans = read_planes(find_xplane(trace_dir))
    return reduce_planes(devices, spans, chips, sizes)
