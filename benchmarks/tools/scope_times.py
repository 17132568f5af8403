#!/usr/bin/env python3
"""Device time per named scope of the program, from one kept trace:

    python3 benchmarks/tools/scope_times.py --workload <cell> --seed <n> [--seconds <s>]

A tool, not a metric: it sets a cell up as ``run.py`` does, traces one window,
keeps the trace (``benchmarks/.work/trace_kept/<cell>``), takes the optimized
HLO text of the program that held the device longest, maps every op the trace
names (``%fusion.46``) to the program's scope (``jax.named_scope``: the
``op_name`` in the instruction's ``metadata``), and prints

- device self time per scope per micro-step, split forward / backward
  (``transpose(jvp``) / remat (``rematted_computation``), with the shares of
  busy time that are ``unscoped`` (no scope of the program in the op's name),
  ``mixed`` (a fusion whose instructions name several scopes; it is still
  booked to one, see :func:`fusion_scope`), ``scatter_unnamed`` (fusions
  whose scatter the compiler rebuilt with no name, and in which nothing else
  names an edge scope: on the TPU the transposes of the gathers) and
  ``other_programs`` (ops of programs other than the one read);
- the shape-rule classes of ``op_classes.json`` on the same trace, to compare
  ``edge_gather + edge_aggregate`` with ``agg_time_share``;
- the device's idle gaps put down to the shortest *program* span
  (``distegnn_tpu.obs``) covering them.

The reduction is ``benchmarks/tracing.py``'s (``read_planes``, ``self_times``,
``union_ns``, ``gaps_ns``, ``attribute``); only the join from op to scope is
here. The result also goes to ``chiprun_out/scope_times_<cell>.json``, beside
the program's HLO text (``.hlo.txt``).
"""

from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import importlib
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EDGE_SCOPES = ("edge_gather", "edge_aggregate")
SCOPES = EDGE_SCOPES + ("edge_mlp", "coord_update", "node_update", "virtual_update", "embed",
                        "loss_mse", "loss_mmd", "grad_reduce", "optimizer")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = .*?[\]})] ([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The innermost scope of the program in an ``op_name`` path, else
    ``unscoped``. A transform wraps the outermost name, so
    ``transpose(jvp(loss_mse))`` names ``loss_mse``."""
    for part in reversed(op_name.split("/")):
        core = part.rsplit("(", 1)[-1].rstrip(")")
        if core in SCOPES:
            return core
    return "unscoped"


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    return "backward" if "transpose(" in op_name else "forward"


def parse_hlo(text: str) -> dict:
    """{instruction name: {"opcode", "op_name", "calls", "root", "computation"}}
    and, under ``"computations"``, {computation: [instruction names]}. Names
    are unique in a module."""
    instructions, computations, current = {}, {}, None
    for line in text.splitlines():
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            root, name, opcode = m.groups()
            op_name = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            instructions[name] = {"opcode": opcode, "op_name": op_name.group(1) if op_name else "",
                                  "calls": calls.group(1) if calls else None,
                                  "root": bool(root), "computation": current}
            computations[current].append(name)
            continue
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            current = m.group(1)
            computations[current] = []
    return {"instructions": instructions, "computations": computations}


def _fused(ins: dict, hlo: dict) -> list:
    """The instructions a fusion runs, those of the fusions nested in it
    included (the TPU compiler nests them; only the innermost carry names)."""
    out = []
    for n in hlo["computations"].get(ins["calls"] or "", []):
        i = hlo["instructions"][n]
        if i["opcode"] in ("parameter", "constant"):
            continue
        out.append(i)
        if i["opcode"] == "fusion":
            out.extend(_fused(i, hlo))
    return out


def fusion_scope(name: str, hlo: dict) -> tuple:
    """(scope, phase, mixed, unnamed) of one instruction. A fusion of several
    scopes goes to the scope of its scatter / gather / custom-call
    instruction, else of its root, else to its own ``op_name``, else to the
    scope most of its instructions name. ``unnamed``: it has a scatter or
    gather and that instruction carries no ``op_name`` at all. The TPU
    compiler rebuilds every scatter as reshape + transpose + a new scatter
    without metadata; the reshape and transpose carry the name of whatever
    produced the updates, which for a forward aggregation is
    ``edge_aggregate/mul`` and for the transpose of a gather is some other
    scope's op or nothing. Such a fusion goes to the edge scope its other
    instructions name, else to ``scatter_unnamed``: its neighbours' scope is
    not its own."""
    ins = hlo["instructions"][name]
    inner = _fused(ins, hlo)
    if not inner:
        return scope_of(ins["op_name"]), phase_of(ins["op_name"]), False, False
    named = [i for i in inner if scope_of(i["op_name"]) != "unscoped"]
    mixed = len({scope_of(i["op_name"]) for i in named}) > 1
    key = ([i for i in inner if i["opcode"] in ("scatter", "gather")]
           or [i for i in inner if i["opcode"] == "custom-call"])[:1]
    unnamed = bool(key) and key[0]["opcode"] != "custom-call" and not key[0]["op_name"]
    candidates = key + [i for i in inner if i["root"]] + [ins]
    first = next((c for c in candidates if scope_of(c["op_name"]) != "unscoped"), None)
    if first is None and named:
        scope = collections.Counter(scope_of(i["op_name"]) for i in named).most_common(1)[0][0]
        first = next(i for i in named if scope_of(i["op_name"]) == scope)
    scope = scope_of(first["op_name"]) if first else "unscoped"
    # no scope inside: any name at all still tells forward from backward
    phase = phase_of((first or next((i for i in inner if i["op_name"]), ins))["op_name"])
    if unnamed and scope not in EDGE_SCOPES:
        scope = "scatter_unnamed"
    return scope, phase, mixed, unnamed


def scope_table(ops: list, program_runs: list, hlo: dict, sizes=None) -> dict:
    """Device ops [(trace name, start, end)] -> {"scopes": {scope: {phase:
    ns}}, "by_class": {scope: {shape-rule class: ns}}, "mixed_ns",
    "unnamed_ns", "other_programs_ns", "unscoped_top"}. Only ops that start inside one of
    ``program_runs`` [(start, end)], the runs of the program ``hlo`` is the
    text of, are looked up in it (another program's ``%copy.1`` is not this
    one's); the rest is ``other_programs_ns``. ``control`` ops (a ``while``'s
    own time) are left out, as ``reduce_planes`` leaves them out."""
    from benchmarks import tracing

    classes = tracing.load_classes(sizes)
    runs = sorted(program_runs)
    starts = [s for s, _ in runs]

    def in_program(start: int) -> bool:
        i = bisect.bisect_right(starts, start) - 1
        return i >= 0 and start < runs[i][1]

    mine = [op for op in ops if in_program(op[1])]
    other_ns = tracing.union_ns([(s, e) for _, s, e in ops if not in_program(s)])
    scopes = collections.defaultdict(lambda: collections.defaultdict(int))
    by_class = collections.defaultdict(lambda: collections.defaultdict(int))
    unscoped = collections.Counter()
    mixed_ns = unnamed_ns = 0
    cache = {}
    for name, self_ns in tracing.self_times(mine):
        cls = tracing.classify(name, classes)
        if cls == "control":
            continue
        label = tracing.op_label(name)
        if label not in hlo["instructions"]:
            other_ns += self_ns
            continue
        if label not in cache:
            cache[label] = fusion_scope(label, hlo)
        scope, phase, mixed, unnamed = cache[label]
        scopes[scope][phase] += self_ns
        by_class[scope][cls] += self_ns
        mixed_ns += self_ns * mixed
        unnamed_ns += self_ns * unnamed
        if scope == "unscoped":
            unscoped[label] += self_ns
    return {"scopes": {s: dict(p) for s, p in scopes.items()},
            "by_class": {s: dict(c) for s, c in by_class.items()}, "mixed_ns": mixed_ns,
            "unnamed_ns": unnamed_ns, "other_programs_ns": other_ns, "unscoped_top": unscoped.most_common(10)}


@contextlib.contextmanager
def _every_host_event(tracing):
    """``read_planes`` keeps the host events whose name starts with
    ``SPAN_PREFIX``; the program's spans have no common prefix. To go when a
    ``benchmark`` issue lets ``read_planes`` take the names (PERF.md section
    7, item 1): this PR may not edit ``tracing.py``."""
    kept, tracing.SPAN_PREFIX = tracing.SPAN_PREFIX, ""
    try:
        yield
    finally:
        tracing.SPAN_PREFIX = kept


def read_kept_trace(trace_dir: str, span_names) -> tuple:
    """(devices, program spans) of a kept trace: ``tracing.read_planes`` with
    the host events filtered to ``span_names``."""
    from benchmarks import tracing

    with _every_host_event(tracing):
        devices, host = tracing.read_planes(tracing.find_xplane(trace_dir))
    names = set(span_names)
    return devices, [s for s in host if s[0] in names]


def busiest_device(devices: dict):
    from benchmarks import tracing

    best = None
    for d in devices.values():
        if d["ops"]:
            busy = tracing.union_ns([(s, e) for _, s, e in d["ops"]])
            if best is None or busy > best[0]:
                best = (busy, d)
    if best is None:
        raise RuntimeError("the trace holds no device operation")
    return best


def main_program(modules: list) -> str:
    """Name of the program that held the device longest: ``jit__step_one``
    from the trace's ``jit__step_one(1234)``."""
    per = collections.Counter()
    for name, s, e in modules:
        per[name.split("(")[0]] += e - s
    return per.most_common(1)[0][0]


def live_hlo_text(module_name: str) -> str:
    """Optimized HLO text of the newest loaded executable called
    ``module_name`` (what ``compiled.as_text()`` gives for it)."""
    import jax.extend

    modules = [m for ex in jax.extend.backend.get_backend().live_executables()
               for m in ex.hlo_modules()]
    hits = ([m for m in modules if m.name == module_name]
            or [m for m in modules if m.name.startswith(module_name)])
    if not hits:
        raise RuntimeError(f"no loaded executable is called {module_name!r}; loaded: "
                           f"{sorted({m.name for m in modules})}")
    return hits[-1].to_string()


def report(devices: dict, spans: list, program: str, hlo_text: str, micro_steps: int,
           chips: int = 1, sizes=None) -> dict:
    from benchmarks import tracing

    busy_ns, dev = busiest_device(devices)
    runs = [(s, e) for name, s, e in dev["modules"] if name.split("(")[0] == program]
    table = scope_table(dev["ops"], runs, parse_hlo(hlo_text), sizes)
    per_step = lambda ns: ns / 1e6 / max(micro_steps, 1)
    share = lambda ns: 100.0 * ns / busy_ns
    rows = {}
    for scope, phases in table["scopes"].items():
        total = sum(phases.values())
        rows[scope] = {"ms_per_step": per_step(total), "share_of_busy": share(total),
                       **{p: per_step(phases.get(p, 0)) for p in ("forward", "backward", "remat")}}
    iv = [(s, e) for _, s, e in dev["ops"]]
    lo, hi = min(s for s, _ in iv), max(e for _, e in iv)
    classes = tracing.reduce_planes(devices, [], chips, sizes)
    agg = sum(classes["class_s"].get(c, 0.0) for c in ("scatter", "gather"))
    return {
        "micro_steps": micro_steps, "busy_s": busy_ns / 1e9, "traced_s": (hi - lo) / 1e9,
        "scopes": dict(sorted(rows.items(), key=lambda kv: -kv[1]["share_of_busy"])),
        "edge_ops_share": share(sum(sum(table["scopes"].get(s, {}).values()) for s in EDGE_SCOPES)),
        "scatter_unnamed_share": share(sum(table["scopes"].get("scatter_unnamed", {}).values())),
        "unscoped_share": share(sum(table["scopes"].get("unscoped", {}).values())),
        "mixed_share": share(table["mixed_ns"]),
        # all fusions whose scatter or gather has no name, those booked to an
        # edge scope by their other instructions included
        "unnamed_scatter_gather_share": share(table["unnamed_ns"]),
        "other_programs_share": share(table["other_programs_ns"]),
        "unscoped_top_ms_per_step": [[k, per_step(v)] for k, v in table["unscoped_top"]],
        "shape_rule_agg_time_share": 100.0 * agg / classes["busy_s"],
        # where the two yardsticks part: the edge scopes' time by shape-rule class
        "edge_scopes_ms_per_step_by_class": {
            s: {c: per_step(ns) for c, ns in sorted(table["by_class"].get(s, {}).items(),
                                                    key=lambda kv: -kv[1])}
            for s in EDGE_SCOPES},
        "idle_gaps_by_program_span": tracing.attribute(tracing.gaps_ns(iv, lo, hi), spans),
        "program_spans_in_host_plane": dict(collections.Counter(n for n, _, _ in spans)),
    }


def render(r: dict) -> str:
    lines = [f"{r['micro_steps']} micro-steps, busy {r['busy_s']:.3f} s of {r['traced_s']:.3f} s traced",
             "scope             ms/step  share%   forward  backward     remat"]
    for scope, row in r["scopes"].items():
        lines.append(f"{scope:<16} {row['ms_per_step']:>8.2f} {row['share_of_busy']:>7.2f} "
                     f"{row['forward']:>9.2f} {row['backward']:>9.2f} {row['remat']:>9.2f}")
    lines.append(f"edge_gather + edge_aggregate {r['edge_ops_share']:.2f}% of busy, with "
                 f"scatter_unnamed {r['edge_ops_share'] + r['scatter_unnamed_share']:.2f}% "
                 f"(shape rules' agg_time_share on this trace: {r['shape_rule_agg_time_share']:.2f}%)")
    for scope, per in r["edge_scopes_ms_per_step_by_class"].items():
        lines.append(f"  {scope} by shape-rule class (ms/step): "
                     + ", ".join(f"{c} {v:.1f}" for c, v in per.items()))
    lines.append(f"unscoped {r['unscoped_share']:.2f}%, mixed {r['mixed_share']:.2f}%, "
                 f"other programs {r['other_programs_share']:.2f}% of busy; in fusions whose "
                 f"scatter/gather has no op_name: {r['unnamed_scatter_gather_share']:.2f}%")
    lines.append("idle gaps by program span (s): " + json.dumps(r["idle_gaps_by_program_span"]))
    lines.append("program spans in the host plane: " + json.dumps(r["program_spans_in_host_plane"]))
    return "\n".join(lines)


def trace_cell(workload: str, seed: int, seconds, out: str, benchmark_file=None,
               platform=None) -> tuple:
    """Set the cell up as ``run.py`` does, trace one window, keep the trace;
    -> (devices, spans, meta, HLO text), with the HLO text written under
    ``out`` before anything is joined. ``benchmark_file`` and ``platform``
    are for the tests, as in ``run.run``."""
    from benchmarks import run

    benchmark_file = benchmark_file or os.path.join(ROOT, "BENCHMARK.json")
    bench, cell, config = run.load_cell(benchmark_file, workload)
    config_file = os.path.join(os.path.dirname(os.path.abspath(benchmark_file)), config["file"])
    seconds = float(bench["run_seconds"]) if seconds is None else seconds

    import jax

    from distegnn_tpu import obs

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the cache's key leaves op metadata out by default: a program compiled by
    # an earlier commit would be fetched and run WITHOUT this tree's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    run.check_device(int(cell["chips"]), platform or run.REQUIRED_PLATFORM)
    # traffic/ sits beside the directory of the configuration's file
    with open(os.path.join(os.path.dirname(os.path.dirname(config_file)), "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    driver_mod = importlib.import_module("benchmarks.drivers." + mix["kind"])

    trace_dir = os.path.join(ROOT, "benchmarks", ".work", "trace_kept", cell["name"])
    with contextlib.redirect_stdout(sys.stderr):
        driver = driver_mod.Driver(config_file, mix, seed)
        driver.setup(driver.family.make_weights(seed, driver.dims))
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.clear_spans()
        jax.profiler.start_trace(trace_dir)
        try:
            window = driver.run_window(seconds)
        finally:
            jax.profiler.stop_trace()
        shapes = driver.shapes()
        devices, spans = read_kept_trace(trace_dir, {s.name for s in obs.recent_spans()})
        _, dev = busiest_device(devices)
        program = main_program(dev["modules"])
        meta = {"workload": cell["name"], "seed": seed, "program": program,
                "micro_steps": window["micro_steps"], "chips": int(cell["chips"]),
                "trace_dir": trace_dir,
                "sizes": {"N": [shapes["padded_nodes"], shapes["graphs"] * shapes["padded_nodes"]],
                          "E": [shapes["padded_edges"], shapes["graphs"] * shapes["padded_edges"]]}}
        hlo_text = live_hlo_text(program)
        with open(os.path.join(out, f"scope_times_{cell['name']}.hlo.txt"), "w") as f:
            f.write(hlo_text)
    return devices, spans, meta, hlo_text


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    devices, spans, meta, hlo_text = trace_cell(args.workload, args.seed, args.seconds, out)
    result = report(devices, spans, meta["program"], hlo_text, meta["micro_steps"],
                    meta["chips"], meta["sizes"])
    result.update(meta)
    with open(os.path.join(out, f"scope_times_{meta['workload']}.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(render(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
