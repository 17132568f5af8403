#!/usr/bin/env python3
"""The benchmark's one entry:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It knows no cell, configuration or metric by name. ``BENCHMARK.json`` names
the cell's configuration and traffic mix; ``traffic/<mix>.json`` names the
driver (``kind``) under ``drivers/``; every metric of ``BENCHMARK.json`` that
applies to the cell is read by ``metrics/<metric>.py``; the limits of
``correct`` are in ``limits/<workload>.json``. A later cell, configuration or
metric adds files and entries and edits nothing here.

One run: set-up (data, weights from ``--seed`` on the device, the driver's
program objects, the first steps that warm the window's shapes and are later
compared) -> the window, ``--seconds`` long, traced when ``--trace 1`` ->
peak memory read -> the program's state freed -> the plain reference follows
the first steps and ``correct`` is decided -> the result line, last on
stdout. It runs on the machine it is started on and needs the accelerator
``peaks.json`` lists: anything else exits non-zero with no result line.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REQUIRED_PLATFORM = "tpu"
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Seconds and counts of XLA compiles and persistent-cache retrievals,
    from ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration_secs
        if event == _BACKEND_COMPILE:
            self.requests += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        """Compile requests the persistent cache did not answer."""
        return self.requests - self.hits


def load_cell(benchmark_file: str, workload: str):
    with open(benchmark_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return bench, cell, config


def metrics_for(bench: dict, cell: dict, group: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_metric(name: str, ctx: dict):
    """``metrics/<name>.py`` -> its value, or None where it finds nothing."""
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + name.replace(".", "_"), os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def check_device(chips: int, platform: str) -> dict:
    import jax

    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"needs platform {platform!r}, JAX reports {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX reports {len(devs)}")
    kind = devs[0].device_kind
    if platform == REQUIRED_PLATFORM and kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    # a test on another platform only exercises the readers' arithmetic
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "peaks": peaks.get(kind) or next(iter(peaks.values()))}


def memory_peak() -> tuple:
    """(peak HBM held on the fullest chip, its parts): the allocator's peak
    of live buffers plus, where the backend keeps compiled programs'
    temporaries in an arena of its own (the TPU's ``bytes_reserved``), that
    arena's peak. Neither includes the other (PERF.md section 4)."""
    import jax

    best = (0, {})
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        parts = {k: int(s.get(k, 0)) for k in ("peak_bytes_in_use", "peak_bytes_reserved",
                                               "bytes_limit")}
        held = parts["peak_bytes_in_use"] + parts["peak_bytes_reserved"]
        if held >= best[0]:
            best = (held, parts)
    return best


def run(argv=None, benchmark_file=None, platform=REQUIRED_PLATFORM) -> dict:
    """One run; returns the result object (``main`` prints it). ``platform``
    and ``benchmark_file`` are for the tests under ``benchmarks/tests``, which
    drive this on the CPU at toy sizes."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    benchmark_file = benchmark_file or os.path.join(ROOT, "BENCHMARK.json")
    bench, cell, config = load_cell(benchmark_file, args.workload)
    base = os.path.dirname(os.path.abspath(benchmark_file))

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the program's own entry points put it here too (runtime.py)
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = check_device(int(cell["chips"]), platform)
    meter = CompileMeter()

    from benchmarks import compare, tracing

    # traffic/ and limits/ sit beside the directory of the configuration's file
    beside = os.path.dirname(os.path.dirname(os.path.join(base, config["file"])))
    with open(os.path.join(beside, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    driver_mod = importlib.import_module("benchmarks.drivers." + mix["kind"])

    with contextlib.redirect_stdout(sys.stderr):     # the program logs to stdout
        driver = driver_mod.Driver(os.path.join(base, config["file"]), mix, args.seed)
        if driver.chips != int(cell["chips"]):
            raise SystemExit(f"configuration {config['name']!r} runs on {driver.chips} chip(s), "
                             f"the cell asks for {cell['chips']}")
        w0 = driver.family.make_weights(args.seed, driver.dims)
        driver.setup(w0)
        setup = {"setup_s": time.perf_counter() - _T_START, "compile_s": meter.seconds,
                 "data_prep_s": driver.prep_s}
        compiled_before = meter.requests

        trace_dir = os.path.join(HERE, ".work", "trace", cell["name"])
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        try:
            window = driver.run_window(args.seconds)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        if meter.requests != compiled_before:
            raise RuntimeError(
                f"{meter.requests - compiled_before} program(s) compiled inside the "
                "measured window: set-up did not warm every shape the window uses")
        peak, peak_parts = memory_peak()

        record = driver.program_record()
        inputs = driver.reference_inputs()
        shapes = driver.shapes()
        driver.free()
        del driver
        gc.collect()
        jax.clear_caches()      # the program's executables hold their temporaries' arena on every chip

        t_ref = time.perf_counter()
        reference = compare.reference_record(inputs, record["w0"])
        nums = compare.numbers(record, reference)
        with open(os.path.join(beside, "limits", cell["name"] + ".json")) as f:
            ok, compared = compare.decide(nums, json.load(f)["limits"])
        reference_s = time.perf_counter() - t_ref

        trace = None
        if args.trace:
            t_red = time.perf_counter()
            trace = tracing.reduce_trace(
                trace_dir, int(cell["chips"]),
                {"N": [shapes["padded_nodes"], shapes["graphs"] * shapes["padded_nodes"]],
                 "E": [shapes["padded_edges"], shapes["graphs"] * shapes["padded_edges"]]})
            trace["reduce_s"] = time.perf_counter() - t_red
            shutil.rmtree(trace_dir, ignore_errors=True)
            if platform == REQUIRED_PLATFORM and trace["busy_s"] <= 0.0:
                raise RuntimeError("the trace holds no device operation")

    ctx = {"window": window, "trace": trace, "shapes": shapes, "setup": setup,
           "peaks": device["peaks"], "chips": int(cell["chips"]),
           "memory_peak_bytes": peak}
    metrics = {}
    for m in metrics_for(bench, cell, "per_layer" if args.trace else "end_to_end"):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": bool(ok and window["failed"] == 0),
              "attempted": int(window["attempted"]), "failed": int(window["failed"]),
              "metrics": metrics, "device": dev}
    if trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = window["wall_s"]
        result["breakdown"] = {"device_ops": trace["ops"], "idle_gaps": trace["gaps"]}
    result["info"] = {"workload": cell["name"], "seed": args.seed,
                      "window_s": window["wall_s"], "micro_steps": window["micro_steps"],
                      "reference_s": reference_s,
                      "trace_reduce_s": trace["reduce_s"] if trace else None,
                      "compiled_in_setup": meter.compiled,
                      "cache_hits": meter.hits, "memory": peak_parts, **setup}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    result = run(argv)
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g}, worst {c['worst']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
