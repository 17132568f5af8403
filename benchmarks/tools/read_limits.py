#!/usr/bin/env python3
"""Readings for the limits of ``correct``, many seeds in one process (set-up
is long; the benchmark's own runs never call this):

    python3 benchmarks/tools/read_limits.py --workload W --seeds 1,2,3 \
        [--control] [--half 3] [--out chiprun_out/limits_W.jsonl]

For each seed the cell's driver starts a fresh state from the seed, drives the
first steps through the window's own call, and the plain reference of the
configuration's family (``model.model_name``, ``benchmarks/family.py``)
follows them: one JSON line of the compared numbers, ``correct`` and ``over`` as
``compare.decide`` gives them under the cell's own limits file (a control or
a fault has to read ``correct`` false), with ``moment`` beside them:
Adam's first moment over all leaves laid end to end (``diff``
||mu_p - mu_r||, ``ref`` ||mu_r||, ``update_norms`` the norms of the
reference's accumulated, clipped gradients, ``bound`` what ||mu_r|| would be
had they not cancelled, ``unfloored`` = diff / ref, which was ``moment_diff``
before PR 29; with ``--leaves`` also every leaf's term of today's).
``--control`` switches on the configuration's lower-precision path
(``control`` in its meta file).
``--half N`` also reads, on the first N seeds, the planted fault "half of the
rows left out, the mean taken over the rest" with the reference put in the
program's place.
Every seed also gives ``fault_params_unchanged``: the program's own record
with its weights left at their start, which costs no run.
``--free-program`` drives every seed's first steps before any reference, keeps
their records and reference inputs on the host, and frees the program: for a
cell whose reference does not fit a chip beside the loaded program (an
800,000-particle graph: 15.4 GB beside the step's 4.5). The last line gives
``memory_stats`` of every local device.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--half", type=int, default=0)
    ap.add_argument("--mantissa", type=int, default=0,
                    help="also read, on the first N seeds, the control 'reference with its MLPs' "
                         "matmul operands rounded to 3 mantissa bits, put in the program's place'")
    ap.add_argument("--mantissa-bits", type=int, default=3)
    ap.add_argument("--leaves", action="store_true", help="emit every leaf's gap")
    ap.add_argument("--free-program", action="store_true",
                    help="all seeds through the program first, then the program freed, then the references")
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--benchmark-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks import compare, run
    from benchmarks.drivers import common

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    bench, cell, config = run.load_cell(args.benchmark_file, args.workload)
    run.check_device(int(cell["chips"]), args.platform)
    base = os.path.dirname(os.path.abspath(args.benchmark_file))
    cfg_file = os.path.join(base, config["file"])
    beside = os.path.dirname(os.path.dirname(cfg_file))
    with open(os.path.join(beside, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(beside, "limits", cell["name"] + ".json")) as f:
        limits = json.load(f)["limits"]
    overrides = common.load_meta(cfg_file)["control"] if args.control else None
    if overrides and any(k.startswith("reference.") for k in overrides):
        raise SystemExit("this configuration's control is the reference at lower precision: "
                         "use --mantissa N --mantissa-bits B")
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    out = open(args.out, "a") if args.out else None

    def emit(line: dict) -> None:
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()

    with contextlib.redirect_stdout(sys.stderr):
        driver = mod.Driver(cfg_file, mix, 0, overrides=overrides)
        t0 = time.perf_counter()
        driver.build()
        build_s = time.perf_counter() - t0
    variant = "control" if args.control else "sound"

    def verdict(nums):
        """``numbers``, and what ``run.py`` would make of them."""
        ok, compared = compare.decide(nums, limits)
        return {"numbers": {k: [v[0], v[1]] for k, v in nums.items()}, "correct": ok,
                "over": [k for k, c in compared.items() if not c["value"] <= c["limit"]]}

    def device_memory():
        return [{k: int(v) for k, v in (d.memory_stats() or {}).items()} for d in jax.local_devices()]

    def leaves(rec, ref):
        keep = compare.moving_leaves(ref["grad_first"])
        delta = lambda r: {k: np.asarray(r[k], np.float64) - rec["w0"][k] for k in r}
        out = {"moment": compare.leaf_gaps(rec["mu"], ref["mu"]),
               "change": compare.leaf_gaps(delta(rec["w"]), delta(ref["w"]), keep),
               "ref_grad_norm": compare._norms(ref["grad_first"]),
               "ref_change_norm": compare._norms(delta(ref["w"]))}
        if rec.get("grad") is not None:
            out["grad"] = compare.leaf_gaps(rec["grad"], ref["grad_first"])
        return out

    def moment(rec, ref):
        if rec.get("mu") is None:
            return None
        diff, norm = compare.whole_parts(rec["mu"], ref["mu"])
        whole = np.sqrt(sum(np.asarray(v, np.float64) ** 2 for v in ref["update_norms"].values()))
        out = {"diff": diff, "ref": norm, "bound": compare.moment_bounds({"all": whole})["all"],
               "update_norms": whole.tolist(), "unfloored": diff / max(norm, 1e-150)}
        if args.leaves:
            out["leaves"] = compare.moment_diffs(rec["mu"], ref["mu"], ref["update_norms"])
        return out

    def program(seed):
        """(record, reference inputs, seconds) of ``seed``'s first steps."""
        t0 = time.perf_counter()
        driver.start(driver.family.make_weights(seed, driver.dims), seed)
        return driver.program_record(), driver.reference_inputs(), time.perf_counter() - t0

    seeds = [int(s) for s in args.seeds.split(",")]
    held = {}
    if args.free_program:
        import gc

        with contextlib.redirect_stdout(sys.stderr):
            held = {seed: program(seed) for seed in seeds}
            program_memory = device_memory()
            driver.free()
            driver = None
            gc.collect()
            jax.clear_caches()
        emit({"workload": args.workload, "variant": variant, "program_memory_stats": program_memory})

    for i, seed in enumerate(seeds):
        with contextlib.redirect_stdout(sys.stderr):
            rec, inputs, program_s = held.pop(seed) if held else program(seed)
            t1 = time.perf_counter()
            ref = compare.reference_record(inputs, rec["w0"])
            t2 = time.perf_counter()
            nums = compare.numbers(rec, ref)
        emit({"workload": args.workload, "seed": seed,
              "variant": variant, **verdict(nums),
              "moment": moment(rec, ref),
              "loss_program": rec["loss"].tolist(), "loss_reference": ref["loss"].tolist(),
              "program_s": program_s, "reference_s": t2 - t1, "build_s": build_s})
        # the fault "the parameters are never written back" needs no run: the
        # program's own record with its weights left where they started
        emit({"workload": args.workload, "seed": seed, "variant": "fault_params_unchanged",
              **verdict(compare.numbers(dict(rec, w=rec["w0"]), ref))})
        if args.leaves:
            emit({"workload": args.workload, "seed": seed, "variant": variant + "_leaves",
                  **leaves(rec, ref)})
        for on, name, kw in ((i < args.half, "fault_half_rows", {"half": True}),
                             (i < args.mantissa, f"control_mantissa{args.mantissa_bits}",
                              {"mlp_mantissa": args.mantissa_bits})):
            if not on:
                continue
            with contextlib.redirect_stdout(sys.stderr):
                bad = compare.reference_record(inputs, rec["w0"], **kw)
                loss = bad["loss"] if rec["loss"].shape == bad["loss"].shape \
                    else np.asarray([bad["loss"].mean()])
                fake = {"loss": loss, "grad": bad["grad_first"] if rec.get("grad") is not None else None,
                        "mu": bad["mu"], "w": bad["w"], "w0": rec["w0"]}
                nums = compare.numbers(fake, ref)
            emit({"workload": args.workload, "seed": seed, "variant": name, **verdict(nums),
                  "moment": moment(fake, ref)})
            if args.leaves:
                emit({"workload": args.workload, "seed": seed, "variant": name + "_leaves",
                      **leaves(fake, ref)})
    emit({"workload": args.workload, "variant": variant, "memory_stats": device_memory()})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
