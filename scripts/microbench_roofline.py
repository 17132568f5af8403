"""Machine roofline probe: what can THIS chip actually sustain, and how
close is the train step to that ceiling?

Motivation: if the step's effective bandwidth sits far below the v5e spec
sheet (~819 GB/s), is the gap (a) per-dispatch overhead, (b) the chip's real
memory ceiling for these access patterns, or (c) inefficiency in our
kernels? Not measured on this machine yet. The probe:

  1. copy at 4 sizes x {f32, bf16}: the slope of time-vs-bytes is the real
     streaming bandwidth; the intercept is fixed overhead per executable.
  2. matmul [8192,512]x[512,512] bf16 and f32: the MXU ceiling.
  3. gather / sorted-scatter at bench shape: achievable for OUR access
     patterns, as a fraction of the copy ceiling.
  4. an analytic byte count of the plain+fuse_agg train step (fwd+bwd
     [E,.] streams) -> step-time floor at the measured copy bandwidth, to
     set beside the measured step time (``step_device_ms`` of
     ``benchmarks/run.py --trace 1``).

Artifact: --json <path>. Run it on the chip; CPU runs are labeled and land
nowhere.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

E, N, H = 1_639_080, 113_140, 64


def timed(fn, *args, warmup=2, steps=10):
    """Fetch-synced timing."""
    import jax.numpy as jnp

    def sync(o):
        while isinstance(o, (tuple, list)):
            o = o[0]
        np.asarray(jnp.ravel(o)[0])

    out = None
    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    plat = dev.platform
    out: dict = {"platform": plat, "device": str(dev.device_kind)}
    rng = np.random.default_rng(0)

    # ---- 1. copy: time vs bytes -> slope (bandwidth) + intercept (overhead)
    copy_pts = []
    for dt_name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        for rows in (E // 8, E // 4, E // 2, E):
            x = jnp.asarray(rng.normal(size=(rows, H)).astype(np.float32)).astype(dt)
            f = jax.jit(lambda d: d * 1.0001)
            ms = timed(f, x)
            bytes_moved = 2 * rows * H * x.dtype.itemsize  # read + write
            copy_pts.append({"dtype": dt_name, "rows": rows, "ms": ms,
                             "GB": bytes_moved / 1e9})
            print(f"copy {dt_name:4s} rows={rows:>8d}  {ms:8.2f} ms  "
                  f"({bytes_moved / 1e9 / (ms / 1e3):6.1f} GB/s apparent)")
    # least-squares slope/intercept over all points (bytes vs ms)
    xs = np.array([p["GB"] for p in copy_pts])
    ys = np.array([p["ms"] for p in copy_pts])
    slope, intercept = np.polyfit(xs, ys, 1)  # ms per GB, ms
    bw_gbps = 1e3 / slope if slope > 0 else float("nan")
    out["copy_points"] = copy_pts
    out["copy_stream_GBps"] = round(bw_gbps, 1)
    out["copy_overhead_ms"] = round(float(intercept), 3)
    print(f"\ncopy roofline: {bw_gbps:.1f} GB/s streaming, "
          f"{intercept:.2f} ms fixed overhead per dispatch")

    # ---- 2. MXU ceiling
    for dt_name, dt in (("bf16", jnp.bfloat16), ("f32", jnp.float32)):
        a = jnp.asarray(rng.normal(size=(8192, 512)).astype(np.float32)).astype(dt)
        b = jnp.asarray(rng.normal(size=(512, 512)).astype(np.float32)).astype(dt)
        # chain 32 dependent matmuls in one executable so dispatch overhead
        # amortizes and XLA cannot elide any of them
        @jax.jit
        def chain(a, b):
            for _ in range(32):
                a = jnp.dot(a, b, preferred_element_type=jnp.float32).astype(dt)
            return a
        ms = timed(chain, a, b)
        tflops = 32 * 2 * 8192 * 512 * 512 / (ms / 1e3) / 1e12
        out[f"matmul_{dt_name}_TFLOPs"] = round(tflops, 2)
        print(f"matmul {dt_name:4s}: {tflops:7.2f} TFLOP/s")

    # ---- 3. our access patterns at bench shape
    ids_np = np.sort(rng.integers(0, N, size=E)).astype(np.int32)
    ids = jnp.asarray(ids_np)
    for dt_name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        xe = jnp.asarray(rng.normal(size=(E, H)).astype(np.float32)).astype(dt)
        xn = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32)).astype(dt)
        g_ms = timed(jax.jit(lambda d, i: d[i]), xn, ids)
        s_ms = timed(jax.jit(lambda d, i: jnp.zeros((N, H), jnp.float32).at[i].add(
            d, indices_are_sorted=True)), xe, ids)
        # effective bandwidth relative to the bytes each op MUST move
        g_bytes = (E + N) * H * xn.dtype.itemsize + E * 4
        s_bytes = E * H * xe.dtype.itemsize + N * H * 4 + E * 4
        out[f"gather_{dt_name}_ms"] = round(g_ms, 2)
        out[f"scatter_{dt_name}_ms"] = round(s_ms, 2)
        out[f"gather_{dt_name}_GBps"] = round(g_bytes / 1e9 / (g_ms / 1e3), 1)
        out[f"scatter_{dt_name}_GBps"] = round(s_bytes / 1e9 / (s_ms / 1e3), 1)
        print(f"gather  {dt_name:4s}: {g_ms:7.2f} ms ({out[f'gather_{dt_name}_GBps']:6.1f} GB/s eff)")
        print(f"scatter {dt_name:4s}: {s_ms:7.2f} ms ({out[f'scatter_{dt_name}_GBps']:6.1f} GB/s eff)")

    # ---- 4. analytic step bytes (plain + fuse_agg + hoisted phi_e, L=4,
    # bf16 MLP streams, f32 geometry/aggregation) vs the measured ceiling.
    # Forward, per layer, [E,.] streams only (node-level [N,.] terms are
    # ~7% of E-level and ignored):
    #   gathers: pre_h rows+cols (2x[E,H] bf16), x rows+cols (2x[E,3] f32)
    #   phi_e dense2: read [E,H] bf16, write [E,H] bf16
    #   phi_x: read [E,H] bf16, write [E,1]; trans [E,3] f32 write
    #   packed agg: read [E,H+4] f32 (or bf16 with agg_dtype)
    f32, bf16 = 4, 2
    fwd_e_bytes = (2 * E * H * bf16 + 2 * E * 3 * f32
                   + 2 * E * H * bf16
                   + E * H * bf16 + E * 3 * f32
                   + E * (H + 4) * f32)
    # Backward without remat: re-read every saved [E,.] activation once on
    # the transpose path, plus weight-grad matmuls re-reading [E,H] inputs,
    # plus cotangent streams mirroring the forward writes. Empirical factor
    # ~2x forward traffic is the standard lower bound; we report both.
    L = 4
    step_bytes_lo = L * fwd_e_bytes * (1 + 2)
    floor_lo_ms = step_bytes_lo / (bw_gbps * 1e9) * 1e3
    out["analytic_fwd_E_bytes_per_layer"] = fwd_e_bytes
    out["analytic_step_bytes_3x"] = step_bytes_lo
    out["analytic_step_floor_ms_at_copy_bw"] = round(floor_lo_ms, 1)
    print(f"\nanalytic step floor (L=4, fwd+2x bwd E-streams at copy BW): "
          f"{floor_lo_ms:.1f} ms vs measured ~553-617 ms (profile/bench "
          f"2026-08-02)")

    if args.json and plat != "cpu":
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    elif args.json:
        print(f"cpu run: NOT writing {args.json} (hardware artifact)")


if __name__ == "__main__":
    main()
