"""Micro-benchmark: scatter-free lowerings for the sorted segment-sum that
dominates the plain-path train step (plug-in-era profile, not reproduced on
this machine: 22-33 ms per [E,64]->[N,64] aggregation, ~4% of HBM bandwidth).

Candidates, all on row-sorted edge ids at LargeFluid shape:
  copy              elementwise [E,64] pass — the HBM bandwidth reference
  gather_rows       x[ids] [N,64]->[E,64] (read side, and the cheap VJP of
                    every segment-sum candidate)
  scatter_sorted    zeros.at[ids].add(x), indices_are_sorted — current path
  cumsum_diff       prefix-sum over E then c[ends-1]-c[starts-1] with
                    host-precomputed CSR row offsets: no scatter at all
  ell_gather_sum    fixed-degree CSR (ELL) padding [N, Dmax] built host-side
                    once: out[n] = sum_d x[ell_idx[n,d]] * ell_msk — pure
                    gather+reduce, exact, ~2x read amplification
  vjp(scatter)/vjp(cumsum)/vjp(ell): cotangent pull-back cost (the backward
                    half of the step is where the round-1 profile said the
                    time goes)

Run on the real chip: `python scripts/microbench_segsum.py [--bf16]`.
"""

from __future__ import annotations

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

    for _ in range(warmup):
        out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / steps * 1e3


def main():
    import jax
    import jax.numpy as jnp

    bf16 = "--bf16" in sys.argv
    dt = jnp.bfloat16 if bf16 else jnp.float32
    rng = np.random.default_rng(0)

    # degree-realistic sorted ids (radius-graph degrees ~ Poisson(14.5));
    # spread the sampling residual one edge per node so no single node's
    # degree (and hence the ELL dmax/read-amp) is distorted
    deg = rng.poisson(E / N, size=N).astype(np.int64)
    diff = E - deg.sum()
    if diff:
        idx = rng.choice(N, size=abs(diff), replace=abs(diff) > N)
        np.add.at(deg, idx, 1 if diff > 0 else -1)
        deg = np.maximum(deg, 0)
        deg[0] += E - deg.sum()  # at most a few leftovers from the clamp
    ids_np = np.repeat(np.arange(N), deg).astype(np.int32)
    starts_np = np.zeros(N + 1, np.int64)
    np.cumsum(deg, out=starts_np[1:])

    dmax = int(deg.max())
    ell_idx_np = np.zeros((N, dmax), np.int32)
    ell_msk_np = np.zeros((N, dmax), np.float32)
    for n in range(N):  # host-side, once per dataset — not on the step path
        k = deg[n]
        ell_idx_np[n, :k] = np.arange(starts_np[n], starts_np[n + 1])
        ell_msk_np[n, :k] = 1.0
    read_amp = N * dmax / E

    x = jnp.asarray(rng.normal(size=(E, H)).astype(np.float32)).astype(dt)
    xn = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32)).astype(dt)
    ids = jnp.asarray(ids_np)
    starts = jnp.asarray(starts_np[:-1])
    ends = jnp.asarray(starts_np[1:])
    ell_idx = jnp.asarray(ell_idx_np)
    ell_msk = jnp.asarray(ell_msk_np).astype(dt)

    from distegnn_tpu.ops.cumsum import prefix_sum

    f_copy = jax.jit(lambda d: d * 1.0001)
    f_gather = jax.jit(lambda d, i: d[i])
    f_scatter = jax.jit(lambda d, i: jnp.zeros((N, H), d.dtype).at[i].add(
        d, indices_are_sorted=True))
    # the prefix pass in isolation, both lowerings (ops/cumsum.py): XLA emits
    # O(log E) shifted-add passes, the Pallas kernel a single sequential pass
    f_prefix_xla = jax.jit(lambda d: prefix_sum(d, impl="xla"))
    f_prefix_pl = jax.jit(lambda d: prefix_sum(d, impl="pallas"))

    def cumsum_diff(d, s, e, impl="auto"):
        c = prefix_sum(d, impl=impl)
        hi = c[e - 1]
        lo = jnp.where((s > 0)[:, None], c[jnp.maximum(s - 1, 0)], 0.0)
        return (hi - lo).astype(d.dtype)

    f_cumsum = jax.jit(lambda d, s, e: cumsum_diff(d, s, e, "xla"))
    f_cumsum_pl = jax.jit(lambda d, s, e: cumsum_diff(d, s, e, "pallas"))

    def ell_sum(d, idx, msk):
        return (d[idx] * msk[..., None]).sum(axis=1)

    f_ell = jax.jit(ell_sum)

    # numerical sanity vs the scatter reference
    ref = np.asarray(f_scatter(x.astype(jnp.float32), ids))
    for name, fn, args in (("cumsum_diff", f_cumsum, (x.astype(jnp.float32), starts, ends)),
                           ("ell", f_ell, (x.astype(jnp.float32), ell_idx,
                                           ell_msk.astype(jnp.float32)))):
        err = np.abs(np.asarray(fn(*args)) - ref).max()
        print(f"max|{name} - scatter| = {err:.3e}")

    # fused per-layer aggregation (EdgeOps.agg_rows_pair): the model's two
    # per-layer aggregations + mean count as ONE packed pass vs three
    # separate passes — the round-4 fuse_agg attack, isolated
    x3 = jnp.asarray(rng.normal(size=(E, 3)).astype(np.float32)).astype(dt)
    f_three = jax.jit(lambda a, b, i: (
        jnp.zeros((N, 3), jnp.float32).at[i].add(
            a.astype(jnp.float32), indices_are_sorted=True),
        jnp.zeros((N, H), jnp.float32).at[i].add(
            b.astype(jnp.float32), indices_are_sorted=True),
        jnp.zeros((N, 1), jnp.float32).at[i].add(
            jnp.ones((E, 1), jnp.float32), indices_are_sorted=True)))
    f_packed = jax.jit(lambda a, b, i: jnp.zeros((N, H + 4), jnp.float32).at[i].add(
        jnp.concatenate([a, b, jnp.ones((E, 1), a.dtype)],
                        axis=-1).astype(jnp.float32),
        indices_are_sorted=True))

    g_scatter = jax.jit(jax.grad(lambda d: f_scatter(d, ids).sum()))
    g_cumsum = jax.jit(jax.grad(lambda d: cumsum_diff(d, starts, ends).sum()))
    g_ell = jax.jit(jax.grad(lambda d: ell_sum(d, ell_idx, ell_msk).sum()))

    tag = "bf16" if bf16 else "f32"
    print(f"dtype={tag}  E={E} N={N} H={H}  ELL dmax={dmax} read_amp={read_amp:.2f}")
    print(f"copy_[E,{H}]       {timed(f_copy, x):8.2f} ms")
    print(f"gather_rows        {timed(f_gather, xn, ids):8.2f} ms")
    print(f"scatter_sorted     {timed(f_scatter, x, ids):8.2f} ms")
    print(f"prefix_xla         {timed(f_prefix_xla, x):8.2f} ms")
    print(f"prefix_pallas      {timed(f_prefix_pl, x):8.2f} ms")
    print(f"cumsum_diff_xla    {timed(f_cumsum, x, starts, ends):8.2f} ms")
    print(f"cumsum_diff_pallas {timed(f_cumsum_pl, x, starts, ends):8.2f} ms")
    print(f"ell_gather_sum     {timed(f_ell, x, ell_idx, ell_msk):8.2f} ms")
    print(f"three_scatters     {timed(f_three, x3, x, ids):8.2f} ms")
    print(f"packed_scatter     {timed(f_packed, x3, x, ids):8.2f} ms")
    print(f"vjp_scatter        {timed(g_scatter, x):8.2f} ms")
    print(f"vjp_cumsum         {timed(g_cumsum, x):8.2f} ms")
    print(f"vjp_ell            {timed(g_ell, x):8.2f} ms")


if __name__ == "__main__":
    main()
