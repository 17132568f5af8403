"""Device-parallel tile rounds — multi-chip serving for million-node scenes.

The sequential tiled executor (serve/tiled.py) walks a scene's tiles one at
a time on ONE device, so a TPU slice serves a giant scene no faster than a
single chip. Because `plan_tiles` quantizes every tile to one shared padded
shape (``TilePlan.shape_key``), tiles stack cleanly on a leading device
axis: this module groups them into *rounds* of D (``ops/tiling.plan_rounds``
— LPT over the plan's work model) and runs each round through ONE pmapped
per-tile EGCL executable across D devices. The compile-cache key extends the
sequential ``("tile_layer",) + shape_key`` tuple with D — exactly one
executable regardless of tile count or scene size, same as the sequential
invariant.

What stays the same, per the exactness argument of ops/tiling.py:

  - Every tile reads LAYER-INPUT state (h/x snapshots + the layer-input
    virtual X/Hv), so tiles of one layer commute — running D of them
    simultaneously is the same sum in a different order.
  - The halo exchange stays a host-side gather between layers; it is merely
    staged per-round, with round k+1's per-device ``device_put`` overlapping
    round k's compute (the double-buffering of the sequential path, widened
    to D transfers). Device residency stays bounded by TWO staged rounds.
  - The virtual-node closure is exact: each round psums its slots' masked
    partials across the device axis (``models/fast_egnn.reduce_tile_
    partials``), the host accumulates round sums across rounds, and
    ``tiled_virtual_update`` closes the layer once — identical numerators
    and denominator as the sequential accumulation.

Ragged last round (``T % D != 0``): free slots carry a zero-filled filler
tile whose node_mask is all-zero AND a 0.0 validity flag, so they
contribute exactly nothing to the psums and their outputs are discarded.

The schedule itself is device-count-agnostic state-free planning: a
``TilePlan`` built (or session-cached) at ``devices: 1`` serves at any D
without a rebuild — ``plan_rounds`` derives rounds from the plan on the
fly. Everything here is CPU-testable on 8 virtual devices
(tests/test_tiled_mesh.py); it ran once on a four-chip host in chip_smoke.py's
multichip phase (parity against the sequential walk). Multi-chip speed: not
measured.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.ops.tiling import TilePlan, plan_rounds

#: pmap axis name for one round's device dimension
ROUND_AXIS = "tile_round"


def resolve_devices(spec, n_tiles: Optional[int] = None) -> int:
    """Resolve the ``serve.tiled.devices`` knob to a usable device count.

    ``"auto"`` takes every local device; an int is clamped (with an obs
    event, never an error — a config written for a 4-chip slice must still
    serve on 1) to what this process actually has. Returns 1 when there is
    nothing to parallelize over (``n_tiles`` <= 1 included: a one-tile
    scene has no round structure worth a pmap dispatch)."""
    avail = jax.local_device_count()
    if spec == "auto":
        d = avail
    else:
        d = int(spec)
        if d > avail:
            obs.event("serve/tiled_devices_clamped", requested=d,
                      available=avail)
            d = avail
    if n_tiles is not None and n_tiles <= 1:
        return 1
    return max(1, d)


def _round_executable(ex, plan: TilePlan, devices) -> Callable:
    """THE round executable: one EGCL layer over D same-shape tiles, one
    per device, partials psum-closed across the round axis. Reuses the
    sequential executor's un-jitted single-tile callable unchanged; the
    compile-cache key is the sequential key extended with D, so every round
    of every layer of every same-rung scene shares this one program."""
    from distegnn_tpu.models.fast_egnn import reduce_tile_partials

    model = ex.engine.model
    fn = ex._layer_callable(plan)
    D = len(devices)

    def mapped(gcl_params, h, x, batch, X, Hv, cm, valid):
        h2, x2, tx, vf, ct = fn(gcl_params, h, x, batch, X, Hv, cm)
        tx, vf, ct = reduce_tile_partials(tx, vf, ct, valid, ROUND_AXIS)
        return h2, x2, tx, vf, ct

    key = ("tile_layer",) + plan.shape_key + (
        int(model.hidden_nf), int(model.virtual_channels), D)
    return ex.engine._compiled(
        key, lambda: jax.pmap(
            mapped, axis_name=ROUND_AXIS,
            in_axes=(None, 0, 0, 0, None, None, None, 0),
            devices=devices))


def run_rounds(ex, plan: TilePlan, batches, h_full: np.ndarray,
               x_full: np.ndarray, X, Hv, gcls, n_layers: int, virt_fn,
               progress: Optional[Callable] = None, n_devices: int = 2):
    """Execute all layers of one tiled scene as device-parallel rounds.

    Mirrors the sequential layer loop of ``TiledExecutor.predict`` (same
    host-side halo gather, same double-buffered staging, same virtual
    closure) with the tile axis folded into rounds of ``n_devices``.
    ``progress(layer=..., round=..., n_layers=..., n_rounds=...,
    n_tiles=...)`` fires after each ROUND; returning False cancels the
    remaining compute at the next round boundary (the NDJSON disconnect
    contract, at round granularity). Returns ``(h_full, x_full, stats,
    cancelled)`` with stats carrying rounds/devices/round_imbalance plus
    the stall, halo-gather, and per-round timing gauge feeds."""
    devices = jax.local_devices()[:n_devices]
    D = len(devices)
    sched = plan_rounds(plan, D)
    rounds = sched.rounds
    R = sched.n_rounds
    L = int(n_layers)
    tn = plan.tile_nodes
    H = h_full.shape[1]
    C = int(X.shape[2])
    nd = int(np.asarray(batches[0].node_mask).shape[1])
    round_fn = _round_executable(ex, plan, devices)

    # ragged-round filler: zero inputs + an all-zero node_mask clone of tile
    # 0's batch (finite math, zero masked partials) + a 0.0 validity flag
    pad_batch = batches[0].replace(
        node_mask=np.zeros_like(np.asarray(batches[0].node_mask)))
    zeros_h = np.zeros((1, nd, H), np.float32)
    zeros_x = np.zeros((1, nd, 3), np.float32)
    valid_1 = np.asarray(1.0, np.float32)
    valid_0 = np.asarray(0.0, np.float32)

    halo_gather_s = 0.0
    # one [D, ...] array per leaf, its leading axis split over the round's
    # devices: what pmap takes without a reshard
    round_sharding = jax.sharding.NamedSharding(
        jax.sharding.Mesh(np.array(devices), (ROUND_AXIS,)),
        jax.sharding.PartitionSpec(ROUND_AXIS))

    def stage_round(ri: int, h_src: np.ndarray, x_src: np.ndarray):
        """Gather round ri's tile inputs from the layer-input snapshot and
        start their per-device H2D; returns sharded device handles (the
        transfers proceed async under the previous round's compute)."""
        nonlocal halo_gather_s
        t0 = time.perf_counter()
        shards = []
        tiles_r = rounds[ri]
        for slot in range(D):
            if slot < len(tiles_r):
                s = plan.tiles[tiles_r[slot]]
                h_t = np.zeros((1, nd, H), np.float32)
                x_t = np.zeros((1, nd, 3), np.float32)
                h_t[0, :s.n_own] = h_src[s.start:s.stop]
                x_t[0, :s.n_own] = x_src[s.start:s.stop]
                hh = int(s.halo.shape[0])
                if hh:
                    h_t[0, tn:tn + hh] = h_src[s.halo]
                    x_t[0, tn:tn + hh] = x_src[s.halo]
                shards.append((h_t, x_t, batches[tiles_r[slot]], valid_1))
            else:
                shards.append((zeros_h, zeros_x, pad_batch, valid_0))
        halo_gather_s += time.perf_counter() - t0
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *shards)
        return jax.device_put(stacked, round_sharding)

    stall_s = 0.0
    round_s = 0.0
    rounds_done = 0
    cancelled = False
    t_loop = time.perf_counter()
    for li in range(L):
        # scene-global coordinate mean of the layer input (psum #1),
        # identical to the sequential path
        cm = jnp.asarray(x_full.mean(axis=0, dtype=np.float64)
                         .astype(np.float32)[None])
        h_next = np.empty_like(h_full)
        x_next = np.empty_like(x_full)
        tx_l = np.zeros((1, 3, C), np.float32)
        vf_l = np.zeros((1, C, H), np.float32)
        ct_l = np.zeros((1,), np.float32)
        staged = stage_round(0, h_full, x_full)
        for ri, tiles_r in enumerate(rounds):
            t_round = time.perf_counter()
            tb = time.perf_counter()
            jax.block_until_ready(staged)   # residual un-hidden H2D
            stall_s += time.perf_counter() - tb
            h_d, x_d, b_d, v_d = staged
            out = round_fn(gcls[li], h_d, x_d, b_d, X, Hv, cm, v_d)
            # double buffer: round ri+1's D transfers overlap this compute.
            # Later rounds read h_full/x_full (the LAYER INPUT), never
            # h_next — the same invariant that makes tiling exact.
            staged = (stage_round(ri + 1, h_full, x_full)
                      if ri + 1 < R else None)
            h_o = np.asarray(out[0])        # [D, 1, nd, H] — syncs compute
            x_o = np.asarray(out[1])
            for slot, t in enumerate(tiles_r):
                s = plan.tiles[t]
                h_next[s.start:s.stop] = h_o[slot, 0, :s.n_own]
                x_next[s.start:s.stop] = x_o[slot, 0, :s.n_own]
            # the psum'd partials are identical on every device: take slot 0
            tx_l += np.asarray(out[2])[0]
            vf_l += np.asarray(out[3])[0]
            ct_l += np.asarray(out[4])[0]
            round_s += time.perf_counter() - t_round
            rounds_done += 1
            if progress is not None:
                ok = progress(layer=li, round=ri, n_layers=L, n_rounds=R,
                              n_tiles=plan.n_tiles)
                if ok is False:
                    cancelled = True
                    break
        if cancelled:
            break
        h_full, x_full = h_next, x_next
        # close the layer's virtual state from the accumulated round psums
        Hv, X = virt_fn(gcls[li], Hv, X, jnp.asarray(tx_l),
                        jnp.asarray(vf_l), jnp.asarray(ct_l))
    loop_s = max(time.perf_counter() - t_loop, 1e-9)
    stats = {
        "devices": D,
        "rounds": R,
        "round_imbalance": sched.round_imbalance,
        "stall_fraction": min(stall_s / loop_s, 1.0),
        "round_ms": round_s / max(rounds_done, 1) * 1e3,
        "halo_gather_ms": halo_gather_s * 1e3,
    }
    return h_full, x_full, stats, cancelled
