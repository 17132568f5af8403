"""Tiled inference executor — million-node scenes through ONE compiled
fixed-shape tile program with host-side halo exchange.

Scenes above the bucket ladder's cap used to be hard 413s (serve/buckets.py).
Here they serve as a *scan over tiles* of a Morton-ordered plan
(ops/tiling.py): every layer runs the SAME jitted single-tile EGCL program
over every tile, reading cross-tile sender (halo) features from the
layer-input snapshot held on the host, and the virtual-node state (X, Hv) —
the paper's only global coupling — is closed once per layer from per-tile
masked partial sums (models/fast_egnn.py ``tile_partials`` mode +
``tiled_virtual_update``). That is exactly the monolithic forward in a
different summation order: every cross-node quantity in the EGCL layer
derives from LAYER-INPUT state, so parity holds to float-accumulation
order (tests/test_tiled.py, 1e-5 scale-normalized).

Why this is the right shape for giant scenes:

  - ONE executable per tile rung (``TilePlan.shape_key``), regardless of
    scene size: tile axes are quantized to geometric rungs, so the whole
    fleet of giant scenes shares a handful of compiled programs, cached in
    the engine's existing compile-cache LRU.
  - Device residency is bounded by TWO staged tiles plus the tiny virtual
    state, not O(N): tile k+1's inputs are ``device_put`` while tile k
    computes (double buffering), and the non-overlapped H2D remainder is
    measured and exported as the stall fraction.
  - Halo exchange is a host-side gather between tile invocations — no
    device-side cross-tile addressing, no ragged shapes, no recompiles.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distegnn_tpu import obs
from distegnn_tpu.ops.graph import GraphBatch, pad_graphs
from distegnn_tpu.ops.tiling import TilePlan, plan_tiles
from distegnn_tpu.serve.buckets import BucketOverflowError

#: serve.tiled: config defaults (config.py mirrors these; keep in sync)
TILED_DEFAULTS = {
    "enable": True,
    "max_nodes": 4_194_304,     # TiledOverflowError beyond this
    "tile_nodes": 65536,        # own-node slots per tile
    "halo_floor": 1024,         # halo rung floor (geometric growth above)
    "edge_floor": 8192,         # plain-layout edge rung floor
    "growth": 2.0,              # rung growth factor (matches the ladder)
    "timeout_factor": 8.0,      # tiled deadline = factor * request_timeout
    "devices": 1,               # 'auto'|N: device-parallel tile rounds
                                # (serve/mesh_tiled.py); 1 = sequential
}


class TiledOverflowError(BucketOverflowError):
    """The scene exceeds even the tiled executor's bound
    (``serve.tiled.max_nodes``). Subclasses BucketOverflowError so the
    gateway's existing 413 mapping applies unchanged."""


class TiledExecutor:
    """Runs one engine's model over a :class:`~distegnn_tpu.ops.tiling.
    TilePlan`, sharing the engine's params, compile cache, and metrics.

    Built by :class:`~distegnn_tpu.serve.engine.InferenceEngine` when a
    ``serve.tiled:`` config block is present; the engine dispatches
    ``n_nodes > ladder.max_nodes`` requests here (serve/transport.py routes
    them under bulk-priority admission).
    """

    def __init__(self, engine, cfg: Optional[dict] = None):
        c = dict(TILED_DEFAULTS)
        c.update(cfg or {})
        self.engine = engine
        self.enable = bool(c["enable"])
        self.max_nodes = int(c["max_nodes"])
        self.tile_nodes = int(c["tile_nodes"])
        self.halo_floor = int(c["halo_floor"])
        self.edge_floor = int(c["edge_floor"])
        self.growth = float(c["growth"])
        self.timeout_factor = float(c["timeout_factor"])
        # 'auto' | int: device-parallel tile rounds (serve/mesh_tiled.py).
        # Resolved per predict against the live device count — plans and
        # shape_key are device-count-independent, so the same (possibly
        # session-cached) plan serves at any setting.
        self.devices = c["devices"] if c["devices"] == "auto" \
            else int(c["devices"])
        g = self.engine.metrics.registry.gauge
        self._g_tiles = g("serve/tiled_tiles")
        self._g_halo = g("serve/tiled_halo_fraction")
        self._g_stall = g("serve/tiled_stall_fraction")
        # mesh-round gauges (serve/mesh_tiled.py): devices used by the last
        # tiled predict, mean compute ms per round, host halo-gather ms
        self._g_devices = g("serve/tiled_devices")
        self._g_round_ms = g("serve/tiled_round_ms")
        self._g_halo_gather = g("serve/tiled_halo_gather_ms")

    # ---- admission -------------------------------------------------------
    def check_admit(self, n: int) -> None:
        if int(n) > self.max_nodes:
            raise TiledOverflowError(
                f"request nodes={int(n)} exceeds the tiled serving bound "
                f"{self.max_nodes}; raise serve.tiled.max_nodes or shard "
                f"the request")

    # ---- planning --------------------------------------------------------
    def plan(self, graph: dict) -> TilePlan:
        """Morton tile plan for one scene (ops/tiling.plan_tiles).
        Cacheable per session (serve/prep.py)."""
        return plan_tiles(
            np.asarray(graph["edge_index"]), np.asarray(graph["loc"]),
            np.asarray(graph["edge_attr"]) if graph.get("edge_attr") is not None else None,
            tile_nodes=self.tile_nodes, halo_floor=self.halo_floor,
            edge_floor=self.edge_floor, growth=self.growth)

    def _plan_ok(self, plan: TilePlan, n: int) -> bool:
        """A cached plan is reusable only if it was built for this scene
        size and tile size."""
        return plan.n_nodes == n and plan.tile_nodes == self.tile_nodes

    # ---- tile batch construction ----------------------------------------
    def _tile_batch(self, plan: TilePlan, spec, loc, vel, feat, node_attr,
                    loc_mean) -> GraphBatch:
        """One tile's padded GraphBatch: own nodes at [0, n_own), halo
        senders at [tile_nodes, tile_nodes + h), node_mask OWN-ONLY so the
        tile's psum partials count each scene node exactly once."""
        nd = plan.tile_nodes + plan.halo_pad
        n_own, halo = spec.n_own, spec.halo
        d_feat = np.zeros((nd, feat.shape[1]), np.float32)
        d_loc = np.zeros((nd, 3), np.float32)
        d_vel = np.zeros((nd, 3), np.float32)
        d_feat[:n_own] = feat[spec.start:spec.stop]
        d_loc[:n_own] = loc[spec.start:spec.stop]
        d_vel[:n_own] = vel[spec.start:spec.stop]
        h = int(halo.shape[0])
        if h:
            d_feat[plan.tile_nodes:plan.tile_nodes + h] = feat[halo]
            d_loc[plan.tile_nodes:plan.tile_nodes + h] = loc[halo]
            d_vel[plan.tile_nodes:plan.tile_nodes + h] = vel[halo]
        d = {"node_feat": d_feat, "loc": d_loc, "vel": d_vel,
             "edge_index": spec.edge_index, "edge_attr": spec.edge_attr,
             "loc_mean": loc_mean}
        if node_attr is not None:
            d_attr = np.zeros((nd, node_attr.shape[1]), np.float32)
            d_attr[:n_own] = node_attr[spec.start:spec.stop]
            if h:
                d_attr[plan.tile_nodes:plan.tile_nodes + h] = node_attr[halo]
            d["node_attr"] = d_attr
        batch = pad_graphs([d], max_nodes=plan.padded_nodes,
                           max_edges=plan.edge_pad, node_bucket=1,
                           edge_bucket=1)
        own = np.zeros((1, batch.node_mask.shape[1]), np.float32)
        own[0, :n_own] = 1.0
        return batch.replace(node_mask=own)

    # ---- compiled pieces -------------------------------------------------
    def _embed_fn(self, feat_nf: int):
        from distegnn_tpu.models.common import TorchDense

        H = int(self.engine.model.hidden_nf)
        tn = self.tile_nodes

        def build():
            dense = TorchDense(H)
            return jax.jit(lambda p, f: dense.apply({"params": p}, f))

        return self.engine._compiled(("tile_embed", tn, feat_nf, H), build)

    def _layer_callable(self, plan: TilePlan):
        """The un-jitted single-tile layer fn: one EGCL layer over one
        tile's padded batch, returning (h', x', transX_partial,
        vef_partial, count). Shared verbatim by the sequential executable
        (``_layer_fn`` jits it) and the device-parallel round executable
        (serve/mesh_tiled.py pmaps it over a round of D tiles)."""
        from distegnn_tpu.models.fast_egnn import EGCLVel

        model = self.engine.model
        gravity = (jnp.asarray(model.gravity, jnp.float32)
                   if getattr(model, "gravity", None) is not None else None)
        layer = EGCLVel(
            hidden_nf=int(model.hidden_nf),
            virtual_channels=int(model.virtual_channels),
            node_attr_nf=int(getattr(model, "node_attr_nf", 0) or 0),
            edge_attr_nf=int(getattr(model, "edge_attr_nf", 0) or 0),
            residual=bool(getattr(model, "residual", True)),
            attention=bool(getattr(model, "attention", False)),
            normalize=bool(getattr(model, "normalize", False)),
            tanh=bool(getattr(model, "tanh", False)),
            has_gravity=gravity is not None,
            axis_name=None, tensor_axis=None,
            compute_dtype=getattr(model, "compute_dtype", None),
            hoist_edge_mlp=bool(getattr(model, "hoist_edge_mlp", True)),
            seg_impl=str(getattr(model, "segment_impl", "scatter")),
            fuse_agg=bool(getattr(model, "fuse_agg", True)),
            agg_dtype=getattr(model, "agg_dtype", None))

        def fn(gcl_params, h, x, batch, X, Hv, cm):
            # a tile's batch is in the plain layout: no blocked slots
            return layer.apply(
                {"params": gcl_params}, h, x, batch.vel, X, Hv, batch,
                gravity=gravity, tile_coord_mean=cm, tile_partials=True)

        return fn

    def _layer_fn(self, plan: TilePlan):
        """THE sequential tile executable: one EGCL layer over one tile.
        Keyed on the plan's shape rung + the model's layer config — every
        tile of every layer of every scene on the same rung shares this one
        program (the round executable extends this key with D)."""
        model = self.engine.model
        key = ("tile_layer",) + plan.shape_key + (
            int(model.hidden_nf), int(model.virtual_channels))
        return self.engine._compiled(
            key, lambda: jax.jit(self._layer_callable(plan)))

    def _virtual_fn(self):
        from distegnn_tpu.models.fast_egnn import tiled_virtual_update

        model = self.engine.model
        residual = bool(getattr(model, "residual", True))
        cdt = getattr(model, "compute_dtype", None)

        def build():
            return jax.jit(lambda p, Hv, X, tx, vf, c: tiled_virtual_update(
                p, Hv, X, tx, vf, c, residual=residual, compute_dtype=cdt))

        key = ("tile_virtual", int(model.hidden_nf),
               int(model.virtual_channels))
        return self.engine._compiled(key, build)

    # ---- execution -------------------------------------------------------
    def predict(self, graph: dict, *, plan: Optional[TilePlan] = None,
                request_id: Optional[str] = None,
                progress: Optional[Callable[..., Optional[bool]]] = None,
                ) -> dict:
        """Serve one giant scene. Returns a dict with the UNPADDED predicted
        positions (original node order) plus the tiling stats the BENCH leg
        and the NDJSON progress stream report.

        ``progress(layer=..., tile=..., n_layers=..., n_tiles=...)`` is
        called after each tile completes; returning False cancels the
        remaining compute at the next tile boundary (the streamed-rollout
        disconnect contract, applied to tiles).
        """
        engine = self.engine
        model = engine.model
        n = int(graph["loc"].shape[0])
        self.check_admit(n)
        t0 = time.perf_counter()
        if plan is None or not self._plan_ok(plan, n):
            plan = self.plan(graph)
        L = int(getattr(model, "n_layers", 1) or 1)
        T = plan.n_tiles
        H = int(model.hidden_nf)
        C = int(model.virtual_channels)
        params = engine.params["params"]
        gcls = [params[f"gcl_{i}"] for i in range(L)]

        # scene arrays in Morton order (plan.perm[new] = old)
        p = plan.perm
        loc = np.ascontiguousarray(np.asarray(graph["loc"], np.float32)[p])
        vel = np.ascontiguousarray(np.asarray(graph["vel"], np.float32)[p])
        feat = np.ascontiguousarray(
            np.asarray(graph["node_feat"], np.float32)[p])
        na = graph.get("node_attr")
        node_attr = (np.ascontiguousarray(np.asarray(na, np.float32)[p])
                     if na is not None and np.asarray(na).size else None)
        loc_mean = np.asarray(graph["loc"], np.float32).mean(axis=0)[None]

        with obs.span("serve/tiled", n=n, tiles=T, layers=L,
                      padded_nodes=plan.padded_nodes,
                      halo_fraction=round(plan.halo_fraction, 4),
                      work_imbalance=round(plan.work_imbalance, 4),
                      request_id=request_id or "") as sp:
            batches = [self._tile_batch(plan, s, loc, vel, feat, node_attr,
                                        loc_mean) for s in plan.tiles]
            prep_ms = (time.perf_counter() - t0) * 1e3

            # bootstrap: h0 = embedding(node_feat) tile-by-tile (fixed shape)
            emb_fn = self._embed_fn(feat.shape[1])
            emb_p = params["embedding_in"]
            h_full = np.empty((n, H), np.float32)
            buf = np.zeros((self.tile_nodes, feat.shape[1]), np.float32)
            for s in plan.tiles:
                buf[:] = 0.0
                buf[:s.n_own] = feat[s.start:s.stop]
                h_full[s.start:s.stop] = np.asarray(emb_fn(emb_p, buf))[:s.n_own]
            x_full = loc.copy()
            X = jnp.repeat(jnp.asarray(loc_mean)[:, :, None], C, axis=2)
            Hv = jnp.asarray(params["virtual_node_feat"])          # [1, H, C]

            virt_fn = self._virtual_fn()

            # device-parallel tile rounds (serve/mesh_tiled.py): D same-
            # shape tiles at once across D devices, behind the same plan,
            # session cache, and queue/gateway contracts
            from distegnn_tpu.serve import mesh_tiled

            D = mesh_tiled.resolve_devices(self.devices, n_tiles=T)
            mesh_stats = None
            if D > 1:
                h_full, x_full, mesh_stats, cancelled = mesh_tiled.run_rounds(
                    self, plan, batches, h_full, x_full, X, Hv, gcls, L,
                    virt_fn, progress=progress, n_devices=D)
                stall_frac = mesh_stats["stall_fraction"]
                rounds = mesh_stats["rounds"]
                sp.set(stall_fraction=round(stall_frac, 4),
                       cancelled=cancelled, devices=D, rounds=rounds,
                       round_ms=round(mesh_stats["round_ms"], 3))
            else:
                h_full, x_full, stall_frac, cancelled = self._run_sequential(
                    plan, batches, h_full, x_full, X, Hv, gcls, L, T, H, C,
                    virt_fn, progress)
                rounds = T      # each sequential tile is its own round
                sp.set(stall_fraction=round(stall_frac, 4),
                       cancelled=cancelled)

        self._g_tiles.set(T)
        self._g_halo.set(round(plan.halo_fraction, 6))
        self._g_stall.set(round(stall_frac, 6))
        self._g_devices.set(D)
        if mesh_stats is not None:
            self._g_round_ms.set(round(mesh_stats["round_ms"], 3))
            self._g_halo_gather.set(round(mesh_stats["halo_gather_ms"], 3))
        out = None
        if not cancelled:
            out = np.ascontiguousarray(x_full[plan.inv_perm])
        result = {
            "prediction": out,
            "n": n,
            "tiles": T,
            "layers": L,
            "devices": D,
            "rounds": rounds,
            "padded_nodes": plan.padded_nodes,
            "halo_fraction": plan.halo_fraction,
            "work_imbalance": plan.work_imbalance,
            "stall_fraction": stall_frac,
            "prep_ms": prep_ms,
            "total_ms": (time.perf_counter() - t0) * 1e3,
            "cancelled": cancelled,
        }
        if mesh_stats is not None:
            result["round_ms"] = mesh_stats["round_ms"]
            result["halo_gather_ms"] = mesh_stats["halo_gather_ms"]
            result["round_imbalance"] = mesh_stats["round_imbalance"]
        return result

    def _run_sequential(self, plan: TilePlan, batches, h_full, x_full,
                        X, Hv, gcls, L: int, T: int, H: int, C: int,
                        virt_fn, progress):
        """The single-device tile loop: one tile at a time through the
        jitted layer executable, double-buffered H2D, per-tile progress.
        Kept verbatim from the pre-mesh executor — ``devices: 1`` and the
        D=1 mesh resolution both land here, so nothing changes for
        single-chip serving."""
        layer_fn = self._layer_fn(plan)

        def stage(t: int, h_src: np.ndarray, x_src: np.ndarray):
            """Gather tile t's layer inputs and start their H2D; returns
            device handles (transfer proceeds async under compute)."""
            s = plan.tiles[t]
            nd = batches[t].node_mask.shape[1]
            h_t = np.zeros((1, nd, H), np.float32)
            x_t = np.zeros((1, nd, 3), np.float32)
            h_t[0, :s.n_own] = h_src[s.start:s.stop]
            x_t[0, :s.n_own] = x_src[s.start:s.stop]
            hh = int(s.halo.shape[0])
            if hh:
                h_t[0, plan.tile_nodes:plan.tile_nodes + hh] = h_src[s.halo]
                x_t[0, plan.tile_nodes:plan.tile_nodes + hh] = x_src[s.halo]
            return jax.device_put((h_t, x_t, batches[t]))

        stall_s = 0.0
        cancelled = False
        t_loop = time.perf_counter()
        for li in range(L):
            # psum #1 host-side: the SCENE-global coordinate mean of the
            # layer input (a tile-local mean would be wrong)
            cm = jnp.asarray(x_full.mean(axis=0, dtype=np.float64)
                             .astype(np.float32)[None])
            h_next = np.empty_like(h_full)
            x_next = np.empty_like(x_full)
            tx_l = np.zeros((1, 3, C), np.float32)
            vf_l = np.zeros((1, C, H), np.float32)
            ct_l = np.zeros((1,), np.float32)
            staged = stage(0, h_full, x_full)
            for ti, s in enumerate(plan.tiles):
                tb = time.perf_counter()
                jax.block_until_ready(staged)   # residual un-hidden H2D
                stall_s += time.perf_counter() - tb
                h_d, x_d, b_d = staged
                out = layer_fn(gcls[li], h_d, x_d, b_d, X, Hv, cm)
                # double buffer: tile ti+1's H2D overlaps this compute.
                # Later tiles read h_full/x_full (the LAYER INPUT), never
                # h_next — that is what makes tiling exact.
                staged = (stage(ti + 1, h_full, x_full)
                          if ti + 1 < T else None)
                h_o, x_o, tx_p, vf_p, ct_p = [np.asarray(o) for o in out]
                h_next[s.start:s.stop] = h_o[0, :s.n_own]
                x_next[s.start:s.stop] = x_o[0, :s.n_own]
                tx_l += tx_p
                vf_l += vf_p
                ct_l += ct_p
                if progress is not None:
                    ok = progress(layer=li, tile=ti, n_layers=L,
                                  n_tiles=T)
                    if ok is False:
                        cancelled = True
                        break
            if cancelled:
                break
            h_full, x_full = h_next, x_next
            # close the layer's virtual state from the tile partials —
            # the scene-wide psums #2/#3, applied exactly once
            Hv, X = virt_fn(gcls[li], Hv, X, jnp.asarray(tx_l),
                            jnp.asarray(vf_l), jnp.asarray(ct_l))
        loop_s = max(time.perf_counter() - t_loop, 1e-9)
        return h_full, x_full, min(stall_s / loop_s, 1.0), cancelled
