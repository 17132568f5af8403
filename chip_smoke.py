#!/usr/bin/env python3
"""Does the system still start on the chip? One process, one command:

    python chip_smoke.py

Drives the two main paths through the entry points a user calls, at the full
width of the LargeFluid model (FastEGNN H=64, L=4, C=3, bf16 MLPs, remat,
MMD, grad accumulation 4; N=113,140 particles), with random weights from the
configs' seeds:

  device     JAX must report a TPU; versions, compile-cache dir, partitioner
  data       synthetic Fluid113K-format simulations from a fixed seed
  train      main.main() on configs/largefluid_distegnn.yaml (only data_dir,
             max_samples and log_dir redirected): a few optimizer steps plus
             eval through run_distributed over every local chip
  serve      the gateway on configs/nbody_serve.yaml wired as
             scripts/serve_gateway.py wires it: warm-up, HTTP predicts on two
             rungs and a rollout checked against the engine, /metrics, drain
  kernels    one LargeFluid train step with the Pallas prefix-sum kernel
             (segment_impl: cumsum) compiled, against the scatter lowering
  multichip  (>= 4 devices) the batch really spans four chips, all four hold
             memory, and tiled serving rounds over four devices match the
             sequential tile walk

Any failed check raises, so a failed phase can only end in a non-zero exit
and no result line. On success the last two lines of stdout are
``summary: {...}`` (per phase its seconds, compile requests, compile seconds
and persistent-cache hits; ``"claim": null``, no statement about speed) and
then the result line the driver parses, which holds exactly
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it.

The chip belongs to one process at a time, so nothing here starts another
process after JAX is up; the data generator is imported, not shelled out to.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

REQUIRED_PLATFORM = "tpu"
PARTICLES = 113_140          # Fluid113K node count
FRAMES = 28                  # > delta_t (20) + a few start frames
MAX_SAMPLES = 8              # graphs per split: 2 optimizer steps per epoch
EPOCHS = 4                   # eval (and warm-up end) at 2, steady state to 4
DATA_SEED = 0
WORK_DATA = os.path.join(ROOT, "data", "chip_smoke")     # git-ignored
WORK_LOGS = os.path.join(ROOT, "logs", "chip_smoke")     # git-ignored

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def check(cond, msg: str) -> None:
    """A failed check ends the run (assert would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


class Phases:
    """Seconds, compile requests, compile seconds and persistent-cache hits
    per phase, read from jax.monitoring (a compile request answered from the
    cache still counts as a request, with its retrieval time)."""

    def __init__(self):
        import jax.monitoring

        from distegnn_tpu.obs.jaxprobe import _COMPILE_EVENT

        self._compile_event = _COMPILE_EVENT
        self.done: dict = {}
        self._cur = None
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if self._cur is not None and event == self._compile_event:
            self._cur["compiles"] += 1
            self._cur["compile_s"] += duration_secs

    def _event(self, event, **_):
        if self._cur is not None and event == _CACHE_HIT_EVENT:
            self._cur["cache_hits"] += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        rec = {"seconds": 0.0, "compiles": 0, "compile_s": 0.0,
               "cache_hits": 0}
        print(f"== phase {name}", flush=True)
        self._cur, t0 = rec, time.perf_counter()
        try:
            yield rec
        finally:
            self._cur = None
        rec["seconds"] = round(time.perf_counter() - t0, 2)
        rec["compile_s"] = round(rec["compile_s"], 2)
        self.done[name] = rec
        print(f"== phase {name} ok: {json.dumps(rec)}", flush=True)


def read_events(exp_dir: str) -> list:
    path = os.path.join(exp_dir, "obs", "events.jsonl")
    check(os.path.exists(path), f"no obs event stream at {path}")
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def watcher_snapshot() -> dict:
    from distegnn_tpu.obs import jaxprobe

    w = jaxprobe.get_compile_watcher()
    check(w is not None, "no CompileWatcher is installed")
    return w.snapshot()


# ---------------------------------------------------------------- phases

def phase_device(dev: dict) -> None:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp

    from distegnn_tpu.native import native_status

    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}
    print(f"platform: {dev['platform']}  device_kind: {dev['kind']}  "
          f"devices: {dev['count']}  versions: {versions}")
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    print(f"partitioner: {native_status()}")
    x = jnp.ones((256, 256), jnp.float32)
    check(float(jax.block_until_ready(x @ x)[0, 0]) == 256.0,
          "a 256x256 matmul on the device returned the wrong value")


def phase_data() -> str:
    import generate_fluid_synthetic as gen

    # keyed by size: a debug run at a tiny PARTICLES must not be reused
    out = os.path.join(WORK_DATA, f"LargeFluid_n{PARTICLES}")
    have = glob.glob(os.path.join(out, "Fluid113K", "sim_*.msgpack.zst"))
    if len(have) == 3 * 16:   # three sims of 16 shards: an earlier run's data
        print(f"data: reusing {out}")
    else:
        gen.generate(out, particles=PARTICLES, frames=FRAMES, sims_train=1,
                     sims_valid=1, sims_test=1, seed=DATA_SEED)
    return out


def phase_train(data_dir: str, run_dir: str, cfg_path: str,
                dev: dict) -> dict:
    import jax
    import numpy as np
    import yaml

    import main as trainer_cli
    from distegnn_tpu.config import load_config
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.serve.buckets import synthetic_graph
    from distegnn_tpu.train.checkpoint import restore_params

    with open(os.path.join(ROOT, "configs", "largefluid_distegnn.yaml")) as f:
        raw = yaml.safe_load(f)
    raw["data"]["data_dir"] = data_dir
    raw["data"]["max_samples"] = MAX_SAMPLES
    raw["log"]["log_dir"] = os.path.join(run_dir, "train")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(raw, f)

    best = trainer_cli.main(["--config_path", cfg_path,
                             "--epochs", str(EPOCHS)])
    check(isinstance(best, dict) and "preempted" not in best
          and "diverged" not in best, f"trainer returned {best}")

    exps = os.listdir(raw["log"]["log_dir"])
    check(len(exps) == 1, f"expected one experiment dir, found {exps}")
    exp_dir = os.path.join(raw["log"]["log_dir"], exps[0])
    log_json = os.path.join(exp_dir, "log", "log.json")
    check(os.path.exists(log_json), f"no {log_json}")
    with open(log_json) as f:
        best_logged, curves, _ = json.load(f)
    losses = curves["loss_train"]
    check(len(losses) == EPOCHS and all(
        isinstance(v, float) and np.isfinite(v) for v in losses),
        f"train losses not finite over {EPOCHS} epochs: {losses}")
    check(len(set(losses)) > 1, f"train loss never changed: {losses}")
    check(all(np.isfinite(best_logged[k]) and best_logged[k] < 1e8
              for k in ("loss_valid", "loss_test")),
          f"eval losses missing: {best_logged}")

    events = read_events(exp_dir)
    start = [e for e in events if e.get("name") == "train/run_start"]
    check(len(start) == 1, "no train/run_start event")
    start = start[0]
    check(start.get("platform") == dev["platform"]
          and start.get("device_kind") == dev["kind"]
          and start.get("devices") == dev["count"],
          f"trainer ran on {start}, the smoke on {dev}")
    check(start.get("mesh") == f"1x{dev['count']}x1",
          f"graph axis is not all local chips: mesh {start.get('mesh')}")
    path = "scanned epoch" if start["scan_epochs"] else "host step loop"
    snap = watcher_snapshot()
    check(snap["warmup_done"] and snap["compiles_after_warmup"] == 0,
          f"compiles after warm-up in the train phase: {snap}")

    # parameters changed: the checkpoint of the last eval epoch against the
    # seeded init (flax init depends on the key and the feature widths only)
    cfg = load_config(cfg_path)
    m = cfg.model
    model = get_model(m, world_size=1, dataset_name=cfg.data.dataset_name)
    tiny = synthetic_graph(8, seed=0, feat_nf=m.node_feat_nf,
                           edge_attr_nf=m.edge_attr_nf)
    tiny["node_attr"] = np.ones((8, m.node_attr_nf), np.float32)
    init = model.init(jax.random.PRNGKey(cfg.seed), pad_graphs([tiny]))
    trained = restore_params(
        os.path.join(exp_dir, "state_dict", "last_model.ckpt"), init)
    moved = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(jax.tree.leaves(init),
                                jax.tree.leaves(trained)))
    check(np.isfinite(moved) and moved > 0.0,
          f"parameters did not change (max |delta| = {moved})")
    print(f"train: N={PARTICLES} H={m.hidden_nf} L={m.n_layers} "
          f"C={m.virtual_channels} mesh={start['mesh']} path={path} "
          f"loss_train={losses} max|dparam|={moved:.3e} watcher={snap}")
    return {"path": path, "mesh": start["mesh"], "loss_train": losses}


def _http(url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


def phase_serve(run_dir: str, cfg_path: str, dev: dict) -> dict:
    import numpy as np
    import yaml

    import serve_gateway
    from distegnn_tpu import obs
    from distegnn_tpu.config import load_config
    from distegnn_tpu.serve.buckets import synthetic_graph
    from distegnn_tpu.serve.transport import graph_from_payload

    with open(os.path.join(ROOT, "configs", "nbody_serve.yaml")) as f:
        raw = yaml.safe_load(f)
    # the one addition: the rollout endpoint is off unless its static
    # neighbour capacities are configured
    raw["serve"]["rollout"] = {"radius": 0.35, "max_degree": 64,
                               "max_per_cell": 64}
    with open(cfg_path, "w") as f:
        yaml.safe_dump(raw, f)
    cfg = load_config(cfg_path)
    exp_dir = os.path.join(run_dir, "serve")
    obs.configure_from_config(cfg, exp_dir, tags={"run": "chip_smoke_serve"})

    registry, gateway = serve_gateway.build_gateway(cfg, port=0)
    server = threading.Thread(target=gateway.serve_forever, daemon=True)
    server.start()
    try:
        name = registry.names()[0]
        entry = registry.get(name)
        check(entry.engine._donate == (dev["platform"] == "tpu"),
              "serve.donate: auto did not resolve to the platform")
        status, body = _http(gateway.url("/readyz"))
        check(status == 200, f"/readyz -> {status} {body[:300]}")
        for w in registry.health()[name]["workers"]:
            check(w["backend"] == "thread" and not w["degraded"],
                  f"replica is not a healthy thread replica: {w}")
        check(watcher_snapshot()["compiles_after_warmup"] == 0,
              "compiles right after warm-up")

        def predict(n, seed):
            g = synthetic_graph(n, seed=seed, feat_nf=entry.feat_nf,
                                edge_attr_nf=entry.edge_attr_nf)
            check(entry.engine.ladder.bucket_of_graph(g) in entry.warmed,
                  f"request graph n={n} misses the warmed rungs")
            payload = {"positions": g["loc"].tolist(),
                       "velocities": g["vel"].tolist(),
                       "edge_index": g["edge_index"].tolist(),
                       "node_feat": g["node_feat"].tolist(),
                       "edge_attr": g["edge_attr"].tolist()}
            status, body = _http(gateway.url(f"/v1/models/{name}/predict"),
                                 payload)
            check(status == 200, f"predict n={n} -> {status} {body[:300]}")
            out = json.loads(body)
            got = np.asarray(out["prediction"], np.float32)
            want = entry.engine.predict(graph_from_payload(
                payload, entry.feat_nf, entry.edge_attr_nf))
            check(got.shape == (n, 3) and np.isfinite(got).all(),
                  f"predict n={n}: shape {got.shape} / non-finite")
            # same executable, same params, same padded batch on both sides
            check(np.allclose(got, want, rtol=0, atol=1e-6),
                  f"predict n={n}: HTTP answer differs from engine.predict "
                  f"by {np.max(np.abs(got - want)):.3e}")
            return out["bucket"]

        rungs = [predict(n, seed) for n, seed in
                 ((48, 0), (96, 0), (40, 3), (96, 0))]
        check(len({(b["n"], b["e"]) for b in rungs}) >= 2,
              f"predicts did not cover two rungs: {rungs}")
        check(watcher_snapshot()["compiles_after_warmup"] == 0,
              "a predict on a warmed rung compiled")

        def rollout():
            scene = synthetic_graph(48, seed=5)
            status, body = _http(
                gateway.url(f"/v1/models/{name}/rollout"),
                {"positions": scene["loc"].tolist(),
                 "velocities": scene["vel"].tolist(), "steps": 3})
            check(status == 200, f"rollout -> {status} {body[:300]}")
            got = np.asarray(json.loads(body)["trajectory"], np.float32)
            want = entry.engine.rollout_batch(
                [{"loc": scene["loc"], "vel": scene["vel"], "steps": 3}])[0]
            check(got.shape == (3, 48, 3) and np.isfinite(got).all(),
                  f"rollout: shape {got.shape} / non-finite")
            check(np.allclose(got, want, rtol=0, atol=1e-6),
                  f"rollout: HTTP answer differs from engine.rollout_batch "
                  f"by {np.max(np.abs(got - want)):.3e}")

        # a rollout executable is keyed on the client's step count, so the
        # first one compiles by design; after it the window must stay quiet
        rollout()
        warm = watcher_snapshot()
        rollout()
        predict(48, 0)
        status, metrics = _http(gateway.url("/metrics"))
        check(status == 200 and "distegnn_gateway_predict_ok" in metrics
              and "distegnn_gateway_rollout_ok" in metrics,
              f"/metrics -> {status}, counters missing")
        steady = watcher_snapshot()
        check(steady["compiles"] == warm["compiles"],
              f"compiles in the steady window: {warm} -> {steady}")
    finally:
        gateway.drain()              # stops accepting, flushes, ends the loop
        server.join(timeout=60.0)
        gateway.close()
        registry.stop(drain=True)
    check(not server.is_alive(), "gateway thread did not stop after drain")
    obs.flush()

    listening = [e for e in read_events(exp_dir)
                 if e.get("name") == "gateway/listening"]
    check(len(listening) == 1
          and listening[0]["platform"] == dev["platform"]
          and listening[0]["device_kind"] == dev["kind"],
          f"gateway listening event does not name the device: {listening}")
    print(f"serve: rungs={rungs} donate={entry.engine._donate} "
          f"backend=thread degraded=False watcher={steady}")
    return {"rungs": rungs, "donate": entry.engine._donate}


def phase_kernels(data_dir: str, train_cfg_path: str) -> dict:
    """segment_impl: cumsum — the Pallas prefix/suffix kernels — in one
    LargeFluid train step, against the scatter lowering on the same batch,
    params and key. SGD(1.0) so the parameter delta IS the gradient."""
    import jax
    import numpy as np
    import optax
    from jax.flatten_util import ravel_pytree

    from distegnn_tpu import runtime
    from distegnn_tpu.config import load_config
    from distegnn_tpu.data.fluid113k import build_fluid_graph, read_sim
    from distegnn_tpu.data.loader import GraphDataset
    from distegnn_tpu.data.partition import split_graph
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.train import TrainState, make_train_step

    cfg = load_config(train_cfg_path)
    d, t = cfg.data, cfg.train
    pos, vel, visc, mass = read_sim(data_dir, d.dataset_name, 1)
    whole = build_fluid_graph(pos[0], vel[0], visc, mass, pos[d.delta_t])
    graph = split_graph(whole, 1, "random", d.inner_radius)[0]  # adds edges
    graph = GraphDataset([graph], node_order=d.node_order)[0]
    batch = jax.device_put(pad_graphs([graph], compute_pair=True))
    n_edges = int(graph["edge_index"].shape[1])

    def one_step(impl):
        model = get_model(cfg.model, world_size=1,
                          dataset_name=d.dataset_name).copy(segment_impl=impl)
        params = model.init(jax.random.PRNGKey(cfg.seed), batch)
        tx = optax.sgd(1.0)
        step = jax.jit(make_train_step(
            model, tx, mmd_weight=t.mmd.weight, mmd_sigma=t.mmd.sigma,
            mmd_samples=t.mmd.samples))
        args = (TrainState.create(params, tx), batch, jax.random.PRNGKey(7))
        lowered = step.lower(*args)
        hlo = lowered.as_text()
        state, metrics = lowered.compile()(*args)
        loss = float(metrics["loss"])
        grad = np.asarray(ravel_pytree(params)[0] -
                          ravel_pytree(state.params)[0])
        check(int(state.step) == 1 and np.isfinite(loss)
              and np.isfinite(grad).all(),
              f"{impl} step: loss {loss}, finite grads "
              f"{bool(np.isfinite(grad).all())}")
        return loss, grad, hlo

    loss_sc, grad_sc, _ = one_step("scatter")
    loss_cs, grad_cs, hlo = one_step("cumsum")
    compiled = "tpu_custom_call" in hlo
    check(compiled == (not runtime.use_interpret()),
          f"cumsum step: Mosaic custom call present={compiled}, "
          f"use_interpret={runtime.use_interpret()}")
    # tolerance from the arithmetic: the f32 prefix over E~1.7M rows carries
    # ~|prefix| * 2^-24 into every segment difference (ops/segment.py), up to
    # ~1e-2 of a segment sum here, and the MLPs round to bf16 (4e-3). A wrong
    # kernel is an O(1) error.
    rel_loss = abs(loss_cs - loss_sc) / max(abs(loss_sc), 1e-30)
    rel_grad = float(np.linalg.norm(grad_cs - grad_sc)
                     / max(np.linalg.norm(grad_sc), 1e-30))
    check(rel_loss <= 5e-2, f"cumsum vs scatter loss: {loss_cs} vs {loss_sc}")
    check(rel_grad <= 1e-1, f"cumsum vs scatter gradient: rel {rel_grad}")
    print(f"kernels: cumsum prefix/suffix "
          f"{'compiled (Mosaic)' if compiled else 'interpreted'} at "
          f"N={graph['loc'].shape[0]} E={n_edges}; loss scatter={loss_sc:.6g} "
          f"cumsum={loss_cs:.6g} (rel {rel_loss:.2e}); grad rel diff "
          f"{rel_grad:.2e}")
    return {"cumsum": "compiled" if compiled else "interpreted",
            "rel_loss": rel_loss, "rel_grad": rel_grad}


def device_peak_bytes(devs) -> list:
    """The trainer's peak is the process's peak: code that has only seen one
    chip would have left devices 1..n-1 empty."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    check(all(p is not None and p > 64 << 20 for p in peaks),
          f"a device held almost nothing during training: peaks {peaks}")
    return peaks


def phase_multichip(data_dir: str, train_cfg_path: str,
                    serve_cfg_path: str) -> dict:
    import jax
    import numpy as np

    from distegnn_tpu.config import load_config
    from distegnn_tpu.data import ShardedGraphLoader, open_dataset
    from distegnn_tpu.data.fluid113k import process_large_fluid_distribute
    from distegnn_tpu.parallel.launch import global_batch_putter
    from distegnn_tpu.parallel.mesh import make_mesh
    from distegnn_tpu.serve import engine_with_params_from_config
    from distegnn_tpu.serve.buckets import synthetic_graph

    devs = jax.local_devices()
    n = len(devs)
    peaks = device_peak_bytes(devs)

    cfg = load_config(train_cfg_path)
    d = cfg.data
    paths = process_large_fluid_distribute(    # cache hit: the trainer's shards
        d.data_dir, d.dataset_name, n, d.max_samples, d.inner_radius,
        d.outer_radius, d.split_mode, d.delta_t, seed=cfg.seed)[0]
    datasets = [open_dataset(p, node_order=d.node_order) for p in paths]
    sizes = [int(ds[0]["loc"].shape[0]) for ds in datasets]
    check(sum(sizes) == PARTICLES and len(set(sizes)) > 1,
          f"partitions are not an uneven cut of {PARTICLES}: {sizes}")
    batch = next(iter(ShardedGraphLoader(
        datasets, d.batch_size, shuffle=False, seed=cfg.seed,
        node_bucket=d.node_bucket, edge_bucket=d.edge_bucket)))
    placed = global_batch_putter(make_mesh(n_graph=n, devices=devs))(batch)
    on = {s.device for s in placed.loc.addressable_shards}
    check(len(on) == n, f"batch shards sit on {len(on)} device(s), not {n}")

    # serve.tiled.devices: rounds of n tiles through one pmapped executable
    # against the sequential tile walk; 'highest' so the two programs may
    # differ by f32 reassociation only
    scfg = load_config(serve_cfg_path)
    scfg.serve.tiled.enable = True
    scfg.serve.tiled.tile_nodes = 1024
    scfg.serve.tiled.devices = n
    with jax.default_matmul_precision("highest"):
        _, engine, _, _ = engine_with_params_from_config(scfg)
        scene = synthetic_graph(6000, radius=0.08, seed=11)
        mesh_out = engine.predict_tiled(dict(scene))
        engine.tiled.devices = 1
        seq_out = engine.predict_tiled(dict(scene))
    check(mesh_out["devices"] == n and seq_out["devices"] == 1
          and mesh_out["rounds"] == -(-mesh_out["tiles"] // n),
          f"tiled rounds: {mesh_out['devices']} devices, "
          f"{mesh_out['rounds']} rounds for {mesh_out['tiles']} tiles")
    a, b = mesh_out["prediction"], seq_out["prediction"]
    err = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
    check(np.isfinite(a).all() and err <= 1e-5,
          f"tiled mesh rounds vs sequential walk: normalized error {err}")
    print(f"multichip: partitions={sizes} batch on {len(on)} devices "
          f"peak_bytes={peaks}; tiled {mesh_out['tiles']} tiles in "
          f"{mesh_out['rounds']} rounds of {n}, error vs sequential "
          f"{err:.2e}")
    return {"partitions": sizes, "peak_bytes_in_use": peaks,
            "tiled_rounds": mesh_out["rounds"], "tiled_err": err}


def result_line(dev: dict) -> str:
    """The last line of stdout, which the driver parses: exactly ``ok`` and
    ``device`` = ``platform``, ``kind`` (text), ``count`` (int). Everything
    else belongs on the ``summary:`` line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}})


def main() -> int:
    from distegnn_tpu import runtime

    dev = runtime.device_summary()
    if dev["platform"] != REQUIRED_PLATFORM:
        print(f"chip_smoke: FAIL: needs a {REQUIRED_PLATFORM.upper()}, but "
              f"JAX found platform={dev['platform']} ({dev['kind']} x "
              f"{dev['count']}); JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}", file=sys.stderr)
        return 2

    import jax

    runtime.configure_compile_cache()
    t0 = time.perf_counter()
    run_dir = os.path.join(WORK_LOGS, time.strftime("%Y%m%d_%H%M%S"))
    os.makedirs(run_dir)
    train_cfg = os.path.join(run_dir, "largefluid_smoke.yaml")
    serve_cfg = os.path.join(run_dir, "nbody_serve_smoke.yaml")
    ph = Phases()
    detail: dict = {}
    with ph.phase("device"):
        phase_device(dev)
    with ph.phase("data"):
        data_dir = phase_data()
    with ph.phase("train"):
        detail["train"] = phase_train(data_dir, run_dir, train_cfg, dev)
    gc.collect()     # the trainer's device-resident dataset goes before the
    with ph.phase("serve"):      # later phases allocate
        detail["serve"] = phase_serve(run_dir, serve_cfg, dev)
    with ph.phase("kernels"):
        detail["kernels"] = phase_kernels(data_dir, train_cfg)
    if dev["count"] >= 4:
        with ph.phase("multichip"):
            detail["multichip"] = phase_multichip(data_dir, train_cfg,
                                                  serve_cfg)
    print("summary: " + json.dumps({
        "claim": None, "seconds": round(time.perf_counter() - t0, 1),
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "phases": ph.done, "detail": detail}))
    print(result_line(dev), flush=True)     # nothing after it on stdout
    return 0


if __name__ == "__main__":
    sys.exit(main())
