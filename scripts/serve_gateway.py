"""Serving gateway: config -> ModelRegistry -> HTTP serve loop.

Boots every model named by the config's ``serve.models:`` list (or one
model from the config itself when the list is absent), warms each engine's
rungs, then serves the JSON predict API plus operational endpoints
(docs/SERVING.md "Transport"):

  POST /v1/models/<name>/predict    GET /v1/models
  GET  /metrics   GET /healthz   GET /readyz

SIGTERM/SIGINT drain gracefully: /readyz flips to 503, in-flight queues
flush (every accepted request gets a real response), then the process exits
0 — the serving-edge mirror of the trainer's preemption contract.

  python scripts/serve_gateway.py --config_path configs/nbody_serve.yaml

CPU works (JAX_PLATFORMS=cpu); the same gateway runs unchanged on TPU, and
the listening line says which (``platform=... device_kind=... devices=N``).
``--port 0`` binds an ephemeral port (printed in the listening line — the
smoke drill in tests/test_cli_e2e.py parses it). Obs events land at
``--obs-dir/obs/events.jsonl``; warmup is marked done after all models
warm, so ``python scripts/obs_report.py <stream> --check`` flags any
steady-state recompile.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_gateway(cfg, *, host=None, port=None, max_inflight=None,
                  warmup_nodes=None):
    """config -> (started + warmed ModelRegistry, bound Gateway): the wiring
    ``main`` serves, importable so chip_smoke.py drives exactly this path.
    ``None`` arguments fall back to the config's ``serve.gateway`` section.
    Logs the listening line, which names the device the engines run on."""
    from distegnn_tpu import obs, runtime
    from distegnn_tpu.obs import jaxprobe
    from distegnn_tpu.serve.registry import ModelRegistry
    from distegnn_tpu.serve.transport import Gateway

    g = cfg.serve.gateway
    if warmup_nodes is None:
        warmup_nodes = [int(n) for n in g.warmup_nodes]
    registry = ModelRegistry.from_config(cfg)
    registry.start()
    obs.log(f"gateway: warming {len(registry)} model(s) at node sizes "
            f"{warmup_nodes}")
    registry.warmup(warmup_nodes)
    # compiles past this point are regressions obs_report --check flags
    jaxprobe.mark_warmup_done()
    jaxprobe.set_phase("serve/http")

    s = cfg.serve
    gateway = Gateway(
        registry,
        host=host if host is not None else str(g.host),
        port=port if port is not None else int(g.port),
        max_inflight=(max_inflight if max_inflight is not None
                      else int(g.max_inflight)),
        drain_grace_s=float(g.drain_grace_s),
        slo_window_s=float((cfg.get("slo") or {}).get("window_s", 60.0)
                           or 60.0),
        autoscale=dict(s.autoscale),
        priority=dict(s.priority),
        stream_chunk_steps=int(s.stream.chunk_steps),
        promote=dict(cfg.get("promote") or {}))
    bound_host, bound_port = gateway.address
    dev = runtime.device_summary()
    obs.event("gateway/listening", host=bound_host, port=bound_port,
              platform=dev["platform"], device_kind=dev["kind"],
              devices=dev["count"], models=registry.names())
    obs.log(f"gateway: listening on http://{bound_host}:{bound_port} "
            f"(models: {', '.join(registry.names())}; "
            f"ready={gateway.ready()}; platform={dev['platform']} "
            f"device_kind={dev['kind']} devices={dev['count']})")
    return registry, gateway


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="distegnn serving gateway")
    ap.add_argument("--config_path", type=str, default=None,
                    help="YAML with serve:/serve.models: sections "
                         "(default: built-ins)")
    ap.add_argument("--host", type=str, default=None,
                    help="bind host (default: serve.gateway.host)")
    ap.add_argument("--port", type=int, default=None,
                    help="bind port, 0 = ephemeral "
                         "(default: serve.gateway.port)")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="gateway shed gate (default: "
                         "serve.gateway.max_inflight)")
    ap.add_argument("--warmup-nodes", type=str, default=None,
                    help="comma-separated node counts warmed per model "
                         "(default: serve.gateway.warmup_nodes)")
    ap.add_argument("--obs-dir", type=str, default="logs/serve_gateway",
                    help="event-stream sink dir (events land at <dir>/obs/"
                         "events.jsonl); '' disables tracing")
    args = ap.parse_args(argv)

    from distegnn_tpu import obs
    from distegnn_tpu.config import ConfigDict, _DEFAULTS, load_config

    cfg = (load_config(args.config_path) if args.config_path
           else ConfigDict(_DEFAULTS))
    if args.obs_dir:
        obs.configure_from_config(cfg, args.obs_dir,
                                  tags={"run": "serve_gateway"})
    registry, gateway = build_gateway(
        cfg, host=args.host, port=args.port, max_inflight=args.max_inflight,
        warmup_nodes=([int(n) for n in args.warmup_nodes.split(",") if n]
                      if args.warmup_nodes else None))
    gateway.install_signal_handlers()
    gateway.serve_forever()          # returns after a signal-driven drain

    gateway.close()
    registry.stop(drain=True)        # idempotent: drain already ran this
    obs.log("gateway: drained and stopped; exiting 0")
    obs.get_tracer().flush()
    return 0


if __name__ == "__main__":
    from distegnn_tpu import runtime

    runtime.configure_compile_cache()
    raise SystemExit(main())
