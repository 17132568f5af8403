"""The remaining cutoff-mode reference configs executed through main.main():
protein_fastegnn.yaml and water3d_fastegnn.yaml on synthetic raw data (the
real datasets are network downloads). The two
distribute-mode configs have their own e2e tests (test_largefluid_e2e.py,
test_water3d_e2e.py). Covers the full CLI path: yaml load + CLI overrides →
preprocessing → loaders → model factory → train loop → log.json.
Reference flow: main.py:95-229."""

from __future__ import annotations

import os
import subprocess

import numpy as np
import pytest
import yaml

import main as main_mod

CONFIG_DIR = os.path.join(os.path.dirname(__file__), "..", "configs")
REPO_DIR = os.path.abspath(os.path.join(CONFIG_DIR, ".."))


def _patched_yaml(tmp_path, name, data_overrides, log_dir):
    with open(os.path.join(CONFIG_DIR, name)) as f:
        cfg = yaml.safe_load(f)
    cfg["data"].update(data_overrides)
    cfg["log"]["log_dir"] = log_dir
    out = str(tmp_path / name)
    with open(out, "w") as f:
        yaml.safe_dump(cfg, f)
    return out


from tests.conftest import assert_run_artifacts as _assert_run_artifacts  # noqa: E402


@pytest.mark.slow
def test_protein_yaml_runs_via_main(tmp_path):
    # synthetic AdK npz (same layout as tests/test_pipelines.py protein_dir)
    rng = np.random.default_rng(2)
    base = tmp_path / "raw" / "protein"
    base.mkdir(parents=True)
    T, N = 4180, 30
    start = rng.uniform(0, 20, size=(1, N, 3)).astype(np.float32)
    steps = rng.normal(size=(T - 1, N, 3)).astype(np.float32) * 0.05
    np.savez_compressed(
        base / "adk_backbone.npz",
        positions=np.concatenate([start, start + np.cumsum(steps, axis=0)], axis=0),
        charges=rng.uniform(0.1, 1.0, size=(N,)).astype(np.float32))

    log_dir = str(tmp_path / "logs")
    path = _patched_yaml(tmp_path, "protein_fastegnn.yaml",
                         {"data_dir": str(tmp_path / "raw")}, log_dir)
    # the reference's fixed 2481/827/863 split is kept by the processor;
    # batch 500 keeps the epoch at ~5 steps on the CPU backend
    main_mod.main(["--config_path", path, "--epochs", "2", "--batch_size", "500"])
    _assert_run_artifacts(log_dir)


@pytest.mark.slow
def test_water3d_cutoff_yaml_runs_via_main(tmp_path):
    from tests.conftest import make_water3d_h5

    data_dir = make_water3d_h5(tmp_path / "raw", 40, 40, step_scale=0.003, seed=5)
    log_dir = str(tmp_path / "logs")
    path = _patched_yaml(tmp_path, "water3d_fastegnn.yaml",
                         {"data_dir": data_dir, "max_samples": 6,
                          "radius": 0.1, "delta_t": 5}, log_dir)
    main_mod.main(["--config_path", path, "--epochs", "2", "--batch_size", "3"])
    _assert_run_artifacts(log_dir)


def test_gateway_smoke_drill(tmp_path):
    """Tier-1 serving-edge drill (the SIGTERM mirror of the preempt drill):
    boot scripts/serve_gateway.py as a REAL process on an ephemeral port,
    predict against a warmed rung, scrape /metrics, SIGTERM it, and assert
    exit 0 with an obs stream that passes obs_report --check (telemetry
    alive, zero steady-state recompiles)."""
    import json
    import re
    import signal
    import sys
    import threading
    import time
    import urllib.request

    with open(os.path.join(CONFIG_DIR, "nbody_serve.yaml")) as f:
        cfg = yaml.safe_load(f)
    # shrink the model so boot+warmup stays in CPU smoke-test territory
    cfg["model"].update(hidden_nf=16, n_layers=2, virtual_channels=2)
    cfg_path = str(tmp_path / "gateway.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cfg, f)

    env = dict(os.environ, PYTHONPATH=REPO_DIR, JAX_PLATFORMS="cpu")
    obs_dir = str(tmp_path / "gwobs")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_DIR, "scripts", "serve_gateway.py"),
         "--config_path", cfg_path, "--port", "0", "--warmup-nodes", "16",
         "--obs-dir", obs_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO_DIR)
    lines = []
    reader = threading.Thread(
        target=lambda: [lines.append(ln) for ln in proc.stdout], daemon=True)
    reader.start()
    try:
        # the gateway prints its bound (ephemeral) port in the listening line
        deadline = time.monotonic() + 240.0
        port = None
        while time.monotonic() < deadline and port is None:
            for ln in list(lines):
                m = re.search(r"listening on http://[\d.]+:(\d+)", ln)
                if m:
                    port = int(m.group(1))
            if proc.poll() is not None:
                raise AssertionError("gateway died: " + "".join(lines))
            time.sleep(0.1)
        assert port, "no listening line: " + "".join(lines)
        base = f"http://127.0.0.1:{port}"

        with urllib.request.urlopen(base + "/readyz", timeout=30) as r:
            assert r.status == 200
        # n=16 == --warmup-nodes: lands on an already-compiled rung, so the
        # obs stream stays free of steady-state recompiles for --check
        from distegnn_tpu.serve import synthetic_graph
        g = synthetic_graph(16, seed=0)
        req = urllib.request.Request(
            base + "/v1/models/default/predict",
            data=json.dumps({"positions": g["loc"].tolist(),
                             "velocities": g["vel"].tolist(),
                             "edge_index": g["edge_index"].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            resp = json.load(r)
        assert np.asarray(resp["prediction"]).shape == (16, 3)
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        assert "distegnn_gateway_requests_total" in metrics
        assert "distegnn_model_default_serve_requests_completed" in metrics

        proc.send_signal(signal.SIGTERM)      # graceful drain -> exit 0
        assert proc.wait(timeout=120) == 0, "".join(lines)
        reader.join(timeout=10)
        assert any("drained and stopped" in ln for ln in lines)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    events = os.path.join(obs_dir, "obs", "events.jsonl")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_DIR, "scripts", "obs_report.py"),
         events, "--check"],
        capture_output=True, text=True, env=env, cwd=REPO_DIR, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_preempt_drill_fast(tmp_path):
    """Tier-1 preemption drill (docs/ROBUSTNESS.md): scripts/preempt_drill.sh
    --fast runs control → deterministic SIGTERM victim (expects exit 75 +
    PREEMPTED marker) → --resume auto, and asserts the resumed final train
    loss matches the control within 1e-6."""
    env = dict(os.environ, PYTHONPATH=REPO_DIR, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        ["bash", os.path.join(REPO_DIR, "scripts", "preempt_drill.sh"),
         "--fast", "--workdir", str(tmp_path / "drill")],
        capture_output=True, text=True, env=env, cwd=REPO_DIR, timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DRILL PASS" in r.stdout
