"""distegnn_tpu/runtime.py (compile cache placement, the one use_interpret),
chip_smoke.py's refusal to run without a TPU, and the CPU-only check that
the Pallas kernels the smoke keeps still LOWER for a TPU — interpret mode
accepts programs Mosaic's lowering rules refuse, and without this the first
to notice is a chip run."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from distegnn_tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ compile cache

def test_cache_env_set_means_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert runtime.configure_compile_cache() is None
    assert calls == []


def test_cache_unset_is_checkout_relative_and_stable(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        here = runtime.configure_compile_cache()
        assert here == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == here
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # another process, another cwd: the same directory (the path is part of
    # the cache key — a pid, a timestamp or the cwd in it would never hit)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c",
         "from distegnn_tpu import runtime; "
         "print(runtime.configure_compile_cache())"],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env=env)
    assert out.returncode == 0, out.stderr[-500:]
    assert out.stdout.strip().splitlines()[-1] == here


# ------------------------------------------------------------ use_interpret

def test_use_interpret_cpu_tpu_and_nothing_else(monkeypatch):
    assert runtime.use_interpret() is True          # the suite runs on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        runtime.use_interpret()


# --------------------------------------------------------------- chip_smoke

def test_chip_smoke_refuses_cpu_quickly():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr and "platform=cpu" in out.stderr
    for line in out.stdout.splitlines():     # and prints no result
        assert not line.lstrip().startswith("{"), line


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond ok/device."""
    import json

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(runtime.device_summary())
    assert "\n" not in line
    out = json.loads(line)
    assert set(out) == {"ok", "device"} and out["ok"] is True
    assert set(out["device"]) == {"platform", "kind", "count"}
    assert isinstance(out["device"]["platform"], str)
    assert isinstance(out["device"]["kind"], str)
    assert type(out["device"]["count"]) is int


# ---------------------------------------------------------- TPU lowering

# a shape no other test uses: the inner jit caches its trace per shape, and
# a trace made with interpret off must not be found by a CPU execution
_ROWS, _COLS = 3 * 4096 + 8, 72


@pytest.mark.parametrize("what", ["prefix", "prefix_grad", "suffix"])
def test_cumsum_kernels_lower_for_tpu(monkeypatch, what):
    """ops/cumsum.py's prefix and suffix kernels (the suffix is the prefix's
    VJP) pass the Pallas TPU lowering from a CPU process."""
    from distegnn_tpu.ops import cumsum

    monkeypatch.setattr(runtime, "use_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((_ROWS, _COLS), jnp.float32)
    fn = {
        "prefix": lambda a: cumsum.prefix_sum(a, impl="pallas"),
        "prefix_grad": jax.grad(
            lambda a: jnp.sum(cumsum.prefix_sum(a, impl="pallas"))),
        "suffix": lambda a: cumsum._suffix_pallas_diff(a),
    }[what]
    lowered = jax.jit(fn).trace(x).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_fused_edge_layer_lowers_for_tpu(monkeypatch):
    """ops/edge_pipeline.py is NOT in the smoke (Mosaic refuses its sublane
    gather on the chip, ROADMAP S2), but the five JAX-level lowering repairs
    of PR 21 are kept from rotting: forward and backward kernels, bf16 (the
    flagship compute dtype), pass the Pallas TPU lowering."""
    import numpy as np

    from distegnn_tpu.ops.edge_pipeline import (EdgeWeights,
                                                build_edge_blocks,
                                                fused_edge_layer)

    monkeypatch.setattr(runtime, "use_interpret", lambda: False)
    T, H, nb = 512, 64, 3
    n, E = nb * T, nb * T
    rng = np.random.default_rng(0)
    row = np.sort(rng.integers(0, T, size=(nb, T)), axis=1) \
        + np.arange(nb)[:, None] * T
    col = rng.integers(0, n, size=E)
    arrs = build_edge_blocks(
        jnp.asarray(row.reshape(-1)), jnp.asarray(col),
        jnp.asarray(rng.normal(size=(E, 2)).astype(np.float32)),
        jnp.ones((E,), jnp.float32), block=T, n_nodes=n)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    w = EdgeWeights(ws=f32(3, H), b1=f32(1, H), w2=f32(H, H), b2=f32(1, H),
                    w3=f32(H, H), b3=f32(1, H), w4=f32(1, H))

    def loss(x, hr, hc, w):
        t, _, e = fused_edge_layer(x, hr, hc, *arrs, w, T, "bf16")
        return jnp.sum(t) + jnp.sum(e)

    # value AND grad: under grad alone the forward kernel is dead code
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).trace(
        f32(n, 3), f32(n, H), f32(n, H), w).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 2   # fwd + bwd


# ------------------------------------------------------------------- bench

def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_peaks_table_refuses_unknown_device():
    bench = _load_bench()
    assert bench.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(SystemExit, match="no published peaks"):
        bench.device_peaks("cpu")


def test_bench_race_exits_nonzero_when_a_leg_fails(monkeypatch, capsys):
    """A race whose leg dies must not end in exit 0, and must never print a
    0.0 under a metric's name."""
    bench = _load_bench()
    monkeypatch.setattr(bench, "RACE_ORDER",
                        ((["--layout", "no-such-layout"], None),))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr()
    assert "leg failed" in out.err
    for line in out.out.splitlines():
        if line.lstrip().startswith("{"):
            assert json.loads(line).get("value") != 0.0
