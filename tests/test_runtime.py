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


@pytest.mark.parametrize("what", ["segment_sum", "gather", "paired_col_gather"])
def test_blocked_kernels_lower_for_tpu(monkeypatch, what):
    """ops/blocked.py's Pallas kernels (``blocked_impl: pallas``: the one-hot
    segment sum, its adjoint gather, and the paired col gather whose backward
    is the segment sum) pass the Pallas TPU lowering, forward and backward."""
    from distegnn_tpu.ops import blocked

    monkeypatch.setattr(runtime, "use_interpret", lambda: False)
    # nb=3 blocks of 256 nodes, 2 edge tiles a block; F=40 is this test's own
    B, block, tile, nb, F = 1, 256, 512, 3, 40
    N, E = nb * block, nb * 2 * tile
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    fn, args = {
        "segment_sum": (
            lambda d, s: blocked.blocked_segment_sum(d, s, N, block, tile),
            (f32(B, E, F), i32(B, E))),
        "gather": (
            lambda h, s: blocked.blocked_gather(h, s, block, tile),
            (f32(B, N, F), i32(B, E))),
        "paired_col_gather": (
            lambda h, c, p, s: blocked.paired_col_gather(h, c, p, s, block, tile),
            (f32(B, N, F), i32(B, E), i32(B, E), i32(B, E))),
    }[what]
    # value AND grad: each kernel's VJP is the other kernel
    lowered = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a)))).trace(*args).lower(
            lowering_platforms=("tpu",))
    want = 1 if what == "paired_col_gather" else 2   # its forward is a take
    assert lowered.as_text().count("tpu_custom_call") >= want
