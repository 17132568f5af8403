"""What this process runs on: the device, whether Pallas kernels compile or
interpret, and where XLA's persistent compile cache lives.

Every entry point (main.py and scripts/serve_gateway.py under
``__main__``, the serving worker child, chip_smoke.py) calls
:func:`configure_compile_cache` first and reports :func:`device_summary`, so a run that JAX quietly dropped to the
CPU says so in its first line instead of in its timings.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> Optional[str]:
    """Place the persistent compile cache. Where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX reads it itself and nothing is set in code (returns None);
    otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of the cache key and a directory that moves
    never hits. Returns the directory set in code."""
    if os.environ.get(_CACHE_ENV):
        return None
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def provision_cpu_devices(n: int) -> None:
    """A multi-device run on the CPU (``JAX_PLATFORMS=cpu``) needs its ``n``
    virtual devices, and they can only be asked for before the backend
    initializes; a no-op on any other platform and when the backend is
    already up (the caller's own device-count check then speaks)."""
    if n > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        try:
            jax.config.update("jax_num_cpu_devices", n)
        except RuntimeError:
            pass


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it. Initializes the backend."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_interpret() -> bool:
    """Pallas kernels interpret on the CPU (the test rig) and compile on a
    TPU. Any other backend is an error: interpreting there would pass for
    "the kernel works" on a device it has never compiled for."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels support the 'cpu' (interpret) and 'tpu' (compiled) "
        f"backends, not {backend!r}")
