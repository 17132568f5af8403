"""Tests of the benchmark's own code, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 suite (``tests/``)."""

import json
import os
import sys

import jax
import pytest

# the four-partition cell's tests need four host devices; valid until a
# backend initializes (a plug-in imports jax before this file, so the
# XLA_FLAGS route is too late: tests/conftest.py does the same)
jax.config.update("jax_num_cpu_devices", 4)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DATA = os.path.join(HERE, "data")
TOY_BENCH = os.path.join(DATA, "BENCHMARK.json")


@pytest.fixture(autouse=True)
def _work_dir(tmp_path, monkeypatch):
    """The stream driver's pool cache goes to the test's own directory."""
    from benchmarks.drivers import train_stream

    monkeypatch.setattr(train_stream, "WORK", str(tmp_path / "work"))


def toy_mix(name: str) -> dict:
    with open(os.path.join(DATA, "traffic", name + ".json")) as f:
        return json.load(f)


def toy_config(name: str) -> str:
    return os.path.join(DATA, "configs", name + ".yaml")
