"""The plain reference against the program's FastEGNN at toy size, through
each driver's first steps (forward, loss with MMD, gradient, Adam with
accumulation and clip): in float32 the two agree to rounding, Morton order on
and off; and the lower-precision control (kept here at a size a test can
hold) reads far above the sound float32 run."""

import contextlib
import importlib
import sys

import pytest

from benchmarks import compare, weights
from benchmarks.tests.conftest import toy_config, toy_mix


def _numbers(config, mix_name, seed, overrides=None):
    mix = toy_mix(mix_name)
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    with contextlib.redirect_stdout(sys.stderr):
        d = mod.Driver(toy_config(config), mix, seed, overrides=overrides)
        d.setup(weights.make_weights(seed, d.dims))
        rec, inputs = d.program_record(), d.reference_inputs()
        d.free()
        ref = compare.reference_record(inputs, rec["w0"])
    return {k: v[0] for k, v in compare.numbers(rec, ref).items()}


@pytest.mark.parametrize("node_order", ["morton", "none"])
def test_stream_float32_agrees_to_rounding(node_order):
    n = _numbers("toy_fluid", "toy_fluid_mix", 3,
                 {"model.compute_dtype": None, "data.node_order": node_order})
    assert n["loss_gap"] < 1e-4 and n["grad_gap"] < 1e-3 and n["change_gap"] < 1e-2, n


def test_scan_float32_agrees_to_rounding():
    n = _numbers("toy_nbody", "toy_nbody_mix", 3)
    assert n["loss_gap"] < 1e-5 and n["moment_gap"] < 1e-4 and n["change_gap"] < 1e-3, n


def test_scan_control_bf16_reads_far_above_float32():
    sound = _numbers("toy_nbody", "toy_nbody_mix", 4)
    control = _numbers("toy_nbody", "toy_nbody_mix", 4, {"model.compute_dtype": "bf16"})
    assert control["moment_gap"] > 100 * sound["moment_gap"], (sound, control)
    assert control["loss_gap"] > 100 * max(sound["loss_gap"], 1e-7), (sound, control)


def test_stream_control_runs_and_gives_numbers():
    # against bf16 MLPs the control's distance is decided on the chip at the
    # cell's own size (PERF.md); here it only has to run
    n = _numbers("toy_fluid", "toy_fluid_mix", 4, {"model.agg_dtype": "bf16"})
    assert all(v == v for v in n.values())
