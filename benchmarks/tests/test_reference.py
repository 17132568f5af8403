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


# ---- edge blocks (PR 29): the same reference, a graph's edges walked in blocks

TOY_DIMS = {"hidden_nf": 16, "n_layers": 2, "virtual_channels": 3, "node_feat_nf": 3,
            "node_attr_nf": 2, "edge_attr_nf": 2, "normalize": False}
TOY_TRAIN = {"learning_rate": 5e-4, "weight_decay": 1e-12, "clip_norm": 0.3,
             "accumulation_steps": 2, "mmd": {"sigma": 3.0, "weight": 0.01, "samples": 5}}
VARIANTS = {"sound": {}, "half": {"half": True}, "mantissa2": {"mlp_mantissa": 2}}


@pytest.fixture(scope="module")
def toy_batches():
    """Four raw toy clouds as one-graph batches, edge lists padded to a
    multiple of 8, and weights: what ``follow`` takes."""
    import numpy as np

    from benchmarks.reference import graphs as ref_graphs
    from benchmarks.traffic.generate import make_samples

    graphs = [ref_graphs.fluid_graph(s, 0.075)
              for s in make_samples(dict(toy_mix("toy_fluid_mix"), graphs_pool=4))]
    E = -(-max(g["row"].shape[0] for g in graphs) // 8) * 8
    rng = np.random.default_rng(0)
    batches = []
    for g in graphs:
        n = g["loc"].shape[0]
        second = np.zeros(n, np.float32)
        second[n // 2:] = 1.0
        batches.append(ref_graphs.stack(
            [dict(g, mmd_idx=rng.integers(0, n, 15).astype(np.int32), second_half=second)], edges=E))
    w0 = {k: np.asarray(v) for k, v in weights.make_weights(3, TOY_DIMS).items()}
    return batches, E, w0


@pytest.fixture(scope="module")
def unblocked(toy_batches):
    from benchmarks.reference import fastegnn

    batches, _, w0 = toy_batches
    return {name: fastegnn.follow(w0, TOY_DIMS, TOY_TRAIN, [dict(b) for b in batches], 1, **kw)
            for name, kw in VARIANTS.items()}


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("parts", [1, 2, 8])
def test_edge_blocks_agree_with_unblocked(toy_batches, unblocked, parts, variant):
    """4 micro-steps, accumulation 2: loss, first gradient, Adam's moment and
    the weights' change, blocked against unblocked, sound and under the fault
    and the control. Tolerance 1e-5 of the whole norm: the same float32 terms
    (eps 6e-8) summed in another order; gradient and moment read 2e-8 to
    5e-8 here, the weights' change up to 6e-7 (Adam's first updates divide
    by the gradient's own size, which carries a last-digit change over). The
    fault and the control read 0.066 and 0.035 from the sound run."""
    import numpy as np

    from benchmarks.reference import fastegnn

    batches, E, w0 = toy_batches
    ref = unblocked[variant]
    got = fastegnn.follow(w0, TOY_DIMS, TOY_TRAIN, [dict(b) for b in batches], 1,
                          edge_block=E // parts, **VARIANTS[variant])
    tol = 1e-5
    delta = lambda rec: {k: rec["w"][k] - w0[k] for k in w0}
    assert np.max(np.abs(got["loss"] / ref["loss"] - 1.0)) < tol
    assert compare.whole_diff(got["grad_first"], ref["grad_first"]) < tol
    assert compare.whole_diff(got["mu"], ref["mu"]) < tol
    assert compare.whole_diff(delta(got), delta(ref)) < tol
    assert all(np.allclose(got["update_norms"][k], ref["update_norms"][k], rtol=1e-3, atol=0)
               for k in w0)
    if variant != "sound":      # the fault and the control survive blocking
        assert compare.whole_diff(got["mu"], unblocked["sound"]["mu"]) > 0.02


def test_edge_block_has_to_divide_the_edges(toy_batches):
    from benchmarks.reference import fastegnn

    batches, E, w0 = toy_batches
    with pytest.raises(ValueError, match="multiple of edge_block"):
        fastegnn.follow(w0, TOY_DIMS, TOY_TRAIN, [dict(batches[0])], 1, edge_block=E // 8 + 1)


def _step_temporaries(nodes, edges, edge_block):
    """Bytes of temporaries of one compiled micro-step at the cell's widths
    (compiled here, never run)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import fastegnn

    dims = dict(TOY_DIMS, hidden_nf=64, n_layers=4)
    mmd = {"sigma": 3.0, "weight": 0.01, "samples": 50}
    S, f, i = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    blk = {"feat": S((1, nodes, 3), f), "attr": S((1, nodes, 2), f), "loc": S((1, nodes, 3), f),
           "vel": S((1, nodes, 3), f), "target": S((1, nodes, 3), f), "loc_mean": S((1, 3), f),
           "row": S((1, edges), i), "col": S((1, edges), i), "eattr": S((1, edges, 2), f),
           "ew": S((1, edges), f), "mmd_idx": S((1, 150), i), "loss_rows": S((1, nodes), f)}
    w = jax.eval_shape(lambda: weights.make_weights(0, dims))
    compiled = fastegnn._block_grad.lower(
        w, blk, S((), f), model_key=fastegnn._hashable(dims), mmd_key=fastegnn._hashable(mmd),
        G=1, edge_block=edge_block).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_blocked_temporaries_do_not_grow_with_the_edges():
    """At a fixed ``edge_block`` four times the edges cost arguments, not
    temporaries (no carry kept per block); unblocked they cost both."""
    n, eb = 4096, 8192
    assert _step_temporaries(n, 16 * eb, eb) / _step_temporaries(n, 4 * eb, eb) < 1.3
    assert _step_temporaries(n, 16 * eb, None) / _step_temporaries(n, 4 * eb, None) > 2.0
