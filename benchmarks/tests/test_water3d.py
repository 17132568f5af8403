"""The Water-3D cell's own files: the reference's graph against the program's,
the cell through ``run.run`` on the CPU at a toy size (sound, and once for
each fault a streamed batch can have), the new metric's reader, and that the
driver calls the program's assembly and builds nothing of its own."""

import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run
from benchmarks.drivers import common
from benchmarks.tests.conftest import DATA, toy_config, toy_mix

BENCH = os.path.join(DATA, "BENCHMARK_water3d.json")
CELL = "toy_water3d_train"


def _run(trace=0, seed=7):
    return run.run(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
                    "--trace", str(trace)], benchmark_file=BENCH, platform="cpu")


# ---- graphs

@pytest.mark.parametrize("k", [0, 5, 11])
def test_reference_graph_is_the_programs_graph(k):
    """Same node features, same edge SET and attributes, from raw positions
    by two neighbour searches (k-d tree; uniform grid)."""
    from benchmarks.reference.water3d_graphs import water_graph
    from benchmarks.traffic.generate_water3d import make_samples
    from distegnn_tpu.data.water3d import build_water3d_graph

    mix = toy_mix("toy_water3d_mix")
    s = make_samples(mix)[k]
    radius = float(mix["radius"])
    ref = water_graph(s, radius)
    prog = build_water3d_graph(s["loc"], s["vel"], s["particle_type"], s["target"], radius)
    np.testing.assert_array_equal(ref["feat"], prog["node_feat"])
    assert ref["attr"].shape == (len(s["loc"]), 0)
    for name in ("loc", "vel", "target"):
        np.testing.assert_array_equal(ref[name], prog[name])
    np.testing.assert_array_equal(ref["loc_mean"], prog["loc_mean"])
    n = len(s["loc"])
    order = np.lexsort((ref["col"], ref["row"]))
    assert len(order) > 5 * n                               # a real graph, not a handful of edges
    np.testing.assert_array_equal(np.stack([ref["row"], ref["col"]])[:, order], prog["edge_index"])
    np.testing.assert_allclose(ref["eattr"][order], prog["edge_attr"], rtol=1e-6)


def test_samples_follow_the_published_layout():
    """Velocity is the one-frame difference, the target lies delta_t frames
    on, frames of one trajectory share their particles, graphs differ."""
    from benchmarks.traffic.generate_water3d import make_samples

    mix = toy_mix("toy_water3d_mix")
    s = make_samples(mix)
    per, stride, dt = mix["frames_per_trajectory"], mix["frame_stride"], mix["delta_t"]
    assert len(s) == mix["trajectories"] * per and dt % stride == 0
    a, b = s[0], s[dt // stride]                    # frames 0 and delta_t of the first trajectory
    np.testing.assert_array_equal(a["target"], b["loc"])
    assert np.all(np.linalg.norm(a["vel"], axis=1) < 0.1 * mix["radius"])
    means = np.stack([x["loc"].mean(axis=0) for x in s])
    assert len(np.unique(means, axis=0)) == len(s)
    again = make_samples(mix)
    np.testing.assert_array_equal(s[-1]["loc"], again[-1]["loc"])


# ---- the cell through the harness

@pytest.fixture(scope="module")
def sound():
    return _run(seed=2 ** 31 + 34)


def test_sound_run_is_correct(sound):
    r = sound
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"train_nodes_per_s_per_chip", "setup_s"}
    assert set(r["compared"]) == {"loss_gap", "grad_diff", "moment_diff", "change_diff"}
    for c in r["compared"].values():
        assert c["value"] <= c["limit"] / 2, r["compared"]
    # whole passes: 12 pool graphs x 2 repeats / batch 3
    assert r["info"]["micro_steps"] % 8 == 0


def test_traced_line_has_the_new_metric():
    from distegnn_tpu import obs

    obs.clear_spans()           # an earlier run's compiles of this process are not this run's
    r = _run(trace=1)
    m = r["metrics"]
    assert {"compile_s", "data_prep_s", "data_stall_share", "step_mfu", "device_peak_hbm_gb",
            "loader_produce_share", "host_busy_share", "max_compiles_per_program",
            "batch_pad_share"} <= set(m)
    assert 0.0 < m["batch_pad_share"]["value"] < 50.0 and m["batch_pad_share"]["unit"] == "%"
    assert m["max_compiles_per_program"]["value"] == 1
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _break_step(monkeypatch, fault):
    """Plant ``fault`` in ``make_train_step`` as ``main.build_cutoff`` reaches it."""
    import main as program
    from distegnn_tpu.train import step as step_mod

    real = step_mod.make_train_step

    def broken(model, tx, **kw):
        inner = real(model, tx, **kw)

        def step(state, batch, key):
            if fault == "params_unchanged":
                # the optimizer's state advances, the weights are never written back
                new, metrics = inner(state, batch, key)
                return dataclasses.replace(new, params=state.params), metrics
            if fault == "state_unchanged":
                _, metrics = inner(state, batch, key)
                return state, metrics
            if fault == "last_graphs_left_out":
                # of a batch's B graphs the last B // 2 no longer count; the mean is over the rest
                B = batch.node_mask.shape[0]
                keep = (jnp.arange(B) < (B + 1) // 2)[:, None]
                return inner(state, batch.replace(node_mask=batch.node_mask * keep), key)
            raise ValueError(fault)

        return step

    monkeypatch.setattr(program, "make_train_step", broken)


@pytest.mark.parametrize("fault", ["params_unchanged", "state_unchanged", "last_graphs_left_out"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    _break_step(monkeypatch, fault)
    r = _run()
    over = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert r["correct"] is False and over, r["compared"]
    if fault == "params_unchanged":
        # four updates: the steps after the first see the weights that never moved too
        assert "change_diff" in over and r["compared"]["change_diff"]["value"] == 1.0


def test_control_precision_is_not_correct():
    """``compute_dtype: bf16`` (the meta file's control) through the same
    driver reads over the toy limits."""
    import importlib
    import json

    from benchmarks import compare, weights

    mix = toy_mix("toy_water3d_mix")
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    cfg = toy_config("toy_water3d")
    d = mod.Driver(cfg, mix, 5, overrides=common.load_meta(cfg)["control"])
    d.setup(weights.make_weights(5, d.dims))
    rec, inputs = d.program_record(), d.reference_inputs()
    nums = compare.numbers(rec, compare.reference_record(inputs, rec["w0"]))
    with open(os.path.join(DATA, "limits", "toy_water3d_train.json")) as f:
        ok, compared = compare.decide(nums, json.load(f)["limits"])
    assert not ok, compared


# ---- the cell's limits against the chip readings they were set from

def _readings():
    import json

    with open(os.path.join(DATA, "readings", "water3d_train_b15.jsonl")) as f:
        return [json.loads(line) for line in f]


def test_limits_decide_the_chip_readings():
    """The 12 sound, 4 control and 4 fault records of PR 34's chip run under
    the committed limits: every sound number at most half its limit, every
    control and fault number that is compared at least twice it, weights
    never written back fail ``change_diff`` and ``change_gap`` alone."""
    import collections
    import json

    from benchmarks import compare
    from benchmarks.tests.conftest import ROOT

    with open(os.path.join(ROOT, "benchmarks", "limits", "water3d_train_b15.json")) as f:
        limits = json.load(f)["limits"]
    seen = collections.Counter()
    for r in _readings():
        nums = {k: tuple(v) for k, v in r["numbers"].items()}
        ok, compared = compare.decide(nums, limits)
        over = {k for k, c in compared.items() if c["value"] > c["limit"]}
        seen[r["variant"]] += 1
        if r["variant"] == "sound":
            assert ok and all(c["value"] <= c["limit"] / 2 for c in compared.values()), r["seed"]
        elif r["variant"] in ("control", "fault_half_rows"):
            assert over == set(limits), (r["seed"], r["variant"], over)
            assert all(c["value"] >= 2 * c["limit"] for c in compared.values()), r["seed"]
        else:
            assert {"change_diff", "change_gap"} <= over and not ok, r["seed"]
    assert seen == {"sound": 12, "control": 4, "fault_half_rows": 4, "fault_params_unchanged": 16}


# ---- the new metric's reader

@pytest.mark.parametrize("counters, want", [
    ({"data_real_edges": 950.0, "data_padded_edges": 1000.0}, 5.0),
    ({"data_real_edges": 1000.0, "data_padded_edges": 1000.0}, 0.0),
    ({"data_stall_s": 0.1}, None),                       # a program without the counters
    ({"data_real_edges": 0.0, "data_padded_edges": 0.0}, None),
    (None, None),                                        # a driver that hands none over
])
def test_batch_pad_share_reader(counters, want):
    window = {"wall_s": 1.0} if counters is None else {"wall_s": 1.0, "counters": counters}
    got = run.read_metric("batch_pad_share", {"window": window})
    assert got == (None if want is None else pytest.approx(want))


# ---- the driver assembles nothing

def test_driver_trains_with_the_programs_own_assembly(monkeypatch):
    """Everything the window runs is an object ``main.build_cutoff`` returned
    (the loader behind the prefetch, the jitted step, the optimizer), the
    driver's module names none of the program's builders, and without the
    function the driver stops before it makes any data."""
    import ast
    import importlib
    import inspect

    import main as program
    from benchmarks import weights
    from distegnn_tpu.data import GraphLoader, PrefetchLoader

    mix = toy_mix("toy_water3d_mix")
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    calls = []
    real = program.build_cutoff

    def spy(config, files):
        calls.append(real(config, files))
        return calls[-1]

    monkeypatch.setattr(program, "build_cutoff", spy)
    d = mod.Driver(toy_config("toy_water3d"), mix, 9)
    d.setup(weights.make_weights(9, d.dims))
    assert len(calls) == 1
    made = calls[0]
    assert d.step is made.train_step and d.tx is made.tx and d.loader.loader is made.feeds[0]
    assert isinstance(made.feeds[0], PrefetchLoader) and made.feeds[0].depth == 2
    assert isinstance(made.feeds[0].loader, GraphLoader) and made.scan_runner is None
    # what the driver takes from the program by name: the graph builder, the
    # assembly, and what a run needs around them; no loader, model, optimizer or step
    imported = {a.name for node in ast.walk(ast.parse(inspect.getsource(mod)))
                if isinstance(node, ast.ImportFrom) and node.module.split(".")[0] in ("distegnn_tpu", "main")
                for a in node.names}
    assert imported == {"build_cutoff", "build_water3d_graph", "derive_runtime_fields",
                        "needs_grad_clip", "fix_seed", "TrainState", "obs"}

    monkeypatch.delattr(program, "build_cutoff")
    d2 = mod.Driver(toy_config("toy_water3d"), mix, 9)
    monkeypatch.setattr("benchmarks.traffic.generate_water3d.make_samples",
                        lambda mix: pytest.fail("data made before the assembly was found"))
    with pytest.raises(ImportError):
        d2.build()


def test_driver_matches_main(tmp_path, monkeypatch):
    """One pass through the driver leaves the parameters that
    ``main.main(["--epochs", "1"])`` writes to its checkpoint, given the same
    split files, weights and seed."""
    import contextlib
    import importlib
    import sys

    import jax
    import yaml

    import main as program
    from benchmarks import weights
    from benchmarks.tests.test_assembly import SEED, _checkpoint_params, _close

    mix = dict(toy_mix("toy_water3d_mix"), compare_steps=8)       # one pass
    with open(toy_config("toy_water3d")) as f:
        cfg = yaml.safe_load(f)
    cfg["log"].update(log_dir=str(tmp_path / "logs"), test_interval=1)
    cfg["seed"] = SEED
    cfg["train"]["scan_epochs"] = False
    path = str(tmp_path / "toy_water3d.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    with open(str(tmp_path / "toy_water3d.meta.json"), "w") as f:
        f.write('{"assumed": {}}')
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    with contextlib.redirect_stdout(sys.stderr):
        d = mod.Driver(path, mix, SEED)
        d.build()
        names = [n for n, _, _, _ in weights.layout(d.dims)]
        w0 = common.to_plain(jax.device_get(d.run.state.params), names)   # main's own start
        d.start(w0, SEED)
        mine = d.program_record()
        template = d.state
        files = d._split_files(d.samples)
        monkeypatch.setattr(program, "process_dataset_edge_cutoff", lambda data, seed=0: files)
        program.main(["--config_path", path, "--epochs", "1"])
    theirs = common.to_plain(_checkpoint_params(str(tmp_path / "logs"), template), names)
    _close(mine["w"], theirs)
