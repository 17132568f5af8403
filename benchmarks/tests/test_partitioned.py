"""The partitioned job (graph axis 4) in the benchmark's own terms: the
reference's weighted MMD draws, the share that adds up to the model, the
driver through the harness on four host devices, and the aggregation's bytes
a chip."""

import contextlib
import importlib
import sys

import numpy as np
import pytest

from benchmarks import compare, counts, run, tracing, weights
from benchmarks.tests.conftest import TOY_BENCH, toy_config, toy_mix
from benchmarks.tests.test_reference import TOY_DIMS
from benchmarks.tests.test_trace_reduce import _planes

MMD = {"sigma": 0.5, "weight": 0.01, "samples": 5}      # a kernel that varies across the toy cloud


@pytest.fixture(scope="module")
def toy_block():
    """One raw toy cloud as a block of one graph, 15 draws, and weights."""
    from benchmarks.reference import graphs as ref_graphs
    from benchmarks.traffic.generate import make_samples

    g = ref_graphs.fluid_graph(make_samples(dict(toy_mix("toy_fluid_mix"), graphs_pool=1))[0], 0.075)
    n = g["loc"].shape[0]
    idx = np.random.default_rng(0).integers(0, n, 15).astype(np.int32)
    blk = ref_graphs.stack([dict(g, mmd_idx=idx, loss_rows=np.ones(n, np.float32))])
    w0 = {k: np.asarray(v) for k, v in weights.make_weights(3, TOY_DIMS).items()}
    return blk, w0


def _block_grad(blk, w0):
    from benchmarks.reference import fastegnn

    return fastegnn._block_grad(w0, blk, np.float32(blk["loc"].shape[1]),
                                model_key=fastegnn._hashable(TOY_DIMS),
                                mmd_key=fastegnn._hashable(MMD), G=1)


def test_draw_weights_of_one_are_the_unweighted_sum_bit_for_bit(toy_block):
    blk, w0 = toy_block
    plain = _block_grad(blk, w0)
    ones = _block_grad(dict(blk, mmd_w=np.ones(blk["mmd_idx"].shape, np.float32)), w0)
    assert float(plain[1]) == float(ones[1]) and float(plain[0]) == float(ones[0])
    for k in w0:
        assert np.array_equal(np.asarray(plain[2][k]), np.asarray(ones[2][k])), k


def test_weighted_draws_are_a_weighted_kernel_sum(toy_block):
    """k_rv of ``_block_terms`` against sum_j w_j sum_c exp(-|t_j - V_c| / 2 sigma^2)
    written out in float64 on the reference's own virtual coordinates."""
    import jax

    from benchmarks.reference import fastegnn

    blk, w0 = toy_block
    w = np.random.default_rng(1).uniform(0.5, 1.5, blk["mmd_idx"].shape).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        _, _, k_rv = fastegnn._block_terms(w0, TOY_DIMS, MMD, dict(blk, mmd_w=w), None, None)
        _, _, k_plain = fastegnn._block_terms(w0, TOY_DIMS, MMD, blk, None, None)
        one = {k: v[0] for k, v in blk.items()}
        _, X = fastegnn.forward(w0, TOY_DIMS, one)
    V = np.asarray(X, np.float64).T
    t = np.asarray(one["target"], np.float64)[one["mmd_idx"]]
    k = np.exp(-np.linalg.norm(t[:, None, :] - V[None, :, :], axis=-1) / (2 * MMD["sigma"] ** 2))
    assert float(k_rv) == pytest.approx(float(np.sum(k * w[0][:, None])), rel=1e-5)
    assert float(k_plain) == pytest.approx(float(np.sum(k)), rel=1e-5)
    assert abs(float(k_rv) - float(k_plain)) > 1e-3 * float(k_plain)      # the weights do something


# ---- the share adds up to the model

SHARES = (0.16, 0.22, 0.28, 0.34)       # partitions that differ by far more than 10%


def _uneven_labels(pos, n_parts, method, outer_radius=None, seed=0):
    """Slabs along x holding ``SHARES`` of the nodes, in place of METIS."""
    assert n_parts == len(SHARES)
    x = np.asarray(pos)[:, 0]
    return np.searchsorted(np.quantile(x, np.cumsum(SHARES[:-1])), x).astype(np.int32)


@pytest.fixture(scope="module")
def uneven_job():
    """The program's shard-mapped step on a graph-4 mesh of host devices, in
    float32, through the driver's first 8 micro-steps (2 updates) on toy
    clouds cut into four uneven slabs by the program's own ``split_graph``;
    and what the driver hands the reference."""
    import distegnn_tpu.data.partition as partition

    mix = dict(toy_mix("toy_fluid_g4_mix"), compare_steps=8)     # two updates; the cell compares one
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    real, partition.assign_partitions = partition.assign_partitions, _uneven_labels
    try:
        with contextlib.redirect_stdout(sys.stderr):
            d = mod.Driver(toy_config("toy_fluid_g4"), mix, 3, overrides={"model.compute_dtype": None})
            d.setup(weights.make_weights(3, d.dims))
            sizes = [[len(ds[i]["loc"]) for ds in d.datasets] for i in range(4)]
            rec, inputs = d.program_record(), d.reference_inputs()
            d.free()
    finally:
        partition.assign_partitions = real
    return rec, inputs, sizes


def _against_follow(rec, inputs):
    ref = compare.reference_record(dict(inputs, batches=[dict(b) for b in inputs["batches"]]), rec["w0"])
    nums = {k: v[0] for k, v in compare.numbers(rec, ref).items()}
    nums["total_gap"] = float(np.max(np.abs(rec["loss_total"] / ref["loss_total"] - 1.0)))
    return nums


ADD_UP = ("loss_gap", "total_gap", "grad_gap", "moment_gap", "change_gap")
TOL = 2e-5


def test_the_shares_add_up_to_the_model(uneven_job):
    """Loss (logged, and with the MMD term), first gradient, Adam's moment
    and the weights' change after 2 updates, worst leaf each: the four
    partitions' shares against ``follow`` on the whole graph with cut edges
    dropped and the draws at ``P n_p / n``. Tolerance 2e-5: both sides sum
    the same float32 terms (eps 6e-8) in another order, over partitions here
    and over the whole graph there; the readings are 8e-8 to 1e-6 (the
    weights' change largest: Adam's first updates divide by the gradient's
    own size). A wrong share is not rounding: see the next test."""
    rec, inputs, sizes = uneven_job
    assert all(max(s) > 2 * min(s) for s in sizes), sizes
    assert all(b["mmd_w"].shape == b["mmd_idx"].shape == (1, 4 * 150) for b in inputs["batches"])
    assert inputs["train"]["mmd"]["samples"] == 4 * 50      # the four draws of 50 x C laid end to end
    nums = _against_follow(rec, inputs)
    assert all(nums[k] < TOL for k in ADD_UP), nums


def test_draws_at_a_quarter_each_do_not_add_up(uneven_job):
    """Every ``mmd_w`` 1 gives each partition's draws 1/4 whatever its size:
    the loss with MMD, the gradient, the moment and the change all read ten
    times the tolerance and more (5e-4, 4e-3, 4e-3, 1e-2); the logged MSE of
    the first steps does not see it."""
    rec, inputs, _ = uneven_job
    quarter = dict(inputs, batches=[dict(b, mmd_w=np.ones_like(b["mmd_w"])) for b in inputs["batches"]])
    nums = _against_follow(rec, quarter)
    assert all(nums[k] > 10 * TOL for k in ADD_UP if k != "loss_gap"), nums


# ---- the driver at P = 4 through the harness

def _run(trace):
    return run.run(["--workload", "toy_fluid_g4_train", "--seed", str(2 ** 31 + 12), "--seconds", "0.5",
                    "--trace", str(trace)], benchmark_file=TOY_BENCH, platform="cpu")


def test_four_partitions_through_the_harness():
    r = _run(0)
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert r["attempted"] > 0 and r["attempted"] % 8 == 0          # whole passes: 4 scenes listed twice
    rate = r["metrics"]["train_nodes_per_s_per_chip"]["value"]
    # the window counts the whole graph's nodes, once, over four chips
    assert rate * 4 * r["info"]["window_s"] == pytest.approx(r["attempted"] * 1200, rel=1e-9)
    assert r["device"]["count"] >= 4


def test_four_partitions_traced():
    r = _run(1)
    assert r["correct"] is True
    m = r["metrics"]
    assert 1.0 <= m["partition_edge_imbalance"]["value"] < 1.5
    assert "data_stall_share" in m and "step_mfu" in m
    # the CPU profiler writes no device plane (tracing.read_planes takes
    # /device:TPU: only), so no op is classed at all and the reader of the
    # collectives' share finds nothing to read: it reports nothing, never 0
    assert "collective_time_share" not in m and "agg_hbm_roofline" not in m


def test_configuration_and_cell_have_to_agree_on_the_chips(tmp_path):
    import json

    with open(TOY_BENCH) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["name"] == "toy_fluid_g4_train":
            w["chips"] = 1
    for c in bench["configs"]:
        c["file"] = str(toy_config(c["name"]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match="4 chip"):
        run.run(["--workload", "toy_fluid_g4_train", "--seed", "1", "--seconds", "0.1"],
                benchmark_file=str(path), platform="cpu")


# ---- faults under the timed path at P = 4, and the limits' chip readings

@pytest.mark.parametrize("fault", ["state_unchanged", "params_unchanged", "half_rows", "no_exchange"])
def test_fault_under_the_four_partition_path_is_not_correct(fault, monkeypatch):
    """The toy cell compares what ``largefluid800k_train_g4`` compares
    (``loss_gap``, ``grad_diff``, ``moment_diff``, ``change_diff`` over 4
    micro-steps = one update). With one update the loss, the first gradient
    and Adam's moment are all taken at the starting weights: weights that are
    never written back are ``change_diff``'s alone to catch, and it reads 1."""
    import jax

    from benchmarks.tests.test_harness import _break_step

    if fault == "no_exchange":
        # every psum over the graph axis left out: each chip keeps its own sums
        monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    else:
        _break_step(monkeypatch, fault)
    r = _run(0)
    over = {k for k, c in r["compared"].items() if not c["value"] <= c["limit"]}
    assert r["correct"] is False and over, r["compared"]
    if fault == "params_unchanged":
        assert over == {"change_diff"} and r["compared"]["change_diff"]["value"] == 1.0


def _chip_readings():
    import json
    import os

    from benchmarks.tests.conftest import DATA, ROOT

    with open(os.path.join(ROOT, "benchmarks", "limits", "largefluid800k_train_g4.json")) as f:
        limits = json.load(f)["limits"]
    with open(os.path.join(DATA, "readings", "largefluid800k_train_g4.jsonl")) as f:
        return limits, [json.loads(line) for line in f]


def test_the_cells_limits_decide_its_chip_readings():
    """``read_limits.py``'s records of the cell at its own size on four chips
    (PR 30: 8 sound seeds, the control at 2 mantissa bits and the fault "half
    of every partition's rows" on 3 each) through ``compare.decide`` under the
    committed limits: every sound record correct with each number at most half
    its limit, every control and fault record not, and a record whose weights
    never moved fails ``change_diff`` alone."""
    limits, records = _chip_readings()
    seen = {"sound": 0, "control_mantissa2": 0, "fault_half_rows": 0}
    for r in records:
        nums = {k: tuple(v) for k, v in r["numbers"].items()}
        ok, compared = compare.decide(nums, limits)
        seen[r["variant"]] += 1
        if r["variant"] == "sound":
            assert ok and all(c["value"] <= c["limit"] / 2 for c in compared.values()), (r["seed"], compared)
            stuck, compared = compare.decide(dict(nums, change_diff=(1.0, "moving leaves")), limits)
            assert not stuck and [k for k, c in compared.items() if c["value"] > c["limit"]] == ["change_diff"]
        else:
            assert not ok, (r["seed"], r["variant"], compared)
    assert seen == {"sound": 8, "control_mantissa2": 3, "fault_half_rows": 3}


# ---- the aggregation's bytes are a chip's

def _roofline(chips):
    shapes = {"graphs": 1, "nodes": 1000, "edges": 15000, "hidden_nf": 64, "n_layers": 4,
              "dtype_bytes": 2}
    devices, spans = _planes()
    ctx = {"trace": tracing.reduce_planes(devices, spans, chips=1), "shapes": shapes, "chips": chips,
           "peaks": {"hbm_bytes_per_s": 819e9}, "window": {"micro_steps": 2}}
    spent = ctx["trace"]["class_s"]["scatter"] + ctx["trace"]["class_s"]["gather"]
    old = 100.0 * (counts.agg_bytes(shapes) / 819e9) / (spent / 2)       # the reader before PR 30
    return run.read_metric("agg_hbm_roofline", ctx), old


def test_agg_roofline_counts_a_chips_bytes():
    """On ``test_trace_reduce``'s trace (scatter 200 us + gather 100 us): at
    one chip the value the reader gave before the division, to every digit;
    at four, for the same per-chip seconds, a quarter of it."""
    one, old = _roofline(1)
    four, _ = _roofline(4)
    assert one == old
    assert four == old / 4


def test_collective_share_reads_the_class(tmp_path):
    devices, spans = _planes()
    devices["/device:TPU:0"]["ops"].append(("%all-reduce.5 = f32[64]", 1600_000, 1700_000))
    trace = tracing.reduce_planes(devices, spans, chips=1)
    ctx = {"trace": trace, "window": {"counters": {}}}
    assert run.read_metric("collective_time_share", ctx) == pytest.approx(100.0 * 100e-6 / 1200e-6)
    assert run.read_metric("partition_edge_imbalance", ctx) is None
    # an all-reduce the TPU compiler did not combine keeps the JAX primitive's name
    classes = tracing.load_classes()
    for name in ("%psum.983 = f32[1]{0:T(128)} all-reduce(%bitcast.179), channel_id=1",
                 "%pmin.6 = f32[1,3]{1,0} all-reduce(%b)", "%pmax.6 = f32[] all-reduce(%a)",
                 "%all-reduce.218 = (f32[1,3]{1,0}, f32[1]{0}) all-reduce(%x, %y)"):
        assert tracing.classify(name, classes) == "collective", name
    quiet = tracing.reduce_planes(*_planes(), chips=1)
    assert run.read_metric("collective_time_share", {"trace": quiet}) is None
