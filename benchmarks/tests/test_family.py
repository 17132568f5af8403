"""The family seam (``benchmarks/family.py``): FastEGNN through it reads what
it read before the seam (weights to the byte, counts to the digit, numbers
frozen from the commit before it), a name with no family ends a run before
set-up, FastTFN's flat weights map onto the program's FastTFN tree, FastTFN's
counts by hand, and a FastTFN cell added with new files only
(``data/BENCHMARK_tfn.json``, ``configs/toy_nbody_tfn.*``,
``limits/toy_nbody_tfn_train.json``, on the n-body mix FastEGNN's toy cell
runs) through ``run.run``, ``read_limits.py`` and ``scope_times.py``."""

import contextlib
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys

import jax
import numpy as np
import pytest

from benchmarks import counts, family, run, weights
from benchmarks.drivers import common
from benchmarks.tests.conftest import DATA, ROOT, toy_config

TFN_BENCH = os.path.join(DATA, "BENCHMARK_tfn.json")
TFN_CELL = "toy_nbody_tfn_train"
TOY = {"hidden_nf": 16, "n_layers": 2, "virtual_channels": 3, "node_feat_nf": 3,
       "node_attr_nf": 2, "edge_attr_nf": 2, "normalize": False}
NBODY = {"hidden_nf": 64, "n_layers": 4, "virtual_channels": 3, "node_feat_nf": 2,
         "node_attr_nf": 0, "edge_attr_nf": 2, "normalize": True}


def _run_tfn(trace=0, seed=7):
    return run.run(["--workload", TFN_CELL, "--seed", str(seed), "--seconds", "0.5",
                    "--trace", str(trace)], benchmark_file=TFN_BENCH, platform="cpu")


# ---- FastEGNN through the seam: what it read before

@pytest.mark.parametrize("dims, seed, digest", [
    (TOY, 3, "37f2fe5ecba8780aa2bff8b128d17eb1360b7d245b0c13b2e2d6ffa11289ac82"),
    (TOY, 2 ** 31 + 11, "300ca3859522123e64d45b534104897cddf2ac4f8092db80f0dc847a565de5ad"),
    (NBODY, 3, "2e71af10e10f102f8b803eb9daec202a8cc132542caafaf5c2b6c168f9956271"),
    (dict(NBODY, model_name="FastEGNN"), 2 ** 31 + 11,
     "219a1afc02787989c4f92d891477f89728fd2ff70367169171351f7d49e8945a"),
])
def test_fastegnn_weights_are_the_parents_bytes(dims, seed, digest):
    """sha256 over names and bytes in layout order, frozen from the commit
    before the seam."""
    h = hashlib.sha256()
    for k, v in weights.make_weights(seed, dims).items():
        h.update(k.encode())
        h.update(np.asarray(v).tobytes())
    assert h.hexdigest() == digest


# step_flops, agg_bytes of each configuration at a size of its cell's, frozen
# from the commit before the seam
CELL_COUNTS = {
    "largefluid_distegnn": ((1, 113140, 1639040), 525175546368.0, 7996252800.0),
    "nbody_fastegnn": ((250, 100, 9900), 544634112000.0, 16555200000.0),
    "largefluid800k_distegnn": ((1, 800000, 2917029), 1955095443456.0, 16855524704.0),
    "water3d_fastegnn": ((15, 7806, 105643), 520491778560.0, 11249811360.0),
}


@pytest.mark.parametrize("config", list(CELL_COUNTS))
def test_fastegnn_counts_of_every_cell_are_the_parents(config):
    (G, N, E), flops, agg = CELL_COUNTS[config]
    path = os.path.join(ROOT, "benchmarks", "configs", config + ".yaml")
    with contextlib.redirect_stdout(sys.stderr):
        cfg = common.load_program_config(path, common.load_meta(path), 1)
    dims = common.model_dims(cfg)
    assert dims["model_name"] == "FastEGNN"
    s = {"graphs": G, "nodes": N, "edges": E,
         "dtype_bytes": 2 if cfg.model.get("compute_dtype") == "bf16" else 4,
         **{k: v for k, v in dims.items() if k != "normalize"}}
    assert counts.step_flops(s) == flops and counts.agg_bytes(s) == agg


def test_unknown_model_name_ends_the_run_before_setup(tmp_path, monkeypatch):
    from benchmarks.drivers import train_scan

    for sub in ("configs", "traffic"):
        os.makedirs(tmp_path / sub)
    with open(toy_config("toy_nbody")) as f:
        text = f.read()
    (tmp_path / "configs" / "toy_other.yaml").write_text(
        text.replace("model_name: FastEGNN", "model_name: SE3Transformer"))
    shutil.copy(toy_config("toy_nbody").replace(".yaml", ".meta.json"),
                tmp_path / "configs" / "toy_other.meta.json")
    shutil.copy(os.path.join(DATA, "traffic", "toy_nbody_mix.json"), tmp_path / "traffic")
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][1], name="toy_other", file="configs/toy_other.yaml")]
    bench["workloads"] = [dict(bench["workloads"][1], name="toy_other_train", config="toy_other")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(train_scan.Driver, "setup", lambda self, w: pytest.fail("set-up ran"))
    monkeypatch.setattr(family.Family, "make_weights", lambda *a: pytest.fail("weights were made"))
    with pytest.raises(SystemExit, match="'SE3Transformer' has no family.*FastEGNN, FastTFN"):
        run.run(["--workload", "toy_other_train", "--seed", "1", "--seconds", "0.1"],
                benchmark_file=str(tmp_path / "BENCHMARK.json"), platform="cpu")


def test_dims_without_a_name_have_no_family_but_in_the_thin_wrappers():
    """A driver's ``family.of(dims)`` refuses sizes that name no family; the
    wrappers for callers that hold sizes alone read them as FastEGNN's."""
    with pytest.raises(SystemExit, match="None has no family"):
        family.of(TOY)
    assert [e[0] for e in weights.layout(TOY)] == [e[0] for e in weights.fastegnn_layout(TOY)]
    s = {"graphs": 2, "nodes": 5, "edges": 12, "dtype_bytes": 4, **TOY}
    assert counts.step_flops(s) == 3 * counts.fastegnn_forward_matmul_flops(s)
    assert counts.agg_bytes(s) == counts.fastegnn_agg_bytes(s)


# ---- FastTFN's weights against the program's tree

def test_fasttfn_names_map_onto_the_programs_tree_one_to_one():
    """Every leaf of ``FastTFN.init``'s tree is named once by the layout, with
    its shape, and the weights go there and back unchanged."""
    from distegnn_tpu.data import GraphDataset, GraphLoader
    from distegnn_tpu.data.nbody import build_nbody_graph
    from distegnn_tpu.models.registry import get_model

    with contextlib.redirect_stdout(sys.stderr):
        cfg = common.load_program_config(toy_config("toy_nbody_tfn"), {}, 1)
        dims = common.model_dims(cfg)
        rng = np.random.default_rng(0)
        graphs = [build_nbody_graph(rng.normal(size=(6, 3)), rng.normal(size=(6, 3)),
                                    np.sign(rng.normal(size=(6, 1))), rng.normal(size=(6, 3)))
                  for _ in range(2)]
        batch = next(iter(GraphLoader(GraphDataset(graphs, node_order="none"), 2, shuffle=False,
                                      seed=0)))
    params = get_model(cfg.model, world_size=1, dataset_name="nbody_100").init(
        jax.random.PRNGKey(0), batch)
    program = {tuple(k.key for k in path): leaf.shape
               for path, leaf in jax.tree_util.tree_leaves_with_path(params["params"])}
    names = {name: shape for name, shape, _, _ in weights.layout(dims)}
    paths = [common.fasttfn_tree_path(n) for n in names]
    assert len(set(paths)) == len(paths) and set(paths) == set(program)
    for name, shape in names.items():
        want = (1,) + shape if name == "virtual_feat" else shape
        assert program[common.fasttfn_tree_path(name)] == want, name
    fam = family.of(dims)
    w = fam.make_weights(4, dims)
    back = fam.to_plain(fam.to_tree(w), list(w))
    assert all(np.array_equal(back[k], np.asarray(w[k])) for k in w)


# ---- FastTFN's counts by hand (3 nodes, 6 edges, as test_counts.py's graph)

S_TFN = {"graphs": 1, "nodes": 3, "edges": 6, "hidden_nf": 4, "n_layers": 1,
         "virtual_channels": 2, "node_feat_nf": 2, "node_attr_nf": 1,
         "edge_attr_nf": 2, "dtype_bytes": 2, "model_name": "FastTFN"}


def test_fasttfn_forward_flops_by_hand():
    N, E, H, C, F, A, D, M = 3, 6, 4, 2, 2, 1, 2, 32
    embed = 2 * N * F * H                                     # 48
    phi_e = 2 * N * H * H * 2 + 2 * E * (1 + D) * H + 2 * E * H * H   # 528, as FastEGNN's
    phi_ev = 96 + 64 + 144 + 192
    heads = 2 * (192 + 48)                # phi_xv and phi_X; no phi_x, no phi_v
    phi_h = 2 * N * (3 * H + A) * H + 96  # 408
    phi_hv = 128 + 64
    radial = 2 * E * (M + M * M + M) + 2 * E * (M + M * M + 3 * M)    # 13,056 + 13,824
    self_int = 2 * N * 3
    by_hand = embed + phi_e + phi_ev + heads + phi_h + phi_hv + radial + self_int
    assert radial == 26880 and by_hand == 29050
    assert counts.forward_matmul_flops(S_TFN) == by_hand
    assert counts.step_flops(S_TFN) == 3 * by_hand


def test_fasttfn_agg_bytes_by_hand():
    m = lambda w: 6 * w + 6 * 4 + 3 * w   # one move of width w bytes
    # x at both ends, two bf16 products, the TFN's and phi_e's sums, each
    # with its transpose; the sources (charge, velocity) once
    layer = 2 * m(12) + 2 * m(4 * 2) + m(12) + m(4 * 4)
    assert layer == 756 and m(16) == 168
    assert counts.agg_bytes(S_TFN) == 2 * layer + m(16) == 1680
    assert counts.agg_bytes(dict(S_TFN, graphs=5, n_layers=3)) == 15 * 1680


# ---- a FastTFN cell of new files, through the harness

def test_fasttfn_cell_runs_to_a_result_line():
    r = _run_tfn(trace=1)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["compared"]) == {"loss_gap", "moment_gap", "change_gap"}
    assert all(math.isfinite(c["value"]) for c in r["compared"].values())
    assert r["metrics"]["step_mfu"]["value"] > 0


def test_the_reference_agrees_with_the_programs_fasttfn():
    """The reference and the program's FastTFN agree to rounding on every
    compared number of the toy cell: charges, velocities, kernels and their
    signs, radial nets and their layer norms (whose eps alone differs: torch's
    1e-5 against flax's 1e-6), self-interaction, virtual nodes and the
    weights' mapping are the program's."""
    r = _run_tfn(seed=11)
    assert r["correct"] is True, r["compared"]
    assert all(c["value"] < c["limit"] / 10 for c in r["compared"].values()), r["compared"]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def families_made(monkeypatch):
    """The family of every set of weights made."""
    made, real = [], family.Family.make_weights

    def spy(self, seed, dims):
        made.append(self.name)
        return real(self, seed, dims)

    monkeypatch.setattr(family.Family, "make_weights", spy)
    return made


def test_read_limits_takes_the_family_from_the_configuration(monkeypatch, capsys, tmp_path,
                                                            families_made):
    mod = _load_tool("read_limits")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr("sys.argv", ["read_limits.py", "--workload", TFN_CELL, "--seeds", "5",
                                     "--half", "1", "--mantissa", "1", "--platform", "cpu",
                                     "--benchmark-file", TFN_BENCH])
    assert mod.main() == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    by_variant = {l["variant"]: l for l in lines if "numbers" in l}
    assert set(by_variant) == {"sound", "fault_half_rows", "control_mantissa3",
                               "fault_params_unchanged"}
    assert families_made == ["FastTFN"]
    for line in by_variant.values():
        assert all(math.isfinite(v[0]) for v in line["numbers"].values()), line


def test_scope_times_takes_the_family_from_the_configuration(tmp_path, families_made):
    """The tool's set-up and traced window on the toy FastTFN cell (a CPU
    trace has no device plane, which the tool says)."""
    scope_times = _load_tool("scope_times")
    with pytest.raises(RuntimeError, match="no device operation"):
        scope_times.trace_cell(TFN_CELL, 5, 0.2, str(tmp_path), benchmark_file=TFN_BENCH,
                               platform="cpu")
    assert families_made == ["FastTFN"]
