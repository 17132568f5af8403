"""The three metrics that read the program's span ring, on a hand-made ring
and on the toy cells; and ``tools/scope_times.py``'s join from trace op to
scope, on HLO text compiled here and on a CPU-recorded trace."""

import collections
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run, span_window, tracing
from benchmarks.tests.conftest import DATA
from benchmarks.tools import scope_times

SPAN_BENCH = os.path.join(DATA, "BENCHMARK_spans.json")
S = 1_000_000_000
Span = collections.namedtuple("Span", "name thread start_ns end_ns cpu_ns id parent attrs")


def _span(name, start_s, end_s, id=0, parent=0, thread="MainThread", cpu_s=0.0, **attrs):
    return Span(name, thread, int(start_s * S), int(end_s * S), int(cpu_s * S), id, parent, attrs)


@pytest.fixture
def _read(monkeypatch):
    """``metrics/<name>.py`` on a ring of ``spans``."""
    import distegnn_tpu.obs as obs

    def read(name, spans, wall_s):
        monkeypatch.setattr(obs, "recent_spans", lambda: spans)
        return run.read_metric(name, {"window": {"wall_s": wall_s}})

    return read


def _ring():
    """A 10 s window that ends with the last train/epoch span, at t = 20."""
    return [
        _span("jax/compile", 1.0, 2.0, id=1, parent=50, fun_name="jit(_step_one)"),
        _span("jax/compile", 2.0, 2.5, id=2, parent=51, fun_name="jit(_step_one)"),
        _span("jax/compile", 3.0, 3.1, id=3, fun_name="jit(add)"),          # no dispatch around it
        _span("jax/compile", 3.2, 3.3, id=4, fun_name="jit(add)"),
        _span("jax/compile", 3.4, 3.5, id=5, fun_name="jit(add)"),
        _span("jax/compile", 12.0, 12.5, id=6, parent=52, fun_name="jit(_step_one)"),  # in the window
        _span("train/dispatch", 0.9, 2.1, id=50), _span("train/dispatch", 1.9, 2.6, id=51),
        _span("train/dispatch", 11.9, 12.6, id=52),
        _span("train/step", 8.0, 9.0, cpu_s=0.4),      # ended before the window: none of it
        _span("train/step", 9.5, 10.5, cpu_s=0.2),     # ended inside the window: all of it
        _span("train/step", 12.0, 12.25, cpu_s=0.25),
        _span("train/step", 19.0, 19.75, cpu_s=0.05),  # blocked on the runtime: long, cheap
        _span("train/epoch", 5.0, 9.9),
        _span("train/epoch", 9.9, 20.0),
        # two producer threads overlap at a pass boundary: union 11..14, not 4 s
        _span("data/produce", 11.0, 13.0, thread="distegnn-prefetch"),
        _span("data/produce", 12.0, 14.0, thread="distegnn-prefetch"),
        _span("data/produce", 8.0, 10.5, thread="distegnn-prefetch"),     # clipped to 0.5 s
    ]


def test_window_ends_with_the_last_epoch_span_and_clips():
    spans, lo, hi = span_window.bounds(_ring(), 10.0)
    assert (lo, hi) == (10 * S, 20 * S)
    assert span_window.clipped(spans, "train/step", lo, hi) == [
        (10 * S, int(10.5 * S)), (12 * S, int(12.25 * S)), (19 * S, int(19.75 * S))]


def test_host_busy_share_sums_the_steps_cpu_time(_read):
    # a span that straddles the window's start counts whole: where inside a
    # mostly blocked span its CPU time fell is not known
    assert _read("host_busy_share", _ring(), 10.0) == pytest.approx(100.0 * (0.2 + 0.25 + 0.05) / 10.0)


def test_loader_produce_share_is_a_union(_read):
    assert _read("loader_produce_share", _ring(), 10.0) == pytest.approx(100.0 * 3.5 / 10.0)
    no_loader = [s for s in _ring() if s.name != "data/produce"]
    assert _read("loader_produce_share", no_loader, 10.0) is None


def test_max_compiles_counts_dispatched_programs_before_the_window(_read):
    # jit(add) compiled three times, but not under a dispatch; the third
    # jit(_step_one) lies inside the window
    assert _read("max_compiles_per_program", _ring(), 10.0) == 2
    assert _read("max_compiles_per_program", _ring(), 17.9) == 1    # window from 2.1: one ended before


def test_max_compiles_reports_nothing_from_a_full_ring(_read, monkeypatch):
    """A full ring has dropped its oldest spans, set-up's compiles perhaps
    among them: no count rather than one too low."""
    import distegnn_tpu.obs as obs

    monkeypatch.setattr(obs, "RING_SIZE", len(_ring()))
    assert _read("max_compiles_per_program", _ring(), 10.0) is None
    assert _read("host_busy_share", _ring(), 10.0) is not None      # reads the window only
    # nothing left from before the window: the window's own spans may be cut
    late = [s for s in _ring() if s.start_ns > 10 * S] + [_span("train/epoch", 10.5, 20.0)]
    assert _read("host_busy_share", late, 10.0) is not None
    monkeypatch.setattr(obs, "RING_SIZE", len(late))
    assert _read("host_busy_share", late, 10.0) is None


@pytest.mark.parametrize("name", ["host_busy_share", "loader_produce_share",
                                  "max_compiles_per_program"])
def test_readers_return_none_without_spans(name, _read):
    assert _read(name, [], 10.0) is None
    assert _read(name, [_span("jax/compile", 1.0, 2.0, fun_name="f")], 10.0) is None


@pytest.mark.parametrize("name", ["host_busy_share", "loader_produce_share",
                                  "max_compiles_per_program"])
def test_readers_return_none_on_a_program_without_the_ring(name, monkeypatch):
    import distegnn_tpu.obs as obs

    monkeypatch.delattr(obs, "recent_spans")
    assert run.read_metric(name, {"window": {"wall_s": 1.0}}) is None


@pytest.mark.parametrize("cell,compiles,loader", [("toy_fluid_train", 2, True),
                                                   ("toy_nbody_train", 1, False)])
def test_toy_cells_report_the_span_metrics(cell, compiles, loader):
    from distegnn_tpu import obs

    obs.clear_spans()
    r = run.run(["--workload", cell, "--seed", "11", "--seconds", "0.5", "--trace", "1"],
                benchmark_file=SPAN_BENCH, platform="cpu")
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0.0 < m["host_busy_share"] <= 100.0
    # the shard-mapped step compiles twice (host-array state, then the mesh's
    # sharding); the scanned epoch once
    assert m["max_compiles_per_program"] == compiles
    assert ("loader_produce_share" in m) == loader
    if loader:
        assert 0.0 < m["loader_produce_share"] <= 100.0
    # every accepted metric of a traced toy line is still there
    assert {"compile_s", "data_prep_s", "step_mfu"} <= set(m)


# ------------------------------------------------------------ scope_times

def test_scope_and_phase_of_an_op_name():
    f = scope_times.scope_of
    assert f("jit(step)/jvp(FastEGNN)/gcl_0/edge_mlp/phi_e/edge_gather/gather") == "edge_gather"
    assert f("jit(step)/transpose(jvp(loss_mse))/mul") == "loss_mse"
    assert f("jit(step)/jvp(FastEGNN)/gcl_0/phi_h/dot_general") == "unscoped"
    assert f("") == "unscoped"
    p = scope_times.phase_of
    assert p("jit(step)/jvp(FastEGNN)/gcl_0/edge_aggregate/add") == "forward"
    assert p("jit(step)/transpose(jvp(FastEGNN))/gcl_0/edge_aggregate/gather") == "backward"
    assert p("jit(s)/transpose(jvp(F))/jvp(F)/checkpoint/rematted_computation/gcl_0/edge_mlp/dot") == "remat"


_HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8], param_1: s32[4]) -> f32[4] {
  %param_0 = f32[8]{0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  %gather.1 = f32[4]{0} gather(%param_0, %param_1), metadata={op_name="jit(step)/jvp(M)/gcl_0/edge_mlp/phi_e/edge_gather/gather"}
  ROOT %multiply.2 = f32[4]{0:T(128)} multiply(%gather.1, %gather.1), metadata={op_name="jit(step)/jvp(M)/gcl_0/edge_mlp/phi_e/mul"}
}

%fused_computation.2 (param_0.1: f32[4]) -> (f32[4], f32[4]) {
  %param_0.1 = f32[4]{0} parameter(0)
  %add.3 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(step)/transpose(jvp(M))/gcl_0/coord_update/add"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%add.3, %add.3)
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/while/body/optimizer/add"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%p, %fusion.9)
}

%fused_computation.3 (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %neg = f32[4]{0} negate(%q)
}

%fused_computation.5 (u: f32[4], j: s32[4]) -> f32[8] {
  %u = f32[4]{0} parameter(0)
  %j = s32[4]{0} parameter(1)
  %zeros = f32[8]{0} constant({...})
  ROOT %scatter.2 = f32[8]{0} scatter(%zeros, %j, %u), to_apply=%add
}

%fused_computation.4 (u.1: f32[4], j.1: s32[4]) -> f32[8] {
  %u.1 = f32[4]{0} parameter(0)
  %j.1 = s32[4]{0} parameter(1)
  %add.7 = f32[4]{0} add(%u.1, %u.1), metadata={op_name="jit(step)/transpose(jvp(M))/gcl_0/edge_mlp/phi_e/add_any"}
  ROOT %fusion.5 = f32[8]{0} fusion(%add.7, %j.1), kind=kCustom, calls=%fused_computation.5
}

ENTRY %main.7 (x: f32[8], i: s32[4]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %fusion.1 = f32[4]{0:T(128)S(1)} fusion(%x, %i), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(M)/gcl_0/edge_mlp/phi_e/mul"}
  %fusion.2 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.5 = f32[4]{0} copy(%fusion.1)
  %fusion.4 = f32[8]{0} fusion(%copy.5, %i), kind=kCustom, calls=%fused_computation.4
  %while.1 = (s32[], f32[4]{0}) while(%fusion.2), condition=%cond, body=%body
  ROOT %r = f32[8]{0} bitcast(%x)
}
"""


def test_parse_hlo_and_fusion_scope():
    hlo = scope_times.parse_hlo(_HLO)
    assert hlo["computations"]["main.7"] == ["x", "i", "fusion.1", "fusion.2", "copy.5",
                                             "fusion.4", "while.1", "r"]
    assert hlo["instructions"]["fusion.1"]["calls"] == "fused_computation.1"
    assert hlo["instructions"]["multiply.2"]["root"]
    # the gather decides, not the root (edge_mlp); two scopes inside: mixed
    assert scope_times.fusion_scope("fusion.1", hlo) == ("edge_gather", "forward", True, False)
    # a tuple root with no name: the scope most instructions name
    assert scope_times.fusion_scope("fusion.2", hlo) == ("coord_update", "backward", False, False)
    # nothing inside names a scope: the fusion's own op_name
    assert scope_times.fusion_scope("fusion.9", hlo) == ("optimizer", "forward", False, False)
    assert scope_times.fusion_scope("copy.5", hlo)[0] == "unscoped"
    # a scatter the compiler rebuilt without a name, nested one fusion down,
    # beside another scope's op: a row of its own, not that scope's
    assert scope_times.fusion_scope("fusion.4", hlo) == ("scatter_unnamed", "backward", False, True)


def test_scope_table_books_self_time_inside_the_program_only():
    hlo = scope_times.parse_hlo(_HLO)
    ops = [("%fusion.1 = f32[4] fusion(f32[8] %x)", 0, 40),
           ("%while.1 = (s32[], f32[4]) while(...)", 40, 100),   # control: own time dropped
           ("%fusion.9 = f32[4] fusion(...)", 50, 80),           # inside the while
           ("%copy.5 = f32[4] copy(...)", 100, 110),
           ("%fusion.1 = f32[2] fusion(...)", 200, 230)]         # another program's fusion.1
    t = scope_times.scope_table(ops, [(0, 120)], hlo)
    assert t["scopes"] == {"edge_gather": {"forward": 40}, "optimizer": {"forward": 30},
                           "unscoped": {"forward": 10}}
    assert t["mixed_ns"] == 40 and t["other_programs_ns"] == 30
    assert t["unscoped_top"] == [("copy.5", 10)]


@pytest.mark.parametrize("seg", ["scatter", "cumsum", "ell"])
def test_compiled_step_names_both_edge_scopes(seg):
    """HLO text only (compiled here, for the CPU): whichever lowering, the
    entry computation has instructions booked to ``edge_gather`` and to
    ``edge_aggregate``, forward and backward."""
    import numpy as np
    from distegnn_tpu.data import build_nbody_graph
    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.train import TrainState, make_optimizer, make_train_step

    rng = np.random.default_rng(0)
    loc, vel = rng.normal(size=(12, 3)), rng.normal(size=(12, 3))
    g = build_nbody_graph(loc, vel, rng.choice([1.0, -1.0], size=(12, 1)), loc + vel, radius=-1.0)
    batch = pad_graphs([g], compute_pair=True, max_in_degree=16)
    model = FastEGNN(node_feat_nf=2, node_attr_nf=1, edge_attr_nf=2, hidden_nf=8,
                     virtual_channels=2, n_layers=1, segment_impl=seg)
    tx = make_optimizer(1e-3)
    state = TrainState.create(model.init(jax.random.PRNGKey(0), batch), tx)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.0, mmd_sigma=1.0, mmd_samples=1))
    hlo = scope_times.parse_hlo(step.lower(state, batch, jax.random.PRNGKey(1)).compile().as_text())
    entry = next(c for c in hlo["computations"] if c.startswith("main"))
    found = {scope_times.fusion_scope(n, hlo)[:2] for n in hlo["computations"][entry]}
    for scope in ("edge_gather", "edge_aggregate"):
        assert {(scope, "forward"), (scope, "backward")} <= found, sorted(found)


def test_program_spans_and_gap_attribution_from_a_recorded_trace(tmp_path):
    """A CPU-recorded trace: the program's spans are read from the host plane
    beside a harness span, and an idle gap goes to the shortest program span
    that covers it."""
    from distegnn_tpu import obs

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/epoch"):
        with obs.span("train/epoch"):
            with obs.span("data/next"):
                jnp.ones((32, 32)).sum().block_until_ready()
            with obs.span("train/step"):
                jnp.ones((16, 16)).sum().block_until_ready()
    jax.profiler.stop_trace()
    devices, spans = scope_times.read_kept_trace(
        str(tmp_path), {"train/epoch", "data/next", "train/step"})
    assert devices == {}                                   # no TPU plane on the CPU
    by = {n: (s, e) for n, s, e in spans}
    assert set(by) == {"train/epoch", "data/next", "train/step"}
    assert tracing.SPAN_PREFIX == "bench/"                 # restored
    assert by["train/epoch"][0] <= by["data/next"][0] <= by["data/next"][1] <= by["train/step"][0]
    mid = sum(by["data/next"]) // 2
    gaps = tracing.attribute([(mid - 10, mid + 10)], spans)
    assert gaps == {"data/next": pytest.approx(2e-8)}


def test_report_on_hand_made_device_events():
    """Device events of the shape ``read_planes`` returns: shares of busy
    time, per micro-step times, gap attribution."""
    us = 1_000
    ops = [("%fusion.1 = f32[4] fusion(f32[8] %x)", 0, 400 * us),
           ("%copy.5 = f32[4] copy(%fusion.1)", 400 * us, 500 * us),
           ("%while.1 = (s32[], f32[4]) while(%fusion.2)", 500 * us, 800 * us),
           ("%fusion.9 = f32[4] fusion(%p)", 500 * us, 800 * us),
           # 200 us idle, then another program's op
           ("%fusion.1 = f32[2] fusion(f32[2] %y)", 1000 * us, 1200 * us)]
    dev = {"ops": ops, "modules": [("jit_step(77)", 0, 800 * us), ("jit_add(3)", 1000 * us, 1200 * us)]}
    spans = [("train/epoch", 0, 1200 * us), ("data/next", 790 * us, 1010 * us)]
    assert scope_times.main_program(dev["modules"]) == "jit_step"
    r = scope_times.report({"/device:TPU:0": dev}, spans, "jit_step", _HLO, 2)
    assert r["busy_s"] == pytest.approx(1000e-6)
    assert r["scopes"]["edge_gather"] == {"ms_per_step": pytest.approx(0.2), "share_of_busy": pytest.approx(40.0),
                                          "forward": pytest.approx(0.2), "backward": 0.0, "remat": 0.0}
    assert r["edge_ops_share"] == pytest.approx(40.0) and r["mixed_share"] == pytest.approx(40.0)
    assert r["unscoped_share"] == pytest.approx(10.0)
    assert r["other_programs_share"] == pytest.approx(20.0)
    assert r["idle_gaps_by_program_span"] == {"data/next": pytest.approx(200e-6)}
    assert "edge_gather" in scope_times.render(r)


def test_trace_cell_keeps_the_trace_and_needs_device_ops(tmp_path):
    """The tool's set-up and traced window on a toy cell here: the trace is
    kept; with no device plane in it (a CPU) the tool says so."""
    with pytest.raises(RuntimeError, match="no device operation"):
        scope_times.trace_cell("toy_nbody_train", 5, 0.2, str(tmp_path),
                               benchmark_file=SPAN_BENCH, platform="cpu")
    kept = os.path.join(scope_times.ROOT, "benchmarks", ".work", "trace_kept", "toy_nbody_train")
    assert tracing.find_xplane(kept).endswith(".xplane.pb")
