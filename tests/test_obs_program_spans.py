"""The program's own instrumentation (docs/OBSERVABILITY.md "Program spans"
and "Device scopes"): the step loop's and the producer's spans in the ring
and, under a profiler trace recorded here on the CPU, in the xplane's host
plane beside a harness-style annotation; the edge-op scopes in the lowered
step of every aggregation lowering, with losses bit for bit those of the
same step without scopes."""

from __future__ import annotations

import contextlib
import glob
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distegnn_tpu import obs
from distegnn_tpu.data.stream import PrefetchLoader
from distegnn_tpu.train.trainer import run_epoch_train


class _Loader:
    """Three batches of two graphs; the protocol run_epoch_train needs."""

    def __init__(self):
        self.batches = [types.SimpleNamespace(loc=jnp.full((2, 5, 3), float(i)))
                        for i in range(3)]

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        return iter(self.batches)


_sum = jax.jit(lambda state, loc: (state + 1, jnp.sum(loc)))


def _train_step(state, batch, key):
    state, loss = _sum(state, batch.loc)
    return state, {"loss": loss}


def _host_events(trace_dir, names):
    """{name: [(start_ns, end_ns)]} of the host planes' events called one of
    ``names``, as ``benchmarks/tracing.py`` reads them."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    found = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in found:
                        found[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return found


def test_step_loop_spans_in_the_ring_and_in_a_recorded_xplane(tmp_path):
    run_epoch_train(_train_step, jnp.zeros(()), _Loader(), 0, 1)       # compiles outside the trace
    obs.clear_spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench/epoch"):              # the harness's wrapper
            state, avg = run_epoch_train(_train_step, jnp.zeros(()), _Loader(), 0, 2)
    finally:
        jax.profiler.stop_trace()
    assert float(state) == 3.0 and avg == pytest.approx((0 + 30 + 60) * 2 / 6)

    spans = obs.recent_spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert {n: len(v) for n, v in by_name.items()} == {
        "train/epoch": 1, "data/next": 4, "train/step": 3, "train/dispatch": 3,
        "train/epoch_sync": 1}
    (epoch,) = by_name["train/epoch"]
    assert epoch.attrs == {"epoch": 2} and epoch.parent == 0
    steps = {s.id: s for s in by_name["train/step"]}
    assert all(s.attrs == {} for s in steps.values())  # no sink: no per-step fields
    assert sorted(d.parent for d in by_name["train/dispatch"]) == sorted(steps)
    for d in by_name["train/dispatch"]:
        outer = steps[d.parent]                       # child of its train/step
        assert outer.start_ns <= d.start_ns <= d.end_ns <= outer.end_ns
    for s in by_name["data/next"] + by_name["train/step"] + by_name["train/epoch_sync"]:
        assert s.parent == epoch.id

    ev = _host_events(str(tmp_path), ["bench/epoch", "train/epoch", "train/step",
                                      "train/dispatch", "data/next"])
    assert len(ev["train/step"]) == 3 and len(ev["data/next"]) == 4
    assert len(ev["train/dispatch"]) == 3
    (bench,), (ep,) = ev["bench/epoch"], ev["train/epoch"]
    assert bench[0] <= ep[0] <= ep[1] <= bench[1]     # one clock: the profiler's
    for s, e in ev["train/step"] + ev["train/dispatch"] + ev["data/next"]:
        assert ep[0] <= s <= e <= ep[1]


def test_one_train_step_record_per_step_in_the_jsonl(tmp_path):
    """With a sink the step loop writes ONE record per micro-step under the
    name train/step: the span, carrying what the event of that name used to
    (epoch, step, stall_s) and the dispatch call's own time."""
    import json

    tracer = obs.configure(log_dir=str(tmp_path))
    try:
        run_epoch_train(_train_step, jnp.zeros(()), _Loader(), 0, 4, tracer=tracer,
                        step_events=True)
        tracer.flush()
    finally:
        obs.configure(log_dir=None)
    with open(tmp_path / "events.jsonl") as f:
        events = [json.loads(l) for l in f]
    steps = [e for e in events if e["name"] == "train/step"]
    assert [(e["kind"], e["epoch"], e["step"]) for e in steps] == [("span", 4, i) for i in range(3)]
    for e in steps:
        assert 0.0 <= e["dispatch_s"] <= e["dur_s"] and e["stall_s"] >= 0.0
    (epoch,) = [e for e in events if e["name"] == "train/epoch"]
    assert epoch["kind"] == "span" and epoch["epoch"] == 4


def test_producer_spans_lie_on_their_own_thread_before_the_wait_they_end():
    obs.clear_spans()
    loader = PrefetchLoader(_Loader(), put=lambda b: b, depth=2)
    run_epoch_train(_train_step, jnp.zeros(()), loader, 0, 5)
    spans = obs.recent_spans()
    produce = [s for s in spans if s.name == "data/produce"]
    assert len(produce) == 4                           # the last finds the end
    assert {s.thread for s in produce} == {"distegnn-prefetch"}
    assert all(p.parent == 0 for p in produce)         # nothing open around them on that thread
    waits = [s for s in spans if s.name == "data/next"]
    assert len(waits) == 4 and all(w.thread == "MainThread" for w in waits)
    ids = {s.id: s for s in produce}
    kids = [s for s in spans if s.name in ("data/collate", "data/put")]
    assert [s.name for s in kids].count("data/collate") == 4
    assert [s.name for s in kids].count("data/put") == 3
    for k in kids:
        assert k.parent in ids and k.thread == "distegnn-prefetch"
    for p, w in zip(produce[:3], waits):               # batch i is built before wait i is over
        assert p.end_ns <= w.end_ns


def test_synchronous_loader_has_the_same_spans_on_the_step_loops_thread():
    obs.clear_spans()
    stall = obs.get_registry().counter("data/stall_s")
    before = stall.value
    run_epoch_train(_train_step, jnp.zeros(()), PrefetchLoader(_Loader(), put=lambda b: b, depth=0),
                    0, 1)
    spans = obs.recent_spans()
    produce = [s for s in spans if s.name == "data/produce"]
    waits = [s.id for s in spans if s.name == "data/next"]
    assert [p.parent for p in produce] == waits and len(waits) == 4   # inside the wait itself
    assert all(p.thread == "MainThread" for p in produce)
    puts = [s for s in spans if s.name == "data/put"]
    assert stall.value - before == pytest.approx(sum(s.end_ns - s.start_ns for s in puts) / 1e9)


# ---------------------------------------------------------------- scopes

def _batch_and_step(seg):
    from distegnn_tpu.data import build_nbody_graph
    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.ops.graph import pad_graphs
    from distegnn_tpu.train import TrainState, make_optimizer, make_train_step

    rng = np.random.default_rng(3)

    def graph(n):
        loc, vel = rng.normal(size=(n, 3)), rng.normal(size=(n, 3))
        return build_nbody_graph(loc, vel, rng.choice([1.0, -1.0], size=(n, 1)),
                                 loc + 0.1 * vel, radius=-1.0)

    batch = pad_graphs([graph(12), graph(9)], compute_pair=True, max_in_degree=16)
    model = FastEGNN(node_feat_nf=2, node_attr_nf=1, edge_attr_nf=2, hidden_nf=8,
                     virtual_channels=2, n_layers=2, segment_impl=seg, remat=True)
    tx = make_optimizer(1e-2, clip_norm=0.3)
    state = TrainState.create(model.init(jax.random.PRNGKey(0), batch), tx)
    step = jax.jit(make_train_step(model, tx, mmd_weight=0.01, mmd_sigma=1.5, mmd_samples=3))
    return batch, state, step


def _losses(batch, state, step):
    out = []
    for i in range(2):
        state, m = step(state, batch, jax.random.PRNGKey(i))
        out.append((np.asarray(m["loss"]), np.asarray(m["loss_with_mmd"])))
    return out, jax.tree.leaves(state.params)


def _strip_scopes(monkeypatch):
    """The same program with no scope of ours (nor of flax's) in it."""
    from distegnn_tpu.ops.blocked import EdgeOps
    from distegnn_tpu.train import loss as loss_mod
    from distegnn_tpu.train import step as step_mod

    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    for name in ("gather_rows", "gather_cols", "gather_sum_diff", "_agg", "agg_rows_pair"):
        monkeypatch.setattr(EdgeOps, name, getattr(EdgeOps, name).__wrapped__)
    for name in ("masked_mse", "mmd_loss"):
        raw = getattr(loss_mod, name).__wrapped__
        monkeypatch.setattr(loss_mod, name, raw)
        monkeypatch.setattr(step_mod, name, raw)


@pytest.mark.parametrize("seg", ["scatter", "cumsum", "ell"])
def test_edge_scopes_in_every_lowering_and_losses_bit_for_bit(seg, monkeypatch):
    batch, state, step = _batch_and_step(seg)
    text = step.lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("edge_gather", "edge_aggregate", "edge_mlp", "coord_update", "node_update",
                  "virtual_update", "embed", "loss_mse", "loss_mmd", "optimizer"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    # forward, transposed and recomputed ops all carry the two edge scopes
    for scope in ("edge_gather", "edge_aggregate"):
        assert any(f"/{scope}/" in l and "transpose(" in l for l in text.splitlines())
        assert any(f"/{scope}/" in l and "rematted_computation" in l for l in text.splitlines())
    scoped, scoped_params = _losses(batch, state, step)

    _strip_scopes(monkeypatch)
    batch, state, step = _batch_and_step(seg)
    bare = step.lower(state, batch, jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "edge_gather" not in bare and "edge_aggregate" not in bare and "loss_mse" not in bare
    unscoped, unscoped_params = _losses(batch, state, step)
    for (a, b), (c, d) in zip(scoped, unscoped):
        assert a.tobytes() == c.tobytes() and b.tobytes() == d.tobytes()
    for p, q in zip(scoped_params, unscoped_params):
        assert np.asarray(p).tobytes() == np.asarray(q).tobytes()
