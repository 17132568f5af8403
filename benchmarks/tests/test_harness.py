"""The harness end to end on the CPU at toy sizes (its look for a chip
skipped): the result line's keys, and ``correct`` coming out false once for
each fault a training cell can have, planted under the timed path."""

import json

import jax.numpy as jnp
import pytest

from benchmarks import run
from benchmarks.tests.conftest import TOY_BENCH

CELLS = ["toy_nbody_train", "toy_fluid_train"]


def _run(cell, trace=0, seed=7):
    return run.run(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                    "--trace", str(trace)], benchmark_file=TOY_BENCH, platform="cpu")


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_keys(cell):
    r = _run(cell, seed=2 ** 31 + 11)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"train_nodes_per_s_per_chip", "setup_s"}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    for c in r["compared"].values():
        assert c["value"] <= c["limit"]
    json.dumps(r)


def test_traced_line_has_per_layer_metrics_and_breakdown():
    r = _run("toy_fluid_train", trace=1)
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"compile_s", "data_prep_s", "data_stall_share", "step_mfu"} <= set(r["metrics"])
    assert "setup_s" not in r["metrics"]


def test_needs_the_accelerator():
    with pytest.raises(SystemExit):
        run.run(["--workload", "toy_nbody_train", "--seed", "1", "--seconds", "0.1"],
                benchmark_file=TOY_BENCH)      # platform tpu, here is none


def _break_step(monkeypatch, fault):
    """Plant ``fault`` in ``distegnn_tpu.train.step.make_train_step`` as both
    drivers reach it."""
    from distegnn_tpu.train import step as step_mod

    real = step_mod.make_train_step

    def broken(model, tx, **kw):
        inner = real(model, tx, **kw)

        def step(state, batch, key):
            if fault == "state_unchanged":
                _, metrics = inner(state, batch, key)
                return state, metrics
            if fault == "half_rows":
                # the second half of the batch no longer counts (of several
                # graphs the later ones, of one graph its later nodes); the
                # mean is over the rest
                B, N = batch.node_mask.shape[-2:]
                keep = ((jnp.arange(B) < (B + 1) // 2)[:, None] if B > 1
                        else (jnp.arange(N) < N // 2)[None, :])
                batch = batch.replace(node_mask=batch.node_mask * keep)
                return inner(state, batch, key)
            raise ValueError(fault)

        return step

    import distegnn_tpu.parallel.launch as launch
    import distegnn_tpu.train as train_pkg

    for mod in (step_mod, train_pkg, launch):
        monkeypatch.setattr(mod, "make_train_step", broken)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_rows"])
def test_fault_under_the_timed_path_is_not_correct(cell, fault, monkeypatch):
    _break_step(monkeypatch, fault)
    r = _run(cell)
    assert r["correct"] is False, r["compared"]
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


def test_nonfinite_loss_counts_as_failed(monkeypatch):
    from distegnn_tpu.train import step as step_mod
    import distegnn_tpu.train as train_pkg

    real = step_mod.make_train_step

    def broken(model, tx, **kw):
        inner = real(model, tx, **kw)

        def step(state, batch, key):
            new, metrics = inner(state, batch, key)
            return new, dict(metrics, loss=metrics["loss"] * jnp.nan)

        return step

    for mod in (step_mod, train_pkg):
        monkeypatch.setattr(mod, "make_train_step", broken)
    r = _run("toy_nbody_train")
    assert r["failed"] == r["attempted"] and r["correct"] is False
