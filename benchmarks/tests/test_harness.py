"""The harness end to end on the CPU at toy sizes (its look for a chip
skipped): the result line's keys, and ``correct`` coming out false once for
each fault a training cell can have, planted under the timed path."""

import dataclasses
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
            if fault == "params_unchanged":
                # the optimizer's state advances, the weights are never written back
                new, metrics = inner(state, batch, key)
                return dataclasses.replace(new, params=state.params), metrics
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


# ---- moment_diff against a scale that does not vanish (PR 29)

LEAVES = 5


def _moment_numbers(grads_ref, grads_prog):
    """Made-up records of ``n`` updates over ``LEAVES`` leaves (gradients
    [n, LEAVES, dim]): Adam's first moment of each side, the reference's
    ``update_norms``. -> (the measure before PR 29, today's ``moment_diff``)."""
    import numpy as np

    from benchmarks import compare

    decay = 0.1 * 0.9 ** np.arange(len(grads_ref) - 1, -1, -1)
    mu = lambda gs: {f"leaf{k}": np.tensordot(decay, gs[:, k], 1) for k in range(LEAVES)}
    norms = {f"leaf{k}": np.linalg.norm(grads_ref[:, k], axis=-1) for k in range(LEAVES)}
    diffs = compare.moment_diffs(mu(grads_prog), mu(grads_ref), norms)
    return compare.whole_diff(mu(grads_prog), mu(grads_ref)), float(np.median(list(diffs.values())))


def _largefluid_moment_limit():
    import os

    from benchmarks.tests.conftest import ROOT

    with open(os.path.join(ROOT, "benchmarks", "limits", "largefluid_train_g1.json")) as f:
        return json.load(f)["limits"]["moment_diff"]


@pytest.mark.parametrize("cancel", [True, False])
def test_moment_diff_does_not_fail_a_sound_run_whose_gradients_cancel(cancel):
    """Reference gradients g, -g, g/10 leave a moment of 0.001 |g|. A program
    off by 1e-3 of |g| in each gradient is as sound as it is when they do not
    cancel; measured against |mu_r| it read over the limit."""
    import numpy as np

    rng = np.random.default_rng(0)
    g = rng.normal(size=(LEAVES, 256))
    signs = np.array([1.0, -1.0 if cancel else 1.0, 0.1])
    grads = signs[:, None, None] * g
    noise = rng.normal(size=grads.shape)
    noise *= 1e-3 * np.linalg.norm(g, axis=-1)[None, :, None] / np.linalg.norm(noise, axis=-1, keepdims=True)
    old, new = _moment_numbers(grads, grads + noise)
    limit = _largefluid_moment_limit()
    assert new < limit / 2
    assert (old > limit) if cancel else (old < limit / 2)


@pytest.mark.parametrize("cancel", [True, False])
def test_moment_diff_still_fails_half_of_the_rows_left_out(cancel):
    """Gradients that are means over rows whose halves differ; the fault
    takes the mean over the first half."""
    import numpy as np

    rng = np.random.default_rng(1)
    rows = rng.normal(size=(3, 64, LEAVES, 256)) + np.where(np.arange(64) < 32, 1.0, -0.5)[None, :, None, None]
    rows = rows * np.array([1.0, -1.0 if cancel else 1.0, 0.1])[:, None, None, None]
    _, new = _moment_numbers(rows.mean(axis=1), rows[:, :32].mean(axis=1))
    assert new > 2 * _largefluid_moment_limit()


def test_moment_diff_is_not_moved_by_a_few_leaves():
    """One leaf of five far off (as the coordinate heads swing) leaves the
    median where it was; three of five move it."""
    import numpy as np

    rng = np.random.default_rng(2)
    grads = rng.normal(size=(3, LEAVES, 256))
    off = lambda k: np.concatenate([np.full(k, 0.5), np.full(LEAVES - k, 1e-3)])[None, :, None]
    _, one = _moment_numbers(grads, grads * (1 + off(1)))
    _, three = _moment_numbers(grads, grads * (1 + off(3)))
    assert one < 0.005 < 0.1 < three


def test_read_limits_prints_what_moment_diff_is_made_of(monkeypatch, capsys, tmp_path):
    import importlib.util
    import os

    from benchmarks.tests.conftest import ROOT

    spec = importlib.util.spec_from_file_location(
        "read_limits", os.path.join(ROOT, "benchmarks", "tools", "read_limits.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # with the variable set the tool places no compile cache in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr("sys.argv", ["read_limits.py", "--workload", "toy_fluid_train", "--seeds", "5",
                                     "--half", "1", "--mantissa", "1", "--mantissa-bits", "2",
                                     "--platform", "cpu", "--benchmark-file", TOY_BENCH])
    assert mod.main() == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    by_variant = {l["variant"]: l for l in lines if "numbers" in l}
    assert set(by_variant) == {"sound", "fault_half_rows", "control_mantissa2", "fault_params_unchanged"}
    # every record is decided under the cell's own limits, as run.py decides a run
    assert by_variant["sound"]["correct"] is True and by_variant["sound"]["over"] == []
    for name in ("fault_half_rows", "control_mantissa2"):
        assert by_variant[name]["correct"] is False and by_variant[name]["over"], name
    stuck = by_variant.pop("fault_params_unchanged")      # costs no run, carries no ``moment``
    assert stuck["numbers"]["change_diff"][0] == 1.0 and set(stuck["over"]) <= {"change_diff"}
    for line in by_variant.values():
        m = line["moment"]
        assert {"diff", "ref", "bound", "update_norms", "unfloored"} <= set(m)
        assert len(m["update_norms"]) == 3 and m["bound"] >= m["ref"] * (1 - 1e-6)
        assert m["unfloored"] == pytest.approx(m["diff"] / m["ref"])
    assert by_variant["sound"]["numbers"]["moment_diff"][0] < 0.05 \
        < by_variant["fault_half_rows"]["numbers"]["moment_diff"][0]
