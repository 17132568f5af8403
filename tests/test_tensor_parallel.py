"""Tensor-parallel third mesh axis — the contracts the 3D mesh must keep:

- parity: a 2x2x2 (data x graph x tensor) mesh computes the SAME forward
  loss / gradients / optimizer step as the degenerate 2x2x1 mesh, on both
  edge layouts (the plain batch and the blocked one);
- cross-mesh checkpoints: params are saved FULL (never tensor-sliced), so a
  checkpoint written under mesh A restores under mesh B — with a typed error
  when the restoring tensor degree cannot divide the saved hidden width;
- coordinated restore barrier (docs/ROBUSTNESS.md): a SIGTERM on ONE host
  stops every host after the same completed step, and resume verifies all
  hosts adopted the same (epoch, step_in_epoch);
- config validation: unsupported tensor layouts fail loudly at load time.

Runs on the conftest-provisioned 8-virtual-device CPU platform.
"""

from __future__ import annotations

import signal

import jax
import numpy as np
import pytest

from distegnn_tpu.config import load_config, validate_config
from distegnn_tpu.parallel.mesh import (
    DATA_AXIS,
    GRAPH_AXIS,
    TENSOR_AXIS,
    make_mesh,
)
from distegnn_tpu.train.checkpoint import (
    check_mesh_restore_compat,
    restore_for_resume,
    save_checkpoint,
    verify_checkpoint,
    verify_resume_consensus,
)
from distegnn_tpu.train.step import TrainState, make_optimizer
from distegnn_tpu.train.trainer import PreemptionGuard

CFG = "configs/nbody_fastegnn.yaml"

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 (virtual) devices")


# ------------------------------------------------------------------ mesh

def test_mesh_always_carries_three_axes():
    mesh = make_mesh(n_graph=2, n_data=1, n_tensor=1, devices=jax.devices()[:2])
    assert mesh.axis_names == (DATA_AXIS, GRAPH_AXIS, TENSOR_AXIS)
    assert dict(mesh.shape) == {DATA_AXIS: 1, GRAPH_AXIS: 2, TENSOR_AXIS: 1}


@needs_8
def test_mesh_3d_shape_and_product_check():
    mesh = make_mesh(n_graph=2, n_data=2, n_tensor=2, devices=jax.devices()[:8])
    assert dict(mesh.shape) == {DATA_AXIS: 2, GRAPH_AXIS: 2, TENSOR_AXIS: 2}
    with pytest.raises(ValueError, match="devices"):
        make_mesh(n_graph=2, n_data=2, n_tensor=2, devices=jax.devices()[:4])


# ---------------------------------------------------------------- parity

@needs_8
@pytest.mark.parametrize("leg", ["plain", "blocked"])
def test_tensor_parity_2x2x2_vs_2x2x1(leg):
    """fwd/grad/train-step within 1e-6 x max(1, scale) of the T=1 baseline —
    the dryrun parity harness, one edge layout per case."""
    import __graft_entry__ as ge

    ge._tensor_parity(jax.devices()[:8], legs=(leg,))


# ---------------------------------------- cross-mesh checkpoint restore

def _state(scale=1.0):
    params = {"w": np.full((3, 2), scale, np.float32),
              "b": np.full((2,), scale * 0.5, np.float32)}
    return TrainState.create(params, make_optimizer(1e-3))


def _cfg_with_mesh(data, graph, tensor, hidden=16):
    return {"parallel": {"mesh": {"data": data, "graph": graph,
                                  "tensor": tensor}},
            "model": {"hidden_nf": hidden}}


def test_checkpoint_records_mesh_and_restores_across_meshes(tmp_path, monkeypatch):
    """Save under 2x2x2, restore under 1x1x8: plain load (params are full),
    reshard event emitted, state and coordinates intact."""
    events = []
    from distegnn_tpu.train import checkpoint as ckpt_mod
    monkeypatch.setattr(ckpt_mod.obs, "event",
                        lambda name, **kw: events.append((name, kw)))

    path = str(tmp_path / "last_model.ckpt")
    st = _state(scale=2.5)
    save_checkpoint(path, st, epoch=4, seed=7, step_in_epoch=2,
                    config=_cfg_with_mesh(2, 2, 2))
    payload = verify_checkpoint(path)
    assert payload["mesh"] == {"data": 2, "graph": 2, "tensor": 2}

    r = restore_for_resume(path, _state(), config=_cfg_with_mesh(1, 8, 1))
    assert (r.epoch, r.step_in_epoch, r.seed) == (4, 2, 7)
    for a, b in zip(jax.tree.leaves(st.params), jax.tree.leaves(r.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    reshard = [kw for name, kw in events if name == "ckpt/reshard"]
    assert reshard and reshard[0]["saved"] == {"data": 2, "graph": 2, "tensor": 2}
    assert reshard[0]["target"] == {"data": 1, "graph": 8, "tensor": 1}


def test_checkpoint_same_mesh_restore_is_silent(tmp_path, monkeypatch):
    events = []
    from distegnn_tpu.train import checkpoint as ckpt_mod
    monkeypatch.setattr(ckpt_mod.obs, "event",
                        lambda name, **kw: events.append(name))
    path = str(tmp_path / "last_model.ckpt")
    save_checkpoint(path, _state(), epoch=1, config=_cfg_with_mesh(2, 2, 2))
    restore_for_resume(path, _state(), config=_cfg_with_mesh(2, 2, 2))
    assert "ckpt/reshard" not in events


def test_restore_rejects_indivisible_tensor_degree(tmp_path):
    """hidden_nf=16 cannot split 3 ways: typed ValueError at the compat gate,
    not a shape error deep inside shard_map."""
    path = str(tmp_path / "last_model.ckpt")
    save_checkpoint(path, _state(), epoch=0, config=_cfg_with_mesh(2, 2, 2))
    with pytest.raises(ValueError, match="not divisible"):
        restore_for_resume(path, _state(), config=_cfg_with_mesh(1, 2, 3))
    # the gate itself, on a bare payload
    with pytest.raises(ValueError, match="hidden_nf"):
        check_mesh_restore_compat(
            {"config": {"model": {"hidden_nf": 16}}},
            config=_cfg_with_mesh(1, 1, 5))


def test_pre_mesh_checkpoint_still_restores(tmp_path):
    """A checkpoint with no recorded mesh (older writer) restores cleanly
    under any target mesh."""
    path = str(tmp_path / "last_model.ckpt")
    save_checkpoint(path, _state(), epoch=2, config=None)
    r = restore_for_resume(path, _state(), config=_cfg_with_mesh(1, 8, 1))
    assert r.epoch == 2


# -------------------------------------- coordinated restore barrier drill

class _FakeCluster:
    """N single-process PreemptionGuards wired to one shared allgather — the
    cross-host flag exchange without OS processes."""

    def __init__(self, n):
        self.guards = [PreemptionGuard(allgather=self._allgather)
                       for _ in range(n)]

    def _allgather(self, _local):
        return np.stack([np.asarray([1 if g.requested else 0], np.int32)
                         for g in self.guards])


def test_sigterm_on_one_host_stops_all_at_same_step():
    """The fault-injection drill: host 1 gets SIGTERM mid-epoch; every host's
    stop_agreed() flips at the SAME step boundary, and the hosts that never
    saw a signal adopt the request (so their preempt checkpoints carry the
    same coordinates)."""
    cluster = _FakeCluster(4)
    # no signal anywhere: nobody stops
    assert [g.stop_agreed() for g in cluster.guards] == [False] * 4

    # deliver the signal to host 1 only (handler path, not a raw flag poke)
    cluster.guards[1]._handle(signal.SIGTERM, None)
    votes = [g.stop_agreed() for g in cluster.guards]
    assert votes == [True] * 4
    assert all(g.requested for g in cluster.guards)
    assert all(g.signum == signal.SIGTERM for g in cluster.guards)

    # all hosts then record the same resume coordinates -> consensus holds
    coords = [(3, 17) for _ in cluster.guards]
    stack = np.stack([np.asarray(c, np.int64) for c in coords])
    verify_resume_consensus(3, 17, allgather=lambda x: stack)


def test_resume_consensus_mismatch_fails_loudly():
    """Half-propagated checkpoint dir: hosts resolve different resume points;
    the barrier must raise a TYPED error BEFORE any step runs, naming the
    lagging host and the local checkpoint path to diff against."""
    from distegnn_tpu.train.checkpoint import ResumeConsensusError

    views = np.asarray([[3, 17], [3, 17], [3, 12], [3, 17]], np.int64)
    with pytest.raises(ResumeConsensusError, match="consensus") as ei:
        verify_resume_consensus(3, 17, allgather=lambda x: views,
                                path="/ckpt/state_dict/step_0000000017.ckpt")
    err = ei.value
    assert err.lagging == [2], "process 2 holds the stale view"
    assert err.coords == [(3, 17), (3, 17), (3, 12), (3, 17)]
    assert err.local_path.endswith("step_0000000017.ckpt")
    msg = str(err)
    assert "process 2" in msg and "step_in_epoch=12" in msg
    assert "step_0000000017.ckpt" in msg


def test_resume_consensus_single_process_noop():
    verify_resume_consensus(0, 0)  # no injected allgather, 1 process: no-op


def test_second_signal_escalates():
    g = PreemptionGuard()
    g._handle(signal.SIGTERM, None)
    assert g.requested
    with pytest.raises(KeyboardInterrupt):
        g._handle(signal.SIGTERM, None)


# ------------------------------------------------------- config validation

def _nbody_cfg(**mesh):
    cfg = load_config(CFG)
    for k, v in mesh.items():
        cfg.parallel.mesh[k] = v
    return cfg


def test_config_defaults_tensor_to_one():
    cfg = load_config(CFG)
    assert int(cfg.parallel.mesh.tensor) == 1
    validate_config(cfg)  # the default layout is always valid


def test_config_tensor_must_divide_hidden():
    cfg = _nbody_cfg(tensor=3)  # hidden_nf=64
    with pytest.raises(ValueError, match="must divide"):
        validate_config(cfg)
    validate_config(_nbody_cfg(tensor=2))  # 64 % 2 == 0: fine


def test_config_rejects_unknown_mesh_key():
    cfg = load_config(CFG)
    cfg.parallel.mesh["pipeline"] = 2
    with pytest.raises(ValueError, match="unknown key"):
        validate_config(cfg)


def test_config_tensor_requires_supported_layout():
    cfg = _nbody_cfg(tensor=2)
    cfg.model.model_name = "EGNN"
    with pytest.raises(ValueError, match="FastEGNN"):
        validate_config(cfg)

    cfg = _nbody_cfg(tensor=2)
    cfg.model.hoist_edge_mlp = False
    with pytest.raises(ValueError, match="hoist_edge_mlp"):
        validate_config(cfg)

    cfg = _nbody_cfg(tensor=2)
    cfg.model.tanh = True
    with pytest.raises(ValueError, match="tanh"):
        validate_config(cfg)


def test_config_mesh_data_conflicts_with_data_parallel():
    cfg = _nbody_cfg(data=2)
    cfg.data.data_parallel = 4
    with pytest.raises(ValueError, match="conflicts"):
        validate_config(cfg)


def test_config_tensor_cli_field():
    cfg = load_config(CFG, overrides={"tensor_parallel": 2})
    assert int(cfg.parallel.mesh.tensor) == 2


# ------------------------------------------------------- memory gauges

def test_record_memory_gauges_is_safe_everywhere():
    """CPU backends expose no memory_stats: the probe must still return a
    dict and set no gauges rather than crash; on TPU/GPU the same call sets
    mem/<tag>/* gauges (asserted indirectly — keys present implies set)."""
    from distegnn_tpu.obs import jaxprobe
    from distegnn_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    stats = jaxprobe.record_memory_gauges("post_warmup", registry=reg)
    assert isinstance(stats, dict)
    snap = reg.snapshot() if hasattr(reg, "snapshot") else {}
    for k in ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size"):
        if k in stats:
            assert any("post_warmup" in name for name in snap)
