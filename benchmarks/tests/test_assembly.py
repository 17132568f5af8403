"""Ties the drivers' assembly to the program's own: at toy size, same
configuration, data and seed, one epoch through each driver leaves the
parameters that ``main.main(["--epochs", "1"])`` writes to its checkpoint.
(The drivers repeat ``main.main`` / ``run_distributed`` because those train to
the end; this test is what notices when the two drift apart.)"""

import contextlib
import importlib
import os
import pickle
import sys

import jax
import numpy as np
import pytest
import yaml

from benchmarks.drivers import common
from benchmarks.tests.conftest import toy_config, toy_mix

SEED = 43


def _derived_yaml(tmp_path, name, edits):
    with open(toy_config(name)) as f:
        cfg = yaml.safe_load(f)
    for dotted, v in edits.items():
        node = cfg
        keys = dotted.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = v
    path = str(tmp_path / (name + ".yaml"))
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    with open(str(tmp_path / (name + ".meta.json")), "w") as f:
        f.write('{"assumed": {}}')
    return path


def _checkpoint_params(log_dir, template_state):
    from distegnn_tpu.train.checkpoint import restore_checkpoint

    hits = [os.path.join(r, f) for r, _, fs in os.walk(log_dir) for f in fs
            if f == "last_model.ckpt"]
    assert len(hits) == 1, hits
    state, epoch, _ = restore_checkpoint(hits[0], template_state)
    assert epoch == 1
    return state.params


def _program_init(driver, sample_batch, model):
    """The parameters ``main`` starts from, under the benchmark's names."""
    from benchmarks import weights

    params = model.init(jax.random.PRNGKey(SEED), sample_batch)
    names = [n for n, _, _, _ in weights.layout(driver.dims)]
    return common.to_plain(params, names), names


def _close(a: dict, b: dict):
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-5, atol=2e-7, err_msg=k)


def test_scan_driver_matches_main(tmp_path, monkeypatch):
    import main as program_main
    from benchmarks.traffic.generate import make_samples

    mix = toy_mix("toy_nbody_mix")
    s = make_samples(mix)
    base = tmp_path / "data" / "nbody_100"
    os.makedirs(base)
    two = lambda a, b: np.stack([a, b], axis=1)             # frames 0 and 1
    for split, sl in (("train", slice(None)), ("valid", slice(0, 10)), ("test", slice(10, 20))):
        np.save(base / f"loc_{split}_toy.npy", two(s["loc"], s["target"])[sl])
        np.save(base / f"vel_{split}_toy.npy", two(s["vel"], s["vel"])[sl])
        np.save(base / f"charges_{split}_toy.npy", s["charges"][sl])
    path = _derived_yaml(tmp_path, "toy_nbody", {
        "data.data_dir": str(tmp_path / "data"), "data.frame_0": 0, "data.frame_T": 1,
        "data.max_samples": 40, "log.log_dir": str(tmp_path / "logs"),
        "log.test_interval": 1, "seed": SEED})

    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    with contextlib.redirect_stdout(sys.stderr):
        d = mod.Driver(path, mix, SEED)
        d.build()
        from distegnn_tpu.models.registry import get_model

        model = get_model(d.cfg.model, world_size=1, dataset_name="nbody_100")
        w0, names = _program_init(d, next(iter(d.runner.loader)), model)
        d.start(w0, SEED)
        mine = d.program_record()
        template = d.state
        program_main.main(["--config_path", path, "--epochs", "1"])
    theirs = common.to_plain(_checkpoint_params(str(tmp_path / "logs"), template), names)
    _close(mine["w"], theirs)


@pytest.mark.parametrize("config, mix_name, parts", [("toy_fluid", "toy_fluid_mix", 1),
                                                     ("toy_fluid_g4", "toy_fluid_g4_mix", 4)])
def test_stream_driver_matches_main(tmp_path, monkeypatch, config, mix_name, parts):
    """One partition on a one-device mesh, and four on the graph axis of a
    four-device mesh, each against ``run_distributed``'s own order."""
    import main as program_main
    import distegnn_tpu.parallel.launch as launch

    mix = dict(toy_mix(mix_name), compare_steps=8)    # one pass over the pool
    repeats = int(mix.get("pass_repeats", 1))
    path = _derived_yaml(tmp_path, config, {
        "log.log_dir": str(tmp_path / "logs"), "log.test_interval": 1, "seed": SEED,
        "train.scan_epochs": False,
        # named for one partition too: left out, the program takes every device it finds
        "parallel.mesh.graph": parts})
    mod = importlib.import_module("benchmarks.drivers." + mix["kind"])
    with contextlib.redirect_stdout(sys.stderr):
        d = mod.Driver(path, mix, SEED)
        assert d.chips == parts
        d.build()
        # the program reads one shard file a partition from disk: hand it the
        # driver's pool, a pass listing each scene as often as the driver's
        shards = []
        for p in range(parts):
            shards.append(str(tmp_path / f"pool_{p}-{parts}.pkl"))
            with open(shards[-1], "wb") as f:
                pickle.dump([scene[p] for scene in d._pool(d.samples)] * repeats, f)
        monkeypatch.setattr(launch, "_dispatch_preprocess",
                            lambda config, ws: [shards, shards, shards])
        from distegnn_tpu.models.registry import get_model

        model = get_model(d.cfg.model, world_size=parts, dataset_name=d.cfg.data.dataset_name)
        sample = jax.tree.map(lambda x: x[0], next(iter(d.inner)))
        w0, names = _program_init(d, sample, model)
        d.start(w0, SEED)
        mine = d.program_record()
        template = d.state
        program_main.main(["--config_path", path, "--epochs", "1"])
    theirs = common.to_plain(_checkpoint_params(str(tmp_path / "logs"), template), names)
    _close(mine["w"], theirs)
