"""Config system tests — schema load, defaults, overrides, validation, derived
fields (reference behavior: main.py:96-157)."""

import pytest

from distegnn_tpu.config import (
    ConfigDict,
    apply_overrides,
    build_arg_parser,
    derive_runtime_fields,
    load_config,
)

CFG = "configs/nbody_fastegnn.yaml"


def test_load_reference_schema():
    cfg = load_config(CFG)
    assert cfg.model.model_name == "FastEGNN"
    assert cfg.model.hidden_nf == 64
    assert cfg.data.dataset_name == "nbody_100"
    assert cfg.data.batch_size == 250
    assert cfg.train.mmd.sigma == 1.5
    assert cfg.seed == 43
    # defaults fill fields the YAML omits
    assert cfg.data.split_mode == "metis"
    assert cfg.model.checkpoint is None


def test_cli_overrides_none_skipped():
    cfg = load_config(CFG, overrides={"lr": 1e-3, "seed": None, "virtual_channels": 5})
    assert cfg.train.learning_rate == 1e-3
    assert cfg.seed == 43  # None → untouched (reference main.py:119-120)
    assert cfg.model.virtual_channels == 5


def test_unknown_override_rejected():
    cfg = load_config(CFG)
    with pytest.raises(KeyError):
        apply_overrides(cfg, {"not_a_field": 1})


def test_validation_distribute_requires_radii():
    cfg = load_config(CFG)
    cfg.data.accelerate_mode = "distribute"
    cfg.data.outer_radius = None
    from distegnn_tpu.config import validate_config

    with pytest.raises(ValueError):
        validate_config(cfg)


def test_distribute_config_loads():
    cfg = load_config("configs/largefluid_distegnn.yaml")
    assert cfg.data.accelerate_mode == "distribute"
    assert cfg.data.outer_radius == 0.075
    assert cfg.train.accumulation_steps == 4
    assert cfg.train.mmd.samples == 50


def test_derived_fields():
    cfg = load_config(CFG)
    derive_runtime_fields(cfg, world_size=4)
    assert cfg.data.world_size == 4
    assert "nbody_100" in cfg.log.exp_name
    assert "ws4" in cfg.log.exp_name
    assert "C3" in cfg.log.exp_name


def test_arg_parser_roundtrip():
    parser = build_arg_parser()
    args = parser.parse_args(["--config_path", CFG, "--lr", "0.001", "--batch_size", "8"])
    cfg = load_config(args.config_path, overrides={k: v for k, v in vars(args).items() if k != "config_path"})
    assert cfg.train.learning_rate == 0.001
    assert cfg.data.batch_size == 8


def test_validation_serve_rollout_and_session_cache():
    from distegnn_tpu.config import validate_config

    cfg = load_config(CFG)
    cfg.serve.session_cache = -1
    with pytest.raises(ValueError, match="session_cache"):
        validate_config(cfg)
    cfg.serve.session_cache = 0          # 0 disables — valid
    cfg.serve.rollout = "radius=0.35"    # must be a mapping, not a string
    with pytest.raises(ValueError, match="rollout"):
        validate_config(cfg)
    cfg.serve.rollout = {"radius": 0.0, "max_degree": 32}
    with pytest.raises(ValueError, match="radius"):
        validate_config(cfg)
    cfg.serve.rollout = {"radius": 0.35, "max_degree": 0}
    with pytest.raises(ValueError, match="max_degree"):
        validate_config(cfg)
    cfg.serve.rollout = {"radius": 0.35, "max_degree": 32, "max_per_cell": 0}
    with pytest.raises(ValueError, match="max_per_cell"):
        validate_config(cfg)
    # max_degree * edge_block must tile the 512-wide kernel chunk
    cfg.serve.rollout = {"radius": 0.35, "max_degree": 3, "edge_block": 256}
    with pytest.raises(ValueError, match="multiple of 512"):
        validate_config(cfg)
    cfg.serve.rollout = {"radius": 0.35, "max_degree": 32,
                         "max_per_cell": 64, "edge_block": 256}
    validate_config(cfg)                 # the serve_bench default: valid


def test_configdict_attribute_access():
    c = ConfigDict({"a": {"b": 1}})
    assert c.a.b == 1
    c.a.b = 2
    assert c["a"]["b"] == 2
    assert c.to_dict() == {"a": {"b": 2}}


def _removed_yaml(tmp_path, key, value):
    import yaml

    with open(CFG) as f:
        raw = yaml.safe_load(f)
    raw["model"][key] = value
    path = tmp_path / "old.yaml"
    path.write_text(yaml.safe_dump(raw))
    return load_config(str(path))


def _removed_cli(tmp_path, flag, value):
    args = build_arg_parser().parse_args(["--config_path", CFG, flag, value])
    return load_config(args.config_path, overrides={
        k: v for k, v in vars(args).items() if k != "config_path"})


def _removed_loader(tmp_path, cls_name, _):
    import distegnn_tpu.data.loader as loader

    # refused before the dataset is looked at
    return getattr(loader, cls_name)([], 1, split_remote=True)


@pytest.mark.parametrize("how,key,value", [
    (_removed_yaml, "edge_impl", "fused"),
    (_removed_yaml, "edge_impl", "fused_stack"),
    (_removed_yaml, "edge_impl", "plain"),
    (_removed_yaml, "stack_vmem_budget", 1),
    (_removed_cli, "--edge_impl", "fused"),
    (_removed_loader, "GraphLoader", None),
    (_removed_loader, "ShardedGraphLoader", None),
], ids=["yaml-edge_impl-fused", "yaml-edge_impl-fused_stack",
        "yaml-edge_impl-plain", "yaml-stack_vmem_budget", "cli-edge_impl",
        "GraphLoader-split_remote", "ShardedGraphLoader-split_remote"])
def test_removed_key_is_refused_by_name(tmp_path, how, key, value):
    """Input that still carries a key of the fused edge pipelines (deleted,
    PR 32) is refused with the key's name and the reason — never run on the
    path that is left, whatever value it carries."""
    name = key.lstrip("-") if how is not _removed_loader else "split_remote"
    with pytest.raises(ValueError, match=rf"{name}.* was removed: .*PR 32"):
        how(tmp_path, key, value)


@pytest.mark.parametrize("name,parts", [("largefluid_distegnn", 1),
                                        ("nbody_fastegnn", None),
                                        ("largefluid800k_distegnn", 4)])
def test_benchmark_configs_build_their_model(name, parts):
    """The seam the benchmark's drivers call (tier-1 does not run
    benchmarks/tests): each of its yamls loads, validates, and gives a model
    through the keywords train_stream.py (``parts`` partitions on the graph
    axis) or train_scan.py (``parts`` None) passes to ``get_model``."""
    from distegnn_tpu.config import validate_config
    from distegnn_tpu.models.fast_egnn import FastEGNN
    from distegnn_tpu.models.registry import get_model
    from distegnn_tpu.parallel.mesh import GRAPH_AXIS

    cfg = load_config(f"benchmarks/configs/{name}.yaml")
    derive_runtime_fields(cfg, world_size=parts or 1)
    validate_config(cfg)
    if parts is None:
        model = get_model(cfg.model, world_size=1,
                          dataset_name=cfg.data.dataset_name)
        assert model.axis_name is None
    else:
        mesh = (cfg.get("parallel") or {}).get("mesh") or {}
        assert int(mesh.get("graph") or 1) == parts
        model = get_model(cfg.model, world_size=parts,
                          dataset_name=cfg.data.dataset_name,
                          axis_name=GRAPH_AXIS, tensor_axis=None)
        assert model.axis_name == GRAPH_AXIS
    assert isinstance(model, FastEGNN)
    assert (model.hidden_nf, model.n_layers, model.virtual_channels) == (64, 4, 3)
    assert model.segment_impl == "scatter" and cfg.data.edge_block == 0
