"""What the training drivers share: the mapping between the benchmark's flat
weights and the program's parameter tree (one for each family), the program's
configuration loaded from the benchmark's copy of the yaml, and small helpers
to look into an optax state. Drivers are the only benchmark code that imports
``distegnn_tpu``."""

from __future__ import annotations

import json
import os

import jax
import numpy as np

_DENSE = ("Dense_0",)
_COORD_HEADS = ("phi_x", "phi_xv", "phi_X")


def fastegnn_tree_path(name: str) -> tuple:
    """Flat weight name -> key path inside FastEGNN's ``params`` dict."""
    leaf = {"w": "kernel", "b": "bias"}
    if name == "virtual_feat":
        return ("virtual_node_feat",)
    parts = name.split(".")
    if parts[0] == "embed":
        return ("embedding_in",) + _DENSE + (leaf[parts[1]],)
    layer, mlp, idx, kind = parts
    gcl = "gcl_" + layer[1:]
    if mlp == "phi_e":
        # the program keeps phi_e's first Dense as one raw kernel (hoisted
        # to the node axis) and its second as a TorchDense
        if idx == "0":
            return (gcl, mlp, leaf[kind])
        return (gcl, mlp, "TorchDense_0") + _DENSE + (leaf[kind],)
    mid = ("MLP_0",) if mlp in _COORD_HEADS else ()
    return (gcl, mlp) + mid + (f"TorchDense_{idx}",) + _DENSE + (leaf[kind],)


def fasttfn_tree_path(name: str) -> tuple:
    """Flat weight name -> key path inside FastTFN's ``params`` dict: its
    ``phi_e`` is a plain MLP, the TFN sits under ``tfn_layer/conv_0``, whose
    ``RadialFunc`` calls its three Denses ``Dense_i`` and its two
    normalizations ``LayerNorm_i`` (weight ``scale``, bias ``bias``)."""
    parts = name.split(".")
    if len(parts) < 2 or parts[1] not in ("phi_e", "tfn"):
        return fastegnn_tree_path(name)
    gcl = "gcl_" + parts[0][1:]
    leaf = {"w": "kernel", "b": "bias", "g": "scale"}
    if parts[1] == "phi_e":
        return (gcl, "phi_e", f"TorchDense_{parts[2]}") + _DENSE + (leaf[parts[3]],)
    conv = (gcl, "tfn_layer", "conv_0")
    if parts[2] == "self":
        return conv + ("self_1",)
    radial = {"r01": "radial_0_1", "r11": "radial_1_1"}[parts[2]]
    sub = parts[3].replace("ln", "LayerNorm_") if parts[3].startswith("ln") else "Dense_" + parts[3]
    return conv + (radial, sub, leaf[parts[4]])


def to_tree(weights: dict, tree_path=fastegnn_tree_path) -> dict:
    """Flat weights -> ``{"params": ...}`` as the family's ``apply`` takes it.
    A driver calls it through its family (``family.Family.to_tree``); the
    default is FastEGNN's mapping, for callers written before a second family
    came."""
    root: dict = {}
    for name, value in weights.items():
        if name == "virtual_feat":
            value = value[None]
        node = root
        path = tree_path(name)
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return {"params": root}


def to_plain(tree: dict, names, tree_path=fastegnn_tree_path) -> dict:
    """The program's tree (parameters, gradients, moments) -> flat host
    arrays under the benchmark's names (``tree_path`` as ``to_tree``'s)."""
    out = {}
    for name in names:
        node = tree["params"]
        for k in tree_path(name):
            node = node[k]
        a = np.asarray(node)
        out[name] = a[0] if name == "virtual_feat" else a
    return out


def find_field(opt_state, field: str):
    """First sub-state of an optax state that has attribute ``field``
    (``acc_grads`` of MultiSteps, ``mu`` of scale_by_adam)."""
    stack = [opt_state]
    while stack:
        s = stack.pop(0)
        if hasattr(s, field) and not isinstance(s, dict):
            return getattr(s, field)
        if isinstance(s, (tuple, list)):
            stack.extend(s)
        elif hasattr(s, "_fields"):
            stack.extend(getattr(s, f) for f in s._fields)
    raise KeyError(f"no {field!r} in optimizer state {type(opt_state).__name__}")


def load_program_config(config_file: str, meta: dict, seed: int, overrides=None):
    """The program's config object from the benchmark's yaml, with the
    meta file's ``assumed`` values (dotted keys), then ``overrides`` (the
    control's lower-precision path), and the run's seed set."""
    import jax
    from distegnn_tpu.config import load_config

    # JAX settings without which the platform would not compute what the
    # configuration states (meta ``jax_config``, each with its reason there)
    for name, value in (meta.get("jax_config") or {}).items():
        jax.config.update(name, value)
    cfg = load_config(config_file)
    for dotted, value in {**(meta.get("assumed") or {}), **(overrides or {})}.items():
        node = cfg
        keys = dotted.split(".")
        for k in keys[:-1]:
            node = node[k]
        node[keys[-1]] = value
    cfg.seed = int(seed)
    return cfg


def load_meta(config_file: str) -> dict:
    with open(os.path.splitext(config_file)[0] + ".meta.json") as f:
        return json.load(f)


def model_dims(cfg) -> dict:
    """The sizes the benchmark's weights, reference and counts need, and the
    family's name (``family.of`` refuses one it does not know)."""
    m = cfg.model
    return {k: int(m[k]) for k in ("hidden_nf", "n_layers", "virtual_channels",
                                   "node_feat_nf", "node_attr_nf", "edge_attr_nf")} | {
        "normalize": bool(m.normalize), "model_name": str(m.model_name)}


def train_spec(cfg, clip_norm) -> dict:
    """The training numbers the reference needs, from the config as run."""
    t = cfg.train
    return {"learning_rate": float(t.learning_rate), "weight_decay": float(t.weight_decay),
            "clip_norm": clip_norm, "accumulation_steps": int(t.accumulation_steps),
            "mmd": {"sigma": float(t.mmd.sigma), "weight": float(t.mmd.weight),
                    "samples": int(t.mmd.samples)}}


def step_shapes(driver, graphs: int) -> dict:
    """Sizes of one micro-step of ``driver`` (``graphs`` graphs), for
    ``counts.py`` and the trace's shape rules."""
    bf16 = driver.cfg.model.get("compute_dtype") == "bf16"
    return {"graphs": graphs, "nodes": driver.nodes_per_graph, "edges": driver.edges_per_graph,
            "padded_nodes": driver.padded[0], "padded_edges": driver.padded[1],
            "dtype_bytes": 2 if bf16 else 4,
            **{k: v for k, v in driver.dims.items() if k != "normalize"}}


def span(name: str):
    """Host span in the profiler's own trace (no-op cost when not tracing)."""
    return jax.profiler.TraceAnnotation("bench/" + name)


def node_perm(fed_loc: np.ndarray, raw_loc: np.ndarray) -> np.ndarray:
    """perm with ``fed_loc[i] == raw_loc[perm[i]]``: where the program's
    loader put each raw node (Morton order or none). Rows are matched by
    value; positions are distinct floats."""
    a = np.lexsort(fed_loc.T[::-1])
    b = np.lexsort(raw_loc.T[::-1])
    perm = np.empty(len(a), np.int64)
    perm[a] = b
    if not np.array_equal(fed_loc, raw_loc[perm]):
        raise RuntimeError("the loader's nodes are not a permutation of the raw nodes")
    return perm
