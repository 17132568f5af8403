"""Weights made by the benchmark from ``--seed``: one jitted call on the
device, float32 (the type every configuration keeps its parameters in).

The layout is the benchmark's own and the family's (``family.py``): a flat
``{name: array}`` with the names of the paper's MLPs (``l2.phi_e.0.w`` ...).
The plain reference consumes it as it is; a driver maps it onto the program's
parameter tree. The program's own initializer is not used, so the reference
takes nothing the program has made.

Distributions follow the published models (torch defaults): ``nn.Linear``
weight and bias U(+-1/sqrt(fan_in)); the coordinate heads xavier-uniform with
gain 1e-3 and no bias; the virtual-node feature seed N(0, 1); FastTFN's own
leaves as ``fasttfn_layout`` says.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


RADIAL_MID = 32    # the published RadialFunc's hidden width (``mid_dim``)


def layout(dims: dict) -> list:
    """``[(name, shape, kind, bound)]`` for the sizes in ``dims``, in the
    layout of their family (``family.py``; FastEGNN's where they name
    none)."""
    from benchmarks import family

    return family.of(dims, default="FastEGNN").layout(dims)


def _linear(out, name, fan_in, fan_out, bias=True, weight_bound=None):
    """A torch ``nn.Linear``: weight and bias U(+-1/sqrt(fan_in)), or the
    weight U(+-``weight_bound``) where an initializer replaces torch's."""
    b = 1.0 / math.sqrt(fan_in)
    out.append((name + ".w", (fan_in, fan_out), "uniform", weight_bound or b))
    if bias:
        out.append((name + ".b", (fan_out,), "uniform", b))


def _coord_head(out, name, fan_in):
    out.append((name + ".w", (fan_in, 1), "uniform", 1e-3 * math.sqrt(6.0 / (fan_in + 1))))


def fastegnn_layout(dims: dict) -> list:
    """FastEGNN: hidden H, layers L, virtual channels C, node features F,
    node attributes A, edge attributes D."""
    H, L, C = dims["hidden_nf"], dims["n_layers"], dims["virtual_channels"]
    F, A, D = dims["node_feat_nf"], dims["node_attr_nf"], dims["edge_attr_nf"]
    out = []
    _linear(out, "embed", F, H)
    out.append(("virtual_feat", (H, C), "normal", 1.0))
    for l in range(L):
        p = f"l{l}."
        _linear(out, p + "phi_e.0", 2 * H + 1 + D, H)
        _linear(out, p + "phi_e.1", H, H)
        _linear(out, p + "phi_ev.0", 2 * H + 1 + C, H)
        _linear(out, p + "phi_ev.1", H, H)
        for head in ("phi_x", "phi_xv", "phi_X"):
            _linear(out, p + head + ".0", H, H)
            _coord_head(out, p + head + ".1", H)
        _linear(out, p + "phi_v.0", H, H)
        _linear(out, p + "phi_v.1", H, 1)
        _linear(out, p + "phi_h.0", 3 * H + A, H)
        _linear(out, p + "phi_h.1", H, H)
        _linear(out, p + "phi_hv.0", 2 * H, H)
        _linear(out, p + "phi_hv.1", H, H)
    return out


def fasttfn_layout(dims: dict) -> list:
    """FastTFN (upstream ``models/FastTFN.py``): FastEGNN's MLPs without the
    real-edge coordinate head ``phi_x`` and the velocity head ``phi_v``, and
    per layer a one-layer TFN that moves the real nodes (``tfn.*``): the
    radial nets of the degree pairs 0->1 (``r01``, 1 output) and 1->1
    (``r11``, 3 outputs, J = 0, 1, 2), each Linear(1, 32) -> BN -> ReLU ->
    Linear(32, 32) -> BN -> ReLU -> Linear(32, out) as the published
    ``RadialFunc`` builds it (Linear weights ``kaiming_uniform_``: U(+-sqrt(6 /
    fan_in)); biases torch's default; the published ``BN`` is a LayerNorm over
    the 32 channels, ``ln*``, weight 1 and bias 0 as torch starts it), and the
    degree-1 self-interaction ``tfn.self.w`` [m_out, m_in] = [1, 1], N(0,
    1/m_in) as the published ``GConvSE3`` draws ``kernel_self``. Assumed,
    SURVEY.md not settling it: ``kaiming_uniform_`` on all three Linears of a
    radial net (the program's ``models/se3/tfn.py`` draws them so) and
    torch's default for their biases."""
    M = RADIAL_MID
    kaiming = lambda fan_in: math.sqrt(6.0 / fan_in)
    out = [e for e in fastegnn_layout(dims) if e[0].split(".")[1:2] not in (["phi_x"], ["phi_v"])]
    for l in range(dims["n_layers"]):
        for net, width in (("r01", 1), ("r11", 3)):
            q = f"l{l}.tfn.{net}"
            _linear(out, q + ".0", 1, M, weight_bound=kaiming(1))
            out += [(q + ".ln0.g", (M,), "const", 1.0), (q + ".ln0.b", (M,), "const", 0.0)]
            _linear(out, q + ".1", M, M, weight_bound=kaiming(M))
            out += [(q + ".ln1.g", (M,), "const", 1.0), (q + ".ln1.b", (M,), "const", 0.0)]
            _linear(out, q + ".2", M, width, weight_bound=kaiming(M))
        out.append((f"l{l}.tfn.self.w", (1, 1), "normal", 1.0))
    return out


def make_weights(seed: int, dims: dict) -> dict:
    """The weights of the family of ``dims`` (FastEGNN's where they name
    none), from ``seed``."""
    return make(seed, layout(dims))


def make(seed: int, spec: list) -> dict:
    """All leaves of the layout ``spec`` in one jitted call, from ``seed``."""

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(spec))
        w = {}
        for k, (name, shape, kind, bound) in zip(keys, spec):
            if kind == "normal":
                w[name] = bound * jax.random.normal(k, shape, jnp.float32)
            elif kind == "const":
                w[name] = jnp.full(shape, bound, jnp.float32)
            else:
                w[name] = jax.random.uniform(k, shape, jnp.float32,
                                             minval=-bound, maxval=bound)
        return w

    return build(jax.random.PRNGKey(seed % (2 ** 32)))
