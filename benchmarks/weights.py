"""FastEGNN weights made by the benchmark from ``--seed``: one jitted call on
the device, float32 (the type both configurations keep their parameters in).

The layout is the benchmark's own, a flat ``{name: array}`` with the names of
the paper's MLPs (``l2.phi_e.w1`` ...). The plain reference consumes it as it
is; a driver maps it onto the program's parameter tree. The program's own
initializer is not used, so the reference takes nothing the program has made.

Distributions follow the published model (torch defaults): ``nn.Linear``
weight and bias U(+-1/sqrt(fan_in)); the three coordinate heads
xavier-uniform with gain 1e-3 and no bias; the virtual-node feature seed
N(0, 1).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layout(dims: dict) -> list:
    """``[(name, shape, kind, bound)]`` for the sizes in ``dims``:
    hidden H, layers L, virtual channels C, node features F, node attributes
    A, edge attributes D."""
    H, L, C = dims["hidden_nf"], dims["n_layers"], dims["virtual_channels"]
    F, A, D = dims["node_feat_nf"], dims["node_attr_nf"], dims["edge_attr_nf"]
    out = []

    def linear(name, fan_in, fan_out, bias=True):
        b = 1.0 / math.sqrt(fan_in)
        out.append((name + ".w", (fan_in, fan_out), "uniform", b))
        if bias:
            out.append((name + ".b", (fan_out,), "uniform", b))

    def coord_head(name, fan_in):
        out.append((name + ".w", (fan_in, 1), "uniform",
                    1e-3 * math.sqrt(6.0 / (fan_in + 1))))

    linear("embed", F, H)
    out.append(("virtual_feat", (H, C), "normal", 1.0))
    for l in range(L):
        p = f"l{l}."
        linear(p + "phi_e.0", 2 * H + 1 + D, H)
        linear(p + "phi_e.1", H, H)
        linear(p + "phi_ev.0", 2 * H + 1 + C, H)
        linear(p + "phi_ev.1", H, H)
        for head in ("phi_x", "phi_xv", "phi_X"):
            linear(p + head + ".0", H, H)
            coord_head(p + head + ".1", H)
        linear(p + "phi_v.0", H, H)
        linear(p + "phi_v.1", H, 1)
        linear(p + "phi_h.0", 3 * H + A, H)
        linear(p + "phi_h.1", H, H)
        linear(p + "phi_hv.0", 2 * H, H)
        linear(p + "phi_hv.1", H, H)
    return out


def make_weights(seed: int, dims: dict) -> dict:
    """All leaves in one jitted call, from ``seed``."""
    spec = layout(dims)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(spec))
        w = {}
        for k, (name, shape, kind, bound) in zip(keys, spec):
            if kind == "normal":
                w[name] = bound * jax.random.normal(k, shape, jnp.float32)
            else:
                w[name] = jax.random.uniform(k, shape, jnp.float32,
                                             minval=-bound, maxval=bound)
        return w

    return build(jax.random.PRNGKey(seed % (2 ** 32)))
