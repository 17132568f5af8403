"""Plain reference of FastTFN training (upstream ``models/FastTFN.py``:
``TFN_GCL_vel``, ``FastTFN``; arXiv:2506.19482 ships it beside FastEGNN): the
forward in straightforward ``jax.numpy`` float32 at matmul precision
``highest``, and ``follow``, which trains it by ``train.py``'s loss, gradient
and Adam. It works on raw graphs as ``fastegnn.py`` does (an unsorted edge
list padded with zero-weight edges, no node padding or reordering), imports
nothing of ``distegnn_tpu`` and takes its weights from
``weights.fasttfn_layout``.

One layer, with edges i <- j (``row`` = i receives from ``col`` = j), C
virtual nodes with coordinates X_c and features Z_c, and the layer's input x;
means run over real edges, real nodes and the C virtual nodes:

    m_ij = phi_e([h_i, h_j, |x_i - x_j|^2, a_ij])
    d_ic = X_c - x_i,  M = (X - mean x)^T (X - mean x),  m_ic = phi_ev([h_i, Z_c, |d_ic|, M_c])
    x_i += mean_j t_ij                          (the TFN step, on the input x)
    x_i += mean_c(-d_ic phi_xv(m_ic)),   X_c += mean_i(d_ic phi_X(m_ic))
    h_i += phi_h([h_i, mean_j m_ij, mean_c m_ic]),   Z_c += phi_hv([Z_c, mean_i m_ic])

with FastEGNN's MLPs and virtual-node messages (``fastegnn._mlp``,
``fastegnn.virtual_messages``). Node attributes, where a configuration has
them, join ``phi_h``'s input as in FastEGNN.

The TFN step (upstream ``coord_model_by_tfn``: one ``GConvSE3`` with
self-interaction from the fibre {0: 1, 1: 1} to {1: 1}, fed the body's charge
q, as upstream's ``model_forward`` passes it, and its input velocity v, the
same in every layer): with r_ij = x_i - x_j, rho = |r_ij| and u = r_ij / rho,

    t_ij = R01(rho) K01(u) q_j + sum_J R11_J(rho) K11_J(u) v_j + w_s v_i
    K01(u) = -u / sqrt(4 pi)                  K11_0(u) = I / sqrt(12 pi)
    K11_1(u) = [u]x / sqrt(8 pi)              K11_2(u) = sqrt(3 / (8 pi)) (u u^T - I / 3)

where [u]x v = u x v. The kernels are K_J(u) = sum_m Y_Jm(u) Q_J[., m] in
Cartesian form: Y_J the real (tesseral) spherical harmonics without the
Condon-Shortley phase, Y_1(u) = sqrt(3 / 4 pi) (u_y, u_z, u_x), and Q_J the
unit-norm null vector of the constraint (D_out x D_in)(R) Q_J = Q_J D_J(R),
the construction of the published code (``get_basis``, ``_basis_transformation_Q_J``).
Q_J is fixed up to its sign by that constraint: for 0 -> 1 it is -I / sqrt(3),
which with Y_1 gives K01; for 1 -> 1, vec(I) / sqrt(3) with Y_0 = 1 / sqrt(4
pi) gives K11_0, the Levi-Civita symbol / sqrt(6) gives sqrt(3 / 4 pi) /
sqrt(6) = 1 / sqrt(8 pi) times [u]x, and an orthonormal basis T_m of the
symmetric traceless matrices / sqrt(5) gives, since Y_2m(u) = sqrt(15 / 8 pi)
(T_m : u u^T), sqrt(3 / 8 pi) (u u^T - I / 3). The degree-1 order (y, z, x)
is a cyclic permutation of (x, y, z) and leaves I, [u]x and u u^T as they
are. The signs are those the null space's SVD gives, as the repository's
``models/se3/so3.py`` solves it; ``benchmarks/tests`` holds these constants
to its basis at random directions. A sign of a K_J is a sign of the radial
net's output J: it changes no model the family can express. The basis
carries no gradient (the published code builds it under ``no_grad``); rho
does.

Radial nets, two a layer (0 -> 1 with one output, 1 -> 1 with three, J = 0,
1, 2), as the published ``RadialFunc``:

    R(rho) = W3 ReLU(BN(W2 ReLU(BN(W1 rho + b1)) + b2)) + b3

where the published ``BN`` (SE(3)-Transformer ``modules.py``, "SE(3)-equvariant
batch/layer normalization") wraps ``nn.LayerNorm(m)``: each edge's 32 channels
normalized by their own mean and biased variance, eps 1e-5 (torch's), affine
weight and bias. Nothing couples the edges or the graphs of a micro-batch, so
the forward works on one graph, and ``follow`` walks ``block`` graphs at a
time, each layer rematerialized, as ``fastegnn.py`` does. The radial nets work
with the edges on the minor axis ([32, e]), ``phi_e`` as FastEGNN's reference
does ([e, 2H+1+D]).

Departures from upstream, each stated: rho on a padding edge (weight 0) is
set to 1, so that its gradient stays finite there (the edge carries no
weight); the signs of the K_J are the repository's construction's (above).
Where the program departs from upstream, the reference follows upstream: the
program's ``RadialFunc`` takes flax's LayerNorm, whose eps is 1e-6.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import train
from benchmarks.reference.fastegnn import _mlp, _round_mantissa, virtual_messages

LN_EPS = 1e-5
K01 = -1.0 / math.sqrt(4.0 * math.pi)
K11 = (1.0 / math.sqrt(12.0 * math.pi), 1.0 / math.sqrt(8.0 * math.pi),
       math.sqrt(3.0 / (8.0 * math.pi)))

# the basis carries no gradient; a test puts the identity here to take the
# derivative the basis would add
_no_grad = jax.lax.stop_gradient


def kernels(u, q, v):
    """The TFN kernels applied to the sources, edges on the minor axis: u
    [3,e] unit vectors, q [e] charges, v [3,e] velocities -> K01(u) q [3,e]
    and (K11_J(u) v for J = 0, 1, 2), each [3,e]."""
    uv = jnp.sum(u * v, axis=0)
    return K01 * u * q, (K11[0] * v, K11[1] * jnp.cross(u, v, axis=0),
                         K11[2] * (u * uv - v / 3.0))


def _linear_t(w, name, y, mantissa):
    """A Linear on columns: y [in, e] -> [out, e]. ``mantissa``: the control,
    see ``fastegnn._dense``."""
    W = w[name + ".w"]
    if mantissa is not None:
        y, W = _round_mantissa(y, mantissa), _round_mantissa(W, mantissa)
    return W.T @ y + w[name + ".b"][:, None]


def _layer_norm(a, w, name):
    """torch's ``LayerNorm`` over the channels of each edge, a [32, e]."""
    mean = jnp.mean(a, axis=0, keepdims=True)
    var = jnp.mean((a - mean) ** 2, axis=0, keepdims=True)
    return (a - mean) / jnp.sqrt(var + LN_EPS) * w[name + ".g"][:, None] + w[name + ".b"][:, None]


def _radial(w, net, rho, mantissa):
    """Net ``net`` (``l0.tfn.r11``...) on rho [e] -> its output [out, e]."""
    a = _linear_t(w, net + ".0", rho[None, :], mantissa)
    for k in (1, 2):
        a = _linear_t(w, net + f".{k}", jax.nn.relu(_layer_norm(a, w, net + f".ln{k - 1}")),
                      mantissa)
    return a


def _tfn_sum(w, p, mantissa, x, row, col, ew, q, v):
    """One graph's TFN messages summed at each receiving node, [3, n]."""
    xt = x.T
    r = xt[:, row] - xt[:, col]                                  # [3,e]
    rho = jnp.sqrt(jnp.where(ew > 0, jnp.sum(r * r, axis=0), 1.0))
    u = _no_grad(r / rho)
    r01 = _radial(w, p + "tfn.r01", rho, mantissa)               # [1,e]
    r11 = _radial(w, p + "tfn.r11", rho, mantissa)               # [3,e]
    vt = v.T
    k01, k11 = kernels(u, q[col], vt[:, col])
    t = r01[0] * k01
    for J in range(3):
        t = t + r11[J] * k11[J]
    t = t + w[p + "tfn.self.w"][0, 0] * vt[:, row]
    return jnp.zeros((3, x.shape[0]), t.dtype).at[:, row].add(t * ew)


def _layer(w, l, mantissa, h, x, X, Z, q, v, attr, row, col, eattr, ew):
    """One FastTFN layer on one graph: h [n,H], x [n,3], X [3,C] virtual
    coordinates, Z [H,C] virtual features; q [n] charges, v [n,3] the input
    velocities."""
    p = f"l{l}."
    n = h.shape[0]
    mlp = functools.partial(_mlp, mantissa=mantissa)

    # real edges: phi_e's messages and the TFN's, summed at the receiving node
    diff = x[row] - x[col]
    radial = jnp.sum(diff * diff, axis=-1, keepdims=True)
    m = mlp(w, p + "phi_e", jnp.concatenate([h[row], h[col], radial, eattr], -1),
            act_last=True) * ew[:, None]
    m_sum = jax.ops.segment_sum(m, row, n)
    deg = jnp.maximum(jax.ops.segment_sum(ew[:, None], row, n), 1.0)
    t_sum = _tfn_sum(w, p, mantissa, x, row, col, ew, q, v)

    vdiff, mv = virtual_messages(w, p, mantissa, h, x, X, Z)
    x_new = x + t_sum.T / deg
    x_new = x_new + jnp.mean(-vdiff * mlp(w, p + "phi_xv", mv)[:, None, :, 0], axis=-1)
    X_new = X + jnp.mean(vdiff * mlp(w, p + "phi_X", mv)[:, None, :, 0], axis=0)
    n_in = jnp.concatenate([h, m_sum / deg, jnp.mean(mv, axis=1), attr], axis=-1)
    h_new = h + mlp(w, p + "phi_h", n_in)
    z_in = jnp.concatenate([Z.T, jnp.mean(mv, axis=0)], axis=-1)     # [C,2H]
    return h_new, x_new, X_new, Z + mlp(w, p + "phi_hv", z_in).T


def forward(w, model, g, mantissa=None):
    """One graph -> (predicted positions [n,3], virtual coordinates [3,C])."""
    C = model["virtual_channels"]
    h = g["feat"] @ w["embed.w"] + w["embed.b"]
    x = g["loc"]
    X = jnp.repeat(g["loc_mean"][:, None], C, axis=1)
    Z = w["virtual_feat"]
    for l in range(model["n_layers"]):
        lay = jax.checkpoint(functools.partial(_layer, w, l, mantissa))
        h, x, X, Z = lay(h, x, X, Z, g["charge"], g["vel"], g["attr"], g["row"], g["col"],
                         g["eattr"], g["ew"])
    return x, X


def _block_terms(w, model, mmd, blk, mantissa=None):
    """Sums over one block of graphs (``train.graph_terms`` of each)."""
    def one(g):
        pred, X = forward(w, model, g, mantissa)
        return train.graph_terms(pred, X, g, mmd)

    sse, k_vv, k_rv = jax.vmap(one)(blk)
    return jnp.sum(sse), jnp.sum(k_vv), jnp.sum(k_rv)


@functools.partial(jax.jit, static_argnames=("model_key", "mmd_key", "G", "mantissa"))
def _block_grad(w, blk, rows, *, model_key, mmd_key, G, mantissa=None):
    """(mse share, mmd share), gradient of their weighted sum, for one block
    of a batch of ``G`` graphs in which ``rows`` rows count towards the MSE."""
    terms = functools.partial(_block_terms, mantissa=mantissa)
    return train.loss_and_grad(terms, w, blk, rows, dict(model_key), dict(mmd_key), G)


def follow(w0, model, train_spec, batches, block, half=False, mlp_mantissa=None, edge_block=None):
    """``train.follow`` with this forward: the first ``len(batches)``
    micro-steps from ``w0``, ``block`` graphs at a time."""
    if edge_block is not None:
        raise ValueError("FastTFN's reference walks whole graphs: it takes no edge_block")
    return train.follow(_block_grad, w0, model, train_spec, batches, block, half=half,
                        mantissa=mlp_mantissa)
