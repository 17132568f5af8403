"""Plain reference of FastEGNN / DistEGNN training: the forward, in
straightforward ``jax.numpy`` float32 at matmul precision ``highest``, and
``follow``, which trains it by ``train.py``'s loss, gradient and Adam.

Written from the paper's equations (arXiv:2506.19482, FastEGNN layer: real
edge messages, C virtual nodes, three global means a layer) and the public
EGNN code it extends (``E_GCL_vel``: ``coord_diff / (sqrt(radial).detach() +
eps)`` under ``normalize``; coordinate heads without bias). It works on RAW
graphs (no node padding or masks, no node reordering, an unsorted edge list
padded with zero-weight edges to one length so that one program serves a pool),
imports nothing of ``distegnn_tpu`` and takes its weights from
``benchmarks/weights.py``. A batch is ``G`` graphs of equal size stacked on a
leading axis and processed ``block`` graphs at a time, each layer
rematerialized, so that 1.64 M edges (one LargeFluid graph) and 2.475 M
(250 n-body graphs) fit beside nothing else on one chip.

A graph that no chip holds that way is walked in edge blocks (``edge_block``,
the number of edges of ONE graph worked at a time; the edge axis has to be a
multiple of it, which the zero-weight padding gives). What is blocked: the
part of a layer that lives on the edge axis, that is the two row gathers, the
``[edge_block, 2H+1+D]`` input and ``[edge_block, H]`` hidden and output of
phi_e, the hidden of phi_x and the three segment sums of a block, all under
``jax.checkpoint``, so that the backward keeps a block's edge list and
rebuilds the rest. What is carried across blocks, in float32: the three
node-sized sums of a layer (messages ``[n, H]``, coordinate updates
``[n, 3]``, degree ``[n, 1]``); the layer then finishes as without blocks.
In a block the features and coordinates of an edge's ends come from one
``[n, H+3]`` table and the three sums are one segment sum of ``H+4`` columns:
the same numbers column by column, packed because the TPU's compiler lays a
``[800000, 64]`` array out column-major and a scatter into it then takes 12
times what one into 68 columns takes (164 against 14 ms for 820,224 rows, my
chip run, PR 29; at 113,140 rows both take 7 ms).
Everything on the node axis (the virtual-node terms, which are means over
real nodes, and the node update) is not blocked. Without ``edge_block`` the
edge part runs once over all edges: the same equations and the same order of
operations as with one block, and the path both one-chip cells run.

The partitioned loss (distribute mode on ``P`` devices, one partition of the
graph a device) is written in the same inputs. Partition ``p`` holds ``n_p`` of
the ``n`` nodes, draws its OWN ``samples * C`` target nodes from them and
differentiates ``(n_p / n) * (MSE_p + weight * MMD_p)``; the gradients are
summed. Over the whole graph that is ``sse / (3 n)``, as here, and
``k(V,V) / C^2 - 2 / (samples * C * C) * sum_p (n_p / n) * k(draws_p, V)``: a
WEIGHTED sum over the ``P`` sets of draws. A batch says so with ``mmd_idx``
the ``P`` draws laid end to end, ``mmd_w`` ``[G, len(mmd_idx)]`` the weight
``P * n_p / n`` on partition ``p``'s draws, and ``samples`` ``P`` times the
configuration's in the ``mmd`` spec (the division by ``G * samples * C * C``
then gives the sum above). Without ``mmd_w`` every draw has weight 1: the
same program as before the key existed. Three facts of the program this
relies on, each held by ``benchmarks/tests``: edges whose ends lie in
different partitions are dropped (each part's radius graph is built from its
own nodes, so the driver takes them out of the edge list it hands over); the
virtual nodes' means run over the nodes of ALL partitions (so ``V`` is one
global set, and ``k(V,V)`` counts once because the shares ``n_p / n`` sum to
1); each partition draws from its own nodes at the share ``n_p / n``.

The loss, its gradient by blocks of graphs, accumulation, clip and Adam are
``train.py``'s, which every family's reference shares.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import train

EPS = 1e-8


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _round_mantissa(x, bits):
    """float32 ``x`` rounded to nearest at ``bits`` explicit mantissa bits
    (an 8-bit float with an ideal scale per element when ``bits`` is 3), with
    a straight-through gradient."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(jax.lax.stop_gradient(x), jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return x + jax.lax.stop_gradient(jax.lax.bitcast_convert_type(u, jnp.float32) - x)


def _dense(x, w, mantissa):
    """``mantissa``: None = float32 as it is; the control rounds both matmul
    operands of every MLP to that many mantissa bits (3: one step below
    bfloat16's 7)."""
    if mantissa is not None:
        x, w = _round_mantissa(x, mantissa), _round_mantissa(w, mantissa)
    return x @ w


def _mlp(w, name, x, act_last=False, mantissa=None):
    """Dense -> SiLU -> Dense [-> SiLU]; the second Dense has no bias where
    the weights carry none (coordinate heads)."""
    x = _silu(_dense(x, w[name + ".0.w"], mantissa) + w[name + ".0.b"])
    x = _dense(x, w[name + ".1.w"], mantissa)
    if name + ".1.b" in w:
        x = x + w[name + ".1.b"]
    return _silu(x) if act_last else x


def _edge_sums(p, normalize, mantissa, pack, w, h, x, row, col, eattr, ew):
    """The real edges' part of layer ``p`` over one list of edges: the sums
    at each receiving node of the messages [n,H], of the coordinate updates
    [n,3] and of the edge weights [n,1]. Edge (row, col) carries a message to
    ``row`` from ``col``; ``ew`` is 1 for an edge and 0 for list padding.
    ``pack`` (the blocked path): features and coordinates are gathered from
    one [n,H+3] table and the three sums are one sum of H+4 columns, the same
    numbers column by column (module docstring, "Edge blocks")."""
    n, H = h.shape
    mlp = functools.partial(_mlp, mantissa=mantissa)
    if pack:
        hx = jnp.concatenate([h, x], axis=-1)
        at_row, at_col = hx[row], hx[col]
        h_row, h_col, diff = at_row[:, :H], at_col[:, :H], at_row[:, H:] - at_col[:, H:]
    else:
        h_row, h_col, diff = h[row], h[col], x[row] - x[col]
    radial = jnp.sum(diff * diff, axis=-1, keepdims=True)
    if normalize:
        diff = diff / (jax.lax.stop_gradient(jnp.sqrt(radial)) + EPS)
    m = mlp(w, p + "phi_e", jnp.concatenate([h_row, h_col, radial, eattr], -1),
            act_last=True) * ew[:, None]                         # [e,H]
    dx = diff * mlp(w, p + "phi_x", m) * ew[:, None]
    if pack:
        sums = jax.ops.segment_sum(jnp.concatenate([m, dx, ew[:, None]], axis=-1), row, n)
        return sums[:, :H], sums[:, H:H + 3], sums[:, H + 3:]
    return (jax.ops.segment_sum(m, row, n), jax.ops.segment_sum(dx, row, n),
            jax.ops.segment_sum(ew[:, None], row, n))


def virtual_messages(w, p, mantissa, h, x, X, Hv):
    """Virtual edges of layer ``p``, every node seeing the C virtual nodes:
    (X_c - x_i [n,3,C], the messages phi_ev([h_i, Hv_c, |X_c - x_i|, M_c])
    [n,C,H] with M = (X - mean x)^T (X - mean x)). FastTFN's are the same."""
    n, H = h.shape
    C = X.shape[1]
    vdiff = X[None, :, :] - x[:, :, None]                        # [n,3,C]
    vrad = jnp.sqrt(jnp.sum(vdiff * vdiff, axis=1))              # [n,C]
    Xc = X - jnp.mean(x, axis=0)[:, None]
    mX = Xc.T @ Xc                                               # [C,C]
    v_in = jnp.concatenate([
        jnp.broadcast_to(h[:, None, :], (n, C, H)),
        jnp.broadcast_to(Hv.T[None], (n, C, H)),
        vrad[:, :, None],
        jnp.broadcast_to(mX[None], (n, C, C)),
    ], axis=-1)
    return vdiff, _mlp(w, p + "phi_ev", v_in, act_last=True, mantissa=mantissa)   # [n,C,H]


def _layer(w, l, normalize, mantissa, edge_block, h, x, X, Hv, vel, attr, row, col, eattr, ew):
    """One FastEGNN layer on one graph. h [n,H], x [n,3], X [3,C] virtual
    coordinates, Hv [H,C] virtual features. ``edge_block``: None, or the
    number of edges worked at a time (module docstring)."""
    p = f"l{l}."
    n, H = h.shape
    mlp = functools.partial(_mlp, mantissa=mantissa)

    sums = functools.partial(_edge_sums, p, normalize, mantissa, edge_block is not None)
    if edge_block is None:
        m_sum, dx_sum, deg = sums(w, h, x, row, col, eattr, ew)
    else:
        E = row.shape[0]
        if E % edge_block:
            raise ValueError(f"{E} edges are not a multiple of edge_block {edge_block}")
        blocks = jax.tree.map(lambda a: a.reshape((E // edge_block, edge_block) + a.shape[1:]),
                              (row, col, eattr, ew))
        one = jax.checkpoint(sums)
        # the sum is outside the checkpoint: the backward of ``carry + part``
        # needs neither, so no carry is kept per block
        zero = (jnp.zeros((n, H), jnp.float32), jnp.zeros((n, 3), jnp.float32),
                jnp.zeros((n, 1), jnp.float32))
        (m_sum, dx_sum, deg), _ = jax.lax.scan(
            lambda acc, blk: (jax.tree.map(jnp.add, acc, one(w, h, x, *blk)), None), zero, blocks)
    deg = jnp.maximum(deg, 1.0)
    vdiff, mv = virtual_messages(w, p, mantissa, h, x, X, Hv)

    # coordinates: mean over incoming edges, mean over virtual nodes, velocity
    x_new = x + dx_sum / deg
    x_new = x_new + jnp.mean(-vdiff * mlp(w, p + "phi_xv", mv)[:, None, :, 0], axis=-1)
    x_new = x_new + mlp(w, p + "phi_v", h) * vel

    # virtual coordinates: global mean over nodes
    X_new = X + jnp.mean(vdiff * mlp(w, p + "phi_X", mv)[:, None, :, 0], axis=0)

    # node features
    agg = m_sum / deg
    n_in = jnp.concatenate([h, agg, jnp.mean(mv, axis=1), attr], axis=-1)
    h_new = h + mlp(w, p + "phi_h", n_in)

    # virtual features: global mean over nodes
    hv_in = jnp.concatenate([Hv.T, jnp.mean(mv, axis=0)], axis=-1)   # [C,2H]
    Hv_new = Hv + mlp(w, p + "phi_hv", hv_in).T
    return h_new, x_new, X_new, Hv_new


def forward(w, model, g, mantissa=None, edge_block=None):
    """One graph -> (predicted positions [n,3], virtual coordinates [3,C])."""
    C = model["virtual_channels"]
    h = g["feat"] @ w["embed.w"] + w["embed.b"]
    x = g["loc"]
    X = jnp.repeat(g["loc_mean"][:, None], C, axis=1)
    Hv = w["virtual_feat"]
    for l in range(model["n_layers"]):
        lay = jax.checkpoint(functools.partial(_layer, w, l, bool(model["normalize"]), mantissa,
                                               edge_block))
        h, x, X, Hv = lay(h, x, X, Hv, g["vel"], g["attr"], g["row"], g["col"],
                          g["eattr"], g["ew"])
    return x, X


def _block_terms(w, model, mmd, blk, mantissa, edge_block):
    """Sums over one block of graphs (``train.graph_terms`` of each)."""
    def one(g):
        pred, X = forward(w, model, g, mantissa, edge_block)
        return train.graph_terms(pred, X, g, mmd)

    sse, k_vv, k_rv = jax.vmap(one)(blk)
    return jnp.sum(sse), jnp.sum(k_vv), jnp.sum(k_rv)


@functools.partial(jax.jit, static_argnames=("model_key", "mmd_key", "G", "mantissa", "edge_block"))
def _block_grad(w, blk, rows, *, model_key, mmd_key, G, mantissa=None, edge_block=None):
    """(mse share, mmd share), gradient of their weighted sum, for one block
    of a batch of ``G`` graphs in which ``rows`` rows count towards the MSE.
    ``mantissa``: the control, see ``_dense``."""
    terms = functools.partial(_block_terms, mantissa=mantissa, edge_block=edge_block)
    return train.loss_and_grad(terms, w, blk, rows, dict(model_key), dict(mmd_key), G)


_hashable = train.hashable


def follow(w0, model, train_spec, batches, block, half=False, mlp_mantissa=None, edge_block=None):
    """``train.follow`` with this forward: the first ``len(batches)``
    micro-steps from ``w0``, ``block`` graphs at a time, each graph's edges
    walked ``edge_block`` at a time where that is given (module docstring)."""
    return train.follow(_block_grad, w0, model, train_spec, batches, block, half=half,
                        mantissa=mlp_mantissa, edge_block=edge_block)
