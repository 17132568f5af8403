"""The row-end gather of a plain row-sorted batch says that its ids ascend
(EdgeOps.gather_rows -> ops/segment.py:gather_rows_sorted), so its transpose
is a SORTED segment sum and is lowered as one: values and gradients against
autodiff's transpose of the unhinted gather, the counter that says a row pass
was traced that way, the batches that keep the unhinted gather (blocked,
unsorted), and what the lowered gradient holds (one unsorted scatter, the col
transpose's, where the unhinted form holds two)."""

import re

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from distegnn_tpu import obs
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops.blocked import EdgeOps, blocked_slot_inv_deg
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.ops.segment import gather_rows_sorted

H, L = 16, 2
MODEL = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=H, virtual_channels=3, n_layers=L)


def _graph(rng, n, shuffle=False):
    from distegnn_tpu.data import build_nbody_graph

    loc = rng.normal(size=(n, 3))
    vel = rng.normal(size=(n, 3))
    charges = rng.choice([1.0, -1.0], size=(n, 1))
    g = build_nbody_graph(loc, vel, charges, loc + 0.1 * vel, radius=-1.0)
    if shuffle:
        order = rng.permutation(g["edge_index"].shape[1])
        g["edge_index"], g["edge_attr"] = g["edge_index"][:, order], g["edge_attr"][order]
    return g


def _batch(rng, kind="sorted", sizes=(24, 17)):
    """Graphs of unequal size, so that the smaller pads nodes and edges (one
    graph: ``max_edges`` leaves padding rows)."""
    graphs = [_graph(rng, n, shuffle=kind == "unsorted") for n in sizes]
    if kind == "blocked":
        return pad_graphs(graphs, edge_block=8)
    e = max(g["edge_index"].shape[1] for g in graphs)
    return pad_graphs(graphs, max_edges=e + 40)


def _ops(g):
    return EdgeOps(g, *blocked_slot_inv_deg(g))


def _unhinted(self, data):
    """The row gather before the hint (and still that of an unsorted batch)."""
    return jnp.take_along_axis(data, self.g.row[..., None], axis=1)


@pytest.mark.parametrize("sizes", [(24,), (24, 17)], ids=["B1", "B2"])
def test_forward_bit_for_bit_and_gradient_equals_autodiffs_transpose(rng, sizes, monkeypatch):
    g = _batch(rng, "sorted", sizes)
    assert g.edges_sorted and not bool(g.edge_mask.all())
    B, N = g.node_mask.shape
    E = g.row.shape[1]
    data = jnp.asarray(rng.standard_normal((B, N, H + 3)), jnp.float32)
    # padding rows carry cotangent too: it lands on slot N-1 in both forms
    ct = jnp.asarray(rng.standard_normal((B, E, H + 3)), jnp.float32)
    ops = _ops(g)
    pull = lambda: jax.vjp(ops.gather_rows, data)
    out, vjp = pull()
    monkeypatch.setattr(EdgeOps, "gather_rows", _unhinted)
    ref, ref_vjp = pull()
    np.testing.assert_array_equal(out, ref)
    got, want = vjp(ct)[0], ref_vjp(ct)[0]
    assert float(jnp.abs(want[:, N - 1]).max()) > 0
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_mean_one_cotangent_at_200k_rows(rng):
    """A cotangent of mean 1 and unit variance over 200,000 rows of degree
    about 14: the case in which a difference of GLOBAL prefixes is wrong by
    percents. Worst segment against autodiff's transpose, relative to the
    segment's sum of absolute values."""
    E, N, F = 204_800, 14_000, 67
    row = np.sort(rng.integers(0, N - 1, size=E)).astype(np.int32)
    row[-300:] = N - 1                                   # the padded tail
    ct = jnp.asarray(1.0 + rng.standard_normal((E, F)), jnp.float32)
    row = jnp.asarray(row)
    zeros = jnp.zeros((N, F), jnp.float32)
    got = jax.vjp(lambda t: gather_rows_sorted(t[None], row[None])[0], zeros)[1](ct)[0]
    want = jax.vjp(lambda t: jnp.take_along_axis(t, row[:, None], axis=0), zeros)[1](ct)[0]
    scale = zeros.at[row].add(jnp.abs(ct))
    assert float(jnp.max(jnp.abs(got - want) / jnp.maximum(scale, 1e-30))) <= 1e-5


def _loss(model, g):
    def f(params, x, h0):
        loc, _ = model.apply(params, g.replace(loc=x, node_feat=h0))
        return jnp.sum((loc - g.target) ** 2 * g.node_mask[..., None])
    return f


def _grads(model, params, g):
    flat = jax.flatten_util.ravel_pytree(
        jax.grad(_loss(model, g), argnums=(0, 1, 2))(params, g.loc, g.node_feat))[0]
    return np.asarray(flat, np.float32)


@pytest.mark.parametrize("kind,passes", [("sorted", L), ("blocked", 0), ("unsorted", 0)])
def test_sorted_row_passes_counter_and_who_keeps_the_unhinted_gather(rng, kind, passes,
                                                                    monkeypatch):
    """``edge/sorted_row_passes``: L a traced forward of FastEGNN on a plain
    row-sorted batch, 0 on a blocked and on an unsorted one, whose gradients
    are those of the unhinted gather (they still run it)."""
    g = _batch(rng, kind)
    assert g.edges_sorted == (kind != "unsorted")
    model = FastEGNN(**MODEL)
    params = model.init(jax.random.PRNGKey(0), g)
    counter = obs.get_registry().counter("edge/sorted_row_passes")
    before = counter.value
    jax.make_jaxpr(lambda p: model.apply(p, g))(params)
    assert counter.value - before == passes
    got = _grads(model, params, g)
    hinted = []
    from distegnn_tpu.ops import segment

    monkeypatch.setattr(segment, "gather_rows_sorted",
                        lambda h, r: hinted.append(1) or gather_rows_sorted(h, r))
    jax.make_jaxpr(lambda p: model.apply(p, g))(params)
    assert len(hinted) == passes
    if kind != "blocked":
        monkeypatch.setattr(EdgeOps, "gather_rows", _unhinted)
        np.testing.assert_allclose(got, _grads(model, params, g), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seg_impl", ["cumsum", "ell"])
def test_a_ruled_sorted_transpose_counts_too(rng, seg_impl):
    """``segment_impl: cumsum | ell`` on a row-sorted plain batch transpose
    the row gather into a sorted segment sum by a rule of their own."""
    g = pad_graphs([_graph(rng, 24), _graph(rng, 17)], compute_pair=True, max_in_degree=32)
    model = FastEGNN(**MODEL, segment_impl=seg_impl)
    params = model.init(jax.random.PRNGKey(0), g)
    counter = obs.get_registry().counter("edge/sorted_row_passes")
    before = counter.value
    jax.make_jaxpr(lambda p: model.apply(p, g))(params)
    assert counter.value - before == L


def test_fastegnn_bf16_remat_gradient_within_the_packs_band(rng, monkeypatch):
    """``remat: true`` with bf16 MLPs (the LargeFluid configuration): outputs
    and gradients against the unhinted gather, within the band
    tests/test_pack_gather.py holds for the pack. The additions are the same,
    so on the CPU they agree far inside it."""
    g = _batch(rng, "sorted")
    model = FastEGNN(**MODEL, compute_dtype="bf16", remat=True)
    params = model.init(jax.random.PRNGKey(0), g)
    out, got = model.apply(params, g), _grads(model, params, g)
    monkeypatch.setattr(EdgeOps, "gather_rows", _unhinted)
    ref_out, ref = model.apply(params, g), _grads(model, params, g)
    for u, v in zip(out, ref_out):
        np.testing.assert_array_equal(u, v)
    np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2 * np.abs(ref).max())


_SORTED_FLAG = re.compile(r"stablehlo\.scatter.*?indices_are_sorted = (true|false)")


def _scatter_adds(jaxpr, found):
    """``indices_are_sorted`` of every scatter-add equation, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found.append(eqn.params["indices_are_sorted"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scatter_adds(sub, found)
    return found


def _pack_grad_scatters(g, E, N, platforms=None):
    """``indices_are_sorted`` of every scatter in the gradient of
    ``EdgeOps.gather_sum_diff`` at ``E`` edges into ``N`` nodes (shapes only:
    nothing of that size is made), ``g`` giving the batch's static facts: as
    traced, and in the lowered text (which holds a function once however
    often it is called: two equal unsorted transposes are one scatter there)."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)

    def loss(a, b, x, edge_index):
        s, d = EdgeOps(g.replace(edge_index=edge_index)).gather_sum_diff(a, b, x)
        return jnp.sum(jnp.tanh(s)) + jnp.sum(d * d)

    traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        f32(1, N, 64), f32(1, N, 64), f32(1, N, 3), jax.ShapeDtypeStruct((1, 2, E), jnp.int32))
    lowered = traced.lower(lowering_platforms=platforms) if platforms else traced.lower()
    return (sorted(_scatter_adds(traced.jaxpr.jaxpr, [])),
            sorted(_SORTED_FLAG.findall(lowered.as_text())))


@pytest.mark.parametrize("platforms", [None, ("tpu",)], ids=["cpu", "tpu"])
def test_lowered_gradient_holds_one_unsorted_scatter_where_it_held_two(rng, platforms):
    """At the one-chip cell's shape (1,640,448 edges into 113,144 nodes, 67
    columns), for this platform and for the TPU from the CPU: the row
    transpose carries the hint, the col transpose cannot; with the batch's
    ``edges_sorted`` off both scatter-adds are unsorted, as they were."""
    g = _batch(rng, "sorted", (24,))
    E, N = 1_640_448, 113_144
    assert _pack_grad_scatters(g, E, N, platforms) == ([False, True], ["false", "true"])
    traced, text = _pack_grad_scatters(g.replace(edges_sorted=False), E, N, platforms)
    assert traced == [False, False] and set(text) == {"false"}
