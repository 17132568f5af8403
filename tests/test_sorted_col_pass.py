"""The col-end gather of a plain batch transposes over a col-sorted view
(EdgeOps.gather_cols -> ops/segment.py:gather_cols_sorted): one stable sort
of ``col`` a forward (``sort_cols``, hoisted by FastEGNN beside the blocked
slots), then in each layer's backward one flat gather of the cotangent into
col order and the sorted segment sum. Gradients against autodiff's transpose
of the unhinted gather, on the hinted scatter and on the interpreted kernel;
a FastEGNN gradient with and without the view, remat on and off; the counter
``edge/sorted_col_passes``; and the compiled programs: one sort in a training
step, none in a forward."""

import re

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from distegnn_tpu import obs
from distegnn_tpu.models import fast_egnn
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops import segment
from distegnn_tpu.ops.blocked import EdgeOps, col_sorted_view
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.ops.segment import gather_cols_sorted, sort_cols

ULP = 2.0 ** -24
H, L = 16, 4
MODEL = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=H, virtual_channels=3, n_layers=L)


def _graph(rng, n):
    from distegnn_tpu.data import build_nbody_graph

    loc, vel = rng.normal(size=(n, 3)), rng.normal(size=(n, 3))
    return build_nbody_graph(loc, vel, rng.choice([1.0, -1.0], size=(n, 1)),
                             loc + 0.1 * vel, radius=-1.0)


def _batch(rng, sizes=(24, 17, 20), kind="plain"):
    """Graphs of unequal size, so that the smaller pad nodes and edges (one
    graph: ``max_edges`` leaves padding slots, whose col is N-1)."""
    graphs = [_graph(rng, n) for n in sizes]
    if kind == "blocked":
        return pad_graphs(graphs, edge_block=8)
    if kind in ("cumsum", "ell"):
        return pad_graphs(graphs, compute_pair=True, max_in_degree=32)
    e = max(g["edge_index"].shape[1] for g in graphs)
    return pad_graphs(graphs, max_edges=e + 40)


@pytest.fixture
def kernel(monkeypatch):
    """``sorted_row_sum`` takes the Pallas kernel (interpreted here)."""
    jax.clear_caches()
    monkeypatch.setattr(segment, "_row_sum_kernel_engages", lambda rows: True)
    yield
    jax.clear_caches()


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _worst_ulp(got, want, ct, col, n):
    """Worst element's distance, in ulp of its segment's sum of magnitudes."""
    B = ct.shape[0]
    scale = np.stack([np.zeros((n, ct.shape[-1])) for _ in range(B)])
    for b in range(B):
        np.add.at(scale[b], np.asarray(col[b]), np.abs(np.asarray(ct[b], np.float64)))
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return float(np.max(diff / np.maximum(scale, 1e-30))) / ULP


def test_sort_cols_is_a_stable_sort_with_padding_last(rng):
    g = _batch(rng)
    col_sorted, col_order = map(np.asarray, sort_cols(g.col))
    col = np.asarray(g.col)
    N = g.node_mask.shape[1]
    for b in range(col.shape[0]):
        np.testing.assert_array_equal(col_order[b], np.argsort(col[b], kind="stable"))
        np.testing.assert_array_equal(col_sorted[b], col[b][col_order[b]])
        pad = int((~np.asarray(g.edge_mask[b]).astype(bool)).sum())
        assert pad > 0 and (col_sorted[b, -pad:] == N - 1).all()


@pytest.mark.parametrize("path", ["scatter", "kernel"])
@pytest.mark.parametrize("sizes", [(24,), (24, 17, 20)], ids=["B1", "B3"])
def test_gradient_equals_autodiffs_transpose(rng, sizes, path, request):
    """Forward bit for bit; the cotangent of the node table within a few ulp
    of its segment's sum of magnitudes (the hinted scatter adds each node's
    rows in edge order, as autodiff's does, so it is exact on the CPU)."""
    if path == "kernel":
        request.getfixturevalue("kernel")
    g = _batch(rng, sizes)
    B, N = g.node_mask.shape
    E = g.col.shape[1]
    assert not bool(g.edge_mask.all())
    assert not bool((jnp.diff(g.col, axis=1) >= 0).all())        # col unsorted
    data = jnp.asarray(rng.standard_normal((B, N, 67)), jnp.float32)
    # padding slots carry cotangent too: it lands on slot N-1 in both forms
    ct = jnp.asarray(rng.standard_normal((B, E, 67)), jnp.float32)
    view = sort_cols(g.col)
    out, vjp = jax.vjp(lambda t: gather_cols_sorted(t, g.col, *view), data)
    ref, ref_vjp = jax.vjp(lambda t: jnp.take_along_axis(t, g.col[..., None], axis=1), data)
    np.testing.assert_array_equal(out, ref)
    got, want = vjp(ct)[0], ref_vjp(ct)[0]
    assert float(jnp.abs(want[:, N - 1]).max()) > 0
    ulp = _worst_ulp(got, want, ct, g.col, N)
    assert ulp <= (0 if path == "scatter" else 8), ulp


def _loss(model, g):
    def f(params, x, h0):
        loc, _ = model.apply(params, g.replace(loc=x, node_feat=h0))
        return jnp.sum((loc - g.target) ** 2 * g.node_mask[..., None])
    return f


def _grads(model, params, g):
    flat = jax.flatten_util.ravel_pytree(
        jax.jit(jax.grad(_loss(model, g), argnums=(0, 1, 2)))(params, g.loc, g.node_feat))[0]
    return np.asarray(flat, np.float32)


@pytest.mark.parametrize("remat", [False, True], ids=["remat_off", "remat_on"])
def test_fastegnn_gradient_with_the_view_matches_without(rng, remat, monkeypatch,
                                                         fresh_traces):
    """A FastEGNN of four layers: outputs bit for bit, and the gradient of
    every parameter, coordinate and feature against the same model with the
    view held off (autodiff's transpose of the col gather)."""
    g = _batch(rng)
    model = FastEGNN(**MODEL, remat=remat)
    params = model.init(jax.random.PRNGKey(0), g)
    out, got = jax.jit(model.apply)(params, g), _grads(model, params, g)
    jax.clear_caches()
    monkeypatch.setattr(fast_egnn, "col_sorted_view", lambda g: None)
    ref_out, ref = jax.jit(model.apply)(params, g), _grads(model, params, g)
    for u, v in zip(out, ref_out):
        np.testing.assert_array_equal(u, v)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6 * np.abs(ref).max())


@pytest.mark.parametrize("kind,passes", [("plain", L), ("blocked", 0), ("cumsum", 0),
                                         ("ell", 0)])
def test_sorted_col_passes_counter(rng, kind, passes):
    """``edge/sorted_col_passes``: L a traced training forward on a plain
    batch; 0 on a blocked one and under ``segment_impl: cumsum | ell``, whose
    col transposes ride the reverse-edge pairing."""
    g = _batch(rng, kind=kind)
    seg = kind if kind in ("cumsum", "ell") else "scatter"
    model = FastEGNN(**MODEL, segment_impl=seg)
    params = model.init(jax.random.PRNGKey(0), g)
    counter = obs.get_registry().counter("edge/sorted_col_passes")
    before = counter.value
    jax.make_jaxpr(jax.grad(_loss(model, g)))(params, g.loc, g.node_feat)
    assert counter.value - before == passes
    assert (col_sorted_view(g) is None) == (kind == "blocked")


_SORT = re.compile(r"= \(([^)]*)\) sort\(")


@pytest.mark.parametrize("remat", [False, True], ids=["remat_off", "remat_on"])
def test_one_sort_of_col_in_the_training_step_and_none_in_a_forward(rng, remat):
    """The compiled gradient of a FastEGNN of four layers holds ONE sort, of
    the ``[B, E]`` col list (a rematted layer keeps the view as an input and
    sorts nothing again); the compiled forward, which is what serving runs,
    holds none: with no backward the view feeds nothing."""
    g = _batch(rng)
    B, E = g.col.shape
    model = FastEGNN(**MODEL, remat=remat)
    params = model.init(jax.random.PRNGKey(0), g)

    def loss(params, g):
        loc, _ = model.apply(params, g)
        return jnp.sum((loc - g.target) ** 2 * g.node_mask[..., None])

    # the batch is an argument: a constant one would have its sort folded
    step = jax.jit(jax.grad(loss)).lower(params, g).compile().as_text()
    assert _SORT.findall(step) == [f"s32[{B},{E}]{{1,0}}, s32[{B},{E}]{{1,0}}"]
    forward = jax.jit(model.apply).lower(params, g).compile().as_text()
    assert not _SORT.findall(forward) and " sort(" not in forward


_SORTED_FLAG = re.compile(r"stablehlo\.scatter.*?indices_are_sorted = (true|false)")


@pytest.mark.parametrize("platforms", [None, ("tpu",)], ids=["cpu", "tpu"])
def test_lowered_pack_gradient_holds_no_unsorted_scatter_with_the_view(rng, platforms):
    """At the one-chip cell's shape (1,640,448 edges into 113,144 nodes, 67
    columns; shapes only), for this platform and for the TPU from the CPU:
    with the view both transposes of ``EdgeOps.gather_sum_diff`` are scatters
    that say their ids are sorted, where without it the col one is not
    (tests/test_sorted_row_pass.py)."""
    g = _batch(rng, (24,))
    E, N = 1_640_448, 113_144
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)

    def loss(a, b, x, edge_index):
        gg = g.replace(edge_index=edge_index)
        s, d = EdgeOps(gg, col_view=col_sorted_view(gg)).gather_sum_diff(a, b, x)
        return jnp.sum(jnp.tanh(s)) + jnp.sum(d * d)

    traced = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        f32(1, N, 64), f32(1, N, 64), f32(1, N, 3), jax.ShapeDtypeStruct((1, 2, E), jnp.int32))
    lowered = traced.lower(lowering_platforms=platforms) if platforms else traced.lower()
    flags = _SORTED_FLAG.findall(lowered.as_text())
    assert flags and set(flags) == {"true"}
