"""One packed gather per edge end (EdgeOps.gather_sum_diff, HoistedEdgeMLP):
the hoisted phi_e products and the coordinates ride the same row and col
pass. Parity against the separate gathers for every lowering, forward (bit
for bit in f32) and gradients, the count of gathers and scatter-adds a layer
leaves in the gradient, the counter that says the pack engages, and the
parameter tree, which is the one the separate form had.

And what ``remat: true`` keeps of those passes (EdgeOps names their results,
FastEGNN's checkpoint policy saves the names): the residuals a rematted model
holds, remat against no remat, the names lowering to nothing outside a
checkpoint, and the gauge ``model/remat_saved_bytes``."""

import re

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # public: print_saved_residuals only

from distegnn_tpu import obs
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops.blocked import EdgeOps, blocked_slot_inv_deg
from distegnn_tpu.ops.graph import pad_graphs

H, L = 16, 2
MODEL = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=H, virtual_channels=3, n_layers=L)
LOWERINGS = ["scatter", "cumsum", "ell", "blocked"]


def _graph(rng, n):
    from distegnn_tpu.data import build_nbody_graph

    loc = rng.normal(size=(n, 3))
    vel = rng.normal(size=(n, 3))
    charges = rng.choice([1.0, -1.0], size=(n, 1))
    return build_nbody_graph(loc, vel, charges, loc + 0.1 * vel, radius=-1.0)


def _batch(rng, lowering):
    # two graphs of unequal size: the smaller one pads nodes and edges
    graphs = [_graph(rng, 24), _graph(rng, 17)]
    if lowering == "blocked":
        return pad_graphs(graphs, edge_block=8)
    return pad_graphs(graphs, compute_pair=True, max_in_degree=32)


def _ops(g, lowering):
    if lowering == "blocked":
        return EdgeOps(g, *blocked_slot_inv_deg(g))
    return EdgeOps(g, seg_impl=lowering)


def _model(lowering, **kw):
    return FastEGNN(**MODEL, segment_impl="scatter" if lowering == "blocked" else lowering, **kw)


def _separate(self, a, b, x):
    """The form before the pack: four gathers."""
    return (self.gather_rows(a) + self.gather_cols(b),
            self.gather_rows(x) - self.gather_cols(x))


def _operands(rng, g, dtype=jnp.float32):
    B, N = g.node_mask.shape
    a, b = (jnp.asarray(rng.standard_normal((B, N, H)), dtype) for _ in range(2))
    x = jnp.asarray(rng.standard_normal((B, N, 3)), jnp.float32)
    return a, b, x


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_gather_sum_diff_bit_for_bit_f32(rng, lowering):
    g = _batch(rng, lowering)
    assert not bool(g.edge_mask.all()) and not bool(g.node_mask.all())
    ops = _ops(g, lowering)
    a, b, x = _operands(rng, g)       # masked node rows hold values too
    s, d = ops.gather_sum_diff(a, b, x)
    ref_s, ref_d = _separate(ops, a, b, x)
    np.testing.assert_array_equal(s, ref_s)
    np.testing.assert_array_equal(d, ref_d)
    assert s.dtype == a.dtype and d.dtype == jnp.float32


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_gather_sum_diff_grads_match_separate_f32(rng, lowering):
    g = _batch(rng, lowering)
    ops = _ops(g, lowering)
    h, _, x = _operands(rng, g)
    w = jnp.asarray(rng.standard_normal((2 * H, H)).astype(np.float32)) / 6.0
    E = g.row.shape[1]
    cs = jnp.asarray(rng.standard_normal((2, E, H)).astype(np.float32))
    cd = jnp.asarray(rng.standard_normal((2, E, 3)).astype(np.float32))
    em = g.edge_mask[..., None]

    def loss(form):
        def f(h, x, w):
            s, d = form(ops, h @ w[:H], h @ w[H:], x)
            return jnp.sum(jnp.tanh(s) * cs * em) + jnp.sum(d * d * cd * em)
        return f

    got = jax.grad(loss(EdgeOps.gather_sum_diff), argnums=(0, 1, 2))(h, x, w)
    ref = jax.grad(loss(_separate), argnums=(0, 1, 2))(h, x, w)
    for u, v in zip(got, ref):
        np.testing.assert_allclose(u, v, rtol=1e-6, atol=1e-6)


def test_gather_sum_diff_bf16_products_widen_and_round_once(rng):
    """bf16 products: x stays f32 bit for bit, the sum is taken in f32 and
    rounded to bf16 once, so it is at least as close to the exact sum as the
    bf16 add of the separate form."""
    g = _batch(rng, "scatter")
    ops = _ops(g, "scatter")
    a, b, x = _operands(rng, g, jnp.bfloat16)
    s, d = ops.gather_sum_diff(a, b, x)
    ref_s, ref_d = _separate(ops, a, b, x)
    assert s.dtype == jnp.bfloat16 and d.dtype == jnp.float32
    np.testing.assert_array_equal(d, ref_d)
    exact = (ops.gather_rows(a.astype(jnp.float32)) + ops.gather_cols(b.astype(jnp.float32)))
    np.testing.assert_array_equal(s, exact.astype(jnp.bfloat16))
    err = lambda v: np.abs(np.asarray(v, np.float32) - np.asarray(exact)).max()
    assert err(s) <= err(ref_s)


def _loss(model, g):
    def f(params, x, h0):
        gg = g.replace(loc=x, node_feat=h0)
        loc, _ = model.apply(params, gg)
        return jnp.sum((loc - g.target) ** 2 * g.node_mask[..., None])
    return f


def _model_grads(model, params, g):
    return jax.grad(_loss(model, g), argnums=(0, 1, 2))(params, g.loc, g.node_feat)


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_fastegnn_grads_match_separate_gathers_f32(rng, lowering, monkeypatch):
    """Whole model, gradients w.r.t. every parameter (phi_e's kernel among
    them), the coordinates and the node features."""
    g = _batch(rng, lowering)
    model = _model(lowering, normalize=True)
    params = model.init(jax.random.PRNGKey(0), g)
    out = model.apply(params, g)
    got = _model_grads(model, params, g)
    monkeypatch.setattr(EdgeOps, "gather_sum_diff", _separate)
    ref_out = model.apply(params, g)
    ref = _model_grads(model, params, g)
    for u, v in zip(out, ref_out):
        np.testing.assert_array_equal(u, v)          # forward: bit for bit
    kernel = lambda t: t[0]["params"]["gcl_0"]["phi_e"]["kernel"]
    np.testing.assert_allclose(kernel(got), kernel(ref), rtol=1e-6, atol=1e-6)
    flat = lambda t: np.asarray(jax.flatten_util.ravel_pytree(t)[0])
    np.testing.assert_allclose(flat(got), flat(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("remat", [False, True])
def test_fastegnn_bf16_compute_within_band_of_separate(rng, remat, monkeypatch):
    """compute_dtype=bf16: the packed pass sums the two gathered products in
    f32 and accumulates their cotangents in f32 where the separate form did
    both in bf16: outputs and gradients agree to bf16 rounding (the band of
    test_fastegnn_fuse_agg_bf16_compute)."""
    g = _batch(rng, "scatter")
    model = FastEGNN(**MODEL, compute_dtype="bf16", remat=remat)
    params = model.init(jax.random.PRNGKey(0), g)
    out = model.apply(params, g)
    got = _model_grads(model, params, g)
    monkeypatch.setattr(EdgeOps, "gather_sum_diff", _separate)
    ref_out = model.apply(params, g)
    ref = _model_grads(model, params, g)
    for u, v in zip(out, ref_out):
        np.testing.assert_allclose(np.asarray(u, np.float32), np.asarray(v, np.float32),
                                   rtol=3e-2, atol=3e-2)
    flat = lambda t: np.asarray(jax.flatten_util.ravel_pytree(t)[0], np.float32)
    a, b = flat(got), flat(ref)
    np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2 * np.abs(b).max())


def _edge_eqns(jaxpr, found, outer=""):
    """``(scope, primitive)`` of every gather / scatter-add equation traced
    under one of the two edge scopes. A nested jaxpr's name stacks are
    relative to the equation that holds it (``take_along_axis`` is a jitted
    call)."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name in ("gather", "scatter-add"):
            for scope in ("edge_gather", "edge_aggregate"):
                if scope in stack:
                    found.append((scope, eqn.primitive.name))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _edge_eqns(sub, found, stack)
    return found


@pytest.mark.parametrize("kw", [dict(), dict(compute_dtype="bf16", remat=True)],
                         ids=["f32", "bf16_remat"])
def test_two_gathers_and_two_scatter_adds_a_layer(rng, kw):
    """The gradient's jaxpr: per layer one gather per edge end and, as its
    transpose, one scatter-add per edge end (four and four before the pack),
    one segment sum and, as its transpose, one gather. Remat repeats NONE of
    them: the rematted layer keeps what the edge passes return (it repeated
    the forward's two gathers and the segment sum while it kept the layer's
    inputs alone)."""
    g = _batch(rng, "scatter")
    model = FastEGNN(**MODEL, **kw)
    params = model.init(jax.random.PRNGKey(0), g)
    passes = obs.get_registry().counter("edge/gather_passes")
    before = passes.value
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, g), argnums=(0, 1)))(params, g.loc, g.node_feat)
    assert passes.value - before == 2 * L
    found = _edge_eqns(jaxpr.jaxpr, [])
    assert found.count(("edge_gather", "gather")) == 2 * L
    assert found.count(("edge_gather", "scatter-add")) == 2 * L
    assert found.count(("edge_aggregate", "scatter-add")) == L
    assert found.count(("edge_aggregate", "gather")) == L


def _kept(model, params, g):
    """The residuals of the gradient that some op computed (not an argument,
    not a constant of the closure), as ``(shape, dtype, where from)``."""
    return [(tuple(a.shape), a.dtype, why)
            for a, why in saved_residuals(_loss(model, g), params, g.loc, g.node_feat)
            if not why.startswith("from ")]


@pytest.mark.parametrize("dtype", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_remat_keeps_what_the_edge_passes_return_and_nothing_else_of_edge_size(rng, lowering,
                                                                               dtype):
    g = _batch(rng, lowering)
    (B, E), N = g.row.shape, g.max_nodes
    assert E not in (B, N, H, 3, 3 + H + 1)
    model = _model(lowering, remat=True, compute_dtype=dtype)
    params = model.init(jax.random.PRNGKey(0), g)
    kept = _kept(model, params, g)
    cd = jnp.dtype(jnp.bfloat16 if dtype else jnp.float32)
    f32 = jnp.dtype(jnp.float32)
    named = sorted((s, d) for s, d, why in kept if "EdgeOps." in why)
    edge = [((B, E, H), cd), ((B, E, 3), f32)]
    if lowering == "blocked":
        # two sums and no count: autodiff keeps the named sums the backward
        # reads (the sum of translations enters x linearly, the last layer's
        # aggregated features reach no loss) and no other
        assert [v for v in named if E in v[0]] == sorted(edge * L)
        assert {v for v in named if E not in v[0]} <= {((B, N, 3), f32), ((B, N, H), f32)}
    else:
        assert named == sorted((edge + [((B, N, 3 + H + 1), f32)]) * L)
    # nothing else with an edge axis lives from the forward to the backward
    assert sorted((s, d) for s, d, _ in kept if E in s) == sorted(edge * L)
    # while without remat the MLPs' edge-sized activations do
    kept = _kept(_model(lowering, compute_dtype=dtype), params, g)
    assert sum(E in s for s, _, _ in kept) > 10 * L


@pytest.mark.parametrize("dtype", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("lowering", ["scatter", "cumsum", "ell"])
def test_remat_equals_no_remat(rng, lowering, dtype):
    """Loss and gradients: the kept values are the values the recompute
    would make. In f32 both agree to round-off and no closer (XLA fuses a
    checkpointed layer differently, and did with the whole layer recomputed:
    3e-8 of the largest gradient, the loss an ulp under ``ell``);
    bf16 within the band of
    ``test_fastegnn_bf16_compute_within_band_of_separate`` (the blocked path:
    tests/test_blocked.py::test_remat_same_outputs_and_grads)."""
    g = _batch(rng, lowering)
    plain, remat = (_model(lowering, compute_dtype=dtype, remat=r) for r in (False, True))
    params = plain.init(jax.random.PRNGKey(0), g)
    value_and_grad = lambda m: jax.value_and_grad(_loss(m, g), argnums=(0, 1, 2))(
        params, g.loc, g.node_feat)
    (ref_loss, ref), (loss, got) = value_and_grad(plain), value_and_grad(remat)
    flat = lambda t: np.asarray(jax.flatten_util.ravel_pytree(t)[0], np.float32)
    a, b = flat(got), flat(ref)
    tol = 1e-6 if dtype is None else 3e-2
    np.testing.assert_allclose(loss, ref_loss, rtol=tol)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_names_lower_to_nothing_without_remat(rng, lowering, monkeypatch):
    """``remat: false`` (the n-body configuration): the gradient's lowered
    text is the text of the program with no name in it, but for the numbers
    the lowering gives its private functions (``@silu_52``)."""
    from distegnn_tpu.ops import blocked

    g = _batch(rng, lowering)
    model = _model(lowering)
    params = model.init(jax.random.PRNGKey(0), g)
    text = lambda: re.sub(r"@(\w+?)_\d+\b", r"@\1", jax.jit(jax.grad(
        _loss(model, g), argnums=(0, 1, 2))).lower(params, g.loc, g.node_feat).as_text())
    named = text()
    calls = []
    monkeypatch.setattr(blocked, "checkpoint_name", lambda x, name: calls.append(name) or x)
    assert text() == named
    assert sorted(set(calls)) == sorted(blocked.REMAT_KEPT)


@pytest.mark.parametrize("dtype", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_remat_saved_bytes_gauge(rng, lowering, dtype):
    """``model/remat_saved_bytes``: set as FastEGNN is traced, to the bytes
    of the named residuals (the formula of docs/OBSERVABILITY.md on a plain
    batch); 0 with remat off."""
    g = _batch(rng, lowering)
    (B, E), N = g.row.shape, g.max_nodes
    gauge = obs.get_registry().gauge("model/remat_saved_bytes")
    model = _model(lowering, remat=True, compute_dtype=dtype)
    params = model.init(jax.random.PRNGKey(0), g)
    gauge.set(-1)
    kept = _kept(model, params, g)
    named = sum(int(np.prod(s)) * d.itemsize for s, d, why in kept if "EdgeOps." in why)
    per_edge = H * (2 if dtype else 4) + 12
    if lowering == "blocked":
        # the gauge counts what is named; of a blocked batch's two sums
        # autodiff keeps those the backward reads
        assert L * B * E * per_edge <= named <= gauge.value
        assert gauge.value == L * B * (E * per_edge + N * (3 + H) * 4)
    else:
        assert gauge.value == named == L * B * (E * per_edge + N * (3 + H + 1) * 4)
    jax.make_jaxpr(lambda p: _model(lowering, compute_dtype=dtype).apply(p, g))(params)
    assert gauge.value == 0


@pytest.mark.parametrize("lowering,passes", [("scatter", 2 * L), ("cumsum", 2 * L),
                                             ("ell", 2 * L), ("blocked", 4 * L)])
def test_gather_passes_counter(rng, lowering, passes):
    """``edge/gather_passes`` counts the gathers EdgeOps emits while a
    program is traced: 2 a layer packed, 4 a layer on a blocked batch,
    which keeps its separate calls."""
    g = _batch(rng, lowering)
    model = _model(lowering)
    params = model.init(jax.random.PRNGKey(0), g)
    counter = obs.get_registry().counter("edge/gather_passes")
    before = counter.value
    jax.make_jaxpr(lambda p: model.apply(p, g))(params)
    assert counter.value - before == passes


def test_param_tree_is_the_unpacked_one():
    """A checkpoint of the separate form loads: phi_e keeps its fused first
    kernel, bias and inner Dense, and the benchmark's flat weights
    (benchmarks/weights.py, untouched) still map onto the whole tree."""
    from benchmarks import weights
    from benchmarks.drivers import common

    rng = np.random.default_rng(0)
    g = _batch(rng, "scatter")
    model = FastEGNN(**MODEL)
    shapes = lambda t: jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), t)
    tree = shapes(model.init(jax.random.PRNGKey(0), g))
    assert tree["params"]["gcl_1"]["phi_e"] == {
        "kernel": ((2 * H + 3, H), "float32"), "bias": ((H,), "float32"),
        "TorchDense_0": {"Dense_0": {"kernel": ((H, H), "float32"), "bias": ((H,), "float32")}}}
    dims = {k: MODEL[k] for k in ("hidden_nf", "n_layers", "virtual_channels", "node_feat_nf",
                                  "edge_attr_nf")} | {"node_attr_nf": 0}
    loaded = common.to_tree(weights.make_weights(0, dims))
    assert shapes(loaded) == tree
    loc, X = model.apply(loaded, g)
    assert np.isfinite(np.asarray(loc)).all() and np.isfinite(np.asarray(X)).all()
