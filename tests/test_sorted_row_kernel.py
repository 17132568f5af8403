"""The sorted segment sum as one Pallas kernel (ops/row_sum.py), interpreted
on the CPU: sums against ``segment_sum`` (empty blocks, segments longer than
a tile, an edge count no tile divides, a batch whose padding rows sit at slot
N-1, f32 over a wide range, bf16 into f32), the ``custom_vjp``'s gradient
against autodiff of the scatter-add, the counter ``edge/row_sum_kernel``, one
FastEGNN's forward and gradient with and without the kernel, and the kernel
compiled for a described v5e at the cells' width."""

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest

from distegnn_tpu import obs
from distegnn_tpu.models.fast_egnn import FastEGNN
from distegnn_tpu.ops import row_sum, segment
from distegnn_tpu.ops.graph import pad_graphs
from distegnn_tpu.ops.segment import gather_rows_sorted, segment_sum, sorted_row_sum

ULP = 2.0 ** -24


def _force_kernel(monkeypatch):
    """``sorted_row_sum`` takes the kernel whatever the backend and size.
    Traces are cached (a rematted layer's by ``jax.checkpoint``), so they
    are dropped on the way in, and on the way out by the fixture below."""
    jax.clear_caches()
    monkeypatch.setattr(segment, "_row_sum_kernel_engages", lambda rows: True)


@pytest.fixture
def kernel(monkeypatch):
    _force_kernel(monkeypatch)
    yield
    jax.clear_caches()


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _worst_ulp(got, data, ids, n):
    """Worst element's error against the float64 sum, in ulp of the
    segment's sum of magnitudes."""
    d = np.asarray(data, np.float64)
    want = np.zeros((n,) + d.shape[1:]); np.add.at(want, ids, d)
    scale = np.zeros_like(want); np.add.at(scale, ids, np.abs(d))
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.maximum(scale, 1e-30))) / ULP


def _rows(rng, B, E, N, pad=0):
    """Ascending rows a graph, the last ``pad`` at slot N-1 (the loader's
    padding rows)."""
    rows = np.sort(rng.integers(0, N - 1 if pad else N, size=(B, E)), axis=1)
    if pad:
        rows[:, E - pad:] = N - 1
    return rows.astype(np.int32)


@pytest.mark.parametrize("case,B,E,N,tile,block", [
    ("empty_blocks", 1, 1024, 5000, 256, 128),     # most blocks get no id
    ("segment_over_tiles", 1, 2048, 6, 256, 128),  # a segment spans several tiles
    ("ragged_tail", 1, 1500, 300, 512, 128),       # no tile divides the edges
    ("batch_ragged", 3, 700, 90, 256, 128),        # each graph's last tile partial
    ("one_block", 2, 512, 40, 256, 128),           # the batch's nodes in one block
])
def test_kernel_matches_segment_sum(rng, case, B, E, N, tile, block):
    rows = _rows(rng, B, E, N, pad=E // 20)
    data = rng.standard_normal((B, E, 5)).astype(np.float32)
    got = row_sum.row_sum(jnp.asarray(data), jnp.asarray(rows), N, tile=tile,
                          block=block)
    want = jax.vmap(lambda d, r: segment_sum(d, r, N))(jnp.asarray(data), jnp.asarray(rows))
    assert got.shape == (B, N, 5) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for b in range(B):
        empty = np.setdiff1d(np.arange(N), rows[b])
        assert not np.asarray(got[b])[empty].any()
    ids = (rows + np.arange(B)[:, None] * N).reshape(-1)
    assert _worst_ulp(got.reshape(B * N, 5), data.reshape(-1, 5), ids, B * N) <= 8


def test_visits_are_sorted_by_block_and_cover_every_block(rng):
    rows, n, tile, block = 5000, 3000, 256, 128
    ids = np.sort(rng.integers(0, n, size=rows)).astype(np.int32)
    first, last = ids[::tile], np.append(ids[tile - 1::tile], ids[-1])[:len(ids[::tile])]
    tiles, blocks, valid = map(np.asarray, row_sum.visits(jnp.asarray(first),
                                                          jnp.asarray(last), n, block))
    n_tiles, n_blocks = -(-rows // tile), -(-n // block)
    assert tiles.shape == (n_tiles + n_blocks,)
    real = valid.astype(bool)
    assert np.all(np.diff(blocks) >= 0) and set(blocks[real]) == set(range(n_blocks))
    # every (tile, block) pair an id needs is visited
    need = {(e // tile, ids[e] // block) for e in range(rows)}
    assert need <= set(zip(tiles[real], blocks[real]))
    assert np.all(tiles < n_tiles) and np.all(blocks < n_blocks)


def test_wide_range_f32_within_a_few_ulp_and_bf16_accumulates_in_f32(rng):
    E, N = 3000, 400
    rows = _rows(rng, 1, E, N)
    data = (rng.standard_normal((1, E, 68))
            * np.exp(rng.uniform(-30, 30, (1, E, 1)))).astype(np.float32)
    got = row_sum.row_sum(jnp.asarray(data), jnp.asarray(rows), N)
    assert _worst_ulp(got[0], data[0], rows[0], N) <= 8
    # bf16 data: the sums are f32's, not bf16's (a bf16 sum of 8+ ones of
    # magnitude 1 would round)
    ones = jnp.ones((1, E, 3), jnp.bfloat16) + jnp.asarray(
        rng.integers(0, 2, (1, E, 3)) * 2.0 ** -7, jnp.bfloat16)
    got = row_sum.row_sum(ones, jnp.asarray(rows), N)
    assert got.dtype == jnp.float32
    assert _worst_ulp(got[0], np.asarray(ones[0], np.float32), rows[0], N) <= 8


def test_batched_entry_point_with_padding_rows(rng, kernel):
    B, E, N, F = 3, 700, 90, 67
    rows = jnp.asarray(_rows(rng, B, E, N, pad=40))
    data = jnp.asarray(rng.standard_normal((B, E, F)), jnp.float32)
    got = sorted_row_sum(data, rows, N)
    want = jax.vmap(lambda d, r: segment_sum(d, r, N))(data, rows)
    assert got.shape == (B, N, F) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(got[:, N - 1]).max()) > 0          # padding lands on N-1
    # bf16 data into an f32 sum (the aggregation), into bf16 (a transpose)
    half = data.astype(jnp.bfloat16)
    assert sorted_row_sum(half, rows, N, jnp.float32).dtype == jnp.float32
    assert sorted_row_sum(half, rows, N).dtype == jnp.bfloat16


def test_custom_vjp_gradient_equals_autodiff_of_the_scatter(rng, kernel):
    B, E, N, F = 2, 600, 70, 6
    rows = jnp.asarray(_rows(rng, B, E, N, pad=30))
    data = jnp.asarray(rng.standard_normal((B, E, F)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((B, N, F)), jnp.float32)
    _, vjp = jax.vjp(lambda d: sorted_row_sum(d, rows, N, jnp.float32), data)
    _, ref = jax.vjp(lambda d: segment._scatter_row_sum(d, rows, N, jnp.float32), data)
    np.testing.assert_array_equal(vjp(ct)[0], ref(ct)[0])
    # and through gather_rows_sorted, whose backward is the kernel
    h = jnp.asarray(rng.standard_normal((B, N, F)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((B, E, F)), jnp.float32)
    got = jax.vjp(lambda t: gather_rows_sorted(t, rows), h)[1](g)[0]
    want = jax.vjp(lambda t: jnp.take_along_axis(t, rows[..., None], axis=1), h)[1](g)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


H, L = 16, 2
MODEL = dict(node_feat_nf=2, edge_attr_nf=2, hidden_nf=H, virtual_channels=3, n_layers=L)


def _graphs(rng, sizes=(24, 17)):
    from distegnn_tpu.data import build_nbody_graph

    out = []
    for n in sizes:
        loc, vel = rng.normal(size=(n, 3)), rng.normal(size=(n, 3))
        out.append(build_nbody_graph(loc, vel, rng.choice([1.0, -1.0], size=(n, 1)),
                                     loc + 0.1 * vel, radius=-1.0))
    e = max(g["edge_index"].shape[1] for g in out)
    return pad_graphs(out, max_edges=e + 40)


def _loss(model, g):
    def f(params, x):
        loc, _ = model.apply(params, g.replace(loc=x))
        return jnp.sum((loc - g.target) ** 2 * g.node_mask[..., None])
    return f


# the cells' two model configurations: LargeFluid's bf16 MLPs under remat,
# n-body's (and Water-3D's) f32 without
CONFIGS = {"bf16_remat": dict(compute_dtype="bf16", remat=True),
           "f32": dict(compute_dtype="float32", remat=False)}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_row_sum_kernel_counter_reads_2L_a_step_program(rng, config, monkeypatch,
                                                        fresh_traces):
    """``edge/row_sum_kernel``: a traced training step (forward and
    gradient) takes the kernel ``2 x L`` times, the L aggregations and the L
    row transposes, under remat too (a rematted layer keeps the sum, and the
    transpose is traced once); 0 where it does not engage (the CPU here, and
    a TPU below the row minimum)."""
    g = _graphs(rng)
    model = FastEGNN(**MODEL, **CONFIGS[config])
    params = model.init(jax.random.PRNGKey(0), g)
    counter = obs.get_registry().counter("edge/row_sum_kernel")
    step = jax.grad(_loss(model, g))
    before = counter.value
    jax.make_jaxpr(step)(params, g.loc)
    assert counter.value - before == 0
    _force_kernel(monkeypatch)
    before = counter.value
    jax.make_jaxpr(step)(params, g.loc)
    assert counter.value - before == 2 * L


def test_the_kernel_engages_on_a_tpu_at_the_row_minimum(monkeypatch):
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "tpu")
    assert not segment._row_sum_kernel_engages(segment._MIN_KERNEL_ROWS - 1)
    assert segment._row_sum_kernel_engages(segment._MIN_KERNEL_ROWS)
    # all four cells' step batches are over it (B x E a chip)
    for rows in (1 * 1_639_040, 15 * 108_672, 250 * 9_984, 1 * 2_950_144):
        assert segment._row_sum_kernel_engages(rows)
    monkeypatch.setattr(segment.jax, "default_backend", lambda: "cpu")
    assert not segment._row_sum_kernel_engages(10 ** 9)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_fastegnn_forward_and_gradient_kernel_against_scatter(rng, config, monkeypatch,
                                                              fresh_traces):
    g = _graphs(rng)
    model = FastEGNN(**MODEL, **CONFIGS[config])
    params = model.init(jax.random.PRNGKey(0), g)

    def run():
        out = jax.jit(model.apply)(params, g)
        grad = jax.jit(jax.grad(_loss(model, g)))(params, g.loc)
        return out, np.asarray(jax.flatten_util.ravel_pytree(grad)[0], np.float32)

    ref_out, ref = run()
    _force_kernel(monkeypatch)
    counter = obs.get_registry().counter("edge/row_sum_kernel")
    before = counter.value
    out, got = run()
    assert counter.value > before
    tol = 1e-5 if config == "f32" else 3e-2
    for u, v in zip(out, ref_out):
        np.testing.assert_allclose(u, v, rtol=tol, atol=tol * float(jnp.abs(v).max()))
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * np.abs(ref).max())


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_compiles_for_a_described_v5e(one_chip, monkeypatch, dtype):
    """Mosaic's own compile, at the one-chip LargeFluid width, under the
    cells' ``jax_default_matmul_precision: highest`` (which made Mosaic
    refuse a bf16 pass that did not say its precision): forward and the
    gradient through ``sorted_row_sum``."""
    from distegnn_tpu import runtime
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(runtime, "use_interpret", lambda: False)
    _force_kernel(monkeypatch)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    B, E, N, F = 1, 40_960 + 384, 3_000, 68
    data = jax.ShapeDtypeStruct((B, E, F), jnp.dtype(dtype), sharding=one_chip)
    rows = jax.ShapeDtypeStruct((B, E), jnp.int32, sharding=one_chip)
    f = jax.value_and_grad(lambda d, r: jnp.sum(sorted_row_sum(d, r, N, jnp.float32)))
    try:
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(f).lower(data, rows).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        jax.clear_caches()       # nothing traced with interpret off is found later
    assert "tpu_custom_call" in compiled.as_text()
