"""SELL (sliced-ELL) kernel tests (ops/sell.py): the degree-sorted
tiered format behind the folded single-chip execution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import sparse

from arrow_matrix_tpu.obs import metrics as metrics_mod
from arrow_matrix_tpu.ops import ell
from arrow_matrix_tpu.ops.sell import (
    SellMatrix,
    sell_from_csr,
    sell_spmm_t,
    tier_boundaries,
)
from arrow_matrix_tpu.utils import barabasi_albert, random_dense
from arrow_matrix_tpu.utils.graphs import random_csr


def spmm_via_sell(a, x, **kw):
    sell, order = sell_from_csr(a, **kw)
    y = x[order] if x.shape[0] == sell.n_rows else None
    assert y is not None
    out_sorted = np.asarray(sell_spmm_t(sell, jnp.asarray(y.T)))
    out = np.empty_like(out_sorted.T)
    out[order] = out_sorted.T
    return out, sell


def test_tier_boundaries():
    deg = np.array([0, 0, 8, 8, 8, 16, 24, 64, 64])
    starts = tier_boundaries(deg, growth=1.5)
    # zero tier, [8..8], [16..24], [64..64]
    assert starts == [0, 2, 5, 7]
    assert tier_boundaries(np.array([], dtype=np.int64)) == [0]
    assert tier_boundaries(np.array([8, 8, 8])) == [0]


def test_sell_matches_scipy_weighted():
    rng = np.random.default_rng(0)
    a = sparse.random(300, 300, density=0.03, format="csr",
                      random_state=rng, dtype=np.float32)
    a = a.tolil()
    a[7, :] = rng.standard_normal(300).astype(np.float32)  # hub row
    a[0, :] = 0.0                                          # empty row
    a = a.tocsr()
    a.sum_duplicates()
    a.sort_indices()
    x = random_dense(300, 8, seed=1)
    out, sell = spmm_via_sell(a, x)
    assert not sell.binary
    np.testing.assert_allclose(out, a @ x, rtol=1e-4, atol=1e-5)


def test_sell_binary_detection_and_padding_bound():
    a = barabasi_albert(2000, 6, seed=3)
    x = random_dense(2048, 8, seed=2)
    out, sell = spmm_via_sell(a, x[:2000], pad_rows_to=None)
    assert sell.binary
    np.testing.assert_allclose(out, a @ x[:2000], rtol=1e-5, atol=1e-5)
    # Padded gather slots bounded by growth x nnz (+ slot alignment).
    align_bound = 8 * 2000
    assert sell.n_slots <= 1.5 * a.nnz + align_bound


def test_sell_pad_rows_and_budget_chunking():
    a = barabasi_albert(100, 3, seed=4)
    trip = (None, a.indices, a.indptr)   # implicit-ones triplet
    sell, order = sell_from_csr(trip, pad_rows_to=128)
    assert sell.n_rows == 128
    x = random_dense(128, 4, seed=3)
    y = x[order]
    # Tiny budget forces slot chunking inside every tier.
    out_sorted = np.asarray(sell_spmm_t(sell, jnp.asarray(y.T),
                                        gather_budget=1 << 12))
    out = np.empty_like(x)
    out[order] = out_sorted.T
    np.testing.assert_allclose(out[:100], a @ x[:100], rtol=1e-5, atol=1e-5)
    assert np.all(out[100:] == 0)


def test_sell_binary_forced_on_weighted_raises():
    a = random_csr(64, 64, 4, seed=3)
    with pytest.raises(ValueError, match="binary"):
        sell_from_csr(a, binary=True)


def test_fold_rejected_by_propagation_models():
    """fold is step/run-only: the flat-feature model drivers must
    reject it up front instead of mis-broadcasting."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.models.propagation import pagerank
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    a = barabasi_albert(128, 3, seed=1)
    levels = arrow_decomposition(a, 16, max_levels=2, block_diagonal=True,
                                 seed=0)
    ml = MultiLevelArrow(levels, 16, mesh=None, fmt="fold")
    with pytest.raises(ValueError, match="fold"):
        pagerank(ml, iterations=1)
    with pytest.raises(ValueError, match="fold"):
        ml.real_row_mask()


def test_power_iteration_on_fold():
    """power_iteration is layout-agnostic: the folded executor's
    feature-major carriage works through step + whole-array reductions."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.models.propagation import power_iteration
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    a = barabasi_albert(200, 4, seed=7)
    levels = arrow_decomposition(a, 16, max_levels=3, block_diagonal=True,
                                 seed=0)
    x0 = np.ones((200, 1), dtype=np.float32)
    mlf = MultiLevelArrow(levels, 16, mesh=None, fmt="fold")
    mle = MultiLevelArrow(levels, 16, mesh=None, fmt="ell")
    vf, lf = power_iteration(mlf, x0, iterations=30)
    ve, le = power_iteration(mle, x0, iterations=30)
    assert abs(lf - le) < 1e-3 * abs(le)
    np.testing.assert_allclose(np.abs(vf), np.abs(ve), rtol=1e-3, atol=1e-4)


def test_fold_from_memmapped_artifact(tmp_path):
    """fold consumes memmapped CsrLike triplet levels (implicit-ones
    data) straight from an on-disk artifact."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.io import (
        as_levels,
        load_decomposition,
        load_level_widths,
        save_decomposition,
    )
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    a = barabasi_albert(600, 3, seed=5)
    levels = arrow_decomposition(a, 64, max_levels=3, block_diagonal=True,
                                 seed=5)
    base = str(tmp_path / "g")
    save_decomposition(levels, base)
    loaded = load_decomposition(base, 64, mem_map=True)
    widths = load_level_widths(base, 64)
    stream_levels = as_levels(loaded, widths if widths is not None else 64,
                              materialize=False)
    assert not hasattr(stream_levels[0].matrix, "nnz")  # triplet, not CSR

    ml = MultiLevelArrow(stream_levels, 64, mesh=None, fmt="fold")
    assert ml.blocks[0].binary          # implicit-ones artifact data
    x = random_dense(600, 8, seed=2)
    out = ml.gather_result(ml.step(ml.set_features(x)))
    np.testing.assert_allclose(out, a @ x, rtol=1e-4, atol=1e-4)


# -- lane-packed gathers at k < 128 -------------------------------------

@pytest.fixture
def registry(monkeypatch):
    """A metrics registry of the test's own, restored after."""
    monkeypatch.setattr(metrics_mod, "_DEFAULT", metrics_mod.MetricsRegistry())
    return metrics_mod.get_registry()


# A gather of whole 128-lane rows of the packed operand, as a jaxpr
# prints it (the k-wide gather takes (k, 1) columns of x_t).
PACKED_GATHER = "slice_sizes=(1, 128)"


def _packed_case(binary: bool) -> sparse.csr_matrix:
    """301 rows (a multiple of no p = 128 / k > 1), positive values, a
    hub row in a tier of its own and an empty row."""
    rng = np.random.default_rng(11)
    n = 301
    a = sparse.random(n, n, density=0.03, format="lil", random_state=rng,
                      dtype=np.float32)
    a[5, :] = rng.random(n, dtype=np.float32) + 0.5
    a[0, :] = 0.0
    a = a.tocsr()
    a.eliminate_zeros()
    if binary:
        a.data[:] = 1.0
    return a


def _sorted_spmm(sell, xt, chunk):
    return np.asarray(sell_spmm_t(sell, xt, chunk=chunk), dtype=np.float32)


@pytest.mark.parametrize("chunk", [None, 1, 8])
@pytest.mark.parametrize("binary", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("k", [1, 8, 16, 32, 64])
def test_lane_packed_gather(monkeypatch, registry, k, dtype, binary, chunk):
    """At k dividing 128 every tier gathers whole packed rows and keeps
    each slot's own lanes: the result is the k-wide gather's, bit for
    bit (the same addends summed in the same order), and the float64
    product's to rounding."""
    a = _packed_case(binary)
    sell, order = sell_from_csr(a)
    assert sell.binary == binary
    assert max(c.shape[0] for c in sell.cols) > 8     # chunk 8 scans
    x = random_dense(a.shape[0], k, seed=k)
    xt = jnp.asarray(x[order].T, dtype=dtype)

    jaxpr = str(jax.make_jaxpr(lambda v: sell_spmm_t(sell, v, chunk=chunk))(xt))
    assert PACKED_GATHER in jaxpr                     # whole-row gathers
    # The select must not round f32 to bf16 on the chip's MXU.
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in jaxpr
    assert registry.gauge("sell.packed_slots").value == sell.n_slots
    got = _sorted_spmm(sell, xt, chunk)

    monkeypatch.setattr(ell, "lane_pack_factor", lambda k: 1)
    today = _sorted_spmm(sell, xt, chunk)
    assert registry.gauge("sell.packed_slots").value == 0

    # The float64 product of the carried (possibly bf16-rounded) x, in
    # the sorted coordinates the operator runs in.
    x64 = np.asarray(xt.astype(jnp.float32), dtype=np.float64).T
    want = (a.astype(np.float64) @ x64[order.argsort()])[order].T
    np.testing.assert_array_equal(got, today)
    rel = 1e-5 if dtype == "float32" else 2.0 ** -7
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


@pytest.mark.parametrize("k", [48, 128])
def test_lane_pack_needs_k_dividing_the_tile(registry, k):
    """k = 128, or k not dividing 128, keeps the k-wide gather: no
    packed gather or select matmul in the program, and a zero gauge."""
    a = _packed_case(True)
    sell, order = sell_from_csr(a)
    x = random_dense(a.shape[0], k, seed=3)
    xt = jnp.asarray(x[order].T)
    jaxpr = str(jax.make_jaxpr(lambda v: sell_spmm_t(sell, v))(xt))
    assert PACKED_GATHER not in jaxpr and "dot_general" not in jaxpr
    assert registry.gauge("sell.packed_slots").value == 0
    out = np.empty_like(x)
    out[order] = np.asarray(sell_spmm_t(sell, xt)).T
    want = a @ x
    assert np.abs(out - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("k", [1, 16, 64])
def test_packed_tier_chunks_as_at_k128(k):
    """The gather intermediate is budgeted at its physical 128 lanes: a
    2^22-row, 8-slot tier under the fold's budget on a 16 GB v5e (half
    its HBM, ``gather_budget_for``) runs as single-slot gathers at any
    packed k, as at k=128, on one chip and on a mesh stack alike."""
    from arrow_matrix_tpu.parallel.multi_level import gather_budget_for
    from arrow_matrix_tpu.parallel.sell_slim import SellShardStack, tier_chunks

    budget = gather_budget_for(8 << 30)
    rows, m = 1 << 22, 8
    assert ell.feature_major_chunk(rows, 128, m, budget) == 1
    assert ell.feature_major_chunk(rows, k, m, budget) == 1
    stack = SellShardStack(
        cols=(jax.ShapeDtypeStruct((4, m, rows), jnp.int32),),
        deg=(jax.ShapeDtypeStruct((4, rows), jnp.int32),))
    assert (tier_chunks(stack, k, 4, budget)
            == tier_chunks(stack, 128, 4, budget) == [(m, rows, 1)])
