"""HYB (split-ELL) whole-level kernel tests (ops/hyb.py): the
single-chip general SpMM replacing arrow blocking within one device
(the role of the reference's per-rank cuSPARSE CSRMM, sp2cp.py:6-16)."""

import jax.numpy as jnp
import numpy as np
import pytest
from scipy import sparse

from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
from arrow_matrix_tpu.ops.hyb import (
    HybLevel,
    choose_light_slots,
    hyb_from_csr,
    hyb_spmm,
)
from arrow_matrix_tpu.parallel import MultiLevelArrow, make_mesh
from arrow_matrix_tpu.utils import barabasi_albert, random_dense


def test_choose_light_slots():
    deg = np.array([1, 2, 3, 100, 200])
    # cap=2 heavy rows: m0 covers the 3rd largest (3), aligned to 8.
    assert choose_light_slots(deg, heavy_cap=2) == 8
    assert choose_light_slots(deg, heavy_cap=0) == 200
    assert choose_light_slots(np.array([], dtype=np.int64), 4) == 0


@pytest.mark.parametrize("chunk", [None, 8])
def test_hyb_spmm_matches_scipy(chunk):
    rng = np.random.default_rng(0)
    a = sparse.random(200, 200, density=0.05, format="csr",
                      random_state=rng, dtype=np.float32)
    # Inject two hub rows so the heavy path is exercised.
    a = a.tolil()
    a[7, :] = rng.standard_normal(200).astype(np.float32)
    a[123, ::2] = 1.0
    a = a.tocsr()
    a.sum_duplicates()
    a.sort_indices()

    h = hyb_from_csr(a, heavy_cap=4)
    assert h.heavy_idx.shape[0] >= 2
    x = random_dense(200, 8, seed=1)
    out = np.asarray(hyb_spmm(h, jnp.asarray(x), chunk=chunk))
    np.testing.assert_allclose(out, a @ x, rtol=1e-4, atol=1e-5)


def test_hyb_row_padding():
    a = sparse.identity(10, format="csr", dtype=np.float32)
    h = hyb_from_csr(a, pad_rows_to=16)
    x = random_dense(16, 4, seed=2)
    out = np.asarray(hyb_spmm(h, jnp.asarray(x)))
    assert out.shape == (16, 4)
    np.testing.assert_allclose(out[:10], x[:10], rtol=1e-6, atol=1e-6)
    assert np.all(out[10:] == 0)


def test_hyb_implicit_ones_triplet():
    a = barabasi_albert(100, 3, seed=4)
    trip = (None, a.indices, a.indptr)   # memmap-style implicit data
    h = hyb_from_csr(trip)
    x = random_dense(100, 4, seed=3)
    out = np.asarray(hyb_spmm(h, jnp.asarray(x)))
    np.testing.assert_allclose(out, a.astype(np.float32) @ x,
                               rtol=1e-5, atol=1e-5)


def test_multi_level_hyb_matches_golden():
    """fmt='hyb' end-to-end, including a grown last level whose arrow
    blocking would be pathological (the protocol-scale finding)."""
    n, width = 480, 32
    a = barabasi_albert(n, 6, seed=19)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=2)
    assert levels[-1].arrow_width > width  # grown last level
    ml = MultiLevelArrow(levels, width, mesh=None, fmt="hyb")
    assert all(isinstance(b, HybLevel) for b in ml.blocks)
    x_host = random_dense(n, 8, seed=3)
    out = ml.gather_result(ml.step(ml.set_features(x_host)))
    np.testing.assert_allclose(out, decomposition_spmm(levels, x_host),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(out, a @ x_host, rtol=1e-3, atol=1e-3)

    # Iterated run (lax.scan) works over HybLevel pytrees too.
    a2 = (a / 8.0).tocsr().astype(np.float32)
    levels2 = arrow_decomposition(a2, width, max_levels=2,
                                  block_diagonal=True, seed=2)
    ml2 = MultiLevelArrow(levels2, width, mesh=None, fmt="hyb")
    xd = ml2.run(ml2.set_features(x_host), 3)
    want = x_host
    for _ in range(3):
        want = a2 @ want
    np.testing.assert_allclose(ml2.gather_result(xd), want,
                               rtol=1e-3, atol=1e-4)


def test_hyb_rejected_on_mesh():
    a = barabasi_albert(128, 3, seed=1)
    levels = arrow_decomposition(a, 16, max_levels=2, block_diagonal=True,
                                 seed=0)
    with pytest.raises(ValueError, match="single-chip"):
        MultiLevelArrow(levels, 16, mesh=make_mesh((8,), ("blocks",)),
                        fmt="hyb")


def test_binary_hyb_detected_and_exact():
    """Binary (implicit-ones) HYB: adjacency data is all ones, so the
    data arrays are dropped and a per-row degree mask replaces the
    multiply.  Must be bit-identical to the f32 path (the mask selects
    the same addends in the same slot order)."""
    a = barabasi_albert(300, 5, seed=11)
    assert np.all(a.data == 1.0)
    hb = hyb_from_csr(a)                      # auto-detects binary
    hf = hyb_from_csr(a, binary=False)
    assert hb.light_data is None and hb.light_deg is not None
    assert hf.light_data is not None and hf.light_deg is None
    # ~half the resident bytes on the light part.
    assert hb.device_nbytes() < 0.6 * hf.device_nbytes()
    x = random_dense(300, 8, seed=5)
    out_b = np.asarray(hyb_spmm(hb, jnp.asarray(x)))
    out_f = np.asarray(hyb_spmm(hf, jnp.asarray(x)))
    np.testing.assert_array_equal(out_b, out_f)
    np.testing.assert_allclose(out_b, a @ x, rtol=1e-5, atol=1e-5)


def test_binary_hyb_chunked_and_padded():
    a = barabasi_albert(200, 4, seed=13)
    h = hyb_from_csr(a, pad_rows_to=256, heavy_cap=4)
    assert h.light_data is None
    x = random_dense(256, 4, seed=6)
    out = np.asarray(hyb_spmm(h, jnp.asarray(x), chunk=8))
    np.testing.assert_allclose(out[:200], a @ x[:200], rtol=1e-5, atol=1e-5)
    assert np.all(out[200:] == 0)


def test_binary_rejected_on_weighted_matrix():
    """Non-unit data must NOT take the binary path under binary='auto',
    and must raise when binary is forced."""
    from arrow_matrix_tpu.utils.graphs import random_csr

    a = random_csr(64, 64, 4, seed=3)
    assert not np.all(a.data == 1.0)
    h = hyb_from_csr(a)
    assert h.light_data is not None
    with pytest.raises(ValueError, match="binary"):
        hyb_from_csr(a, binary=True)


def test_multi_level_hyb_binary_end_to_end():
    n, width = 480, 32
    a = barabasi_albert(n, 6, seed=19)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=2)
    ml = MultiLevelArrow(levels, width, mesh=None, fmt="hyb")
    assert all(b.light_data is None for b in ml.blocks)
    x_host = random_dense(n, 8, seed=3)
    out = ml.gather_result(ml.step(ml.set_features(x_host)))
    np.testing.assert_allclose(out, a @ x_host, rtol=1e-3, atol=1e-3)


def test_fold_matches_golden_and_iterates():
    """fmt='fold': the whole decomposition composed into one operator
    (exact edge partition => A reconstructed in level-0 order)."""
    n, width = 480, 32
    a = barabasi_albert(n, 6, seed=19)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    assert len(levels) >= 2
    ml = MultiLevelArrow(levels, width, mesh=None, fmt="fold")
    assert ml.fmts == ["fold"]
    assert ml.blocks[0].binary          # adjacency folds to binary
    x_host = random_dense(n, 8, seed=3)
    xd = ml.set_features(x_host)
    assert xd.shape[0] == 8             # feature-major carriage
    out = ml.gather_result(ml.step(xd))
    np.testing.assert_allclose(out, decomposition_spmm(levels, x_host),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out, a @ x_host, rtol=1e-4, atol=1e-4)

    # Iterated scan run, weighted (non-binary) matrix.
    a2 = (a / 8.0).tocsr().astype(np.float32)
    levels2 = arrow_decomposition(a2, width, max_levels=3,
                                  block_diagonal=True, seed=2)
    ml2 = MultiLevelArrow(levels2, width, mesh=None, fmt="fold")
    assert not ml2.blocks[0].binary
    xd2 = ml2.run(ml2.set_features(x_host), 3)
    want = x_host
    for _ in range(3):
        want = a2 @ want
    np.testing.assert_allclose(ml2.gather_result(xd2), want,
                               rtol=1e-3, atol=1e-4)


def test_fold_export_load_roundtrip(tmp_path):
    """export_folded -> load_folded must rebuild a fold executor whose
    step is BIT-identical (same packed arrays, same carried
    permutation) without the source decomposition — the offline-pack /
    online-load split the 2^27 on-chip stage depends on.  Covers the
    binary, weighted, and bf16-carriage variants plus the donated-scan
    run path."""
    n, width = 480, 32
    a = barabasi_albert(n, 6, seed=19)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    x_host = random_dense(n, 8, seed=3)
    for tag, kw, mat in (("bin", {}, a),
                         ("bf16", {"feature_dtype": "bf16"}, a),
                         ("wgt", {}, (a / 8.0).tocsr().astype(np.float32))):
        lv = levels if mat is a else arrow_decomposition(
            mat, width, max_levels=3, block_diagonal=True, seed=2)
        ml = MultiLevelArrow(lv, width, mesh=None, fmt="fold", **kw)
        d = tmp_path / tag
        ml.export_folded(str(d))
        ml2 = MultiLevelArrow.load_folded(str(d))
        assert ml2.feature_dtype == ml.feature_dtype
        assert ml2.blocks[0].binary == ml.blocks[0].binary
        np.testing.assert_array_equal(ml2.perm0, ml.perm0)
        want = np.asarray(ml.step(ml.set_features(x_host)))
        got = np.asarray(ml2.step(ml2.set_features(x_host)))
        np.testing.assert_array_equal(got, want, err_msg=tag)
        # donated scan run agrees with the plain run
        r1 = np.asarray(ml.run(ml.set_features(x_host), 2))
        r2 = np.asarray(ml2.run(ml2.set_features(x_host), 2,
                                donate=True))
        np.testing.assert_array_equal(r1, r2, err_msg=tag)


def test_fold_tight_packing_matches_golden():
    """fold_align=1 / fold_growth=1.1 (the 'fold_tight' bench
    candidate): fewer padded slots than tiers aligned to 8, the same
    addends — tile padding costs no gathers, logical slots do
    (ops/sell.py)."""
    n, width = 480, 32
    a = barabasi_albert(n, 6, seed=19)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    ml = MultiLevelArrow(levels, width, mesh=None, fmt="fold",
                         fold_align=8)
    tight = MultiLevelArrow(levels, width, mesh=None, fmt="fold",
                            fold_growth=1.1, fold_align=1)
    assert tight.blocks[0].n_slots < ml.blocks[0].n_slots
    x_host = random_dense(n, 8, seed=3)
    out = tight.gather_result(tight.step(tight.set_features(x_host)))
    np.testing.assert_allclose(out, decomposition_spmm(levels, x_host),
                               rtol=1e-4, atol=1e-4)
    # Same addends, different tiering: agree to f32 reassociation.
    ref = ml.gather_result(ml.step(ml.set_features(x_host)))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_fold_bf16_features():
    """feature_dtype='bf16' halves the carried-feature bytes (the
    k=128 amortization lever) with f32 accumulation: results track the
    f32 path to bf16 rounding, and the carriage dtype is bf16."""
    import ml_dtypes

    n, width = 480, 32
    a = barabasi_albert(n, 6, seed=19)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    x_host = random_dense(n, 8, seed=3)
    want = decomposition_spmm(levels, x_host)

    ml = MultiLevelArrow(levels, width, mesh=None, fmt="fold",
                         feature_dtype="bf16")
    xd = ml.set_features(x_host)
    assert xd.dtype == ml_dtypes.bfloat16
    out = ml.gather_result(ml.step(xd))
    assert out.dtype == np.float32
    rel = (np.linalg.norm(out - want) / np.linalg.norm(want))
    assert rel < 2e-2, rel          # bf16 inputs: ~8-bit mantissa

    # Other formats must refuse (carriage stays f32 there).
    with pytest.raises(ValueError, match="feature_dtype"):
        MultiLevelArrow(levels, width, mesh=None, fmt="hyb",
                        feature_dtype="bf16")


def test_fold_equals_per_level_paths():
    """fold and the per-level hyb/ell paths are the same operator."""
    n, width = 320, 32
    a = barabasi_albert(n, 4, seed=23)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=1)
    x_host = random_dense(n, 4, seed=9)
    outs = {}
    for f in ("fold", "hyb", "ell"):
        ml = MultiLevelArrow(levels, width, mesh=None, fmt=f)
        outs[f] = ml.gather_result(ml.step(ml.set_features(x_host)))
    np.testing.assert_allclose(outs["fold"], outs["ell"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs["hyb"], outs["ell"],
                               rtol=1e-4, atol=1e-5)


def test_fold_rejected_on_mesh():
    a = barabasi_albert(128, 3, seed=1)
    levels = arrow_decomposition(a, 16, max_levels=2, block_diagonal=True,
                                 seed=0)
    with pytest.raises(ValueError, match="single-chip"):
        MultiLevelArrow(levels, 16, mesh=make_mesh((8,), ("blocks",)),
                        fmt="fold")
