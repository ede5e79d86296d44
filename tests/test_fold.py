"""Folded single-chip executor tests (``MultiLevelArrow(fmt="fold")``):
the whole decomposition composed into one SELL operator and stepped as
one SpMM (the role of the reference's per-rank cuSPARSE CSRMM,
sp2cp.py:6-16)."""

import dataclasses

import numpy as np
import pytest

from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
from arrow_matrix_tpu.ops.sell import resolve_binary, sell_from_csr
from arrow_matrix_tpu.parallel import MultiLevelArrow, make_mesh
from arrow_matrix_tpu.parallel.multi_level import gather_budget_for
from arrow_matrix_tpu.utils import barabasi_albert, random_dense


def test_binary_rejected_on_weighted_matrix():
    """Non-unit data must NOT take the binary path under binary='auto',
    and must raise when binary is forced."""
    from arrow_matrix_tpu.utils.graphs import random_csr

    a = random_csr(64, 64, 4, seed=3)
    assert not np.all(a.data == 1.0)
    assert resolve_binary("auto", a.data) is False
    assert not sell_from_csr(a)[0].binary
    with pytest.raises(ValueError, match="binary"):
        resolve_binary(True, a.data)
    assert resolve_binary("auto", None)      # implicit ones


def _memmapped_triplets(levels, tmp_path):
    """Each level as an implicit-ones ``(None, indices, indptr)``
    triplet of memmapped arrays, as a memmapped artifact loads."""
    out = []
    for i, lvl in enumerate(levels):
        trip = []
        for name in ("indices", "indptr"):
            path = tmp_path / f"l{i}_{name}.npy"
            np.save(path, getattr(lvl.matrix, name))
            trip.append(np.load(path, mmap_mode="r"))
        out.append(dataclasses.replace(lvl, matrix=(None, *trip)))
    return out


# Input kinds: BA adjacency (binary), weighted, the adjacency as
# implicit-ones memmapped triplets, a row count no block width divides
# (carriage rows padded past n), and weighted at a fixed slot chunk.
@pytest.mark.parametrize("case", ["adjacency", "weighted", "triplet",
                                  "padded", "weighted_chunk8"])
def test_fold_matches_golden_and_iterates(case, tmp_path):
    """fmt='fold': the whole decomposition composed into one operator
    (exact edge partition => A reconstructed in level-0 order); one
    step and a 3-step scan run against the host goldens."""
    n, width = (470 if case == "padded" else 480), 32
    a = barabasi_albert(n, 6, seed=19)
    if case.startswith("weighted"):
        a = (a / 8.0).tocsr().astype(np.float32)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    assert len(levels) >= 2
    fold_levels = (_memmapped_triplets(levels, tmp_path)
                   if case == "triplet" else levels)
    kw = {"chunk": 8} if case == "weighted_chunk8" else {}
    ml = MultiLevelArrow(fold_levels, width, mesh=None, fmt="fold", **kw)
    assert ml.fmts == ["fold"]
    # adjacency folds to binary, whatever form the levels come in
    assert ml.blocks[0].binary == (not case.startswith("weighted"))
    x_host = random_dense(n, 8, seed=3)
    xd = ml.set_features(x_host)
    assert xd.shape == (8, ml.total_rows)   # feature-major carriage
    yd = ml.step(xd)
    out = ml.gather_result(yd)
    np.testing.assert_allclose(out, decomposition_spmm(levels, x_host),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out, a @ x_host, rtol=1e-4, atol=1e-4)
    if case == "padded":
        assert ml.total_rows > n
        pad = np.asarray(ml.carried_mask())[0] == 0
        assert pad.any() and np.all(np.asarray(yd)[:, pad] == 0)

    # Iterated scan run.
    got = ml.gather_result(ml.run(ml.set_features(x_host), 3))
    want = x_host
    for _ in range(3):
        want = a @ want
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-5 * np.abs(want).max())


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
        MultiLevelArrow(levels, width, mesh=None, fmt="ell",
                        feature_dtype="bf16")


@pytest.mark.parametrize("fmt", ["ell", "dense"])
def test_fold_equals_per_level_paths(fmt):
    """fold and the per-level paths are the same operator."""
    n, width = 320, 32
    a = barabasi_albert(n, 4, seed=23)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=1)
    x_host = random_dense(n, 4, seed=9)
    outs = {}
    for f in ("fold", fmt):
        ml = MultiLevelArrow(levels, width, mesh=None, fmt=f)
        outs[f] = ml.gather_result(ml.step(ml.set_features(x_host)))
    np.testing.assert_allclose(outs["fold"], outs[fmt],
                               rtol=1e-4, atol=1e-5)


def test_fold_rejected_on_mesh():
    a = barabasi_albert(128, 3, seed=1)
    levels = arrow_decomposition(a, 16, max_levels=2, block_diagonal=True,
                                 seed=0)
    with pytest.raises(ValueError, match="single-chip"):
        MultiLevelArrow(levels, 16, mesh=make_mesh((8,), ("blocks",)),
                        fmt="fold")


@pytest.mark.parametrize("schedule", ["overlap_slabs", "repl"])
def test_one_chip_fold_refuses_mesh_schedules(schedule, tmp_path,
                                              monkeypatch):
    """The overlap slabs and the 2.5D replica groups split the features
    to hide or cut a mesh's exchanges; the one-chip fold has none, so
    it refuses both and names the mesh executors that take them."""
    if schedule == "overlap_slabs":
        a = barabasi_albert(128, 3, seed=1)
        levels = arrow_decomposition(a, 16, max_levels=2,
                                     block_diagonal=True, seed=0)
        with pytest.raises(ValueError, match="SellMultiLevel"):
            MultiLevelArrow(levels, 16, mesh=None, fmt="fold",
                            overlap_slabs=2)
        return
    from arrow_matrix_tpu.cli import spmm_arrow

    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit, match="SellMultiLevel"):
        spmm_arrow.main([
            "--vertices", "200", "--width", "16", "--features", "4",
            "--iterations", "1", "--device", "cpu",
            "--fmt", "fold", "--repl", "2",
            "--logdir", str(tmp_path / "logs")])


@pytest.mark.parametrize("k", [16, 128])
def test_load_folded_lowers_identically(k, tmp_path):
    """An executor rebuilt by ``load_folded`` runs the program of the
    executor it was exported from: ``run(x, 10)`` lowers to the same
    text."""
    a = barabasi_albert(480, 6, seed=19)
    levels = arrow_decomposition(a, 32, max_levels=3,
                                 block_diagonal=True, seed=2)
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold")
    ml.export_folded(str(tmp_path))
    ml2 = MultiLevelArrow.load_folded(
        str(tmp_path), gather_budget=gather_budget_for(ml.dense_budget))
    x_host = random_dense(480, k, seed=5)

    def lowered(ex):
        xd = ex.set_features(x_host)
        return ex._scan_steps_donated.lower(
            xd, ex.fwd, ex.bwd, ex.blocks, n=10).as_text()

    assert lowered(ml2) == lowered(ml)
