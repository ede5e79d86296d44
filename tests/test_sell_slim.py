"""SellSlim: the padding-free distributed slim layout (single matrix)
vs the scipy golden and the stacked slim layout."""

import numpy as np
import pytest
from scipy import sparse

from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.parallel import make_mesh
from arrow_matrix_tpu.parallel.sell_slim import SellSlim, degree_ladder
from arrow_matrix_tpu.utils import barabasi_albert, random_dense


def test_degree_ladder():
    lad = degree_ladder(100)
    assert lad[0] == 0 and lad[1] == 8
    assert lad[-1] >= 100
    assert all(b % 8 == 0 for b in lad)
    assert degree_ladder(0) == [0]


def slim_level(n, width, seed):
    a = barabasi_albert(n, 4, seed=seed)
    levels = arrow_decomposition(a, width, max_levels=4,
                                 block_diagonal=True, seed=seed)
    return levels[0]   # one arrow matrix, block-diagonal slim structure


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_sell_slim_matches_golden(n_dev):
    lvl = slim_level(1024, 64, seed=3)
    mesh = make_mesh((n_dev,), ("blocks",))
    d = SellSlim(lvl.matrix, 64, mesh)
    assert d.binary
    n = lvl.matrix.shape[0]
    x = random_dense(n, 8, seed=1)
    got = d.gather_result(d.spmm(d.set_features(x)))
    want = lvl.matrix @ x
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sell_slim_weighted_and_iterated():
    lvl = slim_level(640, 32, seed=9)
    aw = (lvl.matrix * 0.25).tocsr().astype(np.float32)
    mesh = make_mesh((4,), ("blocks",))
    d = SellSlim(aw, 32, mesh)
    assert not d.binary
    n = aw.shape[0]
    x = random_dense(n, 4, seed=2)
    xt = d.set_features(x)
    for _ in range(3):
        xt = d.spmm(xt)
    want = x
    for _ in range(3):
        want = aw @ want
    np.testing.assert_allclose(d.gather_result(xt), want,
                               rtol=1e-4, atol=1e-5)


def test_sell_slim_multi_hop_halos_cover_far_entries():
    """An entry far outside the shard-diagonal grows the halo reach
    (whole-shard ppermute hops) instead of being dropped or rejected —
    correctness degrades gracefully into more communication."""
    a = sparse.csr_matrix((256, 256), dtype=np.float32).tolil()
    a[200, 100] = 2.0    # far off-diagonal, outside head arm at w=32
    a[10, 250] = 3.0     # head row, covered by the head operator
    a[100, 101] = 1.0
    a = a.tocsr()
    mesh = make_mesh((4,), ("blocks",))
    d = SellSlim(a, 32, mesh)
    assert d.ops.hops >= 1
    x = random_dense(256, 4, seed=0)
    got = d.gather_result(d.spmm(d.set_features(x)))
    np.testing.assert_allclose(got, a @ x, rtol=1e-5, atol=1e-6)


def test_sell_multi_level_matches_golden():
    """SellMultiLevel = feature-major mesh multi-level: must equal the
    decomposition golden AND MultiLevelArrow, including a grown banded
    last level (cross-shard halos)."""
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel import MultiLevelArrow
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 1024, 64
    a = barabasi_albert(n, 4, seed=7)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    mesh = make_mesh((4,), ("blocks",))
    sm = SellMultiLevel(levels, width, mesh)
    assert sm.binary
    x = random_dense(n, 8, seed=3)
    got = sm.gather_result(sm.step(sm.set_features(x)))
    want = decomposition_spmm(levels, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    ml = MultiLevelArrow(levels, width, mesh=make_mesh((4,), ("blocks",)),
                         fmt="ell")
    ref = ml.gather_result(ml.step(ml.set_features(x)))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_sell_multi_level_iterated_weighted():
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 640, 32
    a = (barabasi_albert(n, 4, seed=11) * 0.25).tocsr().astype(np.float32)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=1)
    mesh = make_mesh((8,), ("blocks",))
    sm = SellMultiLevel(levels, width, mesh)
    assert not sm.binary
    x = random_dense(n, 4, seed=5)
    xt = sm.run(sm.set_features(x), 3)
    want = x
    for _ in range(3):
        want = a @ want
    np.testing.assert_allclose(sm.gather_result(xt), want,
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("n_dev", [2, 8])
def test_sell_multi_level_mesh_sizes(n_dev):
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 512, 32
    a = barabasi_albert(n, 3, seed=29)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=3)
    mesh = make_mesh((n_dev,), ("blocks",))
    sm = SellMultiLevel(levels, width, mesh)
    x = random_dense(n, 4, seed=1)
    got = sm.gather_result(sm.step(sm.set_features(x)))
    np.testing.assert_allclose(got, decomposition_spmm(levels, x),
                               rtol=1e-4, atol=1e-4)


def test_sell_slim_duplicate_ones_go_weighted():
    """Duplicate all-ones entries sum to 2.0 under canonicalization —
    binary auto-detection must run on the CANONICAL values (regression:
    raw-data detection silently halved such entries)."""
    row = np.array([5, 5, 40, 3])
    col = np.array([7, 7, 2, 60])
    a = sparse.coo_matrix((np.ones(4, np.float32), (row, col)),
                          shape=(128, 128)).tocsr()
    assert not a.has_canonical_format or np.any(a.data != 1.0) or True
    mesh = make_mesh((4,), ("blocks",))
    d = SellSlim(a, 32, mesh)
    assert not d.binary
    x = random_dense(128, 4, seed=0)
    got = d.gather_result(d.spmm(d.set_features(x)))
    a2 = a.copy(); a2.sum_duplicates()
    np.testing.assert_allclose(got, a2 @ x, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("routing", ["gather", "a2a"])
def test_sell_multi_level_routing_modes(routing):
    """Explicit a2a routing for the feature-major carriage must equal
    the GSPMD-gather lowering (and the golden)."""
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 768, 32
    a = barabasi_albert(n, 4, seed=13)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    mesh = make_mesh((4,), ("blocks",))
    sm = SellMultiLevel(levels, width, mesh, routing=routing)
    x = random_dense(n, 8, seed=3)
    got = sm.gather_result(sm.step(sm.set_features(x)))
    np.testing.assert_allclose(got, decomposition_spmm(levels, x),
                               rtol=1e-4, atol=1e-4)
    # iterated run through the scan path too
    x2 = sm.gather_result(sm.run(sm.set_features(x), 2))
    want = np.asarray(a @ np.asarray(a @ x))
    np.testing.assert_allclose(x2, want, rtol=1e-3, atol=1e-3)


def test_sell_multi_level_k128_and_16dev():
    """BASELINE's 128-feature configs and the largest virtual pool."""
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 1024, 32
    a = barabasi_albert(n, 3, seed=31)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=4)
    mesh = make_mesh((16,), ("blocks",))
    sm = SellMultiLevel(levels, width, mesh, routing="a2a")
    x = random_dense(n, 128, seed=2)
    got = sm.gather_result(sm.step(sm.set_features(x)))
    np.testing.assert_allclose(got, decomposition_spmm(levels, x),
                               rtol=1e-4, atol=1e-4)


def test_sell_multi_level_from_artifact(tmp_path):
    """Memmapped artifact triplets flow into the feature-major mesh
    orchestration (as_canonical_csr materializes per level)."""
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.io import (
        as_levels,
        load_decomposition,
        load_level_widths,
        save_decomposition,
    )
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    a = barabasi_albert(600, 3, seed=5)
    levels = arrow_decomposition(a, 64, max_levels=3, block_diagonal=True,
                                 seed=5)
    base = str(tmp_path / "g")
    save_decomposition(levels, base)
    widths = load_level_widths(base, 64)
    stream_levels = as_levels(load_decomposition(base, 64, mem_map=True),
                              widths if widths is not None else 64,
                              materialize=False)
    assert not hasattr(stream_levels[0].matrix, "nnz")

    sm = SellMultiLevel(stream_levels, 64, make_mesh((4,), ("blocks",)))
    assert sm.binary
    x = random_dense(600, 8, seed=2)
    got = sm.gather_result(sm.step(sm.set_features(x)))
    np.testing.assert_allclose(got, decomposition_spmm(levels, x),
                               rtol=1e-4, atol=1e-4)


def test_sell_multi_level_feat_axis():
    """k-dimension tiling: feature rows sharded over a second mesh axis
    compose with the sell orchestration under BOTH routings (the a2a
    tables are per-device and feature-row-independent, so each feature
    slice runs its own identical exchange)."""
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 512, 32
    a = barabasi_albert(n, 3, seed=41)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=1)
    mesh = make_mesh((4, 2), ("blocks", "feat"))
    x = random_dense(n, 8, seed=2)
    want = decomposition_spmm(levels, x)
    for routing in ("gather", "a2a"):
        sm = SellMultiLevel(levels, width, mesh, routing=routing,
                            feat_axis="feat")
        got = sm.gather_result(sm.step(sm.set_features(x)))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_directed_graph_through_fold_and_sell():
    """Asymmetric adjacency end-to-end (reference supports directed via
    symmetrize-before-linearize; the runtime operators must be exact on
    the asymmetric matrix itself)."""
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel import MultiLevelArrow
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 512, 32
    a = barabasi_albert(n, 3, seed=43, directed=True)
    assert (abs(a - a.T)).nnz > 0   # genuinely asymmetric
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=2)
    x = random_dense(n, 4, seed=1)
    want = decomposition_spmm(levels, x)

    mlf = MultiLevelArrow(levels, width, mesh=None, fmt="fold")
    np.testing.assert_allclose(
        mlf.gather_result(mlf.step(mlf.set_features(x))), want,
        rtol=1e-4, atol=1e-4)

    sm = SellMultiLevel(levels, width, make_mesh((4,), ("blocks",)))
    np.testing.assert_allclose(
        sm.gather_result(sm.step(sm.set_features(x))), want,
        rtol=1e-4, atol=1e-4)


def test_sell_bf16_feature_carriage():
    """feature_dtype='bf16' on the mesh sell paths: results track f32
    to bf16 rounding, the carriage dtype is bf16, and the LOWERED HLO
    shows exactly half the collective bytes of the f32 twin (the CPU
    backend upcasts compiled collectives, so the lowered module is the
    honest dtype accounting — commstats.lowered_collective_stats)."""
    import ml_dtypes

    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel
    from arrow_matrix_tpu.utils import commstats

    n, width = 1024, 64
    a = barabasi_albert(n, 4, seed=7)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=7)
    x = random_dense(n, 8, seed=1)
    want = decomposition_spmm(levels, x)
    mesh = make_mesh((8,), ("blocks",))

    sm16 = SellMultiLevel(levels, width, mesh, routing="a2a",
                          feature_dtype="bf16")
    xt = sm16.set_features(x)
    assert xt.dtype == ml_dtypes.bfloat16
    out = sm16.gather_result(sm16.step(xt))
    assert out.dtype == np.float32
    rel = np.linalg.norm(out - want) / np.linalg.norm(want)
    assert rel < 2e-2, rel

    smf = SellMultiLevel(levels, width, mesh, routing="a2a")
    s16 = commstats.lowered_collective_stats(
        sm16._step, xt, sm16._level_args, sm16.fwd, sm16.bwd)
    sf = commstats.lowered_collective_stats(
        smf._step, smf.set_features(x), smf._level_args, smf.fwd,
        smf.bwd)
    assert s16["total_bytes"] > 0
    assert s16["total_bytes"] * 2 == sf["total_bytes"]

    # feature_dtype='f32' (and None) stay the exact default.
    assert smf.feature_dtype is None
    assert SellMultiLevel(levels, width, mesh, routing="a2a",
                          feature_dtype="f32").feature_dtype is None


def test_sell_slim_bf16_halo_bytes_halved():
    """bf16 carriage on the single-matrix SellSlim path: the halo
    ppermute exchanges must CARRY bf16 (lowered HLO shows exactly half
    the f32 twin's collective bytes — VERDICT r4 item 7: the bytes
    must ride the exchanges, not just the resident features), and the
    result stays within bf16 rounding of the golden."""
    import ml_dtypes

    from arrow_matrix_tpu.utils import commstats

    n, w = 768, 32
    a = barabasi_albert(n, 4, seed=13).astype(np.float32)
    mesh = make_mesh((4,), ("blocks",))
    d16 = SellSlim(a, w, mesh, feature_dtype="bf16")
    df = SellSlim(a, w, mesh)
    assert np.max(d16.ops.hops) > 0   # the halo exchange must exist
    x = random_dense(n, 8, seed=2)
    xt = d16.set_features(x)
    assert xt.dtype == ml_dtypes.bfloat16
    out = d16.gather_result(d16.spmm(xt))
    assert out.dtype == np.float32
    want = a @ x
    rel = np.linalg.norm(out - want) / np.linalg.norm(want)
    assert rel < 2e-2, rel

    def stats(d, xt):
        o = d.ops
        return commstats.lowered_collective_stats(
            d._step, o.body, o.head, o.head_unsort, o.orig_pos, xt)

    s16 = stats(d16, xt)
    sf = stats(df, df.set_features(x))
    assert s16["total_bytes"] > 0
    assert s16["total_bytes"] * 2 == sf["total_bytes"]


def test_per_host_build_equivalence():
    """The per-host build (_slim_shares materialize=subset) must agree
    with the full build on every global decision — tier ladder, shared
    tier shapes, orderings — and bit-match the full stacks on the
    materialized shards (remote slices stay zero)."""
    from arrow_matrix_tpu.parallel.sell_slim import (
        _DegreesOnly,
        _pack_shard_tiers,
        _SliceSource,
        _banded_reach,
        _hops_rem,
        _slim_shares,
        degree_ladder,
    )

    n, w, n_dev = 512, 32, 4
    a = barabasi_albert(n, 4, seed=11).astype(np.float32)
    src = _SliceSource(a, n_dev, w)
    hops, _ = _hops_rem(_banded_reach(src, w), src.shard_len,
                        n_dev)

    full_b, full_h = _slim_shares(src, w, hops)
    part_b, part_h = _slim_shares(src, w, hops, materialize={0, 2})

    for d in (1, 3):
        assert isinstance(part_b[d], _DegreesOnly)
        np.testing.assert_array_equal(np.diff(part_b[d].indptr),
                                      np.diff(full_b[d].indptr))
    for d in (0, 2):
        assert (part_b[d] != full_b[d]).nnz == 0

    ladder = degree_ladder(
        max(int(np.diff(s.indptr).max()) if s.nnz else 0
            for s in full_b))
    sf, of, rf = _pack_shard_tiers(full_b, ladder, False, np.float32)
    sp, op, rp = _pack_shard_tiers(part_b, ladder, False, np.float32)
    assert rf == rp
    np.testing.assert_array_equal(of, op)          # orderings identical
    for cf, cp in zip(sf.cols, sp.cols):
        np.testing.assert_array_equal(cf[[0, 2]], cp[[0, 2]])
        assert not np.any(cp[[1, 3]])              # remote = zero pages
    for df, dp in zip(sf.deg, sp.deg):
        np.testing.assert_array_equal(df[[0, 2]], dp[[0, 2]])


def test_tight_ladder_matches_default_with_fewer_slots():
    """ladder='tight' (growth 1.3, align 1): same results to f32
    reassociation, strictly fewer padded gather slots (the align-8
    floor pads block-diagonal levels ~3.4x nnz — slots ARE the gather
    cost, PERFORMANCE.md)."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    n, width = 512, 32
    a = barabasi_albert(n, 4, seed=23)
    levels = arrow_decomposition(a, width, max_levels=3,
                                 block_diagonal=True, seed=3)
    mesh = make_mesh((8,), ("blocks",))
    x = random_dense(n, 8, seed=5)

    base = SellMultiLevel(levels, width, mesh)
    tight = SellMultiLevel(levels, width, mesh, ladder="tight")
    slots = lambda sm: sum(o.body.n_slots + o.head.n_slots
                           for o in sm.ops)
    assert slots(tight) < slots(base)
    got_t = tight.gather_result(tight.step(tight.set_features(x)))
    np.testing.assert_allclose(got_t, decomposition_spmm(levels, x),
                               rtol=1e-4, atol=1e-4)
    got_b = base.gather_result(base.step(base.set_features(x)))
    np.testing.assert_allclose(got_t, got_b, rtol=1e-5, atol=1e-5)


def test_tight_ladder_space_shared_matches():
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.sell_space import SellSpaceShared

    n, width = 384, 32
    a = barabasi_albert(n, 3, seed=29)
    levels = arrow_decomposition(a, width, max_levels=2,
                                 block_diagonal=True, seed=4)
    assert len(levels) == 2
    mesh = make_mesh((2, 4), ("lvl", "blocks"))
    x = random_dense(n, 4, seed=6)
    sp = SellSpaceShared(levels, width, mesh=mesh, ladder="tight")
    got = sp.gather_result(sp.step(sp.set_features(x)))
    np.testing.assert_allclose(got, decomposition_spmm(levels, x),
                               rtol=1e-4, atol=1e-4)


def test_resolve_ladder_validation():
    from arrow_matrix_tpu.parallel.sell_slim import resolve_ladder

    assert resolve_ladder(None) == resolve_ladder("default")
    assert resolve_ladder("tight") == (1.3, 1)
    assert resolve_ladder((1.2, 2)) == (1.2, 2)
    import pytest as _pytest
    with _pytest.raises(ValueError):
        resolve_ladder((0.9, 2))
    with _pytest.raises(ValueError):
        resolve_ladder((1.2, 0))


def test_sliced_halo_exchange_fewer_bytes():
    """The farthest halo hop carries only `reach` rows: versus a
    whole-shard step (rem=0 compatibility mode) the collective-permute
    bytes strictly drop while outputs stay identical."""
    from arrow_matrix_tpu.parallel.sell_slim import (
        SellSlim,
        make_sharded_step,
    )
    from arrow_matrix_tpu.utils import commstats
    from arrow_matrix_tpu.utils.graphs import grid_graph, random_dense

    g = grid_graph(32).astype(np.float32)    # bandwidth 32 << shard
    mesh = make_mesh((4,), ("blocks",))
    sl = SellSlim(g, 32, mesh)
    o = sl.ops
    assert o.hops == 1 and 0 < o.rem < sl.shard_len

    x = random_dense(g.shape[0], 4, seed=1)
    xt = sl.set_features(x)
    want = sl.gather_result(sl.spmm(xt))
    np.testing.assert_allclose(want, np.asarray(g @ x), rtol=1e-5,
                               atol=1e-5)

    import jax

    whole = jax.jit(make_sharded_step(mesh, sl.axis, sl.width,
                                      o.rows_out, hops=o.hops, rem=0))
    got_whole = whole(o.body, o.head, o.head_unsort, o.orig_pos, xt)
    np.testing.assert_allclose(np.asarray(got_whole),
                               np.asarray(sl.spmm(xt)), rtol=1e-6,
                               atol=1e-6)

    sliced_stats = commstats.collective_stats(
        sl._step, o.body, o.head, o.head_unsort, o.orig_pos, xt)
    whole_stats = commstats.collective_stats(
        whole, o.body, o.head, o.head_unsort, o.orig_pos, xt)
    assert (sliced_stats["collective-permute"]["bytes"]
            < whole_stats["collective-permute"]["bytes"])


@pytest.mark.parametrize("budget", [None, 1 << 14])
def test_tier_gather_budget_bounds_chunks(monkeypatch, budget):
    """The mesh tiers take their gather bound from the device (the
    fold's rule); a tight one turns tiers into slot chunks — single-slot
    gathers below one tile — and leaves the result unchanged."""
    from arrow_matrix_tpu.parallel import sell_slim
    from arrow_matrix_tpu.parallel.sell_slim import (
        SellMultiLevel,
        format_tier_chunks,
        tier_chunks,
    )

    if budget is not None:
        monkeypatch.setattr(sell_slim, "mesh_gather_budget",
                            lambda mesh: budget)
    a = barabasi_albert(1 << 10, 8, seed=7)
    levels = arrow_decomposition(a, arrow_width=128, max_levels=3,
                                 block_diagonal=True, seed=7)
    multi = SellMultiLevel(levels, 128, make_mesh((4,), ("blocks",)))
    k = 16
    chunks = [c for ops in multi.ops for stack in (ops.body, ops.head)
              for m, rows, c in tier_chunks(stack, k, 4,
                                            multi.gather_budget)
              if m and rows]
    if budget is None:
        assert multi.gather_budget > 0 and set(chunks) == {None}
    else:
        assert 1 in chunks
    assert "tier gather chunks" in format_tier_chunks(multi, k, 4)
    x = random_dense(a.shape[0], k, seed=3)
    got = multi.gather_result(multi.step(multi.set_features(x)))
    np.testing.assert_allclose(got, np.asarray(a @ x), rtol=1e-4,
                               atol=1e-4)
