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
    fold_tiers,
    optimal_tier_starts,
    sell_from_csr,
    sell_spmm_t,
    tier_boundaries,
)
from arrow_matrix_tpu.utils import barabasi_albert, random_dense
from arrow_matrix_tpu.utils.graphs import grid_graph, random_csr


def spmm_via_sell(a, x, **kw):
    sell, order = sell_from_csr(a, **kw)
    y = x[order] if x.shape[0] == sell.n_rows else None
    assert y is not None
    out_sorted = np.asarray(_jit_spmm(sell, jnp.asarray(y.T)))
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


# -- slot-optimal tiers ---------------------------------------------------

def _slots(sorted_deg, starts):
    """Slots of the tiers starting at ``starts``: each row padded to its
    tier's largest degree."""
    ends = list(starts[1:]) + [sorted_deg.size]
    return sum(int(sorted_deg[hi - 1]) * (hi - lo)
               for lo, hi in zip(starts, ends))


def _histogram(kind, seed):
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        deg = rng.integers(0, 300, 4000)
    elif kind == "sparse":              # few rows over a wide range
        deg = rng.integers(1, 5000, 60)
    elif kind == "wide":                # 2,200 distinct: the DP in blocks
        deg = np.concatenate([np.arange(1000, 3200),
                              rng.integers(1000, 3200, 2000)])
    else:                               # power law, with empty rows
        a = barabasi_albert(6000, 4, seed=seed)
        deg = np.concatenate([np.diff(a.indptr), np.zeros(50, np.int64)])
    return np.sort(deg)


@pytest.mark.parametrize("growth", [1.1, 1.2, 1.5])
@pytest.mark.parametrize("kind,seed", [("uniform", 0), ("uniform", 1),
                                       ("sparse", 2), ("powerlaw", 3),
                                       ("powerlaw", 4), ("wide", 5)])
def test_optimal_tiers_never_beat_by_growth_rule(kind, seed, growth):
    """At the growth rule's tier count the DP's tiers hold no more slots
    than the growth rule's (which is one of its candidates), cover whole
    degree values, and keep the zero-degree prefix a tier of its own."""
    deg = _histogram(kind, seed)
    rule = tier_boundaries(deg, growth)
    aligned, starts = fold_tiers(deg, growth)
    assert aligned is deg
    assert len(starts) <= len(rule)
    assert _slots(deg, starts) <= _slots(deg, rule)
    assert starts[0] == 0 and starts == sorted(set(starts))
    assert all(deg[s - 1] < deg[s] for s in starts[1:])
    zeros = int(np.count_nonzero(deg == 0))
    if zeros:
        assert starts[1] == zeros


def _brute_force_slots(sorted_deg, n_tiers):
    """Fewest slots over every placement of at most n_tiers tiers of
    whole degree values (zero-degree rows a tier of their own)."""
    import itertools

    zeros = int(np.count_nonzero(sorted_deg == 0))
    cuts = [int(i) for i in np.flatnonzero(np.diff(sorted_deg)) + 1
            if i > zeros]
    free = n_tiers - 1 - (zeros > 0)       # tiers past the first nonzero
    head = [0, zeros] if zeros else [0]
    return min(_slots(sorted_deg, head + list(c))
               for r in range(min(free, len(cuts)) + 1)
               for c in itertools.combinations(cuts, r))


@pytest.mark.parametrize("n_tiers", [2, 3, 4, 6])
@pytest.mark.parametrize("seed", range(4))
def test_optimal_tiers_match_brute_force(seed, n_tiers):
    rng = np.random.default_rng(100 + seed)
    deg = np.sort(rng.choice([0, 1, 2, 3, 5, 8, 9, 13, 21, 40],
                             size=int(rng.integers(8, 60))))
    starts = optimal_tier_starts(deg, n_tiers)
    assert len(starts) <= n_tiers
    assert _slots(deg, starts) == _brute_force_slots(deg, n_tiers)


@pytest.mark.parametrize("deg,n_tiers", [
    ([0, 0, 2, 2, 3, 4, 4, 4], 4),      # the lattice: pad rows, 2, 3, 4
    ([2, 3, 3, 4], 3),
    ([5, 5, 5], 1),
    ([0, 7, 9, 11, 30], 8),
])
def test_optimal_tiers_exact_when_few_degrees(deg, n_tiers):
    """With no more distinct degrees than tiers, every degree is a tier
    of its own: the slots are the nonzeros."""
    deg = np.asarray(deg)
    starts = optimal_tier_starts(deg, n_tiers)
    assert _slots(deg, starts) == deg.sum()
    assert starts == [int(i) for i in np.unique(deg, return_index=True)[1]]


def test_fold_tiers_of_a_lattice():
    """The growth rule gives the lattice (pad rows, then degrees 2-4)
    four tiers; over exact degrees the fold packs no padded slot, and an
    explicit alignment of 8 pads each row to 8."""
    deg = np.sort(np.diff(grid_graph(40).indptr))
    deg = np.concatenate([np.zeros(24, np.int64), deg])
    aligned, starts = fold_tiers(deg)
    assert len(starts) == 4 and _slots(aligned, starts) == deg.sum()
    aligned8, starts8 = fold_tiers(deg, slot_align=8)
    assert _slots(aligned8, starts8) == 8 * np.count_nonzero(deg)


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
    out_sorted = np.asarray(_jit_spmm(sell, jnp.asarray(y.T),
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


def _jit_spmm(sell, xt, **kw):
    """``sell_spmm_t`` through a fresh ``jax.jit``: traced anew on each
    call, so trace-time gauges and monkeypatches still apply, and run
    as one program (op-by-op dispatch costs seconds a case)."""
    return jax.jit(lambda s, v: sell_spmm_t(s, v, **kw))(sell, xt)


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

    # One program holds both steps, traced in turn: the lane-packed
    # gather, then the k-wide one with packing patched off — one trace
    # and one compile a case, where eager dispatch cost seconds.
    gauges = []

    def both(s, v):
        got = sell_spmm_t(s, v, chunk=chunk)
        gauges.append(registry.gauge("sell.packed_slots").value)
        monkeypatch.setattr(ell, "lane_pack_factor", lambda k: 1)
        today = sell_spmm_t(s, v, chunk=chunk)
        gauges.append(registry.gauge("sell.packed_slots").value)
        return got, today

    traced = jax.jit(both).trace(sell, xt)
    jaxpr = str(traced.jaxpr)
    assert PACKED_GATHER in jaxpr                     # whole-row gathers
    # The select must not round f32 to bf16 on the chip's MXU.
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in jaxpr
    assert gauges == [sell.n_slots, 0]
    got, today = (np.asarray(r, dtype=np.float32)
                  for r in traced.lower().compile()(sell, xt))

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
    out[order] = np.asarray(_jit_spmm(sell, xt)).T
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


@pytest.mark.parametrize("k", [16, 128])
def test_fold_step_gathers_packed_only_below_the_tile(registry, k):
    """The one-chip fold's step program runs the whole carriage through
    ``sell_spmm_t``: at k=16 every tier gathers lane-packed rows, at
    k=128 none does (a split of the features would silently switch a
    k=128 step onto the packed form and gather twice the rows)."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    a = barabasi_albert(480, 6, seed=19)
    levels = arrow_decomposition(a, 32, max_levels=3, block_diagonal=True,
                                 seed=2)
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold")
    xd = ml.set_features(random_dense(480, k, seed=1))
    jaxpr = str(jax.make_jaxpr(ml.step_fn)(xd, *ml.step_operands()))
    slots = ml.blocks[0].n_slots
    assert registry.gauge("sell.slots").value == slots
    assert registry.gauge("sell.packed_slots").value == (
        slots if k < 128 else 0)
    assert (PACKED_GATHER in jaxpr) == (k < 128)


# -- a chunked tier's last partial chunk ---------------------------------

def _tier(form, m, rows=96, n=384, seed=0):
    """One (m, rows) tier over n columns: ``(x_t, kwargs)`` for
    ``ell_spmm_t``.  Rows and columns are multiples of 8, so nothing is
    padded but what the chunking would pad."""
    rng = np.random.default_rng(seed)
    k = 16 if form == "packed" else 128
    cols = rng.integers(0, n, (m, rows)).astype(np.int32)
    deg = rng.integers(0, m + 1, rows).astype(np.int32)
    deg[:4] = m                              # some rows fill every slot
    live = np.arange(m)[:, None] < deg[None, :]
    cols[~live] = 0
    kw = {"cols": jnp.asarray(cols)}
    if form == "weighted":
        kw["data"] = jnp.asarray(rng.random((m, rows), dtype=np.float32)
                                 * live)
    else:
        kw["deg"] = jnp.asarray(deg)
    x_t = jnp.asarray(rng.random((k, n), dtype=np.float32))
    return x_t, kw


@pytest.mark.parametrize("m,chunk", [(11, 8), (13, 4), (9, 8), (21, 8),
                                     (7, 3)])
@pytest.mark.parametrize("form", ["weighted", "binary", "packed"])
def test_chunk_tail_matches_unchunked(form, m, chunk):
    """When the chunk does not divide the slots, the last m mod c slots
    are gathered after the whole chunks, one at a time: nothing is
    padded, and the result is the unchunked one to f32 rounding."""
    x_t, kw = _tier(form, m)
    assert ell.slot_runs(m, chunk) == [(0, m - m % chunk, chunk),
                                       (m - m % chunk, m, 1)]
    jaxpr = str(jax.make_jaxpr(
        lambda v: ell.ell_spmm_t(x_t=v, chunk=chunk, **kw))(x_t))
    assert "pad[" not in jaxpr
    assert (PACKED_GATHER in jaxpr) == (form == "packed")
    got = np.asarray(jax.jit(
        lambda v: ell.ell_spmm_t(x_t=v, chunk=chunk, **kw))(x_t))
    want = np.asarray(jax.jit(
        lambda v: ell.ell_spmm_t(x_t=v, chunk=None, **kw))(x_t))
    np.testing.assert_allclose(got, want, rtol=4e-6, atol=1e-6)
    w = (np.asarray(kw["data"]) if "data" in kw else
         (np.arange(m)[:, None] < np.asarray(kw["deg"])[None, :]))
    x64 = np.asarray(x_t, dtype=np.float64)
    ref = np.einsum("mr,kmr->kr", w, x64[:, np.asarray(kw["cols"])])
    np.testing.assert_allclose(got, ref, rtol=4e-6, atol=1e-6)


@pytest.mark.parametrize("form", ["weighted", "binary", "packed"])
@pytest.mark.parametrize("m,chunk", [(16, 8), (8, 8), (12, 1), (12, None)])
def test_aligned_chunks_lower_without_tail_code(monkeypatch, form, m,
                                                chunk):
    """Where the chunk divides the slots (every tier of the parent's
    packing), the step lowers as it does with the tail code taken out:
    one run over whole chunks, no slice of the slot axis."""
    x_t, kw = _tier(form, m)

    def lowered():
        return jax.jit(lambda v: ell.ell_spmm_t(x_t=v, chunk=chunk, **kw)
                       ).lower(x_t).as_text()

    with_tail = lowered()
    monkeypatch.setattr(ell, "slot_runs",
                        lambda m, chunk: [(0, m, chunk or m)])
    assert lowered() == with_tail


@pytest.mark.parametrize("k", [16, 128])
@pytest.mark.parametrize("chunk", [None, 1, 3, 8])
def test_gathered_slots_gauge_counts_no_padding(registry, k, chunk):
    """``sell.gathered_slots`` counts the slot-rows the step gathers,
    each tier's slots as ``slot_runs`` walks them: with the tail it
    equals the packed ``sell.slots`` at every chunk."""
    a = _packed_case(False)
    sell, order = sell_from_csr(a)
    ms = [c.shape[0] for c in sell.cols if c.shape[0]]
    if chunk not in (None, 1):
        assert any(m % chunk for m in ms)    # some tier takes a tail
    x = random_dense(a.shape[0], k, seed=4)
    out = np.empty_like(x)
    out[order] = np.asarray(
        _jit_spmm(sell, jnp.asarray(x[order].T), chunk=chunk)).T
    assert (registry.gauge("sell.gathered_slots").value
            == registry.gauge("sell.slots").value == sell.n_slots)
    want = a @ x
    assert np.abs(out - want).max() <= 1e-5 * np.abs(want).max()
