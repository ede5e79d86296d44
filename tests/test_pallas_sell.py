"""Fused SELL-SpMM Pallas kernel tests (ops/pallas_sell.py,
graft-stream): the interpret=True correctness pins against the
``ops/sell.py`` golden, at the protocol shape the acceptance criteria
name (n=2^20 feature table, k=16 and k=128)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arrow_matrix_tpu.ops import pallas_sell
from arrow_matrix_tpu.ops.pallas_sell import (
    GRANULE,
    pack_features_t,
    sell_spmm_t_pallas,
    sell_tier_spmm_packed,
    slab_rows,
    supported_feature_width,
)
from arrow_matrix_tpu.ops.sell import SellMatrix, sell_from_csr, sell_spmm_t
from arrow_matrix_tpu.utils import barabasi_albert, random_dense
from arrow_matrix_tpu.utils.numerics import (
    relative_error,
    relative_tolerance,
)


def _synthetic_binary(n_table: int, rows: int, m_t: int, k: int, seed=0):
    """A single-tier binary SellMatrix over an n_table-row feature
    table, built directly (no decomposition — the kernel contract is
    per-tier)."""
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, n_table, size=(m_t, rows)).astype(np.int32)
    deg = rng.integers(0, m_t + 1, size=rows).astype(np.int32)
    m = SellMatrix(cols=(jnp.asarray(cols),), data=None,
                   deg=(jnp.asarray(deg),), n_rows=rows,
                   row_starts=(0,))
    x_t = jnp.asarray(rng.standard_normal((k, n_table)), dtype=jnp.float32)
    return m, x_t


@pytest.mark.parametrize("k,rows,m_t", [(16, 1 << 14, 16),
                                        (128, 1 << 13, 8)])
def test_matches_golden_protocol_shape(k, rows, m_t):
    # The acceptance shape: a 2^20-row feature table gathered by a
    # binary tier slab; vectorized interpret body (the CPU tier-1 path).
    m, x_t = _synthetic_binary(1 << 20, rows, m_t, k, seed=k)
    want = np.asarray(sell_spmm_t(m, x_t, gather_budget=1 << 28))
    got = np.asarray(sell_spmm_t_pallas(m, x_t))
    assert got.shape == want.shape == (k, rows)
    assert relative_error(got, want) <= relative_tolerance(m_t)


def test_weighted_matches_golden():
    rng = np.random.default_rng(3)
    rows, m_t, k, n_table = 512, 12, 16, 4096
    cols = rng.integers(0, n_table, size=(m_t, rows)).astype(np.int32)
    deg = rng.integers(0, m_t + 1, size=rows)
    data = rng.standard_normal((m_t, rows)).astype(np.float32)
    data *= (np.arange(m_t)[:, None] < deg[None, :])  # explicit zeros
    m = SellMatrix(cols=(jnp.asarray(cols),),
                   data=(jnp.asarray(data),), deg=None,
                   n_rows=rows, row_starts=(0,))
    x_t = jnp.asarray(rng.standard_normal((k, n_table)), dtype=jnp.float32)
    want = np.asarray(sell_spmm_t(m, x_t, gather_budget=1 << 26))
    got = np.asarray(sell_spmm_t_pallas(m, x_t))
    assert relative_error(got, want) <= relative_tolerance(m_t)


def test_full_matrix_via_sell_from_csr():
    # End-to-end against the packed multi-tier format the fold executor
    # actually carries (zero tier + slot-optimal exact-degree tiers).
    a = barabasi_albert(3000, 5, seed=7)
    sell, order = sell_from_csr(a, pad_rows_to=3072)
    x = random_dense(3072, 16, seed=8)[order]
    want = np.asarray(sell_spmm_t(sell, jnp.asarray(x.T)))
    got = np.asarray(sell_spmm_t_pallas(sell, jnp.asarray(x.T)))
    max_deg = max((c.shape[0] for c in sell.cols), default=1)
    assert relative_error(got, want) <= relative_tolerance(max_deg)


def test_stream_dma_path_matches_vectorized():
    # The double-buffered async-copy body at a tiny shape under
    # interpret: the DMA addressing/wave logic must agree bit-for-bit
    # with the vectorized gather (identical accumulation order).
    m, x_t = _synthetic_binary(1024, 64, 5, 16, seed=11)
    x_packed = pack_features_t(x_t)
    cols, deg = m.cols[0], m.deg[0]
    ref = sell_tier_spmm_packed(cols, x_packed, 16, deg=deg,
                                stream=False, interpret=True)
    got = sell_tier_spmm_packed(cols, x_packed, 16, deg=deg,
                                stream=True, wave=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_slab_streaming_bounded_smem(monkeypatch):
    # A tier whose cols exceed the scalar-prefetch budget streams
    # through multiple pallas_calls; the concatenated result is the
    # same answer.
    m, x_t = _synthetic_binary(2048, 1024, 6, 16, seed=13)
    want = np.asarray(sell_spmm_t_pallas(m, x_t))
    # 6 slots * 4 B = 24 B/row -> a few row blocks per slab at most.
    monkeypatch.setattr(pallas_sell, "SMEM_COLS_BUDGET", 64 * 24 * 4)
    got = np.asarray(sell_spmm_t_pallas(m, x_t, row_block=64))
    np.testing.assert_array_equal(got, want)


def test_slab_rows_degenerate_cases():
    # A tier so wide one row exceeds the whole budget still makes
    # forward progress: exactly one row block per slab.
    assert slab_rows(10**9, 64, smem_cols_budget=1 << 18) == 64
    # Normal case: the slab is a whole multiple of the row block and
    # fits the budget (per_row = 4 bytes of int32 cols per slot, slots
    # padded to a multiple of 8 sublanes).
    s = slab_rows(6, 64, smem_cols_budget=64 * 24 * 4)
    assert s % 64 == 0 and s * 8 * 4 <= 64 * 24 * 4
    # Explicit budget wins over the module-level env default, and the
    # arithmetic is exact: budget 512 B / (8 slot rows * 4 B) = 16 rows.
    assert slab_rows(4, 8, smem_cols_budget=512) == 16
    # m_t = 0 (the zero tier) must not divide by zero.
    assert slab_rows(0, 64, smem_cols_budget=1024) >= 64


def test_explicit_smem_budget_matches_unbounded():
    # The per-call budget argument (graft-tune's knob) forces slab
    # streaming without touching the module attribute; same answer.
    m, x_t = _synthetic_binary(2048, 1024, 6, 16, seed=13)
    want = np.asarray(sell_spmm_t_pallas(m, x_t))
    got = np.asarray(sell_spmm_t_pallas(m, x_t, row_block=64,
                                        smem_cols_budget=64 * 24 * 4))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ring", [1, 3, 4])
def test_ring_depth_variants_match_double_buffer(ring):
    # The generalized DMA ring at every depth must agree bit-for-bit
    # with the ring=2 double buffer (identical accumulation order —
    # the ring only changes how many copies are in flight).
    m, x_t = _synthetic_binary(1024, 64, 5, 16, seed=11)
    x_packed = pack_features_t(x_t)
    cols, deg = m.cols[0], m.deg[0]
    ref = sell_tier_spmm_packed(cols, x_packed, 16, deg=deg,
                                stream=True, wave=4, interpret=True)
    got = sell_tier_spmm_packed(cols, x_packed, 16, deg=deg,
                                stream=True, wave=4, ring=ring,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_ring_validation():
    m, x_t = _synthetic_binary(256, 64, 3, 16, seed=1)
    x_packed = pack_features_t(x_t)
    with pytest.raises(ValueError, match="ring"):
        sell_tier_spmm_packed(m.cols[0], x_packed, x_t.shape[0], deg=m.deg[0],
                              stream=True, ring=0, interpret=True)


def test_pack_features_granule_lines():
    x_t = jnp.arange(16 * 10, dtype=jnp.float32).reshape(16, 10)
    packed = pack_features_t(x_t)
    n_pad = ((10 + GRANULE - 1) // GRANULE) * GRANULE
    assert packed.shape == (n_pad // GRANULE, GRANULE * 16)
    assert packed.dtype == jnp.int32
    # Line 0 holds rows 0..7 of the row-major view, contiguous.
    np.testing.assert_array_equal(
        np.asarray(packed)[0].view(np.float32),
        np.asarray(x_t.T[:GRANULE]).reshape(-1))
    # bf16: 16 rows per 128-word line; word q of a row carries features
    # q (low half) and q + k/2 (high half).
    bf = np.asarray(pack_features_t(x_t, "bf16"))
    assert bf.shape == (1, 128)
    halves = bf[0].view(np.uint16).reshape(16, 8, 2)
    want = np.asarray(x_t.T.astype(jnp.bfloat16)).view(np.uint16)
    np.testing.assert_array_equal(halves[:10, :, 0], want[:, :8])
    np.testing.assert_array_equal(halves[:10, :, 1], want[:, 8:])


def test_validation():
    m, x_t = _synthetic_binary(256, 64, 3, 10, seed=1)
    x_packed = pack_features_t(x_t)
    with pytest.raises(ValueError, match="k % 16"):
        sell_tier_spmm_packed(m.cols[0], x_packed, x_t.shape[0], deg=m.deg[0],
                              stream=True, interpret=True)
    with pytest.raises(ValueError, match="interpret-only"):
        sell_tier_spmm_packed(m.cols[0], x_packed, x_t.shape[0], deg=m.deg[0],
                              stream=False, interpret=False)
    with pytest.raises(ValueError, match="requires deg"):
        sell_tier_spmm_packed(m.cols[0], x_packed, 10, interpret=True)
    assert supported_feature_width(16)
    assert supported_feature_width(128)
    assert not supported_feature_width(8)


def test_empty_and_zero_tier():
    # The packed format's zero tier (m_t = 0) and an empty matrix.
    k = 16
    x_t = jnp.zeros((k, 32), dtype=jnp.float32)
    empty = SellMatrix(cols=(), data=None, deg=(), n_rows=0,
                       row_starts=())
    assert sell_spmm_t_pallas(empty, x_t).shape == (k, 0)
    zero_tier = SellMatrix(
        cols=(jnp.zeros((0, 24), dtype=jnp.int32),), data=None,
        deg=(jnp.zeros((24,), dtype=jnp.int32),), n_rows=24,
        row_starts=(0,))
    out = sell_spmm_t_pallas(zero_tier, x_t)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.zeros((k, 24), dtype=np.float32))


def test_jit_wrapper_no_retrace():
    m, x_t = _synthetic_binary(512, 128, 4, 16, seed=21)
    fn = pallas_sell.sell_spmm_t_pallas_jit
    out1 = fn(m, x_t)
    n0 = fn._cache_size()
    out2 = fn(m, x_t * 2)
    assert fn._cache_size() == n0
    np.testing.assert_allclose(np.asarray(out2), 2 * np.asarray(out1),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# graft-kcert certified parity matrix: every (ring, row_block, k,
# carriage) cell of the contract's representative space, interpret
# stream vs the ops/sell.py golden.
# ---------------------------------------------------------------------------

def _parity_problem(k, seed):
    rng = np.random.default_rng(seed)
    rows, m_t, n_table = 256, 4, 256
    cols = rng.integers(0, n_table, size=(m_t, rows)).astype(np.int32)
    deg = rng.integers(0, m_t + 1, size=rows).astype(np.int32)
    x_t = jnp.asarray(rng.standard_normal((k, n_table)),
                      dtype=jnp.float32)
    m = SellMatrix(cols=(jnp.asarray(cols),), data=None,
                   deg=(jnp.asarray(deg),), n_rows=rows,
                   row_starts=(0,))
    return m, x_t


@pytest.mark.parametrize("feature_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("k", [16, 128])
@pytest.mark.parametrize("row_block", [64, 128])
@pytest.mark.parametrize("ring", [1, 2, 3, 4])
def test_certified_parity_matrix(ring, row_block, k, feature_dtype):
    from arrow_matrix_tpu.analysis.kernels import certify_candidate_opts
    from arrow_matrix_tpu.classes import BF16_TOLERANCE

    # Every cell raced here is a cell the certifier admits: the tuner
    # prunes with the same call, so a red cell could never ship.
    assert certify_candidate_opts(
        {"ring": ring, "row_block": row_block}, k,
        feature_dtype=feature_dtype) is None

    m, x_t = _parity_problem(k, seed=ring * 1000 + row_block + k)
    x_packed = pack_features_t(x_t, feature_dtype)
    cols, deg = m.cols[0], m.deg[0]
    got = np.asarray(sell_tier_spmm_packed(
        cols, x_packed, k, deg=deg, stream=True, interpret=True,
        row_block=row_block, wave=4, ring=ring,
        feature_dtype=feature_dtype))
    if feature_dtype == "f32":
        # f32 carriage: the golden is the unfused gather kernel; only
        # accumulation order differs.
        want = np.asarray(sell_spmm_t(m, x_t,
                                      gather_budget=1 << 24)).T
        assert relative_error(got, want) <= relative_tolerance(4)
    else:
        # bf16 carriage: the emulated-bf16 golden quantizes the
        # features exactly like the kernel's carriage cast, then
        # accumulates in f32 (KC4) — agreement must land within the
        # committed approx-class certificate tolerance.
        xq = x_t.astype(jnp.bfloat16).astype(jnp.float32)
        want = np.asarray(sell_spmm_t(m, xq,
                                      gather_budget=1 << 24)).T
        assert relative_error(got, want) <= BF16_TOLERANCE


@pytest.mark.parametrize("k", [16, 128])
def test_bf16_stream_bitwise_matches_vectorized(k):
    # Same accumulation order on both interpret bodies -> the bf16
    # carriage answers bit-identically regardless of the DMA path.
    m, x_t = _parity_problem(k, seed=31 + k)
    x_packed = pack_features_t(x_t, "bf16")
    cols, deg = m.cols[0], m.deg[0]
    vec = sell_tier_spmm_packed(cols, x_packed, k, deg=deg, stream=False,
                                interpret=True, feature_dtype="bf16")
    st = sell_tier_spmm_packed(cols, x_packed, k, deg=deg, stream=True,
                               interpret=True, wave=4, ring=2,
                               feature_dtype="bf16")
    np.testing.assert_array_equal(np.asarray(st), np.asarray(vec))
    assert st.dtype == jnp.float32  # f32 accumulator surfaces f32


def test_bf16_full_matrix_and_jit_static_dtype():
    # The SellMatrix entry point + jit wrapper thread feature_dtype as
    # a static arg: retargeting the carriage recompiles exactly once
    # and lands within the approx-class tolerance of the f32 answer.
    from arrow_matrix_tpu.classes import BF16_TOLERANCE

    m, x_t = _synthetic_binary(512, 128, 4, 16, seed=21)
    fn = pallas_sell.sell_spmm_t_pallas_jit
    f32 = fn(m, x_t)
    n0 = fn._cache_size()
    bf = fn(m, x_t, feature_dtype="bf16")
    assert fn._cache_size() == n0 + 1
    bf2 = fn(m, x_t, feature_dtype="bf16")
    assert fn._cache_size() == n0 + 1
    np.testing.assert_array_equal(np.asarray(bf), np.asarray(bf2))
    assert relative_error(np.asarray(bf),
                          np.asarray(f32)) <= BF16_TOLERANCE
