"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached.  Each case lowers
one kernel or step at a real width for one chip and checks what the
chip's compiler would refuse — an unlowerable primitive, a misaligned
DMA, SMEM/VMEM/HBM overflow — plus that a Pallas kernel really is a
Mosaic kernel (``tpu_custom_call``) and not interpreted.

The topology is described inside a module fixture (never at import:
only one process may hold libtpu, and every xdist worker imports this
file), and skips from there where it cannot be described.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from arrow_matrix_tpu.ops import pallas_blocks, pallas_sell
from arrow_matrix_tpu.ops.sell import SellMatrix, sell_spmm_t

#: v5e HBM as the compiler reports it (15.75 GiB of the 16 GB).
HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache
    off: a compile for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)


@pytest.fixture
def compiled_for_chip(monkeypatch):
    """Take the kernels' TPU branch (``_interpret`` is the CPU-test
    switch) for the duration of one case."""
    monkeypatch.setattr(pallas_blocks, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_sell, "_interpret", lambda: False)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _sell_stream(one_chip, k, carriage):
    # One 2^17-row tier of 16 slots against a 2^20-row feature table.
    m_t, n_t, n = 16, 1 << 17, 1 << 20
    lines = n // pallas_sell.line_geometry(k, carriage)[1]

    def f(cols, xp, deg):
        return pallas_sell.sell_tier_spmm_packed(
            cols, xp, k, deg=deg, stream=True, feature_dtype=carriage)

    return f, (_spec(one_chip, (m_t, n_t), jnp.int32),
               _spec(one_chip, (lines, pallas_sell.LINE_WORDS), jnp.int32),
               _spec(one_chip, (n_t,), jnp.int32))


def _column(one_chip, banded):
    nb, w, k = 8, 512, 16
    blk = _spec(one_chip, (nb, w, w), jnp.float32)
    x = _spec(one_chip, (nb, w, k), jnp.float32)
    x0 = _spec(one_chip, (w, k), jnp.float32)
    if banded:
        return pallas_blocks.column_spmm_pallas, (blk, blk, x, x0, blk,
                                                  blk, x, x)
    return pallas_blocks.column_spmm_pallas, (blk, blk, x, x0)


def _head(one_chip):
    nb, w, k = 8, 512, 16
    return pallas_blocks.head_spmm_pallas, (
        _spec(one_chip, (nb, w, w), jnp.float32),
        _spec(one_chip, (nb, w, k), jnp.float32))


def _synth_schedule(one_chip):
    """The committed graft-synth program: its per-tier schedule over a
    SellMatrix of its own tier shapes (binary)."""
    from arrow_matrix_tpu.tune import synth

    prog = next(iter(synth.load_store()["programs"].values()))
    sched = prog["schedule"]
    k = int(prog["k"])
    rows = [int(e["rows"]) for e in sched]
    starts = tuple(int(s) for s in np.cumsum([0] + rows[:-1]))
    m = SellMatrix(
        cols=tuple(_spec(one_chip, (int(e["m_t"]), r), jnp.int32)
                   for e, r in zip(sched, rows)),
        data=None,
        deg=tuple(_spec(one_chip, (r,), jnp.int32) for r in rows),
        n_rows=sum(rows), row_starts=starts)

    def f(m, x_t):
        return pallas_sell.sell_spmm_t_pallas(m, x_t, schedule=sched)

    return f, (m, _spec(one_chip, (k, sum(rows)), jnp.float32))


def _xla_fold(one_chip):
    """The XLA fold step at n=2^20 scale: BA-like tiers (many low-degree
    rows, a few hubs) at k=16, with a v5e-sized gather budget."""
    tiers = [(8, 614400), (16, 262144), (64, 131072), (512, 40960),
             (4096, 512)]
    n = sum(r for _, r in tiers)
    starts = tuple(int(s) for s in np.cumsum([0] + [r for _, r in
                                                     tiers[:-1]]))
    m = SellMatrix(
        cols=tuple(_spec(one_chip, (m_t, r), jnp.int32)
                   for m_t, r in tiers),
        data=None,
        deg=tuple(_spec(one_chip, (r,), jnp.int32) for _, r in tiers),
        n_rows=n, row_starts=starts)

    def f(m, x_t):
        return sell_spmm_t(m, x_t, gather_budget=2 << 30)

    return f, (m, _spec(one_chip, (16, n), jnp.float32))


def _xla_fold_tails(one_chip):
    """The XLA fold step at k=128 over exact-degree tiers whose slot
    counts the gather budget's chunk of 8 does not divide: each tier's
    last m mod 8 slots are gathered after its whole chunks."""
    tiers = [(10, 114540), (12, 69089), (19, 41117), (1157, 354)]
    n = sum(r for _, r in tiers)
    starts = tuple(int(s) for s in np.cumsum([0] + [r for _, r in
                                                     tiers[:-1]]))
    m = SellMatrix(
        cols=tuple(_spec(one_chip, (m_t, r), jnp.int32)
                   for m_t, r in tiers),
        data=None,
        deg=tuple(_spec(one_chip, (r,), jnp.int32) for _, r in tiers),
        n_rows=n, row_starts=starts)

    def f(m, x_t):
        return sell_spmm_t(m, x_t, gather_budget=1 << 29)

    return f, (m, _spec(one_chip, (128, n), jnp.float32))


CASES = {
    **{f"sell_stream_k{k}_{c}": (lambda oc, k=k, c=c: _sell_stream(oc, k, c),
                                 True)
       for k in (16, 128) for c in ("f32", "bf16", "int8")},
    "column_plain": (lambda oc: _column(oc, banded=False), True),
    "column_banded": (lambda oc: _column(oc, banded=True), True),
    "head": (_head, True),
    "synth_schedule": (_synth_schedule, True),
    "xla_fold_n2e20": (_xla_fold, False),
    "xla_fold_tails": (_xla_fold_tails, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip, compiled_for_chip):
    build, is_pallas = CASES[case]
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    if is_pallas:
        assert "tpu_custom_call" in compiled.as_text(), (
            f"{case}: no Mosaic kernel in the compiled HLO")
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert total < HBM_BYTES, f"{case}: {total} bytes on a 16 GB chip"
