"""graft-stream chunked overlap schedule: S static feature sub-slabs
per step, each running the full exchange+compute independently so the
latency-hiding scheduler can overlap slab i+1's collectives with slab
i's compute.

The contracts pinned here:
  * bit-identical f32 results for S in {1, 2, 4} on an 8-device CPU
    mesh (per-element addends never regroup — the split is along the
    feature axis, orthogonal to every accumulation);
  * S is STATIC: zero recompiles across iterations (the trace-time
    audit from analysis/audit.py);
  * validation — S must divide k, and overlap composes only with the
    unsharded feature axis (feat_axis=None);
  * the exposed_comm_ms model (obs/comm.py): modeled wire time / S,
    always present in a comm account.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
from arrow_matrix_tpu.parallel.mesh import make_mesh
from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
from arrow_matrix_tpu.parallel.routing import (
    build_route,
    overlap_slices,
    routed_take_t,
)
from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel
from arrow_matrix_tpu.utils.graphs import barabasi_albert, random_dense


def test_overlap_slices_values_and_validation():
    assert overlap_slices(16, 1) == [(0, 16)]
    assert overlap_slices(16, 0) == [(0, 16)]
    assert overlap_slices(16, 4) == [(0, 4), (4, 8), (8, 12), (12, 16)]
    assert overlap_slices(8, 2) == [(0, 4), (4, 8)]
    with pytest.raises(ValueError, match="must divide"):
        overlap_slices(16, 3)
    with pytest.raises(ValueError, match="must divide"):
        overlap_slices(4, 8)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((8,), ("blocks",))


@pytest.fixture(scope="module")
def problem():
    a = barabasi_albert(1 << 10, 4, seed=0)
    levels = arrow_decomposition(a, 64, max_levels=3,
                                 block_diagonal=True, seed=0)
    x = random_dense(a.shape[0], 8, seed=1)
    return levels, x


@pytest.mark.parametrize("s", [1, 2, 4])
def test_sell_multi_level_overlap_bit_identical(mesh, problem, s):
    """The overlapped sell executor must be BIT-identical (f32) to the
    serial one: the schedule changes collective/compute interleaving,
    never the arithmetic."""
    levels, x = problem
    base = SellMultiLevel(levels, 64, mesh)
    ref = np.asarray(base.gather_result(base.step(base.set_features(x))))
    sm = SellMultiLevel(levels, 64, mesh, overlap_slabs=s)
    assert sm.overlap_slabs == s
    got = np.asarray(sm.gather_result(sm.step(sm.set_features(x))))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("s", [2, 4])
def test_multi_level_a2a_overlap_bit_identical(mesh, problem, s):
    levels, x = problem
    base = MultiLevelArrow(levels, 64, mesh=mesh, routing="a2a")
    ref = np.asarray(base.gather_result(base.step(base.set_features(x))))
    ml = MultiLevelArrow(levels, 64, mesh=mesh, routing="a2a",
                         overlap_slabs=s)
    got = np.asarray(ml.gather_result(ml.step(ml.set_features(x))))
    np.testing.assert_array_equal(got, ref)


def test_overlap_zero_recompiles(mesh, problem):
    """S is a static schedule: iterating the overlapped step must not
    recompile (the recompile audit is the acceptance gate — a dynamic
    slab boundary would retrace per call)."""
    from arrow_matrix_tpu.analysis.audit import audit_entry

    levels, x = problem
    for name, obj in (
            ("sell_multi_level_s2",
             SellMultiLevel(levels, 64, mesh, overlap_slabs=2)),
            ("multi_level_a2a_s2",
             MultiLevelArrow(levels, 64, mesh=mesh, routing="a2a",
                             overlap_slabs=2))):
        xt = obj.set_features(x)
        rec = audit_entry(
            name, obj.step_fn,
            lambda o=obj, v=xt: jax.block_until_ready(o.step(v)),
            lambda o=obj, v=xt: jax.eval_shape(o.step, v))
        assert rec["recompiles_second_call"] == 0, rec
        assert rec["compiles_first_call"] >= 1, rec


def test_overlap_must_divide_k(mesh, problem):
    levels, x = problem
    sm = SellMultiLevel(levels, 64, mesh, overlap_slabs=3)
    with pytest.raises(ValueError, match="must divide"):
        sm.step(sm.set_features(x))   # k=8, S=3: raised at trace time


def test_overlap_rejects_feat_axis(problem):
    levels, _ = problem
    mesh2 = make_mesh((4, 2), ("blocks", "feat"))
    with pytest.raises(ValueError, match="feat_axis"):
        SellMultiLevel(levels, 64, mesh2, routing="a2a",
                       feat_axis="feat", overlap_slabs=2)


def test_routed_take_t_overlap_matches_serial(mesh):
    rng = np.random.default_rng(0)
    total, k = 1024, 8
    table = rng.permutation(total)
    route = build_route(table, 8)
    x_host = rng.standard_normal((k, total)).astype(np.float32)
    xt = jax.device_put(x_host, NamedSharding(mesh, P(None, "blocks")))
    ref = np.asarray(jax.jit(
        lambda v: routed_take_t(v, route, mesh, "blocks"))(xt))
    got = np.asarray(jax.jit(
        lambda v: routed_take_t(v, route, mesh, "blocks",
                                overlap_slabs=2))(xt))
    np.testing.assert_array_equal(got, ref)
    with pytest.raises(ValueError, match="feat_axis"):
        routed_take_t(xt, route, mesh, "blocks", feat_axis="blocks",
                      overlap_slabs=2)


def test_exposed_comm_ms_model():
    """Exact at both ends: 0 bytes -> 0 ms; S=1 -> full wire time;
    S slabs -> 1/S of it (only the first slab's exchange is
    structurally un-hideable)."""
    from arrow_matrix_tpu.obs.comm import exposed_comm_ms

    assert exposed_comm_ms(0) == 0.0
    full = exposed_comm_ms(45_000_000, link_bytes_per_s=45e9)
    assert full == pytest.approx(1.0)   # 45 MB over 45 GB/s = 1 ms
    assert exposed_comm_ms(45_000_000, overlap_slabs=4,
                           link_bytes_per_s=45e9) == pytest.approx(0.25)
    # degenerate S values clamp to 1
    assert exposed_comm_ms(45_000_000, overlap_slabs=0,
                           link_bytes_per_s=45e9) == pytest.approx(1.0)


def test_account_collectives_always_reports_exposed(mesh, problem):
    """The comm account must carry exposed_comm_ms for every
    algorithm (tools/obs_gate.py rejects reports without it), scaled
    by the executor's overlap_slabs."""
    from arrow_matrix_tpu.obs.comm import account_collectives, ideal_bytes_for

    levels, x = problem
    reports = {}
    for s in (1, 2):
        sm = SellMultiLevel(levels, 64, mesh, overlap_slabs=s)
        xt = sm.set_features(x)
        rep = account_collectives(
            f"sell_s{s}", sm.step_fn, xt, *sm.step_operands(),
            ideal_bytes=ideal_bytes_for(sm, x.shape[1]),
            overlap_slabs=sm.overlap_slabs)
        assert "exposed_comm_ms" in rep
        assert rep["overlap_slabs"] == s
        reports[s] = rep
    assert reports[1]["measured_bytes"] == reports[2]["measured_bytes"]
    assert reports[2]["exposed_comm_ms"] == pytest.approx(
        reports[1]["exposed_comm_ms"] / 2)


def test_dryrun_multichip_mid_records_exposed(monkeypatch):
    """The opt-in mid-scale rung (VERDICT r4 item 7) at
    logic-validation size: both algorithms golden-gated, each record
    carrying the exposed_comm_ms field (fold proves the zero end)."""
    import __graft_entry__ as ge

    monkeypatch.setenv("AMT_DRYRUN_MID_LOGN", "11")
    out = ge.dryrun_multichip(8, scale="mid")
    assert set(out["algorithms"]) == {"fold", "sell_a2a"}
    fold, a2a = out["algorithms"]["fold"], out["algorithms"]["sell_a2a"]
    assert fold["exposed_comm_ms"] == 0.0
    assert a2a["exposed_comm_ms"] > 0
    assert a2a["overlap_slabs"] == 2
    assert out["host_load"] is not None
    with pytest.raises(ValueError, match="unknown scale"):
        ge.dryrun_multichip(8, scale="huge")
