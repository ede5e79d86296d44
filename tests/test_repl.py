"""graft-repl: 2.5D replicated arrow/SELL executors and the
model-driven replication planner.

The contracts pinned here (Lazzaro et al., arxiv 1705.10218, adapted
to the arrow decomposition):

  * the HONEST bit-identity deal — with the block count B fixed,
    buying replicas with extra devices (``make_repl_mesh(B*c, c)``)
    yields ``np.array_equal`` results at every c AND per-device
    measured collective bytes divided by EXACTLY c (each replica
    group runs the identical exchange program on a static k/c
    feature slab);
  * replication is the mesh executors' (``repl_axis``):
    ``MultiLevelArrow`` takes no ``repl`` (one chip has no exchange to
    cut);
  * validation — c must divide the device count and the feature
    width, ``repl_axis`` composes with ``feat_axis=None`` and
    ``routing="a2a"`` only (the GSPMD gather lowering assumes a
    replicated carriage and corrupts the divergent 2.5D slabs);
  * the planner — ``auto_repl`` certifies base×c against the HBM
    budget, minimizes the T(c) model, and degrades LOUDLY to c=1;
  * the checkpoint contract — ``merge_carries`` canonicalizes the
    divergent carriage into a fully replicated bit-exact resume
    state, and the Supervisor's ``canonicalize`` hook applies it
    before every save;
  * accounting — comm reports always carry ``repl``/``reduce_bytes``
    and tools/obs_gate.py rejects repl>1 reports without them.
"""

import numpy as np
import pytest

import jax

from arrow_matrix_tpu.decomposition.decompose import (
    arrow_decomposition,
    decomposition_spmm,
)
from arrow_matrix_tpu.parallel.mesh import (
    largest_replication,
    make_mesh,
    make_repl_mesh,
)
from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
from arrow_matrix_tpu.parallel.routing import repl_slab_width
from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel, SellSlim
from arrow_matrix_tpu.utils.graphs import barabasi_albert, random_dense


# ---------------------------------------------------------------- mesh


def test_largest_replication_values():
    assert largest_replication(1) == 1
    assert largest_replication(2) == 1
    assert largest_replication(4) == 2
    assert largest_replication(8) == 2     # 8 % 16 != 0
    assert largest_replication(12) == 2
    assert largest_replication(16) == 4


def test_make_repl_mesh_shapes_and_validation():
    m = make_repl_mesh(8, 2)
    assert dict(m.shape) == {"blocks": 4, "repl": 2}
    # repl=1 degenerates to a trailing axis of extent 1 so one mesh
    # shape threads through both the replicated and baseline paths.
    m1 = make_repl_mesh(4, 1)
    assert dict(m1.shape) == {"blocks": 4, "repl": 1}
    with pytest.raises(ValueError, match="must divide"):
        make_repl_mesh(8, 3)
    with pytest.raises(ValueError, match=">= 1"):
        make_repl_mesh(8, 0)


def test_repl_slab_width_validation():
    assert repl_slab_width(16, 1) == 16
    assert repl_slab_width(16, 4) == 4
    with pytest.raises(ValueError, match="must divide"):
        repl_slab_width(16, 3)
    with pytest.raises(ValueError, match="must divide"):
        repl_slab_width(2, 4)


# ------------------------------------------------------------- planner


def test_repl_predict_ms_model():
    from arrow_matrix_tpu.obs.comm import repl_predict_ms

    bw = 45e9
    t1 = repl_predict_ms(1, 45_000_000, link_bytes_per_s=bw,
                         latency_s=0.0)
    assert t1 == pytest.approx(1.0)   # 45 MB over 45 GB/s = 1 ms
    # The wire term divides by exactly c; latency does not.
    assert repl_predict_ms(2, 45_000_000, link_bytes_per_s=bw,
                           latency_s=0.0) == pytest.approx(0.5)
    lat = repl_predict_ms(2, 0, n_coll=3, link_bytes_per_s=bw,
                          latency_s=1e-3)
    assert lat == pytest.approx(3.0)
    # The final-merge term is amortized over iterations and absent
    # at c=1 — the term that makes T(c) non-monotone.
    r = repl_predict_ms(2, 0, reduce_bytes=45_000_000, iterations=10,
                        link_bytes_per_s=bw, latency_s=0.0)
    assert r == pytest.approx(0.1)
    assert repl_predict_ms(1, 0, reduce_bytes=45_000_000,
                           link_bytes_per_s=bw, latency_s=0.0) == 0.0


def test_auto_repl_picks_certified_c():
    from arrow_matrix_tpu.obs.comm import auto_repl

    plan = auto_repl(8, 8, base_hbm_bytes=100,
                     budget_bytes=1000, exchange_bytes=1 << 20,
                     quiet=True)
    # Wire-dominated and everything fits: the largest c wins.
    assert plan["c"] == 4
    assert plan["feasible"] == [1, 2, 4]
    assert not plan["degraded"]
    assert plan["predicted_ms"][4] < plan["predicted_ms"][1]
    # Zero-comm problem: ties break toward c=1 (don't pay memory
    # for nothing).
    free = auto_repl(8, 8, base_hbm_bytes=100, budget_bytes=1000,
                     exchange_bytes=0, quiet=True)
    assert free["c"] == 1 and not free["degraded"]


def test_auto_repl_divisibility_rejections():
    from arrow_matrix_tpu.obs.comm import auto_repl

    plan = auto_repl(6, 8, base_hbm_bytes=100, budget_bytes=1000,
                     exchange_bytes=1 << 20, quiet=True)
    assert plan["c"] == 2
    assert "n_dev" in plan["rejected"][4]
    odd_k = auto_repl(8, 7, base_hbm_bytes=100, budget_bytes=1000,
                      exchange_bytes=1 << 20, quiet=True)
    assert odd_k["c"] == 1
    assert "feature width" in odd_k["rejected"][2]


def test_auto_repl_degrades_loudly(monkeypatch, capsys):
    from arrow_matrix_tpu.obs.comm import auto_repl

    monkeypatch.setenv("AMT_HBM_GB", "0.0000001")   # ~107 bytes
    plan = auto_repl(8, 8, base_hbm_bytes=100,
                     exchange_bytes=1 << 20)
    assert plan["c"] == 1
    assert plan["degraded"] is True
    assert "DEGRADED" in capsys.readouterr().err
    # c=1 stays feasible even when the base footprint itself is over
    # budget — the baseline is a capacity problem, not a plan choice.
    assert 1 in plan["feasible"]


def test_hbm_budget_env_override(monkeypatch):
    from arrow_matrix_tpu.obs.comm import hbm_budget_bytes

    monkeypatch.setenv("AMT_HBM_GB", "2")
    assert hbm_budget_bytes() == 2 * 2**30
    monkeypatch.delenv("AMT_HBM_GB")
    assert hbm_budget_bytes(default=123) == 123


def test_largest_fitting_repl_and_predicted_bytes():
    from arrow_matrix_tpu.obs.memview import (
        largest_fitting_repl,
        predicted_bytes_for,
    )

    assert largest_fitting_repl(100, 250) == 2
    assert largest_fitting_repl(100, 1000) == 8
    assert largest_fitting_repl(100, 50) == 1
    assert largest_fitting_repl(100, 250, choices=(1, 2, 4)) == 2

    class _NoRepl:
        def predicted_hbm_bytes(self, k, itemsize=4):
            return 100 * k * itemsize

    # Executors without the repl kwarg get the ×c planning multiplier.
    assert predicted_bytes_for(_NoRepl(), 2) == 800
    assert predicted_bytes_for(_NoRepl(), 2, repl=3) == 2400


# ----------------------------------------------------------- executors


@pytest.fixture(scope="module")
def problem():
    a = barabasi_albert(1 << 9, 4, seed=0)
    levels = arrow_decomposition(a, 32, max_levels=3,
                                 block_diagonal=True, seed=0)
    x = random_dense(a.shape[0], 8, seed=1)
    return levels, x


def test_fold_repl_validation(problem):
    """MultiLevelArrow has no replica groups: the fold refuses ``repl``
    outright, one chip or mesh, any format (the 2.5D executors are
    SellMultiLevel/SellSlim on a ``make_repl_mesh`` mesh)."""
    levels, _ = problem
    for c in (0, 1, 2):
        with pytest.raises(TypeError, match="repl"):
            MultiLevelArrow(levels, 32, mesh=None, fmt="fold", repl=c)
    with pytest.raises(TypeError, match="repl"):
        MultiLevelArrow(levels, 32, mesh=None, fmt="ell", repl=2)
    with pytest.raises(TypeError, match="repl"):
        MultiLevelArrow(levels, 32, mesh=make_mesh((4,), ("blocks",)),
                        fmt="ell", repl=2)


def test_sell_repl_same_B_bit_identical_and_bytes_div_c(problem):
    """The honest 2.5D deal at fixed B=2 block shards: c replicas on
    B*c devices give np.array_equal results and measured per-device
    collective bytes divided by EXACTLY c — the identical exchange
    program runs on a k/c feature slab within each replica group."""
    from arrow_matrix_tpu.obs.comm import (
        account_collectives,
        ideal_bytes_for,
        reduce_bytes_for,
    )

    levels, x = problem
    k = x.shape[1]
    want = decomposition_spmm(levels, x)
    devs = jax.devices()
    base = None
    base_bytes = None
    for c in (1, 2, 4):
        mesh = make_repl_mesh(2 * c, c, devices=devs[:2 * c])
        sm = SellMultiLevel(levels, 32, mesh, routing="a2a",
                            repl_axis=("repl" if c > 1 else None))
        xt = sm.set_features(x)
        got = sm.gather_result(sm.step(xt))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        if base is None:
            base = got
        assert np.array_equal(got, base), f"sell repl c={c} diverged"
        rep = account_collectives(
            f"sell_repl_c{c}", sm.step_fn, xt, *sm.step_operands(),
            ideal_bytes=ideal_bytes_for(sm, k), repl=sm.repl,
            reduce_bytes=reduce_bytes_for(sm, k))
        if base_bytes is None:
            base_bytes = rep["measured_bytes"]
        assert rep["measured_bytes"] * c == base_bytes, (
            f"c={c}: {rep['measured_bytes']} * {c} != {base_bytes}")
        assert rep["repl"] == c
        if c == 1:
            assert reduce_bytes_for(sm, k) == 0
        else:
            assert reduce_bytes_for(sm, k) > 0


def test_sell_slim_single_matrix_repl(problem):
    """SellSlim (one arrow matrix) carries the same repl_axis mode."""
    levels, x = problem
    lvl = levels[0]
    devs = jax.devices()
    mesh1 = make_mesh((2,), ("blocks",), devices=devs[:2])
    d1 = SellSlim(lvl.matrix, 32, mesh1)
    want = d1.gather_result(d1.spmm(d1.set_features(x)))
    mesh2 = make_repl_mesh(4, 2, devices=devs[:4])
    d2 = SellSlim(lvl.matrix, 32, mesh2, repl_axis="repl")
    assert d2.repl == 2
    got = d2.gather_result(d2.spmm(d2.set_features(x)))
    assert np.array_equal(got, want)


def test_repl_axis_validation(problem):
    levels, _ = problem
    mesh = make_repl_mesh(8, 2)
    with pytest.raises(ValueError, match="not a mesh axis"):
        SellMultiLevel(levels, 32, mesh, repl_axis="replicas")
    with pytest.raises(ValueError, match="must differ"):
        SellMultiLevel(levels, 32, mesh, axis="blocks",
                       repl_axis="blocks")
    with pytest.raises(ValueError, match="feat_axis"):
        SellMultiLevel(levels, 32, mesh, repl_axis="repl",
                       feat_axis="repl")
    # The GSPMD gather lowering assumes a replicated carriage; the
    # divergent 2.5D slabs corrupt under it (verified), so it is
    # forbidden outright rather than warned about.
    with pytest.raises(ValueError, match="a2a"):
        SellMultiLevel(levels, 32, mesh, routing="gather",
                       repl_axis="repl")


# --------------------------------------------------- checkpoint merge


def test_merge_carries_canonical_resume(problem):
    """merge_carries folds the divergent per-group slabs into the
    fully replicated canonical carriage: same gathered result, and
    stepping from the merged state is bit-identical to stepping from
    the divergent one (each group re-extracts its own slab, whose
    values only it contributed) — the bit-exact resume contract."""
    levels, x = problem
    devs = jax.devices()
    mesh = make_repl_mesh(4, 2, devices=devs[:4])
    sm = SellMultiLevel(levels, 32, mesh, routing="a2a",
                        repl_axis="repl")
    ct = sm.step(sm.set_features(x))
    merged = sm.merge_carries(ct)
    assert np.array_equal(sm.gather_result(merged),
                          sm.gather_result(ct))
    assert np.array_equal(sm.gather_result(sm.step(merged)),
                          sm.gather_result(sm.step(ct)))
    # Without a replica axis merge_carries is the identity.
    mesh1 = make_mesh((2,), ("blocks",), devices=devs[:2])
    s1 = SellMultiLevel(levels, 32, mesh1)
    c1 = s1.step(s1.set_features(x))
    assert s1.merge_carries(c1) is c1 or np.array_equal(
        np.asarray(s1.merge_carries(c1)), np.asarray(c1))


def test_supervisor_canonicalize_hook(tmp_path):
    """The Supervisor applies the executor-supplied canonicalize
    before every save — checkpoints of a replicated run hold the
    merged carriage, never replica 0's partial view."""
    from arrow_matrix_tpu.faults import Supervisor
    from arrow_matrix_tpu.utils.checkpoint import load_state

    calls = []

    def canon(x):
        calls.append(1)
        return x * 2.0

    ck = str(tmp_path / "ck")
    sup = Supervisor("t", carry=True, checkpoint_path=ck,
                     checkpoint_every=1, verbose=False,
                     canonicalize=canon)
    x0 = jax.numpy.ones((4, 4), np.float32)
    y, ok = sup.run(lambda x, it: x + 1.0, x0, 0, 2)
    assert ok and calls
    saved = load_state(ck)
    assert saved is not None and saved[1] == 2
    np.testing.assert_array_equal(np.asarray(saved[0]),
                                  np.asarray(y) * 2.0)


# ---------------------------------------------------------- accounting


def test_obs_gate_flags_incomplete_repl_report():
    import importlib

    obs_gate = importlib.import_module("tools.obs_gate")

    good = {"algorithms": {"a": {"exposed_comm_ms": 0.1, "repl": 2,
                                 "reduce_bytes": 64}}}
    assert obs_gate.comm_problems(good) == []
    bad = {"algorithms": {"a": {"exposed_comm_ms": 0.1, "repl": 2,
                                "reduce_bytes": None}}}
    assert any("reduce_bytes" in p for p in obs_gate.comm_problems(bad))
    ok1 = {"algorithms": {"a": {"exposed_comm_ms": 0.1, "repl": 1,
                                "reduce_bytes": 0}}}
    assert obs_gate.comm_problems(ok1) == []


def test_account_collectives_defaults_carry_repl_fields():
    from arrow_matrix_tpu import obs

    def f(x):
        return x * 2

    rep = obs.account_collectives(
        "plain", jax.jit(f), np.ones((4,), np.float32))
    assert rep["repl"] == 1
    assert rep["reduce_bytes"] == 0


# --------------------------------------------------------- scale rungs


def test_dryrun_repl_rung_enforces_contract(monkeypatch):
    """The scale-ladder repl rung at logic-validation size: the sell
    ladder bit-identical at every c, its bytes exactly ÷c, plus the
    8-device c=1 production reference."""
    import __graft_entry__ as ge

    monkeypatch.setenv("AMT_DRYRUN_MID_LOGN", "11")
    out = ge.dryrun_multichip(8, scale="repl")
    assert out["scale"] == "repl" and out["B"] == 2
    sell = out["algorithms"]["sell_a2a_repl"]
    for c in ("1", "2", "4"):
        assert sell[c]["bit_identical_to_c1"]
    b1 = sell["1"]["measured_bytes"]
    assert sell["2"]["measured_bytes"] * 2 == b1
    assert sell["4"]["measured_bytes"] * 4 == b1
    assert sell["4"]["bytes_exactly_div_c"]
    assert "sell_a2a_8dev_reference" in out["algorithms"]
    with pytest.raises(ValueError, match="repl"):
        ge.dryrun_multichip(8, scale="huge")


def test_scale_ladder_registers_repl_rung():
    import importlib

    sl = importlib.import_module("tools.scale_ladder")

    assert "dryrun_repl_sweep" in sl.RUNGS
    assert "dryrun_repl_sweep" not in sl.DEFAULT_RUNGS


# ---------------------------------------------------------------- CLI


def test_spmm_arrow_repl_cli_validates(tmp_path, monkeypatch):
    from arrow_matrix_tpu.cli import spmm_arrow

    monkeypatch.chdir(tmp_path)
    rc = spmm_arrow.main([
        "--vertices", "400", "--width", "32", "--features", "4",
        "--iterations", "2", "--validate", "true", "--device", "cpu",
        "--devices", "4", "--fmt", "sell", "--repl", "2",
        "--logdir", str(tmp_path / "logs"),
    ])
    assert rc == 0


def test_spmm_arrow_repl_flag_errors(tmp_path, monkeypatch):
    from arrow_matrix_tpu.cli import spmm_arrow

    monkeypatch.chdir(tmp_path)
    base = ["--vertices", "300", "--width", "32", "--features", "4",
            "--iterations", "1", "--device", "cpu",
            "--logdir", str(tmp_path / "logs")]
    with pytest.raises(SystemExit, match="slim"):
        spmm_arrow.main(base + ["--repl", "2", "--slim", "false"])
    with pytest.raises(SystemExit, match="time"):
        spmm_arrow.main(base + ["--repl", "2", "--mode", "space"])
    with pytest.raises(SystemExit, match="a2a"):
        spmm_arrow.main(base + ["--repl", "2", "--routing", "gather"])
