"""graft-flight (obs.memview / obs.imbalance / obs.flight) — executable
memory accounting vs the formats' static predictors on the checked-in
``ba_256_3`` decomposition fixtures, shard imbalance summaries for
skewed vs uniform layouts, and the flight recorder's crash-artifact
contract (the black box a SIGKILLed bench candidate leaves behind)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from arrow_matrix_tpu import obs
from arrow_matrix_tpu.obs import flight
from arrow_matrix_tpu.obs.__main__ import main as trace_main
from arrow_matrix_tpu.obs.imbalance import summarize_units

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# memory_report / account_memory
# ---------------------------------------------------------------------------


def _toy_jit():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda v: v @ v.T), jnp.ones((32, 16), jnp.float32)


def test_memory_report_components_and_total():
    f, x = _toy_jit()
    rep = obs.memory_report(f, x)
    assert rep["source"] in ("memory_analysis", "avals")
    # 32x16 f32 argument and 32x32 f32 output are known exactly.
    assert rep["argument_bytes"] == 32 * 16 * 4
    assert rep["output_bytes"] == 32 * 32 * 4
    known = [v for v in (rep["argument_bytes"], rep["output_bytes"],
                         rep["temp_bytes"], rep["generated_code_bytes"])
             if v is not None]
    assert rep["total_bytes"] <= sum(known)
    assert rep["total_bytes"] >= rep["output_bytes"]


def test_account_memory_gauges_and_ratio():
    f, x = _toy_jit()
    reg = obs.MetricsRegistry()
    rep = obs.account_memory("toy", f, x, predicted_bytes=1024,
                             registry=reg)
    assert rep["measured_bytes"] > 0
    assert rep["ratio"] == rep["measured_bytes"] / 1024
    assert reg.gauge("hbm_measured_bytes",
                     algorithm="toy").value == rep["measured_bytes"]
    assert reg.gauge("hbm_vs_predicted_ratio",
                     algorithm="toy").value == pytest.approx(rep["ratio"])
    # Human rendering carries the ratio line.
    text = obs.format_memory_report(rep)
    assert "measured vs format-model prediction" in text


def test_account_memory_without_predictor_has_no_ratio():
    f, x = _toy_jit()
    rep = obs.account_memory("toy", f, x)
    assert rep["predicted_bytes"] is None and rep["ratio"] is None
    assert obs.predicted_bytes_for(object(), 4) is None


def test_tree_device_bytes_counts_array_leaves_only():
    tree = {"a": np.zeros((8, 4), np.float32),
            "b": (np.zeros(3, np.int32), None, "label", 7)}
    assert obs.tree_device_bytes(tree) == 8 * 4 * 4 + 3 * 4


# ---------------------------------------------------------------------------
# Static predictor + imbalance on the checked-in decomposition fixture
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fixture_multi(ba_256_3_base):
    import jax

    from arrow_matrix_tpu.io import load_decomposition
    from arrow_matrix_tpu.io.graphio import as_levels
    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow

    levels = as_levels(
        load_decomposition(ba_256_3_base, 32, block_diagonal=True), 32)
    mesh = make_mesh((4,), ("blocks",), devices=jax.devices()[:4])
    return MultiLevelArrow(levels, 32, mesh=mesh), levels


def test_predictor_vs_measured_on_ba_fixture(fixture_multi):
    multi, _ = fixture_multi
    k = 4
    x = multi.set_features(np.random.default_rng(0).standard_normal(
        (multi.total_rows, k)).astype(np.float32))
    pred = obs.predicted_bytes_for(multi, k)
    assert pred and pred > 0
    mem = obs.account_memory("fixture", multi.step_fn, x,
                             *multi.step_operands(),
                             predicted_bytes=pred)
    assert mem["measured_bytes"] > 0
    # The model predicts the per-shard resident bytes from format
    # metadata alone; the compiled executable may add workspace but
    # must stay the same order of magnitude — a blowout here is the
    # OOM-in-waiting the ratio metric exists to catch.
    assert 0.25 <= mem["ratio"] <= 10.0


def test_shard_report_nnz_conserved_on_ba_fixture(fixture_multi):
    multi, levels = fixture_multi
    reg = obs.MetricsRegistry()
    rep = obs.account_imbalance("fixture", multi, registry=reg)
    assert rep is not None and rep["n_units"] > 1
    # Every stored nonzero is attributed to exactly one unit.
    assert rep["nnz_total"] == sum(l.matrix.nnz for l in levels)
    assert rep["slots_total"] >= rep["nnz_total"]
    assert 0.0 <= rep["padded_slot_waste"] <= 1.0
    assert rep["nnz_max_over_mean"] >= 1.0
    assert reg.gauge("shard_nnz_total",
                     algorithm="fixture").value == rep["nnz_total"]


def test_account_imbalance_none_without_shard_report():
    assert obs.shard_report_for(object()) is None
    assert obs.account_imbalance("x", object()) is None


def test_summarize_units_skewed_vs_uniform():
    uniform = summarize_units(rows=[64] * 4, nnz=[100] * 4,
                              slots=[128] * 4, units="device")
    assert uniform["nnz_max_over_mean"] == pytest.approx(1.0)
    assert uniform["rows_max_over_mean"] == pytest.approx(1.0)
    assert uniform["padded_slot_waste"] == pytest.approx(1 - 400 / 512)

    skewed = summarize_units(rows=[64] * 4, nnz=[10, 10, 10, 370],
                             slots=[128] * 4, units="device")
    assert skewed["nnz_total"] == uniform["nnz_total"]
    assert skewed["nnz_max_over_mean"] == pytest.approx(370 / 100)
    # Same totals -> same waste: skew and padding are separate axes.
    assert (skewed["padded_slot_waste"]
            == uniform["padded_slot_waste"])
    text = obs.format_imbalance_report(skewed)
    assert "paper imbalance bound" in text

    empty = summarize_units(rows=[], nnz=[], slots=[])
    assert empty["nnz_max_over_mean"] is None
    assert empty["padded_slot_waste"] is None


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounds_and_roundtrip(tmp_path):
    path = str(tmp_path / "ring.json")
    rec = flight.FlightRecorder(path, capacity=4)
    rec.note_memory_report({"algorithm": "toy", "measured_bytes": 7})
    for i in range(10):
        rec.record("test", f"event{i}", i=i)
    rec.seal("done")
    snap = flight.load(path)
    assert len(snap["events"]) == 4            # bounded ring
    # 11 events total (memreport + 10): 4 kept, 7 dropped.
    assert snap["dropped"] == 7
    assert [e["name"] for e in snap["events"]] == [
        f"event{i}" for i in range(6, 10)]
    assert snap["sealed"] == "done"
    assert snap["last_memory_report"]["measured_bytes"] == 7
    # Seal is first-wins: a later reason must not overwrite the cause.
    rec.seal("exit")
    assert flight.load(path)["sealed"] == "done"
    lines = flight.format_events(snap)
    assert any("event9" in ln for ln in lines)


def test_flight_module_record_is_noop_without_recorder():
    flight.set_recorder(None)
    flight.record("test", "nobody-listening")   # must not raise
    assert flight.get_recorder() is None


def test_metrics_and_spans_mirror_into_flight(tmp_path):
    rec = flight.FlightRecorder(str(tmp_path / "m.json"))
    flight.set_recorder(rec)
    try:
        reg = obs.MetricsRegistry()
        reg.gauge("hbm_measured_bytes", algorithm="a").set(123)
        tr = obs.Tracer("run")
        with tr.span("phase"):
            pass
        kinds = [(e["kind"], e["name"]) for e in rec.snapshot()["events"]]
        assert ("gauge", "hbm_measured_bytes") in kinds
        # Spans are mirrored ONCE, by the tracer, which keeps its own
        # record of them.
        assert kinds.count(("span", "phase")) == 1
        assert [s.name for s in tr.spans] == ["phase"]
    finally:
        flight.set_recorder(None)


def test_flight_seals_on_unhandled_exception(tmp_path):
    """install() chains sys.excepthook: a crashing process leaves a
    sealed artifact naming the exception."""
    path = str(tmp_path / "crash.json")
    code = textwrap.dedent(f"""
        from arrow_matrix_tpu.obs import flight
        flight.install({path!r})
        flight.record("test", "about-to-crash")
        raise RuntimeError("boom")
    """)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)
    assert proc.returncode != 0
    snap = flight.load(path)
    assert snap["sealed"].startswith("exception: RuntimeError: boom")
    assert [e["name"] for e in snap["events"]] == ["about-to-crash"]


def test_flight_artifact_survives_hard_kill(tmp_path):
    """The eager per-event flush is the whole point: a process dying
    with no exit handlers (os._exit stands in for the bench driver's
    SIGKILL-on-timeout) still leaves the ring on disk, unsealed."""
    path = str(tmp_path / "killed.json")
    code = textwrap.dedent(f"""
        import os
        from arrow_matrix_tpu.obs import flight
        flight.install({path!r})
        flight.record("progress", "built", stage=1)
        flight.record("progress", "uploading", stage=2)
        os._exit(1)
    """)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)
    assert proc.returncode == 1
    snap = flight.load(path)
    assert not snap.get("sealed")              # nothing ran at death
    assert [e["name"] for e in snap["events"]] == ["built", "uploading"]
    assert flight.newest_artifact(str(tmp_path)) == path


def test_blackbox_cli_prints_artifact(tmp_path, capsys):
    rec = flight.FlightRecorder(str(tmp_path / "bb.json"))
    rec.record("progress", "step-one")
    rec.seal("exit")
    assert trace_main(["blackbox", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "step-one" in out and "sealed: exit" in out
    assert trace_main(["blackbox",
                       str(tmp_path / "nothing-here")]) == 1


def test_memreport_cli_on_summary(tmp_path, capsys):
    run = tmp_path / "run"
    run.mkdir()
    (run / "summary.json").write_text(json.dumps({"algorithms": {
        "algo": {
            "memory": {"source": "memory_analysis",
                       "argument_bytes": 100, "output_bytes": 50,
                       "temp_bytes": 0, "generated_code_bytes": 0,
                       "alias_bytes": 0, "total_bytes": 150},
            "hbm_measured_bytes": 150, "hbm_predicted_bytes": 100,
            "hbm_vs_predicted": 1.5, "hbm_source": "memory_analysis",
            "imbalance": {"units": "device", "n_units": 2,
                          "rows_total": 8, "nnz_total": 6,
                          "slots_total": 12, "nnz_max_over_mean": 1.2,
                          "rows_max_over_mean": 1.0,
                          "padded_slot_waste": 0.5},
        }}}), encoding="utf-8")
    assert trace_main(["memreport", str(run)]) == 0
    out = capsys.readouterr().out
    assert "1.50x" in out and "paper imbalance bound" in out

    (run / "summary.json").write_text(
        json.dumps({"algorithms": {"algo": {"memory": None}}}),
        encoding="utf-8")
    assert trace_main(["memreport", str(run)]) == 1
