"""Spans and counters of the fold executor build, and the benchmark's
per-layer readers of them (benchmark/metrics/)."""

import importlib.util
import os
import types

import numpy as np
import pytest

from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.obs import metrics as metrics_mod
from arrow_matrix_tpu.obs import tracer as tracer_mod
from arrow_matrix_tpu.ops.sell import sell_stats
from arrow_matrix_tpu.parallel import MultiLevelArrow
from arrow_matrix_tpu.utils import barabasi_albert, random_dense

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD_SPANS = ("fold.compose", "sell.pack", "sell.upload")


@pytest.fixture
def fresh_obs(monkeypatch):
    """A process tracer and registry of the test's own, restored after."""
    monkeypatch.setattr(tracer_mod, "_DEFAULT", tracer_mod.Tracer("t"))
    monkeypatch.setattr(metrics_mod, "_DEFAULT", metrics_mod.MetricsRegistry())
    return tracer_mod.get_tracer(), metrics_mod.get_registry()


@pytest.fixture(scope="module")
def levels():
    a = barabasi_albert(400, 4, seed=5)
    return arrow_decomposition(a, 32, max_levels=3, block_diagonal=True,
                               seed=1)


def _span_names(tracer):
    return sorted(s.name for s in tracer.spans)


def test_fold_build_records_each_span_once(fresh_obs, levels):
    tracer, _ = fresh_obs
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold")
    assert _span_names(tracer) == sorted(BUILD_SPANS)
    # The operator is on the device when the build returns.
    import jax

    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(ml.blocks[0]))


@pytest.mark.parametrize("device_put", [True, False])
def test_load_folded_records_its_upload(fresh_obs, levels, tmp_path,
                                        device_put):
    tracer, _ = fresh_obs
    MultiLevelArrow(levels, 32, mesh=None, fmt="fold").export_folded(
        str(tmp_path))
    tracer.spans.clear()
    ml = MultiLevelArrow.load_folded(str(tmp_path), device_put=device_put)
    assert _span_names(tracer) == (["sell.upload"] if device_put else [])
    x = random_dense(ml.n, 4, seed=2)
    assert np.isfinite(ml.gather_result(ml.step(ml.set_features(x)))).all()


@pytest.mark.parametrize("binary", ["auto", False])
def test_sell_gauges_equal_sell_stats(fresh_obs, levels, binary):
    _, reg = fresh_obs
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold", binary=binary)
    assert ml.blocks[0].binary == (binary == "auto")
    stats = sell_stats(ml.blocks[0])
    assert reg.gauge("sell.slots").value == sum(stats["slots"])
    assert reg.gauge("sell.nnz").value == sum(stats["nnz"])
    assert ml.blocks[0].n_slots == sum(stats["slots"])


@pytest.mark.parametrize("k,packed", [(16, True), (128, False)])
def test_fold_records_packed_slots(fresh_obs, levels, k, packed):
    """Tracing the fold step records how many slot-rows take the
    lane-packed gather: all of them at k=16, none at k=128."""
    _, reg = fresh_obs
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold")
    xt = ml.set_features(random_dense(ml.n, k, seed=4))
    assert reg.gauge("sell.packed_slots").value is None
    ml.run(xt, 2)
    slots = reg.gauge("sell.slots").value
    assert slots == ml.blocks[0].n_slots > 0
    assert reg.gauge("sell.packed_slots").value == (slots if packed else 0)


def test_run_lowering_names_no_span(fresh_obs, levels):
    tracer, _ = fresh_obs
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold")
    xt = ml.set_features(random_dense(ml.n, 4, seed=3))
    before = len(tracer.spans)
    text = ml._scan_steps.lower(xt, ml.fwd, ml.bwd, ml.blocks,
                                n=10).as_text(debug_info=True)
    assert "scan" in text or "while" in text
    for name in BUILD_SPANS:
        assert name not in text
    # Nothing is recorded per iteration.
    ml.run(xt, 2)
    ml.step(xt)
    assert len(tracer.spans) == before


# -- the benchmark's readers ------------------------------------------------

def _reader(name):
    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stub_run(busy_s=0.5, iterations=10):
    return types.SimpleNamespace(
        trace={"busy_s": busy_s, "window_s": 1.0}, iterations=iterations)


def _populate(tracer, reg, slots=1000, nnz=800):
    for name in BUILD_SPANS:
        with tracer.span(name):
            pass
    reg.gauge("sell.slots").set(slots)
    reg.gauge("sell.nnz").set(nnz)


@pytest.mark.parametrize("metric,span", [
    ("fold_compose_s", "fold.compose"),
    ("sell_pack_s", "sell.pack"),
    ("sell_upload_s", "sell.upload"),
])
def test_span_readers(fresh_obs, metric, span):
    tracer, reg = fresh_obs
    read = _reader(metric).read
    assert read(_stub_run()) is None
    _populate(tracer, reg)
    want = tracer.phase_ms()[span] / 1e3
    assert read(_stub_run()) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("fold_pad_share", 20.0),
    ("fold_slot_ns", 1e9 * 0.5 / 10 / 1000),
])
def test_counter_readers(fresh_obs, metric, want):
    tracer, reg = fresh_obs
    read = _reader(metric).read
    assert read(_stub_run()) is None
    _populate(tracer, reg)
    assert read(_stub_run()) == pytest.approx(want)


def test_fold_slot_ns_needs_a_trace(fresh_obs):
    tracer, reg = fresh_obs
    _populate(tracer, reg)
    read = _reader("fold_slot_ns").read
    assert read(types.SimpleNamespace(trace=None, iterations=10)) is None
    assert read(_stub_run(iterations=0)) is None


def test_span_readers_without_process_tracer(fresh_obs, monkeypatch):
    """A program whose obs has no process tracer reads as None."""
    from arrow_matrix_tpu import obs

    _populate(*fresh_obs)
    monkeypatch.delattr(obs, "get_tracer")
    assert _reader("fold_compose_s").read(_stub_run()) is None
