"""fold_gather_pad_share reads the program's ``sell.gathered_slots`` and
``sell.nnz`` gauges, and nothing from a program without them."""

import types

import pytest

from arrow_matrix_tpu.obs import metrics as metrics_mod
from benchmark.metrics import fold_gather_pad_share

RUN = types.SimpleNamespace(trace=None, iterations=10)


@pytest.fixture
def registry(monkeypatch):
    monkeypatch.setattr(metrics_mod, "_DEFAULT", metrics_mod.MetricsRegistry())
    return metrics_mod.get_registry()


@pytest.mark.parametrize("gathered,want", [(1000, 20.0), (800, 0.0)])
def test_reads_the_gathered_pad_share(registry, gathered, want):
    registry.gauge("sell.nnz").set(800)
    registry.gauge("sell.gathered_slots").set(gathered)
    assert fold_gather_pad_share.read(RUN) == pytest.approx(want)


def test_reads_none_without_the_gauge(registry):
    assert fold_gather_pad_share.read(RUN) is None
    registry.gauge("sell.nnz").set(800)
    assert fold_gather_pad_share.read(RUN) is None
