"""fold_packed_share reads the program's ``sell.packed_slots`` and
``sell.slots`` gauges, and nothing from a program without them."""

import types

import pytest

from arrow_matrix_tpu.obs import metrics as metrics_mod
from benchmark.metrics import fold_packed_share

RUN = types.SimpleNamespace(trace=None, iterations=10)


@pytest.fixture
def registry(monkeypatch):
    monkeypatch.setattr(metrics_mod, "_DEFAULT", metrics_mod.MetricsRegistry())
    return metrics_mod.get_registry()


@pytest.mark.parametrize("packed,want", [(1000, 100.0), (0, 0.0)])
def test_reads_the_packed_share(registry, packed, want):
    registry.gauge("sell.slots").set(1000)
    registry.gauge("sell.packed_slots").set(packed)
    assert fold_packed_share.read(RUN) == pytest.approx(want)


def test_reads_none_without_the_gauge(registry):
    assert fold_packed_share.read(RUN) is None
    registry.gauge("sell.slots").set(1000)
    assert fold_packed_share.read(RUN) is None
