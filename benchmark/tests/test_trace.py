"""The reduction from trace to busy time, idle share and breakdown."""

import os
import types

import pytest

from benchmark import trace
from benchmark.metrics import (device_idle_pct, fold_step_roofline,
                               step_busy_ms)
# A traced run of tiny-ba.k16 on one TPU v5e: 20 jobs of 10 iterations
# in a 0.05 s window, one dispatch per job.  The harness reported
# busy_s 0.033786717 and window_s 0.051986499 from it.
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny_ba_k16.xplane.pb.gz")


def _timeline():
    # Window 0..100.  A loop op holds two body ops; one op runs past
    # the window's end.
    tl = trace.Timeline()
    tl.host = [("bench.window", 0, 100), ("bench.dispatch", 0, 5),
               ("bench.block", 5, 50), ("bench.dispatch", 50, 55),
               ("bench.block", 55, 100)]
    tl.devices = {"/device:TPU:0": [
        ("%while.0 = (s32[], f32[8]) while(...)", 10, 40),
        ("%gather.1 = f32[8,128]{1,0} gather(...)", 10, 30),
        ("%fusion.2 = f32[8]{0} fusion(...)", 30, 40),
        ("%gather.1 = f32[8,128]{1,0} gather(...)", 60, 90),
        ("%copy.3 = f32[8]{0} copy(...)", 95, 120)]}
    return tl


def test_busy_is_the_union_clipped_to_the_window():
    s = trace.reduce(_timeline())
    assert s["window_s"] == pytest.approx(100e-9)
    # 10-40 (30) + 60-90 (30) + 95-100 (5, clipped).
    assert s["busy_s"] == pytest.approx(65e-9)
    assert s["devices"] == 1


def test_breakdown_counts_own_time_under_short_names():
    s = trace.reduce(_timeline())
    ops = dict(s["device_ops"])
    assert ops == pytest.approx({"gather.1 f32[8,128]": 50e-9,
                                 "fusion.2 f32[8]": 10e-9,
                                 "copy.3 f32[8]": 5e-9,
                                 "while.0": 0.0})
    gaps = s["idle_gaps"]
    # 40-60 (midpoint 50: the dispatch span is the innermost), 0-10,
    # 90-95 (block).
    assert [round(g[1] * 1e9) for g in gaps] == [20, 10, 5]
    assert gaps[0][0] == "bench.dispatch"
    assert gaps[2][0] == "bench.block"


def test_no_device_ops_reads_nothing():
    tl = _timeline()
    tl.devices = {}
    assert trace.reduce(tl) is None


def test_missing_window_raises():
    tl = _timeline()
    tl.host = [h for h in tl.host if h[0] != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(tl)


def _plane(name, **lines):
    ev = types.SimpleNamespace
    return ev(name=name, lines=[
        ev(name=line, events=[ev(name=n, start_ns=s, duration_ns=d)
                              for n, s, d in events])
        for line, events in lines.items()])


def test_device_ops_come_from_the_op_line_only():
    planes = [_plane("/host:CPU", python=[("bench.window", 0, 100)]),
              _plane("/device:TPU:0",
                     **{"XLA Ops": [("%gather.1 = f32[8]", 10, 20)],
                        "XLA Modules": [("jit_run", 5, 90)]}),
              _plane("/device:CUSTOM:Megascale Trace")]
    tl = trace.timeline(planes)
    assert tl.devices == {"/device:TPU:0": [("%gather.1 = f32[8]", 10,
                                             30)]}
    assert trace.reduce(tl)["busy_s"] == pytest.approx(20e-9)


def test_device_plane_without_op_line_raises():
    planes = [_plane("/host:CPU", python=[("bench.window", 0, 100)]),
              _plane("/device:TPU:0",
                     **{"XLA Modules": [("jit_run", 5, 90)]})]
    with pytest.raises(ValueError, match="XLA Ops"):
        trace.timeline(planes)


def test_chip_trace_reduces_to_the_reported_numbers():
    tl = trace.load(FIXTURE)
    assert list(tl.devices) == ["/device:TPU:0"]
    s = trace.reduce(tl)
    assert s["busy_s"] == pytest.approx(0.033786717, rel=1e-9)
    assert s["window_s"] == pytest.approx(0.051986499, rel=1e-9)
    # The traced run's graph: 4096 rows and 65,408 stored entries.
    run = types.SimpleNamespace(
        trace=s, iterations=200, device_kind="TPU v5 lite",
        work={"nnz": 65408, "n": 4096, "k": 16, "itemsize": 4})
    assert step_busy_ms.read(run) == pytest.approx(0.16893358, rel=1e-6)
    assert device_idle_pct.read(run) == pytest.approx(35.00867, rel=1e-5)
    # The run on the chip reported 0.5680393745324707 %.
    assert fold_step_roofline.read(run) == pytest.approx(0.56803937,
                                                         rel=1e-6)
    # Own times never exceed the busy time; the loop's body ops lead.
    ops = s["device_ops"]
    assert 0 < sum(t for _, t in ops) <= s["busy_s"] * (1 + 1e-9)
    assert ops[0][0].startswith("fusion.")
    # The device waits on the host between jobs: dispatch and block.
    assert {name for name, _ in s["idle_gaps"]} <= {"bench.dispatch",
                                                    "bench.block"}
    assert len(s["idle_gaps"]) == 10
