"""graft-scope (arrow_matrix_tpu.obs) — metrics registry round-trips,
tracer span/Chrome-trace structure, the honest timing helpers, comm
accounting on a real shard_map collective, the reduced-scale smoke run
(the same artifact contract tools/obs_gate.py and amt_doctor assert),
and the graft_trace CLI including the diff regression gate."""

import json
import os

import numpy as np
import pytest

from arrow_matrix_tpu import obs
from arrow_matrix_tpu.obs.__main__ import _diff_records, main as trace_main
from arrow_matrix_tpu.obs.smoke import (
    ALGORITHMS,
    run_smoke,
    validate_run_dir,
)
from arrow_matrix_tpu.utils.logging import SegmentLog


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_round_trip(tmp_path):
    reg = obs.MetricsRegistry(run_dir=str(tmp_path))
    reg.counter("steps", algorithm="a").inc()
    reg.counter("steps", algorithm="a").inc(2)
    reg.gauge("bytes", algorithm="a").set(128)
    for v in (1.0, 2.0, 3.0):
        reg.record("lat_ms", v, algorithm="a")

    snap = reg.snapshot()
    assert snap["counters"][0]["value"] == 3.0
    assert snap["gauges"][0]["value"] == 128.0
    hist = snap["histograms"][0]["summary"]
    assert hist["count"] == 3 and hist["mean"] == 2.0
    assert hist["min"] == 1.0 and hist["max"] == 3.0

    # Same (name, labels) -> same instrument; different labels -> new.
    assert reg.counter("steps", algorithm="a").value == 3.0
    assert reg.counter("steps", algorithm="b").value == 0.0

    path = reg.write_jsonl()
    assert path == str(tmp_path / "metrics.jsonl")
    events = [json.loads(l) for l in open(path, encoding="utf-8")]
    # 2 counter incs + 1 gauge set + 3 histogram observations.
    assert len(events) == 6
    assert all({"ts", "kind", "name", "value", "labels"} <= set(e)
               for e in events)


def test_registry_requires_destination():
    with pytest.raises(ValueError):
        obs.MetricsRegistry().write_jsonl()


def test_merge_segment_log():
    seg = SegmentLog(algorithm="algo", dataset="ds")
    seg.set_iteration_data({"iteration": 0})
    seg.log({"spmm_time": 0.5, "note": "text ignored"})
    seg.log({"spmm_time": 0.7})

    reg = obs.MetricsRegistry()
    assert reg.merge_segment_log(seg) == 2
    h = reg.histogram("spmm_time", algorithm="algo", dataset="ds")
    assert h.summary()["count"] == 2
    # "iteration" context and non-numeric fields are not metrics.
    assert not any(e["name"] in ("iteration", "note") for e in reg.events)


def test_segment_log_raising_body_still_logs():
    # Regression for the try/finally fix: the time-to-failure is part
    # of the run record.
    seg = SegmentLog()
    with pytest.raises(RuntimeError):
        with seg.segment("doomed"):
            raise RuntimeError("boom")
    assert len(seg.entries) == 1 and "doomed" in seg.entries[0]


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_tracer_spans_and_chrome_trace(tmp_path):
    tr = obs.Tracer("myrun")
    with tr.span("outer"):
        with tr.span("inner", detail=7) as args:
            args["extra"] = "x"

    assert tr.phase_ms().keys() == {"outer", "inner"}
    trace = tr.to_chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    meta, *events = trace["traceEvents"]
    assert meta["ph"] == "M" and meta["args"]["name"] == "myrun"
    assert [e["name"] for e in events] == ["outer", "inner"]  # ts order
    outer, inner = events
    assert inner["ph"] == "X" and inner["dur"] >= 0
    assert inner["args"] == {"detail": 7, "extra": "x",
                             "span_id": inner["args"]["span_id"],
                             "parent": "outer",
                             "parent_id": outer["args"]["span_id"]}
    # Each span is recorded once, by the tracer itself.
    assert [(s.name, s.dur_us >= 0) for s in tr.spans] == [
        ("inner", True), ("outer", True)]

    path = tr.save(str(tmp_path / "t.trace.json"))
    assert json.load(open(path, encoding="utf-8"))["traceEvents"]


def test_tracer_records_failed_span():
    tr = obs.Tracer()
    with pytest.raises(ValueError):
        with tr.span("fails"):
            raise ValueError("bad phase")
    assert len(tr.spans) == 1
    assert tr.spans[0].args["error"].startswith("ValueError")


def test_nested_spans_carry_parent_into_chrome_trace(monkeypatch):
    """A span records the innermost open span as its parent, across
    tracers (library code records into the process tracer inside a
    caller's span); siblings share the parent; the top has none."""
    from arrow_matrix_tpu.obs import tracer as tracer_mod

    monkeypatch.setattr(tracer_mod, "_DEFAULT", tracer_mod.Tracer("p"))
    app = obs.Tracer("app")
    with app.span("build"):
        with obs.get_tracer().span("fold.compose"):
            with app.span("inner"):
                pass
        with obs.get_tracer().span("sell.pack"):
            pass
    with app.span("after"):
        pass

    events = {e["name"]: e for tr in (app, obs.get_tracer())
              for e in tr.to_chrome_trace()["traceEvents"][1:]}
    ids = {name: e["args"]["span_id"] for name, e in events.items()}
    assert len(set(ids.values())) == len(ids)
    parents = {name: (e["args"].get("parent"), e["args"].get("parent_id"))
               for name, e in events.items()}
    assert parents == {
        "build": (None, None),
        "fold.compose": ("build", ids["build"]),
        "inner": ("fold.compose", ids["fold.compose"]),
        "sell.pack": ("build", ids["build"]),
        "after": (None, None),
    }
    # A failed span closes: the next one is not its child.
    with pytest.raises(KeyError):
        with app.span("fails"):
            raise KeyError("x")
    with app.span("next"):
        pass
    assert app.spans[-1].parent is None


def test_process_tracer_reset(monkeypatch):
    from arrow_matrix_tpu.obs import tracer as tracer_mod

    monkeypatch.setattr(tracer_mod, "_DEFAULT", tracer_mod.Tracer("p"))
    with obs.get_tracer().span("old"):
        pass
    fresh = obs.init_tracer("run2")
    assert obs.get_tracer() is fresh and fresh.name == "run2"
    assert fresh.spans == []


# ---------------------------------------------------------------------------
# Timing helpers (host-only callables: no jax needed, block tolerant)
# ---------------------------------------------------------------------------


def test_timed_returns_elapsed_seconds():
    assert 0.0 <= obs.timed(lambda: 41 + 1) < 5.0


def test_iteration_time_ms_feeds_back_and_records():
    reg = obs.MetricsRegistry()
    calls = []

    def step(x):
        calls.append(x)
        return x + 1

    samples = obs.iteration_time_ms(step, 0, iters=3, warmup=1,
                                    registry=reg, algorithm="toy")
    assert len(samples) == 3 and all(s >= 0 for s in samples)
    assert calls == [0, 1, 2, 3]          # warmup + 3 iters, chained
    h = reg.histogram("iteration_time_ms", step="step", algorithm="toy")
    assert h.summary()["count"] == 3


def test_chained_iteration_ms_positive():
    def run(x, n):
        return x + n
    x = np.ones((2, 2), np.float32)
    assert obs.chained_iteration_ms(run, x, 2) > 0


# ---------------------------------------------------------------------------
# Communication accounting
# ---------------------------------------------------------------------------


def test_account_collectives_on_shard_map_psum():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from arrow_matrix_tpu.parallel.arrow_layout import shard_map
    from arrow_matrix_tpu.parallel.mesh import (
        make_mesh,
        
    )

    mesh = make_mesh((2,), ("blocks",), devices=jax.devices()[:2])
    f = jax.jit(shard_map(
        lambda v: jax.lax.psum(v, "blocks"), mesh=mesh,
        in_specs=P("blocks"), out_specs=P(),
        check_vma=False))
    x = jnp.ones((4, 8), jnp.float32)

    reg = obs.MetricsRegistry()
    rep = obs.account_collectives("toy", f, x, ideal_bytes=64,
                                  mode="lowered", registry=reg)
    assert rep["source"] == "lowered"
    assert rep["collectives"]["all-reduce"]["count"] >= 1
    assert rep["measured_bytes"] > 0
    assert rep["ratio"] == rep["measured_bytes"] / 64
    assert reg.gauge("comm_measured_bytes",
                     algorithm="toy").value == rep["measured_bytes"]
    assert reg.gauge("comm_vs_ideal_ratio",
                     algorithm="toy").value == pytest.approx(rep["ratio"])


def test_account_collectives_auto_falls_back_when_collective_free():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v: v * 2)
    rep = obs.account_collectives("plain", f,
                                  jnp.ones((4,), jnp.float32))
    assert rep["measured_bytes"] == 0
    assert rep["source"] == "compiled"     # auto fell through
    assert rep["ratio"] is None            # no ideal model given


def test_account_collectives_rejects_unknown_mode():
    with pytest.raises(ValueError):
        obs.account_collectives("x", None, mode="optimistic")


def test_ideal_bytes_for_contract():
    class WithModel:
        def ideal_comm_bytes(self, k, itemsize=4):
            return 10 * k * itemsize

    assert obs.ideal_bytes_for(WithModel(), 4) == 160
    assert obs.ideal_bytes_for(WithModel(), 4, itemsize=2) == 80
    assert obs.ideal_bytes_for(object(), 4) is None


# ---------------------------------------------------------------------------
# Smoke run + graft_trace CLI (one reduced-scale run shared by all the
# artifact-contract assertions; reuses the conftest CPU device pool).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("obs_run"))
    summary = run_smoke(run_dir, n=128, width=32, k=4, n_dev=4, iters=2)
    return run_dir, summary


def test_smoke_run_valid_and_complete(smoke_run):
    run_dir, summary = smoke_run
    assert validate_run_dir(run_dir) == []
    assert set(summary["algorithms"]) == set(ALGORITHMS)
    for name, rec in summary["algorithms"].items():
        assert len(rec["steps_ms"]) == 2
        assert rec["measured_bytes"] >= 0
        # Every algorithm ships a paper cost model -> a ratio exists.
        assert rec["ideal_bytes"] and rec["bytes_vs_ideal"] is not None
        # Perfetto nesting: per-step spans sit inside iterate.
        trace = json.load(open(os.path.join(run_dir, rec["trace"]),
                               encoding="utf-8"))
        spans = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "X"}
        assert {f"{name}/iterate", f"{name}/step"} <= spans


def test_graft_trace_summarize_and_export(smoke_run, tmp_path, capsys):
    run_dir, _ = smoke_run
    assert trace_main(["summarize", run_dir]) == 0
    out = capsys.readouterr().out
    for name in ALGORITHMS:
        assert name in out

    merged = str(tmp_path / "merged.json")
    assert trace_main(["export", run_dir, "--out", merged]) == 0
    trace = json.load(open(merged, encoding="utf-8"))
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert len(pids) == len(ALGORITHMS)    # one pid per algorithm


def test_graft_trace_diff_identical_runs_clean(smoke_run):
    run_dir, _ = smoke_run
    assert trace_main(["diff", run_dir, run_dir]) == 0


def _write_summary(path, step_ms, phase_ms, measured=1000):
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "summary.json"), "w",
              encoding="utf-8") as fh:
        json.dump({"scale": {}, "algorithms": {
            "algo": {"step_ms_mean": step_ms, "measured_bytes": measured,
                     "phase_ms": {"algo/iterate": phase_ms}}}}, fh)


def test_graft_trace_diff_flags_regression(tmp_path, capsys):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _write_summary(a, step_ms=1.0, phase_ms=10.0)
    _write_summary(b, step_ms=2.0, phase_ms=25.0)
    assert trace_main(["diff", a, b, "--threshold", "0.2"]) == 1
    assert "REGRESSED" in capsys.readouterr().out
    # The same delta under a permissive threshold passes.
    assert trace_main(["diff", a, b, "--threshold", "2.0"]) == 0


def test_diff_records_noise_floor_and_missing_algorithm():
    a = {"algo": {"step_ms_mean": 0.010, "measured_bytes": 10,
                  "phase_ms": {}}}
    # +100% relative but only +0.01 ms absolute: under the noise floor.
    b = {"algo": {"step_ms_mean": 0.020, "measured_bytes": 10,
                  "phase_ms": {}}}
    rows = _diff_records(a, b, threshold=0.2, min_delta_ms=0.1)
    assert not any(r["regressed"] for r in rows)
    # Bytes have no noise floor: +100% regresses.
    b2 = {"algo": {"step_ms_mean": 0.010, "measured_bytes": 20,
                   "phase_ms": {}}}
    rows = _diff_records(a, b2, threshold=0.2, min_delta_ms=0.1)
    assert any(r["quantity"] == "measured_bytes" and r["regressed"]
               for r in rows)
    # An algorithm missing from B is itself a regression.
    rows = _diff_records(a, {}, threshold=0.2, min_delta_ms=0.1)
    assert any(r["quantity"] == "presence" and r["regressed"]
               for r in rows)
