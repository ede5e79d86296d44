"""Contract tests for the end-of-round bench (bench.py).

The bench is a driver gate: whatever happens it must print exactly one
JSON line with the metric contract and exit 0 iff a headline value
exists (mirrors the reference's bench always reporting through
wb_logging, arrow/arrow_bench.py:12-137).  Without a TPU it must fail
with an error line; the CPU runs here use the explicit AMT_BENCH_CPU=1
rehearsal knob and exercise the candidate-subprocess race end to end.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_bench(tmp_path, extra_env, timeout=420):
    env = dict(os.environ)
    env.update({
        "AMT_BENCH_CPU": "1",          # the explicit CPU rehearsal
        "AMT_BENCH_N": "32768",
        "AMT_BENCH_COMPARE": "0",
        "AMT_BENCH_K128": "0",
        "AMT_BENCH_DEADLINE": str(timeout - 60),
    })
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=timeout, cwd=tmp_path, env=env)


@pytest.fixture(scope="module")
def bench_success(tmp_path_factory):
    """One shared successful CPU-rehearsal run (the subprocess race is
    the expensive part; both contract tests read the same record)."""
    return _run_bench(tmp_path_factory.mktemp("bench"), {})


def test_degraded_run_succeeds_with_contract(bench_success):
    proc = bench_success
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"exactly one JSON line expected: {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "spmm_iter_ms"
    assert out["unit"] == "ms"
    assert out["value"] > 0
    assert out["vs_baseline"] > 0
    # A CPU number is labelled as one, and the parent never held a
    # backend (its children own the device).
    assert out["platform"] == "cpu" and out["device_kind"] == "cpu"
    assert out["parent_backend_initialized"] is False
    assert out["fmt_used"] in out["device_runs"]
    win = out["device_runs"][out["fmt_used"]]
    assert win["err"] <= out["frobenius_gate"]
    assert out["scipy_cpu_ms"] > 0


def test_degraded_run_reports_roofline_inputs(bench_success):
    out = json.loads(bench_success.stdout.strip().splitlines()[-1])
    assert out["bytes_per_iter_gb"] > 0
    assert out["achieved_gbps"] > 0
    assert out["config"]["levels"] >= 1
    assert out["config"]["edges_nnz"] > 0


def test_no_chip_exits_nonzero_with_error_json(tmp_path):
    """Without a TPU and without the rehearsal knob the bench refuses:
    rc=1, one error line, and no timing under any device name."""
    env = {k: v for k, v in os.environ.items() if k != "AMT_BENCH_CPU"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, BENCH], capture_output=True,
                          text=True, timeout=300, cwd=tmp_path, env=env)
    assert proc.returncode == 1, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["value"] is None and "no TPU" in out["error"]
    assert "device_runs" not in out and "scipy_cpu_ms" not in out
    assert out["parent_backend_initialized"] is False


def test_failed_race_exits_nonzero_with_error_json(tmp_path):
    """An impossible format must produce the diagnosable error line and
    rc=1 — the round-1 postmortem contract (no silent rc without
    JSON)."""
    proc = _run_bench(tmp_path, {"AMT_BENCH_FMT": "no_such_format"},
                      timeout=240)
    assert proc.returncode == 1
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["value"] is None
    assert "error" in out
    assert "no_such_format" in json.dumps(out["device_runs"])


def test_bench_config_overlap_and_pallas_sell_candidate(monkeypatch):
    """graft-stream bench surface: the pallas_sell race candidate
    exists (fold build + fused kernel).  The one-chip fold takes no
    overlap slabs, so the candidate config carries none."""
    sys.path.insert(0, REPO)
    import bench as bench_mod

    kw = bench_mod.CANDIDATE_KWARGS["pallas_sell"]
    assert kw["fmt"] == "fold" and kw["kernel"] == "pallas_sell"
    assert "overlap_slabs" not in kw
    monkeypatch.setenv("AMT_BENCH_OVERLAP_SLABS", "4")
    assert "overlap_slabs" not in bench_mod._bench_config("cpu")
