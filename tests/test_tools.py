"""Smoke tests for the perf tools' CPU fixtures: their non-chip logic
(decompose, golden gates, JSON contracts) must stay green in CI.  Each
runs in a subprocess exactly as an operator invokes it."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, env: dict, timeout: float = 300):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", script)],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, **env}, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_planar_bench_cpu_fixture():
    out = _run("planar_bench.py",
               {"AMT_PLANAR_CPU": "1", "AMT_PLANAR_SIDE": "96"})
    assert out["levels"] == 1          # banded fast path engaged
    assert out["gated"] and out["winner"] in ("fold", "fold_tight")
    # the tight packing's planar slot story: exactly 1.0x nnz
    assert out["runs"]["fold_tight"]["slots_over_nnz"] == 1.0
    assert out["comm_8dev"]["levels"] == 1


def test_planar_bench_bf16_fixture():
    out = _run("planar_bench.py",
               {"AMT_PLANAR_CPU": "1", "AMT_PLANAR_SIDE": "96",
                "AMT_PLANAR_DTYPE": "bf16"})
    assert out["feature_dtype"] == "bf16"
    assert list(out["runs"]) == ["fold_tight"]   # single resident build
    assert out["gated"] and out["err"] < 2e-2


def test_pallas_gather_probe_cpu_fixture():
    out = _run("pallas_gather_probe.py", {"AMT_PROBE_CPU": "1"})
    for name in ("xla_take", "xla_granule", "pallas_granule"):
        assert out["variants"][name].get("exact") is True, out


def test_ba27_bench_refuses_missing_and_toy_export(tmp_path):
    """ba27_bench must exit nonzero (never bench garbage) when the
    export is absent, and refuse a logic-test toy export unless
    explicitly allowed — a regression here would publish toy-scale
    numbers as the 2^27 scale point."""
    def run_with(export_dir):
        return subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "ba27_bench.py")],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "AMT_BA27_EXPORT": str(export_dir)},
            cwd=REPO)

    missing = run_with(tmp_path / "nowhere")
    assert missing.returncode == 2
    assert "no export" in missing.stdout

    toy = tmp_path / "toy"
    toy.mkdir()
    (toy / "meta.json").write_text("{}")
    (toy / "rehearsal.json").write_text(
        json.dumps({"n": 1 << 16, "k": 16, "x_seed": 5}))
    refused = run_with(toy)
    assert refused.returncode == 2
    assert "logic-test toy" in refused.stdout


@pytest.mark.slow
def test_rehearse_rung_and_ba27_chain_cpu_fixture(tmp_path):
    """The offline rung -> online bench chain at logic-test scale:
    rung exports atomically, ba27_bench golden-gates from the export
    (AMT_BA27_FORCE_CPU).  Both ends honor AMT_BA27_EXPORT, so the
    chain runs entirely inside tmp_path — the live bench_cache export
    (possibly the real multi-hour 2^27 one) is never touched."""
    export = str(tmp_path / "ba27_fold")
    env = {**os.environ, "AMT_BA27_EXPORT": export}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scale_ladder.py"),
         "--rung", "rehearse_1e8_ba_step"],
        capture_output=True, text=True, timeout=900,
        env={**env, "AMT_BA27_LOGN": "16"}, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rung = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rung["hbm_budget"]["fits"]
    assert rung["golden_sample_rel_err"] < 2e-2
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ba27_bench.py")],
        capture_output=True, text=True, timeout=600,
        env={**env, "AMT_BA27_ALLOW_SMALL": "1",
             "AMT_BA27_FORCE_CPU": "1", "AMT_BA27_ITERS": "2"}, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["golden_sample_rel_err"] < 2e-2
    assert out["ms_per_iter"] > 0


@pytest.mark.slow
def test_ladder_race_cpu_fixture():
    out = _run("ladder_race.py",
               {"AMT_LADDER_CPU": "1", "AMT_LADDER_N": "16384"},
               timeout=600)
    assert out["runs"]["default"]["gated"]
    assert out["runs"]["tight"]["gated"]
    assert (out["runs"]["tight"]["gather_slots"]
            < out["runs"]["default"]["gather_slots"])


def test_obs_gate_memory_problems():
    """The gate's memory contract: absent report fails, sane ratio
    passes, blown ratio names the algorithm and the bytes."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "obs_gate_under_test", os.path.join(REPO, "tools", "obs_gate.py"))
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)

    ok = {"algorithms": {"a": {"memory": {"total_bytes": 100},
                               "hbm_measured_bytes": 100,
                               "hbm_predicted_bytes": 80,
                               "hbm_vs_predicted": 1.25}}}
    assert gate.memory_problems(ok, 8.0) == []
    # No predictor -> no ratio to enforce, but the report must exist.
    no_model = {"algorithms": {"a": {"memory": {"total_bytes": 100},
                                     "hbm_measured_bytes": 100,
                                     "hbm_vs_predicted": None}}}
    assert gate.memory_problems(no_model, 8.0) == []
    absent = {"algorithms": {"a": {"memory": None}}}
    assert gate.memory_problems(absent, 8.0) == [
        "a: memory report absent"]
    blown = {"algorithms": {"a": {"memory": {"total_bytes": 800},
                                  "hbm_measured_bytes": 800,
                                  "hbm_predicted_bytes": 80,
                                  "hbm_vs_predicted": 10.0}}}
    problems = gate.memory_problems(blown, 8.0)
    assert len(problems) == 1 and "exceeds 8.00" in problems[0]


def test_parse_last_json_line_contract():
    """ONE parser for every bench/tune child's stdout (the final
    JSON-line protocol): noise above the record is fine, noise AFTER
    it — or no record at all — is an explicit None, never a guess."""
    from arrow_matrix_tpu.utils.artifacts import parse_last_json_line

    assert parse_last_json_line(
        'warming up...\ncompile cache miss\n{"ms": 1.5}\n'
    ) == {"ms": 1.5}
    assert parse_last_json_line('{"ms": 1.5}') == {"ms": 1.5}
    assert parse_last_json_line("") is None
    assert parse_last_json_line("   \n  ") is None
    assert parse_last_json_line(None) is None
    # The record must be the LAST line: trailing noise invalidates.
    assert parse_last_json_line('{"ms": 1.5}\nTraceback...') is None
    # A JSON scalar/array is not a record.
    assert parse_last_json_line("[1, 2]") is None
    assert parse_last_json_line("42") is None


def test_obs_gate_comm_problems():
    """Every algorithm's comm record must carry exposed_comm_ms
    (graft-stream): a missing or null field names the algorithm."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "obs_gate_under_test2", os.path.join(REPO, "tools", "obs_gate.py"))
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)

    ok = {"algorithms": {"a": {"exposed_comm_ms": 0.0},
                         "b": {"exposed_comm_ms": 1.25}}}
    assert gate.comm_problems(ok) == []
    missing = {"algorithms": {"a": {}, "b": {"exposed_comm_ms": None}}}
    assert gate.comm_problems(missing) == [
        "a: comm report lacks exposed_comm_ms",
        "b: comm report lacks exposed_comm_ms"]
