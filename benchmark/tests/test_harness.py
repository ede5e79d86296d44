"""The whole harness at rehearsal size on the CPU: a sound run is
correct, the precision control and each planted fault are not.

Each run skips only the harness's look for a chip (``--rehearse``) and
drives the rest: data, build, warm-up, window, fetch and the check.
The faults are planted under the harness, in the driver it loads.
"""

import json
import types

import numpy as np
import pytest

from benchmark import run as harness

WORKLOADS = ["tiny-ba.k16", "tiny-grid.k128"]


def _run(capsys, workload, *extra, seed=2**31 + 17, trace=0):
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.05", "--trace", str(trace),
                       "--rehearse", *extra])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def _plant(monkeypatch, fault):
    """Load drivers through ``fault(driver) -> driver``."""
    real = harness.load_module

    def load(kind, name):
        mod = real(kind, name)
        return fault(mod) if kind == "drivers" else mod

    monkeypatch.setattr(harness, "load_module", load)


def _wrap(mod, **over):
    return types.SimpleNamespace(**{**{k: getattr(mod, k) for k in
                                       ("build", "upload", "dispatch",
                                        "fetch")}, **over})


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(capsys, workload):
    res = _run(capsys, workload)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"iter_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    gap = res["check"]["max_rel_gap"]
    assert gap["value"] < gap["limit"]


def test_traced_rehearsal_reports_setup_layers(capsys):
    res = _run(capsys, "tiny-ba.k16", trace=1)
    assert res["correct"] is True
    # The CPU trace has no device plane: the device metrics stay out.
    assert {"load_s", "build_s", "compile_s"} <= set(res["metrics"])
    assert "step_busy_ms" not in res["metrics"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bf16_control_is_not_correct(capsys, workload):
    res = _run(capsys, workload, "--carriage", "bfloat16")
    assert res["correct"] is False


def _state_unchanged(mod):
    return _wrap(mod, dispatch=lambda ex, xd, it: xd)


def _one_step_short(mod):
    return _wrap(mod, dispatch=lambda ex, xd, it: mod.dispatch(ex, xd,
                                                               it - 1))


def _row_altered(mod):
    def fetch(ex, y):
        got = np.array(mod.fetch(ex, y))
        got[got.shape[0] // 3] *= 1.001
        return got
    return _wrap(mod, fetch=fetch)


def _column_zeroed(mod):
    def fetch(ex, y):
        got = np.array(mod.fetch(ex, y))
        got[:, got.shape[1] // 2] = 0.0
        return got
    return _wrap(mod, fetch=fetch)


def _half_rows_left_out(mod):
    def fetch(ex, y):
        got = np.array(mod.fetch(ex, y))
        got[1::2] = 0.0
        return got
    return _wrap(mod, fetch=fetch)


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", [_state_unchanged, _one_step_short,
                                   _row_altered, _column_zeroed,
                                   _half_rows_left_out])
def test_planted_fault_is_not_correct(capsys, monkeypatch, workload, fault):
    _plant(monkeypatch, fault)
    res = _run(capsys, workload)
    assert res["correct"] is False
    assert res["failed"] == 1


def test_no_chip_no_result(capsys):
    rc = harness.main(["--workload", "tiny-ba.k16", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_unknown_workload_no_result(capsys):
    rc = harness.main(["--workload", "no-such-cell", "--seed", "1",
                       "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize("carriage,correct", [("float32", True),
                                              ("bfloat16", False)])
def test_readings_judge_each_seed(capsys, carriage, correct):
    from benchmark import readings

    seeds = [5, 2**33 + 3]
    rc = readings.main(["--workload", "tiny-grid.k128", "--seeds",
                        *map(str, seeds), "--carriage", carriage,
                        "--rehearse"])
    assert rc == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert [x["seed"] for x in lines] == seeds
    assert all(x["correct"] is correct for x in lines)
