"""graft-tune tests (arrow_matrix_tpu/tune/): structure-hash
invariances, TunePlan persistence + version skew, candidate-space
pruning, the subprocess search with its pure-cache-hit property,
``plan="auto"`` consumption (loud TunePlanMiss fallback), the serve
pickup event, and the tools/tune_gate.py CI gate."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.tune import (
    TunePlan,
    TunePlanMiss,
    enumerate_candidates,
    load_plan,
    save_plans,
    structure_fingerprint,
    structure_hash,
)
from arrow_matrix_tpu.tune.plan import resolve_plan
from arrow_matrix_tpu.utils import barabasi_albert, random_dense

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _levels(n=120, width=16, seed=3, m=3, max_levels=4):
    a = barabasi_albert(n, m, seed=seed)
    return arrow_decomposition(a, width, max_levels=max_levels,
                               block_diagonal=True, seed=seed)


# ---------------------------------------------------------------------------
# Structure fingerprint + hash
# ---------------------------------------------------------------------------

def test_hash_deterministic_across_redecomposition():
    # Same graph, same seed, two independent decompositions: the hash
    # reads structure, not object identity.
    h1 = structure_hash(_levels(), 16)
    h2 = structure_hash(_levels(), 16)
    assert h1 == h2 and len(h1) == 16


def test_hash_stable_across_graphio_roundtrip(tmp_path):
    # CSR levels and loaded CsrLike-triplet levels must fingerprint
    # identically — plans tuned on a live decomposition apply to the
    # committed artifact and vice versa.
    from arrow_matrix_tpu.io import save_decomposition
    from arrow_matrix_tpu.io.graphio import (
        as_levels,
        load_decomposition,
        load_level_widths,
    )

    levels = _levels()
    base = str(tmp_path / "g")
    save_decomposition(levels, base, block_diagonal=True)
    loaded = load_decomposition(base, 16, block_diagonal=True)
    widths = load_level_widths(base, 16, len(loaded))
    relevels = as_levels(loaded, widths)
    assert structure_hash(relevels, 16) == structure_hash(levels, 16)


def test_hash_sensitive_to_knobs_that_change_the_operator():
    levels = _levels()
    base = structure_hash(levels, 16)
    assert structure_hash(levels, 32) != base          # fold width
    assert structure_hash(levels, 16, growth=1.5) != base   # tier split
    assert structure_hash(levels, 16, slot_align=8) != base
    assert structure_hash(levels, 16, dtype="bf16") != base  # carriage


@pytest.mark.parametrize("growth,align", [(1.2, None), (1.1, 1), (1.2, 8)])
def test_fingerprint_ladder_is_the_built_one(growth, align):
    """The hashed ladder is the fold's own tiering (``ops/sell.fold_tiers``
    in both), so a plan keyed on it names the operator that is built."""
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    levels = _levels()
    fp = structure_fingerprint(levels, 16, growth=growth, slot_align=align)
    ml = MultiLevelArrow(levels, 16, mesh=None, fmt="fold",
                         fold_growth=growth, fold_align=align)
    sell = ml.blocks[0]
    assert fp["ladder"]["tier_starts"] == list(sell.row_starts)
    assert fp["ladder"]["slot_width"] == [c.shape[0] for c in sell.cols]
    assert sum(fp["ladder"]["slots"]) == sell.n_slots


def test_fingerprint_schema_and_k_independence():
    levels = _levels()
    fp = structure_fingerprint(levels, 16)
    # The operator is k-independent: one plan file carries per-k
    # entries, so k must NOT appear anywhere in the hashed record.
    assert "k" not in fp
    assert fp["n"] == 120
    ladder = fp["ladder"]
    assert (len(ladder["rows"]) == len(ladder["nnz"])
            == len(ladder["slots"]) == len(ladder["slot_width"]))
    assert sum(ladder["rows"]) == fp["total_rows"]
    assert sum(ladder["nnz"]) == sum(lvl["nnz"] for lvl in fp["levels"])
    assert sum(fp["slot_hist"]["count"]) == fp["total_rows"]


# ---------------------------------------------------------------------------
# TunePlan persistence
# ---------------------------------------------------------------------------

def test_plan_file_merges_per_k_and_selects_largest(tmp_path):
    d = str(tmp_path / "plans")
    p16 = TunePlan(structure_hash="h", k=16, candidate="chunk_4096",
                   chunk=4096)
    p128 = TunePlan(structure_hash="h", k=128, candidate="default")
    save_plans("h", {16: p16}, directory=d)
    save_plans("h", {128: p128}, directory=d)   # merge, not overwrite
    got = load_plan("h", 16, d)
    assert got.candidate == "chunk_4096" and got.chunk == 4096
    # k=None is the amortized regime: largest cached k wins.
    assert load_plan("h", None, d).k == 128
    with pytest.warns(TunePlanMiss, match="no entry for k=64"):
        assert load_plan("h", 64, d) is None


def test_plan_version_skew_is_a_loud_miss(tmp_path):
    d = str(tmp_path / "plans")
    save_plans("h", {16: TunePlan(structure_hash="h", k=16)},
               directory=d)
    path = os.path.join(d, "h.json")
    with open(path, encoding="utf-8") as fh:
        record = json.load(fh)
    record["version"] = 999
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(record, fh)
    with pytest.warns(TunePlanMiss, match="version skew"):
        assert load_plan("h", 16, d) is None
    # A stale in-memory plan object is rejected the same way.
    stale = TunePlan(structure_hash="h", k=16, version=999)
    with pytest.warns(TunePlanMiss, match="version skew"):
        assert resolve_plan(stale) is None


def test_v1_plan_with_fold_schedules_is_a_loud_miss(tmp_path):
    """Version 1 plans could carry the one-chip fold's overlap slabs
    and replica groups; version 2 dropped both knobs, so such a cached
    plan misses loudly and never reaches an executor."""
    import dataclasses

    from arrow_matrix_tpu.tune.plan import PLAN_VERSION

    assert PLAN_VERSION == 2
    fields = {f.name for f in dataclasses.fields(TunePlan)}
    assert not fields & {"overlap_slabs", "repl"}
    d = str(tmp_path / "plans")
    entry = dict(TunePlan(structure_hash="h", k=16).to_dict(), version=1,
                 candidate="overlap2", overlap_slabs=2, repl=1)
    os.makedirs(d)
    with open(os.path.join(d, "h.json"), "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "structure_hash": "h",
                   "plans": {"16": entry}}, fh)
    with pytest.warns(TunePlanMiss, match="version skew"):
        assert load_plan("h", 16, d) is None
    with pytest.warns(TunePlanMiss, match="version skew"):
        assert resolve_plan(entry) is None


def test_resolve_plan_forms():
    p = TunePlan(structure_hash="h", k=16)
    assert resolve_plan(None) is None
    assert resolve_plan(p) is p
    assert resolve_plan(p.to_dict()) == p
    with pytest.raises(ValueError, match="levels and width"):
        resolve_plan("auto")
    with pytest.raises(ValueError, match="unknown plan"):
        resolve_plan("yes please")


# ---------------------------------------------------------------------------
# Candidate space + feasibility pruning
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_fp():
    return structure_fingerprint(_levels(), 16)


def test_pruning_divisibility_and_interpret(small_fp):
    cands, pruned = enumerate_candidates(small_fp, 7, platform="cpu")
    names = {c.name for c in cands}
    assert "default" in names
    # DMA-ring depth is stream-only; the interpret evaluator runs the
    # vectorized body, so racing it would measure nothing.
    assert "pallas_sell_ring1" in pruned and "pallas_sell_ring4" in pruned
    assert "stream-only" in pruned["pallas_sell_ring1"]
    # ...but the fused kernel itself races fine under interpret.
    assert "pallas_sell" in names


def test_pruning_onchip_needs_k16(small_fp):
    _, pruned = enumerate_candidates(small_fp, 20, platform="tpu")
    assert "pallas_sell" in pruned and "k % 16" in pruned["pallas_sell"]
    cands, pruned = enumerate_candidates(small_fp, 32, platform="tpu")
    names = {c.name for c in cands}
    assert "pallas_sell" in names and "pallas_sell_ring4" in names


def test_pruning_restrict_and_int8_optin(small_fp):
    cands, pruned = enumerate_candidates(
        small_fp, 16, platform="cpu",
        restrict=["default", "fold_tight"])
    assert {c.name for c in cands} == {"default", "fold_tight"}
    assert all("restricted" in why for why in pruned.values())
    names = {c.name for c in
             enumerate_candidates(small_fp, 16, allow_int8=True)[0]}
    assert "int8" in names
    int8 = [c for c in enumerate_candidates(
        small_fp, 16, allow_int8=True)[0] if c.name == "int8"][0]
    bf16 = [c for c in enumerate_candidates(small_fp, 16)[0]
            if c.name == "bf16"][0]
    # Carriage-dtype experiments are diagnostics: never f32
    # bit-identical, so never eligible to win.
    assert not int8.eligible and not bf16.eligible


# ---------------------------------------------------------------------------
# The search itself (subprocess race + pure cache hit)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_reports(tmp_path_factory):
    """ONE smoke search (3 children) + an immediate second search of
    the unchanged structure, shared by the consumption/gate tests."""
    from arrow_matrix_tpu.tune import smoke_tune

    d = str(tmp_path_factory.mktemp("tune_smoke"))
    old_flight = os.environ.get("AMT_FLIGHT_DIR")
    os.environ["AMT_FLIGHT_DIR"] = os.path.join(d, "flight")
    try:
        r1 = smoke_tune(d)
        r2 = smoke_tune(d)
    finally:
        if old_flight is None:
            os.environ.pop("AMT_FLIGHT_DIR", None)
        else:
            os.environ["AMT_FLIGHT_DIR"] = old_flight
    return d, r1, r2


def test_search_races_children_and_persists_winner(smoke_reports):
    d, r1, _ = smoke_reports
    assert r1["ok"] and not r1["cache_hit"]
    assert r1["children_spawned"] == 3     # restricted smoke space
    assert r1["winner"] in r1["results"]
    plan = r1["plan"]
    # A winner must have proven f32 bit-identity vs the golden
    # ops/sell.py fold path; its margin vs the default is recorded.
    assert plan["bit_identical"] is True
    assert plan["measured_ms"] is not None
    assert plan["margin"] is not None and plan["margin"] >= 0.0
    assert plan["host_load"] is not None
    assert os.path.exists(r1["plan_path"])
    # The default is always raced and always trivially bit-identical.
    assert r1["results"]["default"]["bit_identical"] is True


def test_second_search_is_pure_cache_hit(smoke_reports):
    # THE acceptance property: an unchanged structure's second search
    # spawns ZERO bench children.
    _, r1, r2 = smoke_reports
    assert r2["ok"] and r2["cache_hit"]
    assert r2["children_spawned"] == 0
    assert r2["plan"]["candidate"] == r1["plan"]["candidate"]


# ---------------------------------------------------------------------------
# Consumption: plan="auto", loud miss, serve pickup
# ---------------------------------------------------------------------------

def _smoke_levels():
    # Exactly the structure smoke_tune searches (tune/search.py).
    return _levels(n=96, width=16, seed=3, m=3, max_levels=4)


def test_plan_auto_consumption_bitwise(smoke_reports, monkeypatch):
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    d, r1, _ = smoke_reports
    monkeypatch.setenv("AMT_TUNE_PLAN_DIR",
                       os.path.join(d, "tune_plans"))
    levels = _smoke_levels()
    tuned = MultiLevelArrow(levels, 16, plan="auto")
    assert tuned.tune_plan is not None
    assert tuned.tune_plan.structure_hash == r1["structure_hash"]
    # The tuned executor must still be bit-identical to the golden
    # fold path AT THE PLAN'S k (that is exactly what made its
    # candidate eligible to win — reduction order is shape-dependent,
    # so the promise is per-k and per-format, fmt="fold").
    default = MultiLevelArrow(levels, 16, fmt="fold")
    x = random_dense(default.n, int(r1["k"]), seed=5)
    want = np.asarray(default.gather_result(
        default.step(default.set_features(x))), dtype=np.float32)
    got = np.asarray(tuned.gather_result(
        tuned.step(tuned.set_features(x))), dtype=np.float32)
    np.testing.assert_array_equal(got, want)


def test_plan_auto_miss_is_loud(tmp_path, monkeypatch):
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    monkeypatch.setenv("AMT_TUNE_PLAN_DIR", str(tmp_path / "empty"))
    with pytest.warns(TunePlanMiss, match="no plan file"):
        multi = MultiLevelArrow(_smoke_levels(), 16, plan="auto")
    assert multi.tune_plan is None         # defaults, loudly


def test_sell_multi_level_consumes_plan_dict(smoke_reports):
    from arrow_matrix_tpu.parallel import make_mesh
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    _, r1, _ = smoke_reports
    mesh = make_mesh((2,), ("blocks",))
    sml = SellMultiLevel(_smoke_levels(), 16, mesh, plan=r1["plan"])
    assert sml.tune_plan is not None
    assert sml.tune_plan.candidate == r1["plan"]["candidate"]


def test_serve_applies_tune_plan_as_base_rung(smoke_reports, tmp_path):
    from arrow_matrix_tpu.obs import flight
    from arrow_matrix_tpu.serve import (
        ArrowServer,
        ExecConfig,
        ba_executor_factory,
    )

    _, r1, _ = smoke_reports
    fac, _n = ba_executor_factory(64, 16, 3, fmt="fold")
    rec = flight.FlightRecorder(str(tmp_path / "flight.json"))
    flight.set_recorder(rec)
    try:
        srv = ArrowServer(fac, ExecConfig(), name="tuned",
                          tune_plan=r1["plan"])
    finally:
        flight.set_recorder(None)
    assert srv.tune_plan is not None
    applied = [e["data"] for e in rec.events
               if e.get("name") == "tune_plan_applied"
               and e.get("data", {}).get("server") == "tuned"]
    assert applied
    assert applied[-1]["structure_hash"] == r1["structure_hash"]
    assert (applied[-1]["base_config"]["kernel"]
            == r1["plan"]["kernel"])


# ---------------------------------------------------------------------------
# The CI gate
# ---------------------------------------------------------------------------

def test_tune_gate_passes_on_fresh_cache(smoke_reports):
    d, _, _ = smoke_reports
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tune_gate.py"),
         "--plan-dir", os.path.join(d, "tune_plans"),
         "--iters", "2", "--repeats", "1", "--quiet"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "tune-gate OK" in proc.stdout
    assert "cache-purity" in proc.stdout
    assert "bit-identity" in proc.stdout


def test_tune_gate_detects_hash_drift(smoke_reports, tmp_path):
    from arrow_matrix_tpu.tune.gate import check_structure

    d, r1, _ = smoke_reports
    drifted = str(tmp_path / "drifted")
    shutil.copytree(os.path.join(d, "tune_plans"), drifted)
    path = os.path.join(drifted, f"{r1['structure_hash']}.json")
    with open(path, encoding="utf-8") as fh:
        record = json.load(fh)
    record["structure_hash"] = "0" * 16    # tampered artifact
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(record, fh)
    source = record["context"]["source"]
    res = check_structure(source, directory=drifted, timing=False,
                          quiet=True)
    assert not res["ok"]
    assert any("hash drift" in f for f in res["failures"])


def test_tune_gate_empty_cache_is_failure(tmp_path):
    from arrow_matrix_tpu.tune.gate import run_gate

    assert run_gate(directory=str(tmp_path / "nothing")) == 1


def test_save_plans_concurrent_writers_drop_no_entry(tmp_path):
    """The fleet-workers race: N writers merge DIFFERENT k entries
    into the same plan file concurrently.  Without the advisory file
    lock around the read-merge-write, two writers read the same stale
    file and the slower rewrite drops the faster one's entry; with it,
    every entry survives."""
    import threading

    d = str(tmp_path / "plans")
    h = "f" * 16
    ks = list(range(1, 9))
    errors = []

    def write(k):
        try:
            save_plans(h, {k: TunePlan(h, k)}, directory=d)
        except Exception as e:          # surfaced below, not swallowed
            errors.append(e)

    threads = [threading.Thread(target=write, args=(k,)) for k in ks]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    from arrow_matrix_tpu.tune.plan import load_plan_file

    doc = load_plan_file(h, d)
    assert sorted(int(s) for s in doc["plans"]) == ks
    for k in ks:                        # every entry loads cleanly too
        assert load_plan(h, k, directory=d).k == k


def test_search_parent_never_initializes_a_backend(tmp_path):
    """One process per chip: the search parent learns the platform from
    a child and races candidates in children — it must never create a
    JAX backend itself (on a chip it would hold the device)."""
    code = ("from arrow_matrix_tpu.tune.search import smoke_tune; "
            "from arrow_matrix_tpu.utils.platform import "
            "backend_initialized; "
            f"r = smoke_tune({str(tmp_path)!r}); "
            "print(r['ok'], r['children_spawned'], backend_initialized())")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-3:] == ["True", "3", "False"]
