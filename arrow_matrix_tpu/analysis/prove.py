"""HLO-level collective-contract verifier (graft-lint engine 3).

The paper's value proposition is a *provable* communication bound —
arrow decomposition caps per-step exchange volume — yet obs/comm can
only check that bound dynamically, after a run.  This engine proves it
statically: each parallel executor exports a ``collective_contract``
(analysis/contracts.py), the prover lowers every shipped entry point
on the host-CPU virtual mesh, parses the optimized HLO into a
structured ``CollectiveSummary``, and checks six rules:

* **H1** no unattributed collectives — every collective kind in the
  lowered AND compiled step must be declared (a GSPMD surprise
  all-gather fails here before it ever regresses a bench);
* **H2** collective bytes match the contract's ideal within the
  declared ratio band (the static twin of obs/comm's measured/ideal);
* **H3** repl=c programs carry k/(c·S) feature slabs through every
  collective (the ÷c law, visible as the leading shape dimension) and
  the deferred psum merge prices EXACTLY ``reduce_comm_bytes``;
* **H4** no silent dtype upcasts: no f64 anywhere in the lowered step
  and no float-widening ``convert`` ops beyond the benign index/mask
  allowlist.  graft-classes relaxes this *per-class* into **H4'**: a
  reduced-precision contract (dtype bf16/int8, the approx traffic
  class) declares its carriage->f32 accumulator widening — that
  convert is benign — but in exchange every collective operand must
  actually carry the reduced dtype (an approx program whose exchanges
  still move f32 never earned its smaller byte band);
* **H5** donated inputs are actually aliased — the lowered stablehlo
  carries ``jax.buffer_donor``/``tf.aliasing_output`` and the compiled
  HLO header carries ``input_output_alias`` for the declared
  parameters (a dropped donation shows neither: the phantom-copy /
  use-after-donate detector);
* **H6** no layout thrash in the hot loop: zero ``transpose`` ops and
  at most ``hot_copy_budget`` ``copy`` ops inside while-loop bodies.

Results land in ``bench_cache/hlo_manifest.json`` (checked in and
diffable, like compile_manifest.json).  Run standalone:
``python -m arrow_matrix_tpu.analysis prove`` or the ``graft_prove``
console script; ``tools/proof_gate.py`` is the nonzero-exit CI
wrapper, and the tier-1 suite re-runs the prover at the same reduced
scale and fails on manifest drift.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from arrow_matrix_tpu.analysis.contracts import CollectiveContract
from arrow_matrix_tpu.utils import commstats

RULE_IDS = ("H1", "H2", "H3", "H4", "H5", "H6", "H7")

DEFAULT_MANIFEST = os.path.join("bench_cache", "hlo_manifest.json")

#: Prove scale — shared by the CLI default, the checked-in manifest,
#: and the tier-1 drift test (tests/test_prove.py); the manifest is
#: only comparable at one fixed scale.
PROVE_SCALE = {"n": 128, "width": 32, "k": 8, "n_dev": 4}

# ---------------------------------------------------------------------------
# HLO text analysis (host-only; no jax import required)
# ---------------------------------------------------------------------------

#: ``%y = f32[8,16] convert(s32[8,16] %x)`` -> ("f32", "s32").
_CONVERT_RE = re.compile(r"=\s*(\w+)\[[0-9,]*\]\S*\s+convert\(\s*(\w+)\[")

_FLOAT_BYTES = {"f16": 2, "bf16": 2, "f32": 4, "f64": 8}

#: Carriage itemsize by contract dtype name, for the H4' operand
#: check (HLO spells int8 "s8"; contracts use the numpy name).
_CARRIAGE_BYTES = {"s8": 1, "u8": 1, "int8": 1, "uint8": 1,
                   "f16": 2, "bf16": 2, "f32": 4, "f64": 8}

#: (src, dst) convert pairs that are benign on every backend: index
#: widening and mask materialization, not a carried-value upcast.
BENIGN_CONVERTS = frozenset({
    ("pred", "f32"), ("pred", "s32"),
    ("s8", "s32"), ("u8", "s32"), ("s16", "s32"), ("u16", "s32"),
    ("u32", "s32"), ("s32", "u32"),
})


@dataclasses.dataclass
class CollectiveSummary:
    """Structured account of one HLO program text."""

    #: kind -> {"count": int, "bytes": int} (commstats schema).
    kinds: Dict[str, dict]
    total_bytes: int
    #: Leading dimension of every collective output shape, in order.
    leading_dims: List[int]
    #: Element dtype of every collective output shape, in order
    #: (tuple shapes contribute one entry per element) — the H4'
    #: evidence that an approx program's exchanges really carry the
    #: reduced carriage dtype.
    collective_dtypes: List[str]
    #: (src_dtype, dst_dtype) of every convert op.
    converts: List[Tuple[str, str]]
    has_f64: bool
    #: copy / transpose ops inside while-loop body computations.
    while_copies: int
    while_transposes: int
    #: Parameter numbers carried by the input_output_alias header.
    aliased_params: Tuple[int, ...]

    def present_kinds(self) -> frozenset:
        return frozenset(k for k in commstats.COLLECTIVE_OPS
                         if self.kinds[k]["count"])


def _collective_shapes(text: str) -> Tuple[List[int], List[str]]:
    """(leading dims, element dtypes) of every collective output
    shape, in program order."""
    dims: List[int] = []
    dtypes: List[str] = []
    for line in text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for kind in commstats.COLLECTIVE_OPS:
            m = re.search(rf"=\s*(.+?)\s{re.escape(kind)}(?:-start)?\(", s)
            if m:
                for dt, d in commstats._SHAPE_RE.findall(m.group(1)):
                    dtypes.append(dt)
                    first = d.split(",")[0]
                    if first:
                        dims.append(int(first))
                break
    return dims, dtypes


def _computation_blocks(text: str) -> Dict[str, List[str]]:
    """HLO computation name -> its body lines."""
    blocks: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\.clone\S*)?\(",
                     line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            blocks[cur] = []
        elif cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                blocks[cur].append(line)
    return blocks


def _while_body_ops(text: str) -> Tuple[int, int]:
    """(copy, transpose) op counts inside while-loop body computations."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    blocks = _computation_blocks(text)
    copies = transposes = 0
    for name in bodies:
        for line in blocks.get(name, ()):
            if re.search(r"=\s*\S+\s+copy\(", line):
                copies += 1
            elif re.search(r"=\s*\S+\s+transpose\(", line):
                transposes += 1
    return copies, transposes


def _aliased_params(text: str) -> Tuple[int, ...]:
    """Parameter numbers in the compiled-HLO input_output_alias header,
    e.g. ``input_output_alias={ {}: (0, {}, may-alias) }`` -> (0,)."""
    m = re.search(r"input_output_alias=\{(.*?)\}\s*(?:,|$)", text,
                  flags=re.MULTILINE | re.DOTALL)
    if not m:
        return ()
    return tuple(sorted({int(p) for p in
                         re.findall(r"\(\s*(\d+)\s*,", m.group(1))}))


def summarize_hlo(text: str) -> CollectiveSummary:
    """Parse one HLO program text into a CollectiveSummary."""
    stats = commstats._parse_hlo_collectives(text)
    copies, transposes = _while_body_ops(text)
    dims, coll_dtypes = _collective_shapes(text)
    return CollectiveSummary(
        kinds={k: dict(stats[k]) for k in commstats.COLLECTIVE_OPS},
        total_bytes=int(stats["total_bytes"]),
        leading_dims=dims,
        collective_dtypes=coll_dtypes,
        converts=[(src, dst) for dst, src in _CONVERT_RE.findall(text)],
        has_f64=bool(re.search(r"\bf64\[", text)),
        while_copies=copies,
        while_transposes=transposes,
        aliased_params=_aliased_params(text),
    )


# ---------------------------------------------------------------------------
# The six rules.  Each returns {"status": "pass"|"fail"|"skip",
# "detail": str}; pure functions over summaries so the fixture tests
# and proof_gate share them without compiling anything.
# ---------------------------------------------------------------------------


def _res(status: str, detail: str) -> dict:
    return {"status": status, "detail": detail}


def check_h1(lowered: CollectiveSummary, compiled: CollectiveSummary,
             contract: CollectiveContract) -> dict:
    """No unattributed collectives in either HLO source."""
    bad = []
    for label, summ, allowed in (
            ("lowered", lowered, frozenset(contract.lowered_kinds)),
            ("compiled", compiled, frozenset(contract.compiled_kinds))):
        extra = summ.present_kinds() - allowed
        if extra:
            bad.append(f"{label} HLO contains undeclared "
                       f"{sorted(extra)} (declared: {sorted(allowed)})")
    if bad:
        return _res("fail", "; ".join(bad))
    return _res("pass",
                f"lowered={sorted(lowered.present_kinds())} "
                f"compiled={sorted(compiled.present_kinds())} all declared")


def check_h2(measured_bytes: int, source: str,
             contract: CollectiveContract) -> dict:
    """Collective bytes match the contract's ideal within tolerance."""
    if contract.step_bytes == 0:
        if measured_bytes == 0:
            return _res("pass", "zero-comm contract, zero measured")
        return _res("fail",
                    f"contract promises zero communication but the "
                    f"{source} HLO carries {measured_bytes} collective "
                    f"bytes")
    ratio = measured_bytes / contract.step_bytes
    lo, hi = contract.ratio_band
    if lo <= ratio <= hi:
        return _res("pass",
                    f"{measured_bytes} B ({source}) / ideal "
                    f"{contract.step_bytes} B = {ratio:.3f} in "
                    f"[{lo}, {hi}]")
    return _res("fail",
                f"{measured_bytes} B ({source}) / ideal "
                f"{contract.step_bytes} B = {ratio:.3f} outside "
                f"[{lo}, {hi}]")


def check_host_bytes(contract: CollectiveContract, num_hosts: int,
                     num_devices: int, measured_bytes: int,
                     pattern: str = "ring",
                     band: Optional[Tuple[float, float]] = None) -> dict:
    """graft-host extension of H2: the measured bytes that cross a
    host fault-domain boundary match the contract's inter-host slice
    (``CollectiveContract.inter_host_bytes``) within the band.

    Deliberately NOT in :data:`RULE_IDS` — H1–H7 are topology-free
    promises checked against the checked-in manifest at one fixed
    scale, while the inter-host slice depends on the deployment's
    host split; this check runs from the fleet/host gates, which know
    the split they rehearsed.  Defaults to the contract's own H2
    ``ratio_band``."""
    ideal = contract.inter_host_bytes(num_hosts, num_devices,
                                      pattern=pattern)
    if ideal == 0:
        if measured_bytes == 0:
            return _res("pass",
                        f"hosts={num_hosts}: no inter-host slice "
                        f"promised, none measured")
        return _res("fail",
                    f"hosts={num_hosts} promises zero inter-host "
                    f"bytes but {measured_bytes} B crossed a domain "
                    f"boundary")
    lo, hi = band if band is not None else contract.ratio_band
    ratio = measured_bytes / ideal
    detail = (f"{measured_bytes} B inter-host / ideal {ideal} B "
              f"({pattern}, hosts={num_hosts}, devices={num_devices})"
              f" = {ratio:.3f} vs [{lo}, {hi}]")
    return _res("pass" if lo <= ratio <= hi else "fail", detail)


def check_h3(lowered: CollectiveSummary, contract: CollectiveContract,
             k: int, merge_bytes: Optional[int] = None) -> dict:
    """The ÷c law: repl=c exchanges carry k/(c·S) slabs, and the
    deferred psum merge prices exactly ``reduce_comm_bytes``."""
    if contract.h3_exempt:
        return _res("skip", contract.h3_exempt)
    if contract.repl <= 1:
        if contract.reduce_bytes != 0:
            return _res("fail",
                        f"repl=1 contract declares nonzero merge bytes "
                        f"({contract.reduce_bytes})")
        return _res("pass", "repl=1: no replica merge priced")
    slab = contract.expected_slab(k)
    bad_dims = [d for d in lowered.leading_dims if d != slab]
    if bad_dims:
        return _res("fail",
                    f"repl={contract.repl} S={contract.overlap_slabs} "
                    f"expects every collective to carry a {slab}-row "
                    f"feature slab, found leading dims {bad_dims}")
    if merge_bytes is not None and merge_bytes != contract.reduce_bytes:
        return _res("fail",
                    f"replica merge program carries {merge_bytes} B "
                    f"but the contract prices exactly "
                    f"{contract.reduce_bytes} B")
    return _res("pass",
                f"all collectives carry the k/(c*S)={slab} slab; merge "
                f"prices {contract.reduce_bytes} B"
                + (" (verified)" if merge_bytes is not None else ""))


def check_h4(lowered: CollectiveSummary,
             contract: CollectiveContract) -> dict:
    """No silent dtype upcasts in the lowered (dtype-honest) step.

    The exact (f32) class gets the original H4.  A reduced-precision
    contract (graft-classes approx carriage: bf16 or int8) gets H4':
    the carriage->f32 accumulator widening is *declared* by the
    contract's dtype, so that one convert is benign — but in exchange
    every collective operand must actually carry a dtype no wider
    than the carriage, otherwise the program is paying exact-class
    exchange bytes while claiming the approx byte band."""
    carriage = contract.dtype
    approx = carriage in _CARRIAGE_BYTES and _CARRIAGE_BYTES[carriage] < 4
    bad = []
    if lowered.has_f64 and carriage != "f64":
        bad.append(f"f64 shapes in a {carriage}-carriage program "
                   f"(weak-type promotion or a float64 literal)")
    for src, dst in lowered.converts:
        if approx and src == carriage and dst == "f32":
            continue   # H4': the declared accumulator widening
        if (src in _FLOAT_BYTES and dst in _FLOAT_BYTES
                and _FLOAT_BYTES[dst] > _FLOAT_BYTES[src]
                and (src, dst) not in BENIGN_CONVERTS):
            bad.append(f"float-widening convert {src}->{dst}")
    if approx:
        limit = _CARRIAGE_BYTES[carriage]
        wide = sorted({dt for dt in lowered.collective_dtypes
                       if _CARRIAGE_BYTES.get(dt, 0) > limit})
        if wide:
            bad.append(f"{carriage}-class collectives carry "
                       f"full-precision operands {wide} — the approx "
                       f"byte band was never earned")
    if bad:
        return _res("fail", "; ".join(sorted(set(bad))))
    if approx:
        n_acc = sum(1 for src, dst in lowered.converts
                    if src == carriage and dst == "f32")
        kinds = sorted(set(lowered.collective_dtypes)) or ["none"]
        return _res("pass",
                    f"H4'({carriage}): collective operands {kinds}, "
                    f"{n_acc} declared accumulator widening(s), no "
                    f"other upcasts")
    n_benign = len(lowered.converts)
    return _res("pass",
                f"no f64, no widening converts "
                f"({n_benign} benign index/mask convert(s))")


def check_h5(donor_attrs: bool, compiled_scan: Optional[CollectiveSummary],
             contract: CollectiveContract) -> dict:
    """Donated inputs actually alias their outputs."""
    if not contract.donated_params:
        return _res("skip", "no donated entry point shipped")
    if compiled_scan is None:
        return _res("fail", "contract declares donated params but no "
                            "donated program was provided to the prover")
    missing = set(contract.donated_params) - set(
        compiled_scan.aliased_params)
    if not donor_attrs:
        return _res("fail",
                    "donation dropped at lowering: no jax.buffer_donor/"
                    "tf.aliasing_output attribute in the stablehlo (the "
                    "donated argument no longer matches an output)")
    if missing:
        return _res("fail",
                    f"compiled HLO aliases params "
                    f"{list(compiled_scan.aliased_params)} but the "
                    f"contract donates {list(contract.donated_params)} "
                    f"— phantom copy on {sorted(missing)}")
    return _res("pass",
                f"params {list(contract.donated_params)} aliased in "
                f"the compiled HLO (input_output_alias)")


def check_h7(stage_summaries: Optional[List[CollectiveSummary]],
             contract: CollectiveContract) -> dict:
    """graft-reshard's bounded-scratch law, statically: every stage of
    a staged exchange keeps its per-device send+recv collective
    buffers within the declared scratch budget.  The HLO accountant
    counts each all-to-all's per-device recv shape once; the send
    payload is the same size, so a stage's scratch is 2x its counted
    collective bytes.  An over-budget stage in the LOWERED HLO means
    the plan compiler emitted exactly the memory cliff the staging
    exists to remove."""
    if contract.scratch_budget_bytes <= 0:
        return _res("skip", "no staged scratch budget declared")
    if not stage_summaries:
        return _res("fail",
                    "contract declares a scratch budget of "
                    f"{contract.scratch_budget_bytes} B but no stage "
                    f"programs were provided to the prover")
    budget = contract.scratch_budget_bytes
    over = []
    peak = 0
    for i, s in enumerate(stage_summaries):
        scratch = 2 * s.total_bytes
        peak = max(peak, scratch)
        if scratch > budget:
            over.append(f"stage {i} carries {scratch} B send+recv "
                        f"> budget {budget} B")
    if over:
        return _res("fail", "; ".join(over))
    return _res("pass",
                f"{len(stage_summaries)} stage(s), peak per-device "
                f"send+recv {peak} B <= budget {budget} B")


def check_h6(compiled: CollectiveSummary,
             contract: CollectiveContract) -> dict:
    """No layout-thrash copy/transpose ops in the hot loop."""
    if compiled.while_transposes:
        return _res("fail",
                    f"{compiled.while_transposes} transpose op(s) in "
                    f"while-loop bodies — layout thrash every iteration")
    if compiled.while_copies > contract.hot_copy_budget:
        return _res("fail",
                    f"{compiled.while_copies} copy op(s) in while-loop "
                    f"bodies exceed the budget of "
                    f"{contract.hot_copy_budget}")
    return _res("pass",
                f"hot loop: {compiled.while_copies} copy(s) (budget "
                f"{contract.hot_copy_budget}), 0 transposes")


# ---------------------------------------------------------------------------
# Fixture verification (shared by tests, proof_gate --fixture, doctor)
# ---------------------------------------------------------------------------


def fixture_contract() -> CollectiveContract:
    """The contract the checked-in repl=2 HLO fixtures are judged
    against (tests/fixtures/collectives_repl2.hlo and its
    intentionally-broken sibling): a SELL-style repl=2 step at k=8
    (4-row slabs) — one tuple all-to-all (2 x f32[4,64] = 2048 B) and
    one replica-group all-reduce (f32[4,64] = 1024 B), merge priced at
    2048 B."""
    return CollectiveContract(
        algorithm="fixture_sell_repl2",
        step_bytes=3072, reduce_bytes=2048, repl=2, overlap_slabs=1,
        dtype="f32",
        lowered_kinds=("all-to-all", "all-reduce"),
        compiled_kinds=("all-to-all", "all-reduce"),
        ratio_band=(0.5, 2.0),
        notes="pinned parsing contract for the H1-H3 fixture tests")


def verify_fixture(text: str, contract: Optional[CollectiveContract] = None,
                   k: int = 8, merge_bytes: int = 2048) -> dict:
    """Run H1-H3 on one HLO fixture text; returns
    ``{"H1": {...}, "H2": {...}, "H3": {...}, "ok": bool}``.  The same
    text stands in for both sources (fixtures are single programs)."""
    contract = contract or fixture_contract()
    summ = summarize_hlo(text)
    results = {
        "H1": check_h1(summ, summ, contract),
        "H2": check_h2(summ.total_bytes, "fixture", contract),
        "H3": check_h3(summ, contract, k, merge_bytes=merge_bytes),
    }
    results["ok"] = all(r["status"] == "pass" for r in results.values()
                        if isinstance(r, dict))
    return results


#: Minimal inline twins of the checked-in fixtures, for the in-process
#: self-test (amt_doctor must not depend on the tests/ tree existing).
_SELFTEST_GOOD = """\
HloModule selftest_repl2_good
ENTRY %main (p0: f32[4,64]) -> f32[4,64] {
  %p0 = f32[4,64]{1,0} parameter(0)
  %a2a = (f32[4,64], f32[4,64]) all-to-all(f32[4,64]{1,0} %p0, f32[4,64]{1,0} %p0), replica_groups={{0,1}}
  ROOT %ar = f32[4,64]{1,0} all-reduce(f32[4,64]{1,0} %p0), replica_groups={{0,1}}, to_apply=%add
}
"""

_SELFTEST_BROKEN = _SELFTEST_GOOD.replace(
    "ROOT %ar",
    "%ag = f32[8,256]{1,0} all-gather(f32[4,64]{1,0} %p0), "
    "replica_groups={{0,1}}, dimensions={0}\n  ROOT %ar")


def selftest() -> bool:
    """The gate must pass a conforming program and trip on a planted
    surprise all-gather (wrong kind, wrong bytes, wrong slab)."""
    good = verify_fixture(_SELFTEST_GOOD)
    broken = verify_fixture(_SELFTEST_BROKEN)
    return bool(good["ok"]) and not broken["ok"] and all(
        broken[r]["status"] == "fail" for r in ("H1", "H2", "H3"))


# ---------------------------------------------------------------------------
# The proved entry points
# ---------------------------------------------------------------------------


def _entries(n: int, width: int, k: int, n_dev: int):
    """Build every contracted executor over the (c, S) grid at prove
    scale; yield ``(name, contract, programs)`` where programs is a
    dict of lowerable callables:

    * ``step``: (jit_fn, args, kwargs) — the per-iteration program;
    * ``scan``: donated scan entry, when the executor ships one;
    * ``merge``: the deferred 2.5D psum merge, when repl > 1.

    Unsupported grid combos are yielded as ``(name, None, reason)`` so
    the manifest records WHY a cell is absent instead of silently
    shrinking coverage.
    """
    import jax

    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.parallel.mesh import make_mesh, make_repl_mesh
    from arrow_matrix_tpu.utils.graphs import (
        barabasi_albert,
        random_csr,
        random_dense,
    )

    devs = jax.devices()[:n_dev]
    import numpy as np

    a = random_csr(n, n, 4, seed=7).astype(np.float32)
    x_host = random_dense(n, k, seed=3)

    ba = barabasi_albert(n, 4, seed=11)
    levels = arrow_decomposition(ba, width, max_levels=3,
                                 block_diagonal=True, seed=1)

    # -- spmm_1d (petsc-style 1-D): no replication/overlap modes -------
    from arrow_matrix_tpu.parallel.spmm_1d import MatrixSlice1D

    d1 = MatrixSlice1D(a, make_mesh((n_dev,), ("slices",), devices=devs))
    x1 = d1.set_features(x_host)
    yield ("spmm_1d[c=1,S=1]", d1.collective_contract(k), {
        "step": (d1._step, (d1.l_cols, d1.l_data, d1.nl_cols,
                            d1.nl_data, d1.send_idx, x1), {}),
    })
    yield ("spmm_1d[c=2]", None,
           "MatrixSlice1D has no replication mode (the 1.5D/SELL "
           "executors carry the 2.5D scheme)")

    # -- spmm_15d (A-stationary 1.5D): c via the mesh repl axis --------
    from arrow_matrix_tpu.parallel.spmm_15d import SpMM15D

    for c in (1, 2):
        mesh15 = make_mesh((n_dev // c, c), ("rows", "repl"),
                           devices=devs)
        d15 = SpMM15D(a, mesh15)
        x15 = d15.set_features(x_host)
        yield (f"spmm_15d[c={c},S=1]", d15.collective_contract(k), {
            "step": (d15._step, (d15.a_cols, d15.a_data, x15), {}),
        })
    yield ("spmm_15d[S=2]", None,
           "SpMM15D has no overlap schedule (its round loop already "
           "pipelines the broadcast)")

    # -- sell_slim / sell_multi over the full (c, S) grid --------------
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel, SellSlim

    for c in (1, 2):
        if c == 1:
            mesh = make_mesh((n_dev,), ("blocks",), devices=devs)
            repl_axis = None
        else:
            mesh = make_repl_mesh(n_dev, c, devices=devs)
            repl_axis = "repl"
        for s in (1, 2):
            ds = SellSlim(levels[0].matrix, width, mesh,
                          overlap_slabs=s, repl_axis=repl_axis)
            xs = ds.set_features(
                random_dense(levels[0].matrix.shape[0], k, seed=5))
            o = ds.ops
            progs = {"step": (ds._step, (o.body, o.head, o.head_unsort,
                                         o.orig_pos, xs), {})}
            if c > 1:
                ct = ds.spmm(xs)
                progs["merge"] = (ds._merge, (ct,), {})
            yield (f"sell_slim[c={c},S={s}]",
                   ds.collective_contract(k), progs)

            ml = SellMultiLevel(levels, width, mesh, routing="a2a",
                                overlap_slabs=s, repl_axis=repl_axis)
            xm = ml.set_features(random_dense(ml.n, k, seed=5))
            args = (xm,) + ml.step_operands()
            progs = {
                "step": (ml._step, args, {}),
                "scan": (ml._scan_donated, args, {"n": 2}),
            }
            if c > 1:
                ct = ml.step(xm)
                progs["merge"] = (ml._merge, (ct,), {})
            yield (f"sell_multi[c={c},S={s}]",
                   ml.collective_contract(k), progs)

    # -- multi_level: a2a mesh (c=1) and the single-chip fold ---------
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow

    meshb = make_mesh((n_dev,), ("blocks",), devices=devs)
    for s in (1, 2):
        ml = MultiLevelArrow(levels, width, mesh=meshb, routing="a2a",
                             overlap_slabs=s)
        xm = ml.set_features(x_host[:ba.shape[0]])
        args = (xm,) + ml.step_operands()
        yield (f"multi_level_a2a[c=1,S={s}]",
               ml.collective_contract(k), {
                   "step": (ml._step, args, {}),
                   "scan": (ml._scan_steps_donated, args, {"n": 2}),
               })
    yield ("multi_level_a2a[c=2]", None,
           "MultiLevelArrow has no replica axis (mesh replication is "
           "the SellSlim/SellMultiLevel repl_axis mode)")

    mf = MultiLevelArrow(levels, width, mesh=None, fmt="fold")
    xf = mf.set_features(x_host[:ba.shape[0]])
    args = (xf,) + mf.step_operands()
    yield ("multi_level_fold", mf.collective_contract(k), {
        "step": (mf._step, args, {}),
        "scan": (mf._scan_steps_donated, args, {"n": 2}),
    })

    # -- graft-classes approx carriage (H4') ---------------------------
    # The traffic-class entries: the mesh executors at bf16 (real
    # reduced-precision collectives, ideal bands halved by the contract
    # itemsize) and the single-chip fold at int8 (zero-comm quantized
    # (q, scale) carriage).  One grid cell each — the dtype is the
    # variable, the (c, S) sweep above already covers the schedules.
    smb = SellMultiLevel(levels, width,
                         make_mesh((n_dev,), ("blocks",), devices=devs),
                         routing="a2a", feature_dtype="bf16")
    xsb = smb.set_features(random_dense(smb.n, k, seed=5))
    args = (xsb,) + smb.step_operands()
    yield ("sell_multi[c=1,S=1,bf16]", smb.collective_contract(k), {
        "step": (smb._step, args, {}),
        "scan": (smb._scan_donated, args, {"n": 2}),
    })

    yield ("multi_level_a2a[c=1,S=1,bf16]", None,
           "MultiLevelArrow carries feature_dtype on fmt='fold' only; "
           "the mesh approx carriage is SellMultiLevel's "
           "(feature-major, the executor graft-tune promotes)")

    mfi = MultiLevelArrow(levels, width, mesh=None, fmt="fold",
                          feature_dtype="int8")
    xfi = mfi.set_features(x_host[:ba.shape[0]])
    args = (xfi,) + mfi.step_operands()
    yield ("multi_level_fold[int8]", mfi.collective_contract(k), {
        "step": (mfi._step, args, {}),
        "scan": (mfi._scan_steps_donated, args, {"n": 2}),
    })

    # -- graft-synth generated program (H1-H7 over a synthesized ------
    # per-level schedule): the fold executor running the degree-ladder-
    # derived schedule through the fused kernel.  Zero-comm contract —
    # a generated schedule repartitions slabs, it must introduce no new
    # collective kinds and hold the fold's copy discipline: the
    # contract budget grows by one declared 8-copy loop-state set per
    # scheduled tier (scalar/index-sized carried state of each tier's
    # streaming loop under interpret lowering), and H6 still forbids
    # any (rows, k) slab-sized copy or transpose in the hot loop.
    from arrow_matrix_tpu.tune.fingerprint import structure_fingerprint
    from arrow_matrix_tpu.tune.synth import synthesize_schedule

    sched = synthesize_schedule(
        structure_fingerprint(levels, width, np.float32))
    if sched:
        mfs = MultiLevelArrow(levels, width, mesh=None, fmt="fold",
                              kernel="pallas_sell",
                              kernel_opts={"interpret": True,
                                           "schedule": sched})
        xfs = mfs.set_features(x_host[:ba.shape[0]])
        args = (xfs,) + mfs.step_operands()
        yield ("multi_level_fold[synth]",
               mfs.collective_contract(k), {
                   "step": (mfs._step, args, {}),
                   "scan": (mfs._scan_steps_donated, args, {"n": 2}),
               })
    else:
        yield ("multi_level_fold[synth]", None,
               "the prove-scale structure synthesized an empty "
               "schedule (no non-zero ladder tiers)")

    # -- graft-reshard staged redistribution (H7) ----------------------
    # Two (src, dst) layout pairs, including a repl c change: the plan
    # compiler's bounded-scratch promise, proved from each stage's
    # lowered all-to-all buffers.  The one-shot route is the entry's
    # "step" (H1/H2 price its full exchange); the staged sub-routes are
    # the "stages" H7 audits against the declared budget.
    from jax.sharding import NamedSharding, PartitionSpec

    from arrow_matrix_tpu.parallel import routing as routing_mod
    from arrow_matrix_tpu.parallel.mesh import put_global
    from arrow_matrix_tpu.parallel.reshard import (
        Layout,
        plan_route_table,
        redistribution_plan,
    )

    reshard_budget = 2048
    rng = np.random.default_rng(13)
    pairs = [
        ("reshard[shuffle,d4]",
         Layout(n, n_dev=n_dev, tag="prove_src"),
         Layout(n, n_dev=n_dev, tag="prove_dst"),
         rng.permutation(n).astype(np.int64)),
        ("reshard[repl1to2,d4]",
         Layout(n, n_dev=n_dev, repl=1, tag="prove_src"),
         Layout(n, n_dev=n_dev, repl=2, tag="prove_dst"),
         None),
    ]
    mesh_r = make_mesh((n_dev,), ("blocks",), devices=devs)
    x_r = put_global(x_host.astype(np.float32),
                     NamedSharding(mesh_r, PartitionSpec("blocks")))

    def _route_fn(rt):
        return jax.jit(lambda xx: routing_mod.routed_take(
            xx, rt, mesh_r, "blocks"))

    for rname, src_lay, dst_lay, perm in pairs:
        plan = redistribution_plan(src_lay, dst_lay, reshard_budget,
                                   k=k, perm_map=perm)
        tbl, mask = plan_route_table(plan)
        route = routing_mod.build_route(
            tbl, n_dev, src_total=src_lay.stored_rows, pad_mask=mask)
        sroute = routing_mod.split_route_stages(route, k,
                                                reshard_budget)
        contract = CollectiveContract(
            algorithm=rname,
            step_bytes=route.device_bytes_per_exchange(k, 4),
            reduce_bytes=0, repl=1, overlap_slabs=1, dtype="f32",
            lowered_kinds=("all-to-all",),
            compiled_kinds=("all-to-all",),
            ratio_band=(0.99, 1.01),
            scratch_budget_bytes=reshard_budget,
            h3_exempt="redistribution carries full-k rows, not "
                      "replica slabs",
            notes=f"staged (src={src_lay.total_rows}x{src_lay.repl}"
                  f"c -> dst={dst_lay.total_rows}x{dst_lay.repl}c on "
                  f"{n_dev} devices): plan {plan.n_stages} host "
                  f"stage(s), route {sroute.n_stages} device "
                  f"stage(s)")
        yield (rname, contract, {
            "step": (_route_fn(routing_mod.shard_route(
                route, mesh_r, "blocks")), (x_r,), {}),
            "stages": [
                (_route_fn(routing_mod.shard_route(st, mesh_r,
                                                   "blocks")),
                 (x_r,), {})
                for st in sroute.stages],
        })


def _auto_bytes(lowered: CollectiveSummary,
                compiled: CollectiveSummary) -> Tuple[int, str]:
    """The obs/comm "auto" account: the lowered (explicit-collective)
    bytes when any exist, else the compiled (partitioner) bytes."""
    if lowered.total_bytes > 0:
        return lowered.total_bytes, "lowered"
    if compiled.total_bytes > 0:
        return compiled.total_bytes, "compiled"
    return 0, "lowered"


def prove_entry(name: str, contract: CollectiveContract,
                programs: dict, k: int) -> dict:
    """Lower + compile one entry's programs and run H1-H6."""
    step_fn, step_args, step_kwargs = programs["step"]
    step_lowered = step_fn.lower(*step_args, **step_kwargs)
    lowered = summarize_hlo(step_lowered.as_text(dialect="hlo"))
    compiled = summarize_hlo(step_lowered.compile().as_text())

    merge_bytes = None
    if "merge" in programs:
        m_fn, m_args, m_kwargs = programs["merge"]
        m_low = m_fn.lower(*m_args, **m_kwargs)
        m_lowered = summarize_hlo(m_low.as_text(dialect="hlo"))
        m_compiled = summarize_hlo(m_low.compile().as_text())
        merge_bytes, _ = _auto_bytes(m_lowered, m_compiled)

    donor_attrs = False
    scan_compiled = None
    hot = compiled
    if "scan" in programs:
        s_fn, s_args, s_kwargs = programs["scan"]
        s_low = s_fn.lower(*s_args, **s_kwargs)
        stable = s_low.as_text()
        donor_attrs = ("jax.buffer_donor" in stable
                       or "tf.aliasing_output" in stable)
        scan_compiled = summarize_hlo(s_low.compile().as_text())
        hot = scan_compiled

    stage_summaries = None
    if "stages" in programs:
        stage_summaries = []
        for g_fn, g_args, g_kwargs in programs["stages"]:
            g_low = g_fn.lower(*g_args, **g_kwargs)
            stage_summaries.append(
                summarize_hlo(g_low.as_text(dialect="hlo")))

    measured, source = _auto_bytes(lowered, compiled)
    rules = {
        "H1": check_h1(lowered, compiled, contract),
        "H2": check_h2(measured, source, contract),
        "H3": check_h3(lowered, contract, k, merge_bytes=merge_bytes),
        "H4": check_h4(lowered, contract),
        "H5": check_h5(donor_attrs, scan_compiled, contract),
        "H6": check_h6(hot, contract),
        "H7": check_h7(stage_summaries, contract),
    }
    return {
        "entry": name,
        "contract": contract.to_json(),
        "measured": {
            "auto_bytes": measured,
            "source": source,
            "lowered_bytes": lowered.total_bytes,
            "compiled_bytes": compiled.total_bytes,
            "lowered_kinds": {kd: v for kd, v in lowered.kinds.items()
                              if v["count"]},
            "compiled_kinds": {kd: v for kd, v in compiled.kinds.items()
                               if v["count"]},
            "merge_bytes": merge_bytes,
            "hot_loop_copies": hot.while_copies,
            "hot_loop_transposes": hot.while_transposes,
            "aliased_params": (list(scan_compiled.aliased_params)
                               if scan_compiled is not None else None),
            "stage_scratch_bytes": (
                [2 * s.total_bytes for s in stage_summaries]
                if stage_summaries is not None else None),
        },
        "rules": rules,
        "ok": all(r["status"] in ("pass", "skip")
                  for r in rules.values()),
    }


# ---------------------------------------------------------------------------
# Manifest
# ---------------------------------------------------------------------------

#: Keys the drift comparison ignores (environment, not behavior).
VOLATILE_KEYS = ("timestamp", "jax_version", "platform", "generated_by")


def manifest_digest(manifest: dict) -> dict:
    """The behavior-only view of a manifest the drift gate compares:
    entry names, per-rule statuses, measured byte accounts, and the
    skip ledger — everything except the volatile environment keys."""
    return {
        "scale": manifest.get("scale"),
        "entries": {
            e["entry"]: {
                "ok": e["ok"],
                "rules": {r: v["status"]
                          for r, v in e["rules"].items()},
                "auto_bytes": e["measured"]["auto_bytes"],
                "merge_bytes": e["measured"]["merge_bytes"],
            }
            for e in manifest.get("entries", ())
        },
        "skipped": {s["entry"]: s["reason"]
                    for s in manifest.get("skipped", ())},
        "ok": manifest.get("ok"),
    }


def manifest_drift(old: dict, new: dict) -> List[str]:
    """Human-readable differences between two manifests' digests
    (empty = no drift)."""
    a, b = manifest_digest(old), manifest_digest(new)
    problems: List[str] = []
    if a["scale"] != b["scale"]:
        problems.append(f"scale changed: {a['scale']} -> {b['scale']}")
    for name in sorted(set(a["entries"]) | set(b["entries"])):
        if name not in b["entries"]:
            problems.append(f"entry disappeared: {name}")
        elif name not in a["entries"]:
            problems.append(f"new unrecorded entry: {name}")
        elif a["entries"][name] != b["entries"][name]:
            problems.append(
                f"entry changed: {name}: {a['entries'][name]} -> "
                f"{b['entries'][name]}")
    for name in sorted(set(a["skipped"]) | set(b["skipped"])):
        if a["skipped"].get(name) != b["skipped"].get(name):
            problems.append(f"skip ledger changed for {name}")
    if a["ok"] != b["ok"]:
        problems.append(f"overall ok changed: {a['ok']} -> {b['ok']}")
    return problems


def run_prove(out_path: str = DEFAULT_MANIFEST,
              n: int = PROVE_SCALE["n"], width: int = PROVE_SCALE["width"],
              k: int = PROVE_SCALE["k"], n_dev: int = PROVE_SCALE["n_dev"],
              write: bool = True) -> dict:
    """Prove every contracted entry point; return (and write) the
    manifest.  Requires an initialized multi-device jax (the CLI path
    forces a virtual CPU pool first; under pytest the conftest pool is
    reused)."""
    import datetime

    import jax

    entries: List[dict] = []
    skipped: List[dict] = []
    for name, contract, programs in _entries(n, width, k, n_dev):
        if contract is None:
            skipped.append({"entry": name, "reason": programs})
            continue
        entries.append(prove_entry(name, contract, programs, k))
    manifest = {
        "generated_by": "python -m arrow_matrix_tpu.analysis prove",
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "jax_version": jax.__version__,
        "platform": jax.devices()[0].platform,
        "n_devices": n_dev,
        "scale": {"n": n, "width": width, "k": k},
        "entries": entries,
        "skipped": skipped,
        "ok": all(e["ok"] for e in entries),
    }
    if write:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return manifest


def _format_entry(e: dict) -> str:
    mark = "ok  " if e["ok"] else "FAIL"
    verdicts = " ".join(
        f"{r}:{e['rules'][r]['status']}" for r in RULE_IDS)
    line = (f"[{mark}] {e['entry']}: {e['measured']['auto_bytes']} B "
            f"({e['measured']['source']}) vs ideal "
            f"{e['contract']['step_bytes']} B | {verdicts}")
    for r in RULE_IDS:
        if e["rules"][r]["status"] == "fail":
            line += f"\n       {r}: {e['rules'][r]['detail']}"
    return line


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="graft_prove", description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_MANIFEST)
    ap.add_argument("--devices", type=int, default=PROVE_SCALE["n_dev"],
                    help="virtual CPU devices (forced before jax init)")
    ap.add_argument("--n", type=int, default=PROVE_SCALE["n"])
    ap.add_argument("--width", type=int, default=PROVE_SCALE["width"])
    ap.add_argument("--k", type=int, default=PROVE_SCALE["k"])
    ap.add_argument("--check", action="store_true",
                    help="do not write; fail on any violation OR drift "
                         "against the checked-in manifest")
    args = ap.parse_args(argv)

    # The prover is a CPU-trace exercise by contract: force the virtual
    # pool BEFORE the first backend touch (a TPU would prove the wrong
    # partitioning).
    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(args.devices)

    manifest = run_prove(out_path=args.out, n=args.n, width=args.width,
                         k=args.k, n_dev=args.devices,
                         write=not args.check)
    for e in manifest["entries"]:
        print(_format_entry(e))
    for s in manifest["skipped"]:
        print(f"[skip] {s['entry']}: {s['reason']}")

    rc = 0 if manifest["ok"] else 1
    if args.check:
        try:
            with open(args.out, encoding="utf-8") as fh:
                checked_in = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"no readable checked-in manifest at {args.out}: {e}")
            return 1
        drift = manifest_drift(checked_in, manifest)
        for d in drift:
            print(f"drift: {d}")
        if drift:
            print(f"proof drift against {args.out} — rerun "
                  f"`python -m arrow_matrix_tpu.analysis prove` and "
                  f"commit the refreshed manifest")
            rc = 1
    else:
        print(f"manifest: {args.out}")
    print("proof passed" if rc == 0 else "PROOF FAILED")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
