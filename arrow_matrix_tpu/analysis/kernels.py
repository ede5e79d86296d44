"""graft-kcert: static certifier for the Pallas kernel layer (KC1-KC5).

The rule families above this layer — R1-R9 (lint), H1-H7 (prove),
RC1-RC5 (sync) — stop at the HLO boundary: nothing checked what the
hand-written Pallas kernels actually do with their grids, DMA rings,
and accumulators.  graft-kcert closes that last tier.  Every Pallas
kernel builder exports a frozen :class:`~arrow_matrix_tpu.ops.
kernel_contract.KernelContract` plus ``kcert_metas()`` — literal
descriptions of its concretized ``pallas_call``\\ s at representative
parameter points, the SAME dicts the builder derives its real
grid/block/scratch numbers from — and this module proves five rules
over them:

* **KC1** every index into SMEM cols / VMEM slabs / the HBM-packed
  feature table is in bounds given the fingerprint invariants
  (exact block tiling, grid-extent x block <= shape, slot-major slab
  arithmetic, granule packing), backed by an interpret-mode boundary
  witness in which every slot points at the LAST feature row;
* **KC2** the sum of double-buffered VMEM BlockSpec blocks plus
  ``scratch_shapes`` fits the declared VMEM budget, and the
  SMEM column-block bytes fit the SMEM budget, statically per
  (row_block, ring, k) point;
* **KC3** DMA ring discipline — extracted from the builder source by
  AST (the ``copy``/``issue``/``wait`` schedule convention of
  ``ops/pallas_sell.kernel_stream``) and then replayed in a Python
  ring simulator at every certified (ring, wave, n_waves) point:
  every ``pltpu.make_async_copy`` is waited before its semaphore slot
  is reused, reuse distance >= ring depth, sem indices ring-modular,
  no two in-flight copies alias one scratch slab.  A kernel whose
  copies do not match the recognized schedule fails CLOSED;
* **KC4** the accumulation dtype is >= f32 regardless of the carriage
  dtype (H4' at the kernel level), both in the declared meta and in
  the source (no narrow ``jnp.zeros`` accumulator, every ``jnp.dot``
  pinned to ``preferred_element_type=f32``);
* **KC5** the output BlockSpec index map covers every output block
  exactly once across the whole grid — no gap, no overlap — except
  grid axes the contract explicitly declares as revisiting
  (``head_spmm_pallas``'s k-innermost accumulation axis), which must
  revisit uniformly.

Verdicts land in the drift-detected ``bench_cache/
kernel_manifest.json`` (the hlo/sync manifest discipline) and a
``kind="kcert"`` ledger record so ledger_gate drift-checks rule-count
regressions; ``tune/space.py`` calls :func:`certify_candidate_opts`
to prune uncertifiable candidates BEFORE any child process spawns,
and ROADMAP item 3's generated programs enter through
``kernel_contract.register_kernel`` and are certified with zero
changes here.

Usage:
  python -m arrow_matrix_tpu.analysis kernels            certify + write
  python -m arrow_matrix_tpu.analysis kernels --check    certify + drift
  python -m arrow_matrix_tpu.analysis kernels --selftest inline twins
  python -m arrow_matrix_tpu.analysis kernels --fixture F planted fixture
(``graft_kcert`` is the console script; tools/kernel_gate.py the CI
wrapper.)
"""

from __future__ import annotations

import ast
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from arrow_matrix_tpu.ops.kernel_contract import (
    CARRIAGE_ITEMSIZE,
    WIDE_ACCUM_DTYPES,
    KernelEntry,
    registered_kernels,
)

RULE_IDS = ("KC1", "KC2", "KC3", "KC4", "KC5")

RULE_TITLES = {
    "KC1": "every SMEM/VMEM/HBM index in bounds at every grid point",
    "KC2": "VMEM blocks + scratch and SMEM prefetch fit their budgets",
    "KC3": "DMA ring discipline: waited before slot reuse, no aliasing",
    "KC4": "accumulation dtype >= f32 regardless of carriage dtype",
    "KC5": "output index map covers every output block exactly once",
}

DEFAULT_MANIFEST = os.path.join("bench_cache", "kernel_manifest.json")

#: Keys the drift comparison ignores (environment, not behavior).
VOLATILE_KEYS = ("timestamp", "python_version", "platform",
                 "generated_by")

#: KC5 refuses to enumerate grids beyond this many points: a generated
#: program with an absurd grid is a finding, not a hang.
MAX_GRID_POINTS = 1_000_000


class Finding:
    """One rule violation at one (kernel, parameter point)."""

    __slots__ = ("rule", "kernel", "where", "message")

    def __init__(self, rule: str, kernel: str, where: str,
                 message: str):
        self.rule = rule
        self.kernel = kernel
        self.where = where
        self.message = message

    def format(self) -> str:
        return f"{self.kernel}[{self.where}]: {self.rule}: {self.message}"

    def to_json(self) -> dict:
        return {"rule": self.rule, "kernel": self.kernel,
                "where": self.where, "message": self.message}


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def point_label(meta: dict) -> str:
    """Deterministic compact label of one meta (manifest/digest key)."""
    parts = [str(meta.get("kind", "?"))]
    st = meta.get("stream")
    if st:
        parts.append(f"rb{st.get('row_block')}g{st.get('ring')}"
                     f"w{st.get('wave')}")
    grid = meta.get("grid") or []
    parts.append("grid" + ("x".join(str(s) for _a, s in grid) or "0"))
    out = meta.get("out") or {}
    parts.append("out" + "x".join(str(b) for b in out.get("block", ())))
    parts.append(str(meta.get("carriage_dtype", "f32")))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# Meta checks: KC1, KC2, KC4 (declared), KC5
# ---------------------------------------------------------------------------


def check_meta(meta: dict) -> List[Finding]:
    """Prove KC1/KC2/KC4/KC5 arithmetically over one concretized call
    meta (see ``ops/pallas_sell.slab_call_meta`` for the schema)."""
    findings: List[Finding] = []
    kernel = str(meta.get("kernel", "?"))
    where = point_label(meta)

    def fail(rule: str, message: str) -> None:
        findings.append(Finding(rule, kernel, where, message))

    grid = list(meta.get("grid") or [])
    axes: Dict[str, int] = {}
    for axis, size in grid:
        if int(size) < 1:
            fail("KC1", f"grid axis {axis!r} has nonpositive extent "
                        f"{size}")
        axes[str(axis)] = int(size)

    # -- KC4: declared dtypes -------------------------------------------------
    accum = str(meta.get("accum_dtype", "")).lower()
    if accum not in WIDE_ACCUM_DTYPES:
        fail("KC4", f"accumulation dtype {meta.get('accum_dtype')!r} "
                    f"is narrower than f32 (carriage "
                    f"{meta.get('carriage_dtype')!r} may narrow, the "
                    f"accumulator may not)")
    carriage = str(meta.get("carriage_dtype", "f32"))
    if carriage not in CARRIAGE_ITEMSIZE:
        fail("KC4", f"unknown carriage dtype {carriage!r} (contract "
                    f"serves {tuple(CARRIAGE_ITEMSIZE)})")

    # -- KC1: exact tiling + bounds per blocked operand ----------------------
    out = meta.get("out") or {}
    operands = [("out", out)]
    operands += [(str(op.get("name", f"in{i}")), op)
                 for i, op in enumerate(meta.get("ins") or ())
                 if op.get("block") is not None]
    for name, op in operands:
        shape = list(op.get("shape") or ())
        block = list(op.get("block") or ())
        index = list(op.get("index") or ())
        if not (len(shape) == len(block) == len(index)):
            fail("KC1", f"{name}: shape/block/index ranks disagree "
                        f"({len(shape)}/{len(block)}/{len(index)})")
            continue
        for d, (s, b, ix) in enumerate(zip(shape, block, index)):
            s, b = int(s), int(b)
            if b < 1 or b > s:
                fail("KC1", f"{name} dim {d}: block {b} outside "
                            f"(0, shape={s}]")
                continue
            if s % b:
                fail("KC1", f"{name} dim {d}: block {b} does not "
                            f"tile shape {s} exactly")
            if isinstance(ix, str):
                n = axes.get(ix)
                if n is None:
                    fail("KC1", f"{name} dim {d}: index references "
                                f"unknown grid axis {ix!r}")
                elif n * b > s:
                    fail("KC1", f"{name} dim {d}: grid axis {ix} "
                                f"({n} steps) x block {b} = {n * b} "
                                f"rows exceeds shape {s}")
            else:
                if (int(ix) + 1) * b > s:
                    fail("KC1", f"{name} dim {d}: static origin "
                                f"{ix} x block {b} exceeds shape {s}")

    # -- KC1/KC3: slot-major streaming invariants ----------------------------
    st = meta.get("stream")
    if st:
        rb = int(st.get("row_block", 0))
        wave = int(st.get("wave", 0))
        n_waves = int(st.get("n_waves", 0))
        ring = int(st.get("ring", 0))
        c = int(st.get("granule", 1)) or 1
        slab = int(st.get("slab", 0))
        if wave * n_waves != rb:
            fail("KC1", f"stream: wave {wave} x n_waves {n_waves} != "
                        f"row_block {rb} — the wave loop misses rows")
        if rb % c:
            fail("KC1", f"stream: row_block {rb} is not a granule "
                        f"({c}) multiple")
        if slab < rb or (rb and slab % rb):
            fail("KC1", f"stream: slab {slab} is not a whole number "
                        f"of row blocks ({rb})")
        if grid and rb:
            gsz = axes.get(str(grid[0][0]))
            if gsz is not None and gsz != slab // rb:
                fail("KC1", f"stream: grid extent {gsz} != slab/"
                            f"row_block = {slab // rb}")
        lines = int(st.get("lines", 0))
        if int(st.get("table_rows", lines * c)) != lines * c:
            fail("KC1", f"stream: table_rows "
                        f"{st.get('table_rows')} != lines {lines} x "
                        f"granule {c} — packed-table addressing is "
                        f"off")
        scratch = list(meta.get("scratch") or ())
        if scratch:
            srows = int((scratch[0].get("shape") or (0,))[0])
            if srows != rb:
                fail("KC1", f"stream: scratch rows {srows} != "
                            f"row_block {rb} — a wave lands out of "
                            f"its slab")
        sems = meta.get("sems") or {}
        sshape = list(sems.get("shape") or ())
        if sshape != [ring, wave]:
            fail("KC3", f"stream: semaphore shape {sshape} != "
                        f"[ring={ring}, wave={wave}] — sem indices "
                        f"can leave range")
        if ring < 1:
            fail("KC3", f"stream: ring depth {ring} < 1")

    # -- KC2: VMEM + SMEM budgets --------------------------------------------
    vmem_budget = int(meta.get("vmem_budget") or 0)
    if vmem_budget:
        total = 0
        pieces = []
        if out.get("block"):
            nb = _prod(out["block"]) * int(out.get("itemsize", 4)) * 2
            total += nb
            pieces.append(f"out={nb}")
        for op in meta.get("ins") or ():
            if op.get("block") is not None and \
                    op.get("space", "vmem") == "vmem":
                nb = _prod(op["block"]) * int(op.get("itemsize", 4)) * 2
                total += nb
                pieces.append(f"{op.get('name', 'in')}={nb}")
        for scr in meta.get("scratch") or ():
            nb = _prod(scr.get("shape") or ()) * \
                int(scr.get("itemsize", 4))
            total += nb
            pieces.append(f"{scr.get('name', 'scratch')}={nb}")
        if total > vmem_budget:
            fail("KC2", f"VMEM footprint {total} B exceeds budget "
                        f"{vmem_budget} B ({', '.join(pieces)}; "
                        f"mapped blocks double-buffered)")
    smem = meta.get("smem")
    if smem and smem.get("budget") is not None:
        sbytes = int(smem.get("bytes", 0))
        sbudget = int(smem["budget"])
        if sbytes > sbudget and not smem.get("single_block"):
            fail("KC2", f"SMEM column bytes {sbytes} exceed the "
                        f"SMEM budget {sbudget} and the slab is not "
                        f"already minimal")

    # -- KC5: output coverage -------------------------------------------------
    findings.extend(_check_coverage(meta, kernel, where, axes))
    return findings


def _check_coverage(meta: dict, kernel: str, where: str,
                    axes: Dict[str, int]) -> List[Finding]:
    """Enumerate every grid point and prove the output index map covers
    every output block exactly once (modulo declared revisit axes)."""
    import itertools

    findings: List[Finding] = []

    def fail(rule: str, message: str) -> None:
        findings.append(Finding(rule, kernel, where, message))

    out = meta.get("out") or {}
    shape = list(out.get("shape") or ())
    block = list(out.get("block") or ())
    index = list(out.get("index") or ())
    if not shape or len(shape) != len(block) or \
            len(index) != len(shape):
        return findings  # rank problems already reported under KC1
    if any(int(b) < 1 or int(s) % int(b) for s, b in zip(shape, block)):
        return findings  # tiling problems already reported under KC1

    order = [str(a) for a, _s in (meta.get("grid") or [])]
    n_points = _prod(axes[a] for a in order) if order else 1
    if n_points > MAX_GRID_POINTS:
        fail("KC5", f"grid has {n_points} points (> {MAX_GRID_POINTS})"
                    f" — refusing to certify coverage")
        return findings

    used = {ix for ix in index if isinstance(ix, str)}
    unused = [a for a in order if a not in used]
    revisit_declared = {str(a) for a in meta.get("revisit_axes") or ()}
    bad_revisit = [a for a in unused if a not in revisit_declared]
    expected = _prod(axes[a] for a in unused) if unused else 1
    if expected > 1 and bad_revisit:
        fail("KC5", f"grid axes {bad_revisit} do not appear in the "
                    f"output index map and are not declared revisit "
                    f"axes — every step overwrites the same block")

    counts: Dict[tuple, int] = {}
    for point in itertools.product(*(range(axes[a]) for a in order)):
        env = dict(zip(order, point))
        coord = tuple(env[ix] if isinstance(ix, str) else int(ix)
                      for ix in index)
        counts[coord] = counts.get(coord, 0) + 1

    want = set(itertools.product(
        *(range(int(s) // int(b)) for s, b in zip(shape, block))))
    missing = sorted(want - set(counts))
    if missing:
        fail("KC5", f"{len(missing)} output block(s) never written "
                    f"(first gap at block {missing[0]}) out of "
                    f"{len(want)}")
    extra = sorted(set(counts) - want)
    if extra:
        fail("KC5", f"index map writes {len(extra)} block(s) outside "
                    f"the output (first at {extra[0]})")
    uneven = {coord: n for coord, n in counts.items()
              if coord in want and n != expected}
    if uneven and not missing:
        coord, n = sorted(uneven.items())[0]
        fail("KC5", f"uneven coverage: block {coord} written {n}x, "
                    f"expected {expected}x"
                    + (" (revisit axes must revisit uniformly)"
                       if expected > 1 else ""))
    return findings


# ---------------------------------------------------------------------------
# Source checks: KC3 (ring schedule), KC4 (narrow accumulators / dots)
# ---------------------------------------------------------------------------

_NARROW_DTYPES = {"bfloat16", "float16", "int8", "float8_e4m3",
                  "float8_e5m2"}


def _dtype_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _call_name(node: ast.Call) -> str:
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return ""


def _scan_kernel_fn(fn: ast.AST) -> dict:
    """Collect the KC3 schedule signals from one kernel function."""
    info = {"copies": 0, "starts": 0, "waits": 0,
            "sem_mod_ring": False, "prologue_min_ring": False,
            "issue_offset_ring": False}
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name == "make_async_copy":
                info["copies"] += 1
                for arg in ast.walk(node):
                    if isinstance(arg, ast.BinOp) and \
                            isinstance(arg.op, ast.Mod):
                        info["sem_mod_ring"] = True
            elif name == "start" and isinstance(node.func,
                                                ast.Attribute):
                info["starts"] += 1      # copy(...).start(): a method
            elif name == "wait" and isinstance(node.func,
                                               ast.Attribute):
                info["waits"] += 1       # copy(...).wait(), not the
                                         # local wait() helper
            elif name == "min" and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.BinOp) and \
                        isinstance(a0.op, ast.Sub) and \
                        isinstance(a0.right, ast.Constant) and \
                        a0.right.value == 1:
                    info["prologue_min_ring"] = True
            else:
                # issue(j, w + ring - 1): any call carrying the
                # "+ ring - 1" top-up offset.
                for arg in node.args:
                    if isinstance(arg, ast.BinOp) and \
                            isinstance(arg.op, ast.Sub) and \
                            isinstance(arg.right, ast.Constant) and \
                            arg.right.value == 1 and \
                            isinstance(arg.left, ast.BinOp) and \
                            isinstance(arg.left.op, ast.Add):
                        info["issue_offset_ring"] = True
    return info


def simulate_ring(ring: int, wave: int, n_waves: int) -> List[str]:
    """Replay the recognized prologue/top-up/wait schedule against a
    semaphore-slot model; every returned string is a KC3 violation.
    Proves: slot free on issue (reuse distance >= ring), wave waited
    exactly once, in-flight scratch rows disjoint, ring drained at the
    slot-body end."""
    violations: List[str] = []
    in_flight: Dict[int, int] = {}   # sem slot -> wave id

    def issue(w: int) -> None:
        slot = w % ring
        if slot in in_flight:
            violations.append(
                f"sem slot {slot} reissued for wave {w} while wave "
                f"{in_flight[slot]} is still in flight (reuse "
                f"distance < ring={ring})")
            return
        lo, hi = w * wave, (w + 1) * wave
        for ow in in_flight.values():
            if max(lo, ow * wave) < min(hi, (ow + 1) * wave):
                violations.append(
                    f"waves {ow} and {w} in flight alias scratch "
                    f"rows [{lo}, {hi})")
        in_flight[slot] = w

    def wait(w: int) -> None:
        slot = w % ring
        if in_flight.get(slot) != w:
            violations.append(
                f"wait({w}) finds slot {slot} holding "
                f"{in_flight.get(slot)} — copy never issued or "
                f"already consumed")
        else:
            del in_flight[slot]

    for p in range(min(ring - 1, n_waves)):
        issue(p)
    for w in range(n_waves):
        if w + ring - 1 < n_waves:
            issue(w + ring - 1)
        wait(w)
    if in_flight:
        violations.append(
            f"{len(in_flight)} cop(ies) still in flight at the "
            f"slot-body end (waves {sorted(in_flight.values())})")
    return violations


def analyze_kernel_source(
        source: str, path: str = "<source>",
        stream_points: Sequence[Tuple[int, int, int]] = (),
        ) -> List[Finding]:
    """AST pass over a kernel builder module: KC3 on every function
    whose name contains ``kernel`` and issues async copies, KC4 on
    narrow accumulators and unpinned dots in those functions."""
    findings: List[Finding] = []
    base = os.path.basename(path)
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:
        return [Finding("KC1", base, "source",
                        f"unparseable kernel source: {exc}")]

    kernel_fns = [node for node in ast.walk(tree)
                  if isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))
                  and "kernel" in node.name]
    for fn in kernel_fns:
        where = f"{fn.name}:{fn.lineno}"
        info = _scan_kernel_fn(fn)
        if info["copies"]:
            if not info["waits"]:
                findings.append(Finding(
                    "KC3", base, where,
                    "make_async_copy issued but never .wait()ed — "
                    "the scratch slab is read while the DMA is in "
                    "flight"))
            elif not info["sem_mod_ring"]:
                findings.append(Finding(
                    "KC3", base, where,
                    "semaphore index is not ring-modular "
                    "(sems.at[w % ring, ...]) — in-flight slot "
                    "aliasing cannot be excluded"))
            elif not (info["prologue_min_ring"]
                      and info["issue_offset_ring"]):
                findings.append(Finding(
                    "KC3", base, where,
                    "unrecognized DMA schedule (no min(ring-1, ...) "
                    "prologue / w + ring - 1 top-up) — failing "
                    "closed"))
            else:
                for ring, wv, n_waves in stream_points:
                    for v in simulate_ring(ring, wv, n_waves):
                        findings.append(Finding(
                            "KC3", base,
                            f"{where}@ring{ring}w{wv}n{n_waves}", v))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in ("zeros", "full", "empty", "zeros_like"):
                for kw in node.keywords:
                    if kw.arg == "dtype" and \
                            _dtype_name(kw.value) in _NARROW_DTYPES:
                        findings.append(Finding(
                            "KC4", base,
                            f"{fn.name}:{node.lineno}",
                            f"accumulator initialized at narrow "
                            f"dtype {_dtype_name(kw.value)} — the "
                            f"carriage may narrow, the accumulator "
                            f"may not"))
            elif name == "dot":
                kws = {kw.arg for kw in node.keywords}
                if "preferred_element_type" not in kws:
                    findings.append(Finding(
                        "KC4", base, f"{fn.name}:{node.lineno}",
                        "jnp.dot without preferred_element_type — "
                        "the MXU accumulates at the carriage dtype"))
    return findings


# ---------------------------------------------------------------------------
# Entry certification + manifest
# ---------------------------------------------------------------------------


def stream_points_of(metas: Sequence[dict]) -> List[Tuple[int, int, int]]:
    return sorted({(int(m["stream"]["ring"]), int(m["stream"]["wave"]),
                    int(m["stream"]["n_waves"]))
                   for m in metas if m.get("stream")})


def certify_entry(entry: KernelEntry) -> dict:
    """Prove KC1-KC5 for one registered kernel; returns its manifest
    record (rule verdicts, witness detail, wall time)."""
    t0 = time.perf_counter()
    findings: List[Finding] = []
    try:
        metas = list(entry.metas())
    except Exception as exc:
        metas = []
        findings.append(Finding("KC1", entry.name, "metas",
                                f"meta enumeration raised: {exc!r}"))
    for meta in metas:
        findings.extend(check_meta(meta))
    src = entry.source()
    if src is not None:
        findings.extend(analyze_kernel_source(
            src, path=entry.source_path or "<source>",
            stream_points=stream_points_of(metas)))
    witness_detail = None
    if entry.witness is not None:
        ok, detail = entry.witness()
        witness_detail = detail
        if not ok:
            findings.append(Finding("KC1", entry.name, "witness",
                                    detail))
    wall_ms = (time.perf_counter() - t0) * 1000.0

    rules: Dict[str, dict] = {}
    for rule in RULE_IDS:
        hits = [f for f in findings if f.rule == rule]
        if hits:
            detail = "; ".join(f.format() for f in hits[:8])
            if len(hits) > 8:
                detail += f" (+{len(hits) - 8} more)"
            rules[rule] = {"status": "fail", "detail": detail}
        else:
            rules[rule] = {"status": "pass",
                           "detail": RULE_TITLES[rule]}
    return {
        "name": entry.name,
        "module": entry.contract.module,
        "kind": entry.contract.kind,
        "contract": entry.contract.to_json(),
        "points": len(metas),
        "rules": rules,
        "witness": witness_detail,
        "wall_ms": round(wall_ms, 2),
        "findings": [f.to_json() for f in findings],
        "ok": not findings,
    }


def certify_all(entries: Optional[Sequence[KernelEntry]] = None
                ) -> List[dict]:
    return [certify_entry(e)
            for e in (registered_kernels() if entries is None
                      else entries)]


def build_manifest(records: Sequence[dict]) -> dict:
    import datetime
    import platform as _platform

    rules: Dict[str, dict] = {}
    for rule in RULE_IDS:
        failed = [r["name"] for r in records
                  if r["rules"][rule]["status"] == "fail"]
        rules[rule] = ({"status": "fail",
                        "detail": "fails in: " + ", ".join(failed)}
                       if failed else
                       {"status": "pass",
                        "detail": RULE_TITLES[rule]})
    return {
        "generated_by": "python -m arrow_matrix_tpu.analysis kernels",
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "python_version": sys.version.split()[0],
        "platform": _platform.platform(),
        "package": "arrow_matrix_tpu",
        "kernels": sorted(records, key=lambda r: r["name"]),
        "rules": rules,
        "counts": {
            "kernels": len(records),
            "points": sum(r["points"] for r in records),
            "findings": sum(len(r["findings"]) for r in records),
            "rules_pass": sum(
                1 for r in records for rule in RULE_IDS
                if r["rules"][rule]["status"] == "pass"),
        },
        "ok": all(r["ok"] for r in records),
    }


def _jsonify(value):
    """Normalize tuples -> lists so an in-memory digest compares equal
    to its JSON round trip."""
    return json.loads(json.dumps(value))


def manifest_digest(manifest: dict) -> dict:
    """The behavior-only view the drift gate compares: rule verdicts,
    contracts, per-point findings — not timestamps or wall times."""
    return _jsonify({
        "rules": {r: v["status"]
                  for r, v in manifest.get("rules", {}).items()},
        "kernels": {
            k["name"]: {
                "kind": k["kind"],
                "contract": k["contract"],
                "points": k["points"],
                "rules": {r: v["status"]
                          for r, v in k["rules"].items()},
                "findings": sorted(
                    f"{f['rule']}:{f['where']}:{f['message']}"
                    for f in k.get("findings", ())),
            }
            for k in manifest.get("kernels", ())
        },
        "counts": {k: v for k, v in
                   (manifest.get("counts") or {}).items()},
        "ok": manifest.get("ok"),
    })


def manifest_drift(old: dict, new: dict) -> List[str]:
    """Human-readable differences between two manifests' digests
    (empty = no drift)."""
    a, b = manifest_digest(old), manifest_digest(new)
    problems: List[str] = []
    for rule in sorted(set(a["rules"]) | set(b["rules"])):
        if a["rules"].get(rule) != b["rules"].get(rule):
            problems.append(f"rule {rule} changed: "
                            f"{a['rules'].get(rule)} -> "
                            f"{b['rules'].get(rule)}")
    for name in sorted(set(a["kernels"]) | set(b["kernels"])):
        if name not in b["kernels"]:
            problems.append(f"kernel disappeared: {name}")
        elif name not in a["kernels"]:
            problems.append(f"new unrecorded kernel: {name}")
        else:
            ka, kb = a["kernels"][name], b["kernels"][name]
            for key in ("kind", "contract", "points", "rules"):
                if ka[key] != kb[key]:
                    problems.append(f"kernel {name}: {key} changed")
            if ka["findings"] != kb["findings"]:
                problems.append(f"kernel {name}: finding set changed")
    if a["counts"] != b["counts"]:
        problems.append(f"verdict counts changed: {a['counts']} -> "
                        f"{b['counts']}")
    if a["ok"] != b["ok"]:
        problems.append(f"overall ok changed: {a['ok']} -> {b['ok']}")
    return problems


def _record_ledger(manifest: dict,
                   ledger_dir: Optional[str] = None) -> None:
    """kind="kcert" verdict-count record: ledger_gate drift-checks the
    pass count the same way it bands perf (a dropped rule or kernel
    shows up as a count regression)."""
    from arrow_matrix_tpu.ledger.store import record as ledger_record

    counts = manifest.get("counts") or {}
    ledger_record(
        "kcert", "rules_pass", float(counts.get("rules_pass", 0)),
        directory=ledger_dir, unit="count", host_load=None,
        knobs={"kernels": counts.get("kernels", 0),
               "points": counts.get("points", 0)},
        payload={"findings": counts.get("findings", 0),
                 "ok": bool(manifest.get("ok"))})


def run_kernels(out_path: str = DEFAULT_MANIFEST,
                write: bool = True,
                ledger_dir: Optional[str] = None,
                record: bool = False) -> dict:
    """Certify every registered kernel; return (and write) the
    manifest."""
    manifest = build_manifest(certify_all())
    if write:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if record:
        _record_ledger(manifest, ledger_dir=ledger_dir)
    return manifest


# ---------------------------------------------------------------------------
# Tune-candidate certification (the pruning hook)
# ---------------------------------------------------------------------------


def certify_candidate_opts(kernel_opts: Optional[dict], k: int, *,
                           interpret: bool = False,
                           feature_dtype=None,
                           m_t: int = 8) -> Optional[str]:
    """Certify one tune candidate's pallas_sell options BEFORE any
    child process spawns: returns ``None`` when the concretized call
    meta proves out under KC1-KC5, else a ``"kcert: ..."`` prune
    reason.  ``m_t`` is a representative tier width (certification is
    shape-generic in m_t: the meta arithmetic scales linearly)."""
    from arrow_matrix_tpu.ops import pallas_sell as ps

    cc = ps.KERNEL_CONTRACT
    opts = dict(kernel_opts or {})
    stream = not interpret
    if stream and not cc.supports_k(k):
        return (f"kcert: streaming pallas_sell needs k % "
                f"{cc.stream_k_multiple} == 0 and k | {cc.line_k} on "
                f"chip (k={k})")
    if feature_dtype is None:
        feature_dtype = opts.get("feature_dtype")
    try:
        carriage, _dt = ps.resolve_carriage_dtype(feature_dtype)
    except ValueError as exc:
        return f"kcert: {exc}"
    if carriage not in cc.carriage_dtypes:
        return (f"kcert: carriage dtype {carriage!r} outside the "
                f"contract ({cc.carriage_dtypes})")

    def _point(rb, wave, ring, budget, pt_carriage, pt_m_t):
        # Mimic the runtime's rb/wave normalization; ring and budgets
        # are taken literally (they are what the plan executes with).
        rb = ps._tier_row_block(1 << 30, int(rb),
                                ps.out_rows_per_line(k, pt_carriage))
        w = min(int(wave), rb)
        while w > 1 and rb % w:
            w -= 1
        try:
            meta = ps.slab_call_meta(
                pt_m_t, ps.slab_rows(pt_m_t, rb, budget), k, rb, True,
                stream, w, int(ring), carriage=pt_carriage)
        except (ValueError, ZeroDivisionError) as exc:
            return f"kcert: {exc}"
        findings = check_meta(meta)
        if findings:
            f0 = findings[0]
            return f"kcert: {f0.rule}: {f0.message}"
        return None

    schedule = opts.get("schedule")
    if schedule:
        # graft-synth per-level schedule: certify EVERY tier's
        # concretized point with its own knobs and realized slot width
        # — one uncertifiable tier prunes the whole candidate.
        try:
            sched = ps._schedule_overrides(schedule)
        except (ValueError, TypeError) as exc:
            return f"kcert: {exc}"
        for t in sorted(sched):
            ov = sched[t]
            pt_c = ov.get("carriage", carriage)
            if pt_c == "int8":
                return (f"kcert: tier {t}: per-tier int8 carriage is "
                        f"not schedulable (whole-call quantization)")
            if pt_c not in cc.carriage_dtypes:
                return (f"kcert: tier {t}: carriage {pt_c!r} outside "
                        f"the contract ({cc.carriage_dtypes})")
            why = _point(
                ov.get("row_block", opts.get("row_block",
                                             ps.DEFAULT_ROW_BLOCK)),
                ov.get("wave", opts.get("wave", ps.DEFAULT_WAVE)),
                ov.get("ring", opts.get("ring", ps.DEFAULT_RING)),
                ov.get("smem_cols_budget", opts.get("smem_cols_budget")),
                pt_c, int(ov.get("m_t", m_t)) or m_t)
            if why is not None:
                return f"kcert: tier {t}: {why[len('kcert: '):]}"
        return None
    return _point(opts.get("row_block", ps.DEFAULT_ROW_BLOCK),
                  opts.get("wave", ps.DEFAULT_WAVE),
                  opts.get("ring", ps.DEFAULT_RING),
                  opts.get("smem_cols_budget"), carriage, m_t)


# ---------------------------------------------------------------------------
# Fixtures + selftest
# ---------------------------------------------------------------------------


def fixture_contract(path: str) -> str:
    """Expected rule for a planted-broken-kernel fixture, from its
    ``kcN_*.py`` filename."""
    base = os.path.basename(path)
    for rule in RULE_IDS:
        if base.lower().startswith(rule.lower() + "_"):
            return rule
    raise ValueError(
        f"fixture {base!r} does not follow the kcN_<slug>.py "
        f"convention")


def certify_paths(paths: Sequence[str]) -> List[Finding]:
    """Certify arbitrary kernel files: literal ``META``/``METAS``
    assignments go through the meta checks, the source through the
    KC3/KC4 AST pass (with stream points read off the metas)."""
    findings: List[Finding] = []
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        metas: List[dict] = []
        try:
            tree = ast.parse(src)
        except SyntaxError as exc:
            findings.append(Finding(
                "KC1", os.path.basename(path), "source",
                f"unparseable kernel source: {exc}"))
            continue
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and
                    t.id in ("META", "METAS") for t in node.targets):
                try:
                    val = ast.literal_eval(node.value)
                except ValueError:
                    findings.append(Finding(
                        "KC1", os.path.basename(path),
                        f"line {node.lineno}",
                        "META must be a pure literal"))
                    continue
                metas.extend(val if isinstance(val, list) else [val])
        for meta in metas:
            findings.extend(check_meta(meta))
        findings.extend(analyze_kernel_source(
            src, path=path, stream_points=stream_points_of(metas)))
    return findings


def verify_fixture(path: str) -> Tuple[bool, str]:
    """(ok, detail): the fixture must fire its expected rule."""
    expected = fixture_contract(path)
    findings = certify_paths([path])
    fired = sorted({f.rule for f in findings})
    if expected in fired:
        return True, (f"{os.path.basename(path)}: {expected} fired "
                      f"({len(findings)} finding(s))")
    return False, (f"{os.path.basename(path)}: expected {expected}, "
                   f"got {fired or 'nothing'}")


_SELFTEST_GOOD_META = {
    "kernel": "selftest_sell", "kind": "sell_stream",
    "grid": [["i", 4]],
    "out": {"shape": [128, 128], "block": [32, 128],
            "index": ["i", 0], "itemsize": 4},
    "ins": [
        {"name": "cols_vmem", "shape": [8, 1024], "block": [8, 256],
         "index": [0, "i"], "space": "vmem", "itemsize": 4},
        {"name": "weights", "shape": [1, 1024], "block": [1, 256],
         "index": [0, "i"], "space": "vmem", "itemsize": 4},
        {"name": "x_packed", "shape": [512, 128], "block": None,
         "index": None, "space": "any", "itemsize": 4},
    ],
    "smem": {"name": "cols_prefetch", "bytes": 32768,
             "budget": 1048576, "single_block": False},
    "scratch": [{"name": "dma_scratch", "shape": [256, 128],
                 "itemsize": 4}],
    "sems": {"shape": [2, 16]},
    "vmem_budget": 8388608,
    "accum_dtype": "f32",
    "carriage_dtype": "f32",
    "revisit_axes": [],
    "stream": {"ring": 2, "wave": 16, "n_waves": 16,
               "row_block": 256, "granule": 8, "slab": 1024,
               "m_t": 8, "lines": 512, "table_rows": 4096},
}


def _broken_meta(**patch) -> dict:
    import copy

    meta = copy.deepcopy(_SELFTEST_GOOD_META)
    for key, val in patch.items():
        if isinstance(val, dict) and isinstance(meta.get(key), dict):
            meta[key].update(val)
        else:
            meta[key] = val
    return meta


_SELFTEST_BROKEN_METAS = {
    # grid x block overruns the out rows AND the slab arithmetic.
    "KC1": _broken_meta(grid=[["i", 5]]),
    # 32 MB scratch against the 8 MB budget.
    "KC2": _broken_meta(scratch=[{"name": "dma_scratch",
                                  "shape": [4096, 2048],
                                  "itemsize": 4}]),
    # sem ring narrower than declared: slot aliasing in range.
    "KC3": _broken_meta(sems={"shape": [1, 16]}),
    # narrow accumulator declared.
    "KC4": _broken_meta(accum_dtype="bf16"),
    # grid covers 3 of 4 output blocks.
    "KC5": _broken_meta(grid=[["i", 3]],
                        stream={"slab": 768},
                        smem={"bytes": 24576},
                        out={"shape": [96, 128]},
                        ins=[
                            {"name": "cols_vmem", "shape": [8, 768],
                             "block": [8, 256], "index": [0, "i"],
                             "space": "vmem", "itemsize": 4},
                            {"name": "weights", "shape": [1, 768],
                             "block": [1, 256], "index": [0, "i"],
                             "space": "vmem", "itemsize": 4},
                            {"name": "x_packed", "shape": [512, 128],
                             "block": None, "index": None,
                             "space": "any", "itemsize": 4},
                        ]),
}

# KC5 twin: out shape [96,128] tiles into 3 blocks but grid covers 3 —
# make the gap real by keeping 4 blocks of output with a 3-step grid.
_SELFTEST_BROKEN_METAS["KC5"]["out"] = {
    "shape": [128, 128], "block": [32, 128], "index": ["i", 0],
    "itemsize": 4}

_SELFTEST_GOOD_SOURCE = '''
def kernel_stream(cols_smem, x_any, out_ref, scratch, sems):
    def copy(j, w, r):
        rr = w * wave + r
        g = cols_smem[j, rr]
        return pltpu.make_async_copy(
            x_any.at[g], scratch.at[rr], sems.at[w % ring, r])

    def issue(j, w):
        jax.lax.fori_loop(
            0, wave, lambda r, _: (copy(j, w, r).start(), 0)[1], 0)

    def wait(j, w):
        jax.lax.fori_loop(
            0, wave, lambda r, _: (copy(j, w, r).wait(), 0)[1], 0)

    def slot_body(j, acc):
        for p in range(min(ring - 1, n_waves)):
            issue(j, p)

        def wave_body(w, carry):
            @pl.when(w + ring - 1 < n_waves)
            def _():
                issue(j, w + ring - 1)
            wait(j, w)
            return carry

        jax.lax.fori_loop(0, n_waves, wave_body, 0)
        return acc + jnp.zeros((8, 16), dtype=jnp.float32)

    out_ref[...] = slot_body(0, 0)
'''

_SELFTEST_BROKEN_SOURCES = {
    "KC3": _SELFTEST_GOOD_SOURCE.replace(
        "(copy(j, w, r).wait(), 0)[1]", "0"),
    "KC4": _SELFTEST_GOOD_SOURCE.replace(
        "dtype=jnp.float32", "dtype=jnp.bfloat16"),
}


def selftest() -> Tuple[bool, List[str]]:
    """Inline good/broken twins — host-only, no jax import, runnable
    from any cwd (the doctor KCERT probe's first half)."""
    lines: List[str] = []
    ok = True

    good = check_meta(_SELFTEST_GOOD_META)
    if good:
        ok = False
        lines.append("selftest GOOD meta produced findings: " +
                     "; ".join(f.format() for f in good))
    else:
        lines.append("good meta clean")
    for rule, meta in sorted(_SELFTEST_BROKEN_METAS.items()):
        fired = {f.rule for f in check_meta(meta)}
        if rule not in fired:
            ok = False
            lines.append(f"selftest broken meta for {rule} did not "
                         f"fire (got {sorted(fired) or 'nothing'})")
        else:
            lines.append(f"{rule} fires on its broken meta")

    pts = [(2, 16, 16), (1, 8, 8), (4, 16, 16)]
    good_src = analyze_kernel_source(_SELFTEST_GOOD_SOURCE,
                                     "<good>", stream_points=pts)
    if good_src:
        ok = False
        lines.append("selftest GOOD source produced findings: " +
                     "; ".join(f.format() for f in good_src))
    else:
        lines.append("good source clean (schedule recognized + "
                     "simulated at 3 ring points)")
    for rule, src in sorted(_SELFTEST_BROKEN_SOURCES.items()):
        fired = {f.rule for f in analyze_kernel_source(
            src, f"<broken-{rule}>", stream_points=pts)}
        if rule not in fired:
            ok = False
            lines.append(f"selftest broken source for {rule} did not "
                         f"fire (got {sorted(fired) or 'nothing'})")
        else:
            lines.append(f"{rule} fires on its broken source")

    # The ring simulator itself must reject a broken schedule: issue
    # distance ring+1 reuses a slot while in flight.
    sim = simulate_ring(1, 8, 4)
    if sim:
        ok = False
        lines.append("simulator rejected the serial ring=1 schedule")
    else:
        lines.append("simulator accepts ring=1..4 canonical "
                     "schedules")
    return ok, lines


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _print_manifest(manifest: dict) -> None:
    for k in manifest["kernels"]:
        for rule in RULE_IDS:
            v = k["rules"][rule]
            mark = "ok  " if v["status"] == "pass" else "FAIL"
            print(f"[{mark}] {k['name']} {rule}: {v['detail']}")
    counts = manifest["counts"]
    print(f"kernels: {counts['kernels']}  points: {counts['points']}  "
          f"rule verdicts passing: {counts['rules_pass']}/"
          f"{counts['kernels'] * len(RULE_IDS)}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="graft_kcert", description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_MANIFEST)
    ap.add_argument("--check", action="store_true",
                    help="do not write; fail on any violation OR "
                         "drift against the checked-in manifest")
    ap.add_argument("--selftest", action="store_true",
                    help="run the inline good/broken twins (host-"
                         "only) and exit")
    ap.add_argument("--fixture", action="append", default=[],
                    help="verify a planted-broken-kernel fixture "
                         "fires its expected rule (repeatable)")
    ap.add_argument("--paths", nargs="+", default=None,
                    help="certify these kernel files and exit "
                         "nonzero on any finding")
    ap.add_argument("--ledger", default=None,
                    help="also append the kind=kcert verdict-count "
                         "record to this ledger directory")
    args = ap.parse_args(argv)

    if args.selftest:
        ok, lines = selftest()
        for ln in lines:
            print(ln)
        print("selftest passed" if ok else "SELFTEST FAILED")
        return 0 if ok else 1

    if args.fixture:
        rc = 0
        for path in args.fixture:
            ok, detail = verify_fixture(path)
            print(("ok   " if ok else "FAIL ") + detail)
            rc = rc or (0 if ok else 1)
        return rc

    if args.paths:
        findings = certify_paths(args.paths)
        for f in findings:
            print(f.format())
        if findings:
            print(f"kcert: {len(findings)} finding(s) in "
                  f"{len(args.paths)} file(s)", file=sys.stderr)
            return 1
        print("kcert: paths certify clean", file=sys.stderr)
        return 0

    manifest = run_kernels(out_path=args.out, write=not args.check,
                           ledger_dir=args.ledger,
                           record=bool(args.ledger))
    _print_manifest(manifest)

    rc = 0 if manifest["ok"] else 1
    if args.check:
        try:
            with open(args.out, encoding="utf-8") as fh:
                checked_in = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"no readable checked-in manifest at {args.out}: "
                  f"{e}")
            return 1
        drift = manifest_drift(checked_in, manifest)
        for d in drift:
            print(f"drift: {d}")
        if drift:
            print(f"kernel drift against {args.out} — rerun `python "
                  f"-m arrow_matrix_tpu.analysis kernels` and commit "
                  f"the refreshed manifest")
            rc = 1
    else:
        print(f"manifest: {args.out}")
    print("kernel certification passed" if rc == 0
          else "KERNEL CERTIFICATION FAILED")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
