"""graft-synth: structure-JIT kernel synthesis (ROADMAP item 3).

The tune layer raced a FIXED menu of hand-written configurations
(``tune/space.py``) while graft-lens proved the cost is per-level
heterogeneous — on the committed ba_256_3 point the entire bf16-vs-f32
gap lands on the L0 tail tier as decode/accumulate, not bytes.  This
module closes the loop the JITSPMM way (arxiv 2312.05639: row-block
specialization derived from the sparsity structure; arxiv 1705.10218:
schedule parameters priced per structure, not globally): it reads the
degree-ladder fingerprint and *derives* a per-level Pallas schedule —
head levels get dense-ish wide-row-block / shallow-ring tilings, tail
levels get scatter-ish narrow-row-block / deep-ring tilings — instead
of choosing among uniform knob settings.

A synthesized schedule is a parameterized program over the existing
meta-first builders (``ops/pallas_sell.slab_call_meta`` et al.), never
new kernel source: the per-tier overrides flow through
``sell_spmm_t_pallas(schedule=...)`` into the SAME certified
``sell_tier_spmm_packed`` slab calls.  The pipeline a generated
program rides, end to end:

* :func:`synth_candidates` emits candidates into the race through
  ``enumerate_candidates(extra=...)`` — screened by the graft-lens
  cost model (per-level predictions, 3x rule) and certified KC1-KC5
  (``analysis/kernels.certify_candidate_opts`` walks every schedule
  entry) BEFORE any child spawns;
* the subprocess-isolated harness races survivors under the unchanged
  f32 bit-identity win rule (an all-f32 per-level schedule changes the
  slab partitioning, never the per-row accumulation order, so it CAN
  be bitwise-exact against the golden fold path);
* the winner persists in the TunePlan cache keyed by structure hash —
  a second search on an unchanged structure is a pure hit with ZERO
  children (PR 10's promise, now covering generated programs);
* :func:`persist_program` writes the synthesized program into the
  committed store (``bench_cache/synth_programs.json``) and
  ``ops/kernel_contract.registered_kernels()`` lazily re-registers it
  via :func:`register_persisted_programs`, so graft-kcert certifies
  generated programs in every process, manifest-drift-gated like the
  hand-written builders.

This module is import-light on purpose (no jax at import time): the
kernel-contract registry must stay loadable host-only, and the metas /
witness callables import ``ops/pallas_sell`` lazily.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Dict, List, Optional, Tuple

from arrow_matrix_tpu.ops.kernel_contract import (
    KernelContract,
    KernelEntry,
    register_kernel,
)

STORE_VERSION = 1

#: Degree-ladder family bands (slot width w = realized tier m_t), the
#: SAME bands obs/costmodel.tier_family prices with.
TAIL_WIDTH = 8
MID_WIDTH = 64

#: Per-family schedule policy: (row_block, wave, ring, slab_blocks).
#: Tail tiers are scatter-ish — short rows mean each wave moves few
#: bytes, so keep the VMEM tile narrow, the DMA ring deep (latency
#: hiding over bandwidth), and the slab short; head tiers are dense-ish
#: — wide rows amortize the launch, so widen the tile, keep the ring
#: shallow, and let the slab grow to the full slab budget
#: (slab_blocks=None).
FAMILY_POLICY: Dict[str, Tuple[int, int, int, Optional[int]]] = {
    "tail": (64, 8, 4, 4),
    "mid": (128, 8, 3, 8),
    "head": (256, 16, 2, None),
}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: The committed generated-program store.  ``AMT_SYNTH_STORE`` is the
#: test/override hook; the default is repo-anchored so certification
#: finds the same programs from any working directory.
DEFAULT_STORE_PATH = os.path.join(_REPO_ROOT, "bench_cache",
                                  "synth_programs.json")


def store_path(path: Optional[str] = None) -> str:
    if path is not None:
        return path
    return os.environ.get("AMT_SYNTH_STORE", DEFAULT_STORE_PATH)


def ladder_family(width: int) -> str:
    """The degree-ladder band of one tier's slot width — mirrors
    ``obs/costmodel.tier_family`` ("zero" handled by the caller: a
    zero-width tier launches no kernel)."""
    if width <= TAIL_WIDTH:
        return "tail"
    if width <= MID_WIDTH:
        return "mid"
    return "head"


def synthesize_schedule(fp: dict, *,
                        carriage_policy: str = "exact") -> List[dict]:
    """Derive the per-level schedule from a structure fingerprint's
    degree ladder.  Returns a list of per-tier entries (the
    ``sell_spmm_t_pallas(schedule=...)`` / TunePlan payload), each
    carrying the synthesis provenance (``m_t``, ``rows``, ``family``)
    alongside the runtime knobs.

    ``carriage_policy="exact"`` keeps every tier f32 (the schedule can
    win at f32 bit-identity); ``"mixed"`` narrows byte-dominated
    head/mid tiers to bf16 while keeping decode-dominated tail tiers
    f32 — exactly the graft-lens ba_256_3 attribution finding (the
    bf16 penalty lives on the tail tier).
    """
    if carriage_policy not in ("exact", "mixed"):
        raise ValueError(f"unknown carriage policy {carriage_policy!r}")
    ladder = fp["ladder"]
    widths = [int(w) for w in ladder["slot_width"]]
    rows = [int(r) for r in ladder["rows"]]
    schedule: List[dict] = []
    for t, (w, r) in enumerate(zip(widths, rows)):
        if w < 1 or r < 1:
            continue        # zero-degree prefix: no kernel launch
        fam = ladder_family(w)
        row_block, wave, ring, slab_blocks = FAMILY_POLICY[fam]
        if slab_blocks is None:
            budget = None   # full slab budget: long slabs
        else:
            # Bound the slab to ``slab_blocks`` row blocks of cols
            # (int32: m_t * 4 B per row) — slab_rows() floors at one
            # block, so a tiny budget still streams.
            budget = w * 4 * row_block * slab_blocks
        carriage = "f32"
        if carriage_policy == "mixed" and fam != "tail":
            carriage = "bf16"
        entry = {"tier": t, "m_t": w, "rows": r, "family": fam,
                 "row_block": row_block, "wave": wave, "ring": ring,
                 "carriage": carriage}
        if budget is not None:
            entry["smem_cols_budget"] = budget
        schedule.append(entry)
    return schedule


def schedule_summary(schedule: List[dict]) -> str:
    """One-line human summary: ``L1:head rb256/r2 ...``."""
    return " ".join(
        f"L{e['tier']}:{e['family']} rb{e['row_block']}/r{e['ring']}"
        + ("/" + e["carriage"] if e.get("carriage", "f32") != "f32"
           else "")
        for e in schedule)


def program_name(structure_hash: str) -> str:
    return f"pallas_synth_{structure_hash[:8]}"


def synth_candidates(fp: dict, *, traffic_class: str = "exact",
                     interpret: bool = False) -> List[Any]:
    """The generated candidates for one fingerprint, ready for
    ``enumerate_candidates(extra=...)``:

    * ``synth_ladder`` — the all-f32 per-level schedule; exact-class
      eligible (bit-identity is preserved: per-tier knobs repartition
      slabs, the per-row accumulation order is unchanged);
    * ``synth_ladder_mixed`` — bf16 on byte-dominated head/mid tiers,
      f32 on decode-dominated tail tiers; approx-class eligible only,
      raced alongside ``pallas_sell_bf16``.

    Uniform-knob structures (a one-tier ladder) still synthesize — the
    value is that NOTHING here is hand-enumerated; the menu shrinks to
    a fallback.
    """
    from arrow_matrix_tpu.tune.space import Candidate

    exact = synthesize_schedule(fp, carriage_policy="exact")
    if not exact:
        return []
    approx = traffic_class == "approx"
    out = [Candidate(
        "synth_ladder",
        build={"kernel": "pallas_sell"},
        kernel_opts={"schedule": exact},
        note=("generated per-level schedule from the degree ladder: "
              + schedule_summary(exact)))]
    mixed = synthesize_schedule(fp, carriage_policy="mixed")
    if any(e.get("carriage") == "bf16" for e in mixed):
        out.append(Candidate(
            "synth_ladder_mixed",
            build={"kernel": "pallas_sell"},
            kernel_opts={"schedule": mixed},
            eligible=approx,
            note=("generated mixed-carriage schedule (bf16 head/mid, "
                  "f32 tail): " + schedule_summary(mixed)
                  + ("; tolerance-gated winner" if approx else
                     "; diagnostic (never f32 bit-identical)"))))
    return out


# ---------------------------------------------------------------------------
# Persistence: the committed generated-program store
# ---------------------------------------------------------------------------


def load_store(path: Optional[str] = None) -> dict:
    p = store_path(path)
    try:
        with open(p, encoding="utf-8") as fh:
            doc = json.load(fh)
    except FileNotFoundError:
        return {"version": STORE_VERSION, "programs": {}}
    if not isinstance(doc, dict) or "programs" not in doc:
        raise ValueError(f"synth store {p!r} is not a program store")
    if int(doc.get("version", -1)) != STORE_VERSION:
        raise ValueError(
            f"synth store version skew: {p!r} carries "
            f"{doc.get('version')!r}, this build reads {STORE_VERSION}")
    return doc


def synth_program_record(fp: dict, structure_hash: str, k: int,
                         schedule: List[dict]) -> dict:
    """The store record of one generated program.  Budgets and lane
    constants are captured at persist time so host-only loads rebuild
    the KernelContract without importing jax."""
    from arrow_matrix_tpu.ops import pallas_sell as ps

    return {
        "structure_hash": structure_hash,
        "k": int(k),
        "n": int(fp["n"]),
        "binary": bool(fp["binary"]),
        "schedule": [dict(e) for e in schedule],
        "granule": ps.GRANULE,
        "stream_k_multiple": ps.STREAM_K_MULTIPLE,
        "smem_cols_budget": ps.DEFAULT_SMEM_COLS_BUDGET,
        "vmem_budget": ps.KERNEL_CONTRACT.vmem_budget_bytes,
        "summary": schedule_summary(schedule),
    }


def persist_program(fp: dict, structure_hash: str, k: int,
                    schedule: List[dict],
                    path: Optional[str] = None) -> str:
    """Write (merge) one generated program into the store and register
    it in-process; returns the program name.  Read-merge-write with an
    atomic replace — the store is tiny and synth runs are rare, so a
    lost concurrent merge re-synthesizes identically next search."""
    p = store_path(path)
    name = program_name(structure_hash)
    doc = load_store(p)
    doc["version"] = STORE_VERSION
    doc["programs"][name] = synth_program_record(fp, structure_hash, k,
                                                 schedule)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p) or ".",
                               prefix=".synth_", suffix=".json")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, p)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    register_kernel(entry_from_program(name, doc["programs"][name]))
    return name


# ---------------------------------------------------------------------------
# Registration: generated programs as certifiable KernelEntry objects
# ---------------------------------------------------------------------------


def _normalized_points(prog: dict) -> List[dict]:
    """The runtime-normalized (m_t, rows, rb, wave, ring, carriage,
    budget) points of one program — EXACTLY the numbers
    ``sell_tier_spmm_packed`` would execute, so the certified metas and
    the executed calls cannot drift (the meta-first discipline)."""
    from arrow_matrix_tpu.ops import pallas_sell as ps

    default_budget = int(prog["smem_cols_budget"])
    points = []
    for e in prog["schedule"]:
        m_t, rows = int(e["m_t"]), int(e["rows"])
        if m_t < 1 or rows < 1:
            continue
        carriage = e.get("carriage", "f32")
        rb = ps._tier_row_block(
            rows, int(e.get("row_block", 256)),
            ps.out_rows_per_line(int(prog["k"]), carriage))
        w = min(int(e.get("wave", 16)), rb)
        while w > 1 and rb % w:
            w -= 1
        points.append({
            "m_t": m_t, "rows": rows, "row_block": rb, "wave": w,
            "ring": int(e.get("ring", 2)),
            "carriage": carriage,
            "budget": int(e.get("smem_cols_budget", default_budget)),
        })
    return points


def _program_metas(prog: dict) -> List[dict]:
    """Concretized slab-call metas for every per-tier point of one
    generated program (lazy jax import — certification time only)."""
    from arrow_matrix_tpu.ops import pallas_sell as ps

    k = int(prog["k"])
    n = int(prog["n"])
    binary = bool(prog["binary"])
    metas = []
    for pt in _normalized_points(prog):
        n_lines = max(1, -(-n // ps.line_geometry(k, pt["carriage"])[1]))
        rb = pt["row_block"]
        rows_pad = -(-pt["rows"] // rb) * rb
        slab = min(ps.slab_rows(pt["m_t"], rb, pt["budget"]), rows_pad)
        metas.append(ps.slab_call_meta(
            pt["m_t"], slab, k, rb, binary, True, pt["wave"],
            pt["ring"], n_lines=n_lines, carriage=pt["carriage"]))
    return metas


def _program_witness(prog: dict):
    """Boundary-column interpret witness over the program's distinct
    (row_block, wave, ring, carriage) configurations: every slot
    points at the last feature row, streamed and vectorized bodies
    must agree bitwise (the generated-program twin of
    ``pallas_sell.kcert_witness``, at witness scale k=16)."""
    import numpy as np

    import jax.numpy as jnp

    from arrow_matrix_tpu.ops import pallas_sell as ps

    k, n_table = 16, 64
    configs = sorted({(pt["row_block"], pt["wave"], pt["ring"],
                       pt["carriage"])
                      for pt in _normalized_points(prog)})
    if not configs:
        return False, "program has no certifiable schedule points"
    x_t = jnp.asarray(
        np.linspace(-1.0, 1.0, k * n_table, dtype=np.float32)
        .reshape(k, n_table))
    try:
        for rb, wave, ring, carriage in configs:
            rows, m_t = min(rb, 32), 3
            cols = jnp.full((m_t, rows), n_table - 1, dtype=jnp.int32)
            deg = jnp.full((rows,), m_t, dtype=jnp.int32)
            x_packed = ps.pack_features_t(x_t, carriage)
            vec = ps.sell_tier_spmm_packed(
                cols, x_packed, k, deg=deg, stream=False,
                interpret=True, row_block=rb, wave=wave,
                feature_dtype=carriage)
            st = ps.sell_tier_spmm_packed(
                cols, x_packed, k, deg=deg, stream=True, interpret=True,
                row_block=rb, wave=wave, ring=ring,
                feature_dtype=carriage)
            if not np.array_equal(np.asarray(vec), np.asarray(st)):
                return False, (f"stream/vectorized mismatch at rb={rb}"
                               f" wave={wave} ring={ring} "
                               f"({carriage})")
            if not np.isfinite(np.asarray(st)).all():
                return False, f"non-finite boundary output (rb={rb})"
    except Exception as exc:   # a raise IS the out-of-bounds evidence
        return False, f"boundary interpret run raised: {exc!r}"
    return True, (f"{len(configs)} schedule config(s): boundary-column "
                  f"interpret round trip ok (stream==vectorized)")


def entry_from_program(name: str, prog: dict) -> KernelEntry:
    """A generated program as a certifiable :class:`KernelEntry`.  The
    contract envelope is derived from the stored schedule; the source
    under KC3/KC4 AST review is the REAL ring-schedule builder
    (``ops/pallas_sell.py``) the program parameterizes."""
    points = _normalized_points(prog)
    contract = KernelContract(
        name=name,
        module="arrow_matrix_tpu.tune.synth",
        kind="sell_stream",
        granule=int(prog["granule"]),
        stream_k_multiple=int(prog["stream_k_multiple"]),
        row_blocks=tuple(sorted({pt["row_block"] for pt in points})),
        rings=tuple(sorted({pt["ring"] for pt in points})),
        waves=tuple(sorted({pt["wave"] for pt in points})),
        ks=(int(prog["k"]),),
        carriage_dtypes=tuple(sorted({pt["carriage"]
                                      for pt in points})),
        accum_dtype="f32",
        smem_cols_budget=int(prog["smem_cols_budget"]),
        vmem_budget_bytes=int(prog["vmem_budget"]),
    )

    def _source_path():
        from arrow_matrix_tpu.ops import pallas_sell as ps

        return ps.__file__

    return KernelEntry(
        contract=contract,
        metas=lambda: _program_metas(prog),
        source_path=os.path.join(
            os.path.dirname(os.path.abspath(__file__)), os.pardir,
            "ops", "pallas_sell.py"),
        witness=lambda: _program_witness(prog),
    )


def register_persisted_programs(path: Optional[str] = None) -> List[str]:
    """Register every program in the store; returns the names (empty
    when the store is absent).  Called lazily by
    ``kernel_contract.registered_kernels()`` so generated programs ride
    certification in every process that looks at the registry."""
    doc = load_store(path)
    names = []
    for name in sorted(doc["programs"]):
        register_kernel(entry_from_program(name, doc["programs"][name]))
        names.append(name)
    return names
