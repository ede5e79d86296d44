"""Canonical structure fingerprint + hash (graft-tune).

A tuned plan is only reusable if the thing it was tuned FOR can be
named.  This module names it: a deterministic fingerprint of the
decomposition's *structure* — per-level rows/nnz/arrow widths, the
folded degree ladder at the requested tier split, the slot histogram,
and the tier imbalance scalars (``obs/imbalance.summarize_units``) —
hashed to a short hex key.  Everything is derived from the levels on
the host with numpy only; no executor is built and no device is
touched, so the hash is cheap enough to compute at every
``plan="auto"`` construction.

Invariances (pinned by tests/test_tune.py):

* re-decomposing the same graph with the same seed → same hash
  (the fingerprint reads structure, not object identity or memory
  layout);
* a save/load round trip through ``io/graphio.py`` artifacts → same
  hash (CSR vs CsrLike-triplet levels fingerprint identically);
* different width, tier split (growth/align), or dtype → different
  hash (those change the packed operator, so plans must not cross).

The hash deliberately does NOT include the feature width ``k``: the
operator is k-independent, so one plan file carries per-k entries
(see ``tune/plan.py``).
"""

from __future__ import annotations

import hashlib
import json
from typing import List, Optional

import numpy as np

#: Bump when the fingerprint schema changes — a hash from another
#: version must never silently collide with the current one.
FINGERPRINT_VERSION = 1


def _per_level_degrees(matrix) -> np.ndarray:
    """Per-row nnz of one level matrix (CSR or CsrLike triplet)."""
    from scipy import sparse

    if isinstance(matrix, sparse.csr_matrix):
        indptr = matrix.indptr
    else:
        indptr = matrix[2]
    return np.diff(np.asarray(indptr, dtype=np.int64))


def folded_total_rows(levels, width: int) -> int:
    """The shared flat row count of the single-chip (mesh=None) build
    — the same derivation ``MultiLevelArrow.__init__`` performs, so
    the fingerprint's ladder is computed over exactly the rows the
    executor packs."""
    from arrow_matrix_tpu.io.graphio import number_of_blocks
    from arrow_matrix_tpu.parallel.mesh import pad_to_multiple

    widths = []
    for i, lvl in enumerate(levels):
        is_last = i == len(levels) - 1
        if lvl.arrow_width > width or is_last:
            widths.append(-(-lvl.arrow_width // width) * width)
        else:
            widths.append(width)
    unit = max(widths)
    max_rows = max(number_of_blocks(lvl.matrix, w) * w
                   for lvl, w in zip(levels, widths))
    return pad_to_multiple(max_rows, unit)


def folded_degrees(levels, total: int) -> np.ndarray:
    """Per-row nnz of the folded operator in level-0 order: every
    level's row degrees routed through the same
    ``inv_perm0[pad_permutation(perm)]`` coordinate map the fold uses
    (``MultiLevelArrow._init_folded``), summed.  Levels are
    edge-disjoint, so the sum IS the folded degree."""
    from arrow_matrix_tpu.parallel.multi_level import pad_permutation

    perms = [pad_permutation(np.asarray(lvl.permutation), total)
             for lvl in levels]
    inv_perm0 = np.argsort(perms[0])
    deg = np.zeros(total, dtype=np.int64)
    for lvl, p in zip(levels, perms):
        mp = inv_perm0[p]
        ld = np.zeros(total, dtype=np.int64)
        d = _per_level_degrees(lvl.matrix)
        ld[:d.size] = d
        deg[mp] += ld
    return deg


def structure_fingerprint(levels, width: int, dtype=np.float32,
                          growth: float = 1.2,
                          slot_align: Optional[int] = None,
                          binary="auto") -> dict:
    """The canonical structure record the hash is taken over.  All
    values are plain python ints/floats/strings (JSON-stable); floats
    that come from ratios are rounded so bit-level numpy noise cannot
    split a hash."""
    from arrow_matrix_tpu.io.graphio import num_rows
    from arrow_matrix_tpu.obs.imbalance import summarize_units
    from arrow_matrix_tpu.ops.sell import fold_tiers
    from arrow_matrix_tpu.parallel.multi_level import (
        resolve_block_dtype,
        resolve_levels_binary,
    )

    if slot_align is None:
        slot_align = 1
    dtype = resolve_block_dtype(dtype)
    total = folded_total_rows(levels, width)
    deg = folded_degrees(levels, total)

    # The exact ladder the SELL packer builds (ops/sell.fold_tiers).
    sorted_deg = np.sort(deg, kind="stable")
    aligned, starts = fold_tiers(sorted_deg, growth, slot_align)
    starts = starts + [total]
    tier_rows, tier_nnz, tier_slots, tier_width = [], [], [], []
    for lo, hi in zip(starts[:-1], starts[1:]):
        m_t = int(aligned[hi - 1]) if hi > lo else 0
        tier_rows.append(int(hi - lo))
        tier_nnz.append(int(sorted_deg[lo:hi].sum()))
        tier_slots.append(m_t * (hi - lo))
        tier_width.append(m_t)

    # Slot histogram: distinct aligned degrees and their row counts —
    # the padded-gather cost surface the tier split carves up.
    vals, counts = np.unique(aligned, return_counts=True)

    imb = summarize_units(tier_rows, tier_nnz, tier_slots, units="tier")

    def _r(v):
        return None if v is None else round(float(v), 6)

    levels_fp = []
    for lvl in levels:
        d = _per_level_degrees(lvl.matrix)
        levels_fp.append({
            "rows": int(num_rows(lvl.matrix)),
            "nnz": int(d.sum()),
            "arrow_width": int(lvl.arrow_width),
        })

    return {
        "version": FINGERPRINT_VERSION,
        "n": int(num_rows(levels[0].matrix)),
        "total_rows": int(total),
        "width": int(width),
        "dtype": np.dtype(dtype).name,
        "binary": bool(resolve_levels_binary(levels, binary)),
        "growth": round(float(growth), 6),
        "slot_align": int(slot_align),
        "levels": levels_fp,
        "ladder": {
            "tier_starts": [int(s) for s in starts[:-1]],
            "rows": tier_rows,
            "nnz": tier_nnz,
            "slots": tier_slots,
            "slot_width": tier_width,
        },
        "slot_hist": {
            "deg": [int(v) for v in vals],
            "count": [int(c) for c in counts],
        },
        "imbalance": {
            "nnz_max_over_mean": _r(imb["nnz_max_over_mean"]),
            "rows_max_over_mean": _r(imb["rows_max_over_mean"]),
            "padded_slot_waste": _r(imb["padded_slot_waste"]),
        },
    }


def fingerprint_hash(fp: dict) -> str:
    """sha256 over the canonical JSON encoding, truncated to 16 hex
    chars — the plan-cache file name."""
    blob = json.dumps(fp, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def structure_hash(levels, width: int, dtype=np.float32,
                   growth: float = 1.2,
                   slot_align: Optional[int] = None,
                   binary="auto") -> str:
    """Fingerprint + hash in one call (the common consumer path)."""
    return fingerprint_hash(structure_fingerprint(
        levels, width, dtype=dtype, growth=growth,
        slot_align=slot_align, binary=binary))
