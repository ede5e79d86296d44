"""The work of one iteration ``Y := A @ X``, from A's shape alone.

Counted from ``nnz``, ``n`` and ``k`` and never from the program's
slots, tiers or levels, so the count stays the same whatever
implements the product.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def compulsory_bytes(nnz: int, n: int, k: int, itemsize: int) -> int:
    """A's column indices once (int32; the graphs are unweighted, so
    values are implicit), X read once and Y written once."""
    return 4 * nnz + 2 * n * k * itemsize


def flops(nnz: int, k: int) -> int:
    """One multiply and one add per stored entry and feature column."""
    return 2 * nnz * k


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """Published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path} (known: {sorted(table)})")
    return table[device_kind]


def roofline_seconds(nnz: int, n: int, k: int, itemsize: int,
                     peak: dict) -> tuple[float, str]:
    """Least time one iteration can take on a chip with ``peak``, and
    which bound sets it ("bytes" or "flops")."""
    t_bytes = compulsory_bytes(nnz, n, k, itemsize) / peak["hbm_bytes_per_s"]
    t_flops = flops(nnz, k) / peak["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
