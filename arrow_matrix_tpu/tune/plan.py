"""Versioned, persisted tuning plans (graft-tune).

A :class:`TunePlan` is ONE planned configuration: every knob the
executors previously took as independent arguments — format / tier
split, kernel choice, chunking, carriage dtype, and the fused kernel's
``row_block`` / ``wave`` / ``smem_cols_budget`` / ``ring`` — plus the
provenance that justifies
it (measured ms vs the default, margin, bit-identity verdict,
host-load context, evaluator platform).

Plans persist as one JSON file per structure hash under
``bench_cache/tune_plans/`` (override: ``AMT_TUNE_PLAN_DIR``), with
per-feature-width entries::

    {"version": 2, "structure_hash": "...",
     "fingerprint": {...}, "plans": {"16": {...}, "128": {...}}}

Consumption contract (wired through ``MultiLevelArrow`` /
``SellSlim`` / ``SellMultiLevel`` ``plan="auto"`` and
``serve/scheduler.ArrowServer``): a cache hit applies the knobs with
ZERO search cost; a miss or a version skew falls back to the built-in
defaults LOUDLY — a :class:`TunePlanMiss` warning, never silence —
so an operator can tell a tuned run from an untuned one.
"""

from __future__ import annotations

import dataclasses
import json
import os
import warnings
from dataclasses import dataclass
from typing import Any, Dict, Optional

from arrow_matrix_tpu.utils.artifacts import (
    atomic_write_json,
    locked_file,
)

#: Bump when the TunePlan schema or knob semantics change; a cached
#: plan from another version is a loud miss, never a silent apply.
#: Version 2 dropped the one-chip fold's ``overlap_slabs`` / ``repl``.
PLAN_VERSION = 2

DEFAULT_PLAN_DIR = os.path.join("bench_cache", "tune_plans")


class TunePlanMiss(UserWarning):
    """Raised-as-warning when ``plan="auto"`` finds no usable cached
    plan (no file, no entry for the requested k, or version skew) —
    the executor proceeds on defaults, loudly."""


def plan_dir(override: Optional[str] = None) -> str:
    """The plan-cache directory: explicit override, else
    ``AMT_TUNE_PLAN_DIR``, else ``bench_cache/tune_plans``."""
    if override:
        return override
    return os.environ.get("AMT_TUNE_PLAN_DIR", DEFAULT_PLAN_DIR)


def plan_path(structure_hash: str,
              directory: Optional[str] = None) -> str:
    return os.path.join(plan_dir(directory), f"{structure_hash}.json")


@dataclass(frozen=True)
class TunePlan:
    """One planned configuration for one (structure, k)."""

    structure_hash: str
    k: int
    version: int = PLAN_VERSION

    # --- knobs (executor build arguments) ---
    fmt: str = "fold"
    kernel: str = "xla"
    chunk: Any = "auto"
    fold_growth: float = 1.2
    fold_align: Optional[int] = None       # None -> the executor's own
    feature_dtype: Optional[str] = None    # None -> f32 carriage

    # --- knobs (fused pallas_sell kernel call) ---
    row_block: int = 256
    wave: int = 16
    smem_cols_budget: Optional[int] = None
    ring: int = 2
    #: graft-synth per-level schedule (list of per-tier override
    #: dicts, ``tune/synth.synthesize_schedule`` shape).  None = the
    #: uniform knobs above apply to every tier; when set, the uniform
    #: knobs are the fallback for tiers the schedule doesn't name.
    schedule: Optional[list] = None

    # --- provenance ---
    candidate: str = "default"
    measured_ms: Optional[float] = None
    default_ms: Optional[float] = None
    margin: Optional[float] = None          # (default - measured)/default
    bit_identical: Optional[bool] = None
    host_load: Optional[float] = None
    platform: Optional[str] = None
    evaluator: Optional[str] = None         # e.g. "cpu-interpret"
    created_unix: Optional[float] = None

    # --- accuracy class (graft-classes) ---
    # "exact" plans win on bit-identity (today's contract, and the
    # default every pre-class cached plan file deserializes to);
    # "approx" plans win on the class tolerance and carry their
    # accuracy certificate (arrow_matrix_tpu/classes.py
    # Certificate.to_dict) as provenance.
    traffic_class: str = "exact"
    certificate: Optional[dict] = None

    def build_kwargs(self) -> Dict[str, Any]:
        """Executor construction overrides (``MultiLevelArrow``
        argument names)."""
        return {
            "fmt": self.fmt,
            "kernel": self.kernel,
            "chunk": self.chunk,
            "fold_growth": self.fold_growth,
            "fold_align": self.fold_align,
            "feature_dtype": self.feature_dtype,
        }

    def kernel_opts(self) -> Dict[str, Any]:
        """Per-call knobs of ``ops/pallas_sell.sell_spmm_t_pallas``."""
        opts = {
            "row_block": self.row_block,
            "wave": self.wave,
            "smem_cols_budget": self.smem_cols_budget,
            "ring": self.ring,
        }
        if self.schedule is not None:
            opts["schedule"] = [dict(e) for e in self.schedule]
        return opts

    def exec_config(self):
        """The serving rung this plan corresponds to — the degradation
        ladder (``serve/scheduler.degradation_ladder``) steps any of
        these knobs back down under pressure."""
        from arrow_matrix_tpu.serve.scheduler import ExecConfig

        return ExecConfig(kernel=self.kernel,
                          feature_dtype=self.feature_dtype)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunePlan":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def load_plan_file(structure_hash: str,
                   directory: Optional[str] = None) -> Optional[dict]:
    """The raw plan file for one structure hash, or None when absent
    or unreadable (the caller warns)."""
    path = plan_path(structure_hash, directory)
    try:
        with open(path, encoding="utf-8") as fh:
            d = json.load(fh)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return None
    return d if isinstance(d, dict) else None


def load_plan(structure_hash: str, k: Optional[int] = None,
              directory: Optional[str] = None,
              quiet: bool = False) -> Optional[TunePlan]:
    """The cached :class:`TunePlan` for ``(structure_hash, k)``.

    ``k=None`` selects the largest-k entry (the amortized regime — the
    consumer that doesn't know its feature width yet, e.g. a server
    building its resident executor before the first request).  Any
    miss — no file, version skew, no entry for k — warns
    :class:`TunePlanMiss` (unless ``quiet``) and returns None.
    """
    def _miss(why: str) -> None:
        if not quiet:
            warnings.warn(
                f"tune plan miss for {structure_hash}: {why}; "
                f"falling back to built-in defaults "
                f"(run `graft_tune search` to populate the cache)",
                TunePlanMiss, stacklevel=3)

    d = load_plan_file(structure_hash, directory)
    if d is None:
        _miss(f"no plan file in {plan_dir(directory)!r}")
        return None
    if int(d.get("version", -1)) != PLAN_VERSION:
        _miss(f"version skew (file v{d.get('version')}, "
              f"runtime v{PLAN_VERSION})")
        return None
    plans = d.get("plans") or {}
    if not plans:
        _miss("plan file has no entries")
        return None
    if k is None:
        key = max(plans, key=lambda s: int(s))
    else:
        key = str(int(k))
        if key not in plans:
            _miss(f"no entry for k={k} "
                  f"(cached k: {sorted(int(s) for s in plans)})")
            return None
    entry = dict(plans[key])
    if int(entry.get("version", -1)) != PLAN_VERSION:
        _miss(f"entry version skew for k={key}")
        return None
    return TunePlan.from_dict(entry)


def save_plans(structure_hash: str, plans: Dict[int, TunePlan],
               fingerprint: Optional[dict] = None,
               directory: Optional[str] = None,
               context: Optional[dict] = None) -> str:
    """Merge ``plans`` (one per k) into the structure's plan file,
    atomically; returns the path.  Existing entries for other k values
    are preserved — one file is the whole per-structure cache."""
    d = plan_dir(directory)
    os.makedirs(d, exist_ok=True)
    path = plan_path(structure_hash, directory)
    # The read-merge-write is one critical section under the advisory
    # cross-process lock: atomic_write_json alone keeps readers safe,
    # but two fleet workers merging different k entries concurrently
    # would each rewrite the file from their own stale read and drop
    # the other's entry.
    with locked_file(path):
        existing = load_plan_file(structure_hash, directory)
        merged: Dict[str, dict] = {}
        if existing and int(existing.get("version", -1)) == \
                PLAN_VERSION:
            merged.update(existing.get("plans") or {})
        for k, p in plans.items():
            merged[str(int(k))] = p.to_dict()
        record = {
            "version": PLAN_VERSION,
            "structure_hash": structure_hash,
            "fingerprint": fingerprint,
            "context": context,
            "plans": merged,
        }
        atomic_write_json(path, record, indent=2, sort_keys=True)
    return path


def resolve_plan(plan, *, levels=None, width: Optional[int] = None,
                 dtype=None, growth: float = 1.2,
                 slot_align: Optional[int] = None, binary="auto",
                 plan_k: Optional[int] = None,
                 directory: Optional[str] = None
                 ) -> Optional[TunePlan]:
    """Normalize an executor's ``plan=`` argument to a
    :class:`TunePlan` (or None = defaults, after a loud miss).

    Accepted forms: a TunePlan (version-checked), a plan dict
    (``TunePlan.to_dict`` shape), or the string ``"auto"`` — hash the
    given levels and look the plan up in the cache.
    """
    if plan is None:
        return None
    if isinstance(plan, TunePlan):
        if int(plan.version) != PLAN_VERSION:
            warnings.warn(
                f"tune plan version skew (plan v{plan.version}, "
                f"runtime v{PLAN_VERSION}); ignoring the plan",
                TunePlanMiss, stacklevel=2)
            return None
        return plan
    if isinstance(plan, dict):
        return resolve_plan(TunePlan.from_dict(plan), plan_k=plan_k,
                            directory=directory)
    if plan == "auto":
        if levels is None or width is None:
            raise ValueError(
                "plan='auto' needs the levels and width to hash")
        from arrow_matrix_tpu.tune.fingerprint import structure_hash

        import numpy as np

        h = structure_hash(levels, width,
                           dtype=np.float32 if dtype is None else dtype,
                           growth=growth, slot_align=slot_align,
                           binary=binary)
        return load_plan(h, plan_k, directory)
    raise ValueError(f"unknown plan {plan!r} (expected 'auto', a "
                     f"TunePlan, a plan dict, or None)")
