"""The discrete plan space + feasibility pruning (graft-tune).

Candidates are the small set of configurations worth racing for one
(structure, k) on the one-chip fold: tier-split variants of the SELL
fold (the ``fold_tight`` / single-tier-ELL axes), chunking, the fused
``pallas_sell`` kernel with its slab/SMEM/ring knobs, and the
carriage-dtype experiments (bf16, plus opt-in int8).

Pruning happens BEFORE any child is spawned, with the models the repo
already trusts:

* evaluator capability: the streaming pallas path needs
  ``k % 16 == 0`` on a real chip — read from the ONE predicate the
  kernel itself validates with
  (``ops/pallas_sell.supported_feature_width`` ->
  ``KernelContract.supports_k``, graft-kcert) so the tuner and the
  kernel can never disagree; DMA-ring variants are stream-only so
  they are pruned on the interpret (CPU) evaluator;
* kernel certification (graft-kcert): every pallas candidate's
  concretized call meta is proven under KC1-KC5
  (``analysis/kernels.certify_candidate_opts``) BEFORE any child
  spawns — an uncertifiable grid/ring/budget combination is pruned
  with a ``"kcert: ..."`` reason and zero children.  Generated
  programs (ROADMAP item 3) ride the same screen through the
  ``extra`` candidate hook.

Carriage-dtype eligibility is per traffic class (graft-classes): for
``traffic_class="exact"`` (the default, today's contract) bf16/int8
are marked ``eligible=False`` — they cannot be bit-identical to the
f32 golden by construction, so they are timed as diagnostics but can
never be persisted as the winner.  For ``traffic_class="approx"`` the
same candidates become ``eligible=True``: the winner gate is the class
tolerance (measured rel-Frobenius vs the golden,
``arrow_matrix_tpu/classes.py``), not bit-identity, and the winning
plan records its accuracy certificate.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass(frozen=True)
class Candidate:
    """One raceable configuration: executor build overrides plus
    fused-kernel call knobs (see ``TunePlan``)."""

    name: str
    build: Dict[str, Any] = field(default_factory=dict)
    kernel_opts: Dict[str, Any] = field(default_factory=dict)
    eligible: bool = True
    note: str = ""


def enumerate_candidates(fp: dict, k: int, *,
                         platform: str = "cpu",
                         allow_int8: bool = False,
                         restrict: Optional[List[str]] = None,
                         traffic_class: str = "exact",
                         extra: Optional[List[Candidate]] = None,
                         lens_model=None
                         ) -> Tuple[List[Candidate], Dict[str, str]]:
    """The candidate list for one (fingerprint, k), already pruned.

    Returns ``(candidates, pruned)`` where ``pruned`` maps each
    rejected candidate name to its reason — the search report records
    both, so a plan's provenance shows what was *not* tried and why.

    ``restrict`` (names) narrows the space — the smoke/doctor path
    races 3 candidates instead of ~12.

    ``traffic_class="approx"`` flips the carriage-dtype candidates to
    ``eligible=True`` (tolerance-gated winners, see module docstring);
    int8 still needs the explicit ``allow_int8`` opt-in even there.

    ``extra`` appends caller-supplied candidates (the generated-
    program hook): they ride the same screens, including graft-kcert
    certification for pallas kernels — an uncertifiable candidate is
    pruned here, before any child spawns.

    ``lens_model`` (a fitted ``obs.costmodel.CostModel`` for THIS
    structure) arms the compute-side screen — the comm-only T(c)
    screen's twin: a candidate whose lens-predicted iteration time
    exceeds 3x the default candidate's prediction is pruned before
    any child spawns, with a ``"lens: …"`` reason.  The margin is
    deliberately conservative (the model ranks, the bench decides)
    and the screen never touches eligibility — f32 bit-identity and
    winner rules are unchanged.
    """
    from arrow_matrix_tpu.classes import TRAFFIC_CLASSES

    if traffic_class not in TRAFFIC_CLASSES:
        raise ValueError(f"unknown traffic class {traffic_class!r} "
                         f"(expected one of {TRAFFIC_CLASSES})")
    approx = traffic_class == "approx"
    interpret = platform == "cpu"
    raw: List[Candidate] = [
        Candidate("default", note="the hand-tuned baseline; always "
                                  "raced, trivially bit-identical"),
        Candidate("fold_tight",
                  build={"fold_growth": 1.1, "fold_align": 1},
                  note="minimal padded slots (more tiers)"),
        Candidate("fold_coarse",
                  build={"fold_growth": 1.5},
                  note="fewer tiers, more padding"),
        Candidate("ell_one_tier",
                  build={"fold_growth": 1e9, "fold_align": 1},
                  note="degenerate tier split: one ELL tier "
                       "(plus the zero-degree prefix)"),
        Candidate("chunk_4096",
                  build={"chunk": 4096},
                  note="fixed gather chunk vs the auto budget"),
        Candidate("pallas_sell",
                  build={"kernel": "pallas_sell"},
                  note="fused gather->FMA kernel"),
        Candidate("pallas_sell_smem_small",
                  build={"kernel": "pallas_sell"},
                  kernel_opts={"smem_cols_budget": 1 << 14},
                  note="forced slab streaming (small SMEM budget)"),
        Candidate("pallas_sell_rb128",
                  build={"kernel": "pallas_sell"},
                  kernel_opts={"row_block": 128},
                  note="half-size VMEM row tile"),
        Candidate("pallas_sell_ring1",
                  build={"kernel": "pallas_sell"},
                  kernel_opts={"ring": 1},
                  note="serial DMA (no waves in flight)"),
        Candidate("pallas_sell_ring4",
                  build={"kernel": "pallas_sell"},
                  kernel_opts={"ring": 4},
                  note="deeper VMEM ring"),
        Candidate("pallas_sell_bf16",
                  build={"kernel": "pallas_sell",
                         "feature_dtype": "bf16"},
                  eligible=approx,
                  note=("fused kernel, bf16 carriage / f32 "
                        "accumulate (KC1-KC5 certified); "
                        "tolerance-gated winner" if approx else
                        "fused kernel, bf16 carriage diagnostic "
                        "(never f32 bit-identical; cannot win)")),
        Candidate("bf16",
                  build={"feature_dtype": "bf16"}, eligible=approx,
                  note=("bf16 carriage: approx-class candidate "
                        "(tolerance-gated winner)" if approx else
                        "bf16 carriage diagnostic (never f32 "
                        "bit-identical; cannot win)")),
    ]
    if allow_int8:
        raw.append(Candidate(
            "int8", build={"feature_dtype": "int8"}, eligible=approx,
            note=("opt-in int8 (q, scale) carriage: approx-class "
                  "candidate" if approx else
                  "opt-in int8-carriage experiment (diagnostic only)")))
    if approx or allow_int8:
        # The fused (q, scale) SELL variant (ROADMAP item 2's last
        # kernel): int8 carriage lines + f32 accumulate in-kernel, the
        # per-feature scale applied outside.  Raced for approx plans
        # alongside pallas_sell_bf16; allow_int8 also surfaces it as
        # an exact-class diagnostic.
        raw.append(Candidate(
            "pallas_sell_int8",
            build={"kernel": "pallas_sell", "feature_dtype": "int8"},
            eligible=approx,
            note=("fused kernel, int8 (q, scale) carriage / f32 "
                  "accumulate (KC1-KC5 certified); tolerance-gated "
                  "winner" if approx else
                  "fused kernel, int8 (q, scale) carriage diagnostic "
                  "(never f32 bit-identical; cannot win)")))
    if extra:
        raw.extend(extra)

    lens_base = 0.0
    if lens_model is not None:
        from arrow_matrix_tpu.obs.costmodel import predict_candidate_ms
        lens_base = predict_candidate_ms(lens_model, fp, k, {}, {})

    out, pruned = [], {}
    for c in raw:
        if restrict is not None and c.name not in restrict:
            pruned[c.name] = "not in restricted candidate set"
            continue
        if c.build.get("kernel") == "pallas_sell":
            # The ONE streaming-gate predicate: the kernel's own
            # contract (supported_feature_width -> supports_k).
            from arrow_matrix_tpu.ops.pallas_sell import (
                supported_feature_width)
            if not interpret and not supported_feature_width(k):
                pruned[c.name] = ("streaming pallas_sell needs "
                                  f"k % 16 == 0 and k | 128 on chip (k={k})")
                continue
            if interpret and "ring" in c.kernel_opts:
                pruned[c.name] = ("DMA ring depth is a stream-only "
                                  "knob; interpret evaluator runs the "
                                  "vectorized body")
                continue
            from arrow_matrix_tpu.analysis.kernels import (
                certify_candidate_opts)
            reason = certify_candidate_opts(
                c.kernel_opts, k, interpret=interpret,
                feature_dtype=c.build.get("feature_dtype"))
            if reason is not None:
                pruned[c.name] = reason
                continue
        if lens_model is not None and lens_base > 0.0 \
                and c.name != "default":
            predicted = predict_candidate_ms(lens_model, fp, k,
                                             c.build, c.kernel_opts)
            if predicted > 3.0 * lens_base:
                pruned[c.name] = (
                    f"lens: predicted compute {predicted:.3f} ms > "
                    f"3x default {lens_base:.3f} ms")
                continue
        out.append(c)
    return out, pruned
