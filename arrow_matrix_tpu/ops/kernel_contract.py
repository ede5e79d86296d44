"""Declared Pallas kernel contracts (graft-kcert).

Every Pallas kernel builder in the package exports ONE frozen
:class:`KernelContract` naming the envelope it promises to stay
inside: the grid-spec parameters it accepts (row blocks, DMA ring
depths, waves), the feature widths it can carry (the ``k %
stream_k_multiple`` streaming gate), the carriage dtypes it serves,
the accumulator dtype it guarantees (H4' at the kernel level: the
accumulator may widen, the carriage may not force it narrower), and
the SMEM/VMEM budgets its concretized BlockSpecs must fit.

The contract is the single source of truth three consumers read:

* ``analysis/kernels.py`` (the KC1-KC5 certifier) walks
  ``registered_kernels()`` and proves every representative parameter
  point against the contract — verdicts land in the drift-detected
  ``bench_cache/kernel_manifest.json``;
* ``ops/pallas_sell.supported_feature_width`` and the ``tune/space.py``
  candidate pruning both delegate to :meth:`KernelContract.supports_k`,
  so the streaming gate can never disagree between the kernel's own
  validation and the tuner's feasibility screen;
* ROADMAP item 3's *generated* programs plug in here:
  :func:`register_kernel` adds a (contract, metas, source) entry and
  the certifier picks it up with zero changes — an uncertified
  generated kernel never reaches the tune race
  (``analysis/kernels.certify_candidate_opts``).

A kernel's *meta* is the literal description of one concretized
``pallas_call`` (grid, BlockSpecs, scratch, budgets) the certifier
checks arithmetically; the builder derives its real grid/shape numbers
FROM the meta (``pallas_sell.slab_call_meta`` /
``pallas_blocks.column_call_meta``), so the certified description and
the executed call cannot drift apart.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Callable, Dict, List, Optional, Tuple

#: Carriage dtypes a contract may declare, with their itemsizes.  The
#: accumulator is NOT in this table on purpose: KC4 pins it to >= f32
#: regardless of the carriage.
CARRIAGE_ITEMSIZE: Dict[str, int] = {"f32": 4, "bf16": 2, "int8": 1}

#: Accumulator dtypes KC4 accepts.
WIDE_ACCUM_DTYPES = ("f32", "float32", "f64", "float64")


@dataclass(frozen=True)
class KernelContract:
    """The declared envelope of one Pallas kernel builder."""

    name: str                 # builder function name
    module: str               # dotted module exporting the builder
    kind: str                 # "sell_stream" | "dense_blocks"
    granule: int = 1          # rows per packed feature line (C)
    stream_k_multiple: int = 1  # streaming gate: k % this == 0
    line_k: int = 0           # streaming gate: k divides this (0: off)
    row_blocks: Tuple[int, ...] = ()
    rings: Tuple[int, ...] = ()
    waves: Tuple[int, ...] = ()
    ks: Tuple[int, ...] = (16, 128)
    carriage_dtypes: Tuple[str, ...] = ("f32",)
    accum_dtype: str = "f32"
    smem_cols_budget: int = 0       # column bytes per call (slab)
    vmem_budget_bytes: int = 0      # KC2 budget for blocks + scratch
    #: Grid axes allowed to revisit the SAME output block (the
    #: matmul k-innermost accumulation pattern, head_spmm_pallas);
    #: any other unused output axis is a KC5 overlap.
    revisit_axes: Tuple[str, ...] = ()

    def supports_k(self, k: int) -> bool:
        """The streaming-gate predicate BOTH
        ``pallas_sell.supported_feature_width`` and the ``tune/space``
        pruning read — one predicate, one answer."""
        k = int(k)
        return (k >= 1 and k % self.stream_k_multiple == 0
                and (not self.line_k or self.line_k % k == 0))

    def to_json(self) -> dict:
        return asdict(self)


@dataclass(frozen=True)
class KernelEntry:
    """One certifiable kernel: its contract, a callable producing the
    concretized metas at the contract's representative parameter
    points, the builder source for the AST rules (KC3/KC4), and an
    optional trace/interpret witness."""

    contract: KernelContract
    metas: Callable[[], List[dict]]
    source_path: Optional[str] = None
    source_text: Optional[str] = None
    #: Optional callable -> (ok, detail): an abstract-eval / tiny
    #: interpret-mode round trip at a representative point (the KC1
    #: boundary witness).  Failure is a KC1 finding.
    witness: Optional[Callable[[], Tuple[bool, str]]] = None

    @property
    def name(self) -> str:
        return self.contract.name

    def source(self) -> Optional[str]:
        if self.source_text is not None:
            return self.source_text
        if self.source_path is not None:
            with open(self.source_path, encoding="utf-8") as fh:
                return fh.read()
        return None


#: Generated-program hook (ROADMAP item 3): register_kernel() adds an
#: entry; the certifier and the tune pruning see it immediately.
_REGISTRY: Dict[str, KernelEntry] = {}


def register_kernel(entry: KernelEntry) -> KernelEntry:
    """Register a non-builtin (e.g. generated) kernel for
    certification.  Re-registering a name replaces the entry (a
    regenerated program supersedes its predecessor)."""
    _REGISTRY[entry.name] = entry
    return entry


def unregister_kernel(name: str) -> None:
    _REGISTRY.pop(name, None)


def builtin_kernels() -> List[KernelEntry]:
    """The two hand-written Pallas builders (imported lazily — this
    module must stay importable without jax)."""
    from arrow_matrix_tpu.ops import pallas_blocks, pallas_sell

    return [
        KernelEntry(contract=pallas_sell.KERNEL_CONTRACT,
                    metas=pallas_sell.kcert_metas,
                    source_path=pallas_sell.__file__,
                    witness=pallas_sell.kcert_witness),
        KernelEntry(contract=pallas_blocks.KERNEL_CONTRACT,
                    metas=pallas_blocks.kcert_metas,
                    source_path=pallas_blocks.__file__,
                    witness=pallas_blocks.kcert_witness),
    ]


#: One-shot guard for the persisted-program load below.
_SYNTH_LOADED = False


def _load_persisted_programs() -> None:
    """Re-register graft-synth programs persisted in the committed
    store (``bench_cache/synth_programs.json``) so certification and
    the tune race see generated kernels across processes.  Lazy and
    best-effort: ``tune/synth.py`` is jax-free at import, a missing or
    unreadable store simply registers nothing, and a failure here must
    never take down a host-only ``registered_kernels()`` caller."""
    global _SYNTH_LOADED
    if _SYNTH_LOADED:
        return
    _SYNTH_LOADED = True
    try:
        from arrow_matrix_tpu.tune import synth

        synth.register_persisted_programs()
    except Exception:  # graft-lint: disable=R8 — a corrupt store is
        pass           # a kernel-gate finding (tools/kernel_gate.py
                       # re-reads it and fails loudly), not a reason
                       # to take down a host-only registry caller


def registered_kernels() -> List[KernelEntry]:
    """Builtins first, then registered (generated) kernels, each name
    once — a registered entry shadows a builtin of the same name."""
    _load_persisted_programs()
    out: List[KernelEntry] = []
    seen = set(_REGISTRY)
    for e in builtin_kernels():
        if e.name not in seen:
            out.append(e)
    out.extend(_REGISTRY[name] for name in sorted(_REGISTRY))
    return out
