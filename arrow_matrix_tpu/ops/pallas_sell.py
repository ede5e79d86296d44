"""Fused SELL-SpMM Pallas TPU kernel (graft-stream).

The XLA fold kernel (``ops/sell.py`` -> ``ops/ell.py ell_spmm_t``) pays
for a materialized ``(k, chunk, rows)`` gather intermediate per tier —
one full HBM round trip of every gathered feature row before the
weighted reduction touches it.  This kernel fuses gather -> multiply
-> accumulate in VMEM (on the chip it is correct but, so far, ~4.7x
slower than the XLA fold — PERF.md, PR 21):

  * features are packed into **word lines**: 128 32-bit words (512 B)
    holding ``C`` consecutive rows of the row-major ``(n, k)`` view in
    the carriage dtype (C = 8 at f32 k=16, 1 at f32 k=128; bf16 and
    int8 pack 2 and 4 features per word), so every gather is one
    single-row DMA of a full-lane line — the only row DMA Mosaic takes
    from an HBM table (v5e, PR 21);
  * column indices ride in twice per row block: an SMEM block for the
    DMA addresses (``line = col // C`` needs scalar reads) and a VMEM
    block, transposed in-kernel to per-row columns, for the
    vectorized sub-row select (``off = col % C``);
  * the streaming path issues ``wave``-sized groups of
    ``pltpu.make_async_copy`` line fetches with **two waves in
    flight** (double-buffered DMA: wave w+1's copies are started
    before wave w is awaited); the select masks each row's segment,
    lane rotations fold it onto every segment, and the weighted
    contribution accumulates in f32 — the ``(k, chunk, rows)``
    intermediate never exists;
  * wide tiers walk their slots in chunks along an inner grid axis
    that accumulates into the row block's output; long tiers are cut
    into row slabs, one ``pallas_call`` each.

Two statically-selected bodies share the select/accumulate math:

  ``stream=True``   — the wave-pipelined async-copy gather (the TPU
                      path; also runs under ``interpret=True`` at tiny
                      shapes to pin the DMA logic on CPU);
  ``stream=False``  — a vectorized in-kernel gather (``interpret``
                      only: it reads the packed feature table wholesale,
                      which Mosaic forbids on a real HBM ref).  This is
                      the tier-1 correctness path at protocol shape —
                      same grid, same masking, same accumulation order.

Correctness contract: matches ``ops.sell.sell_spmm_t`` within the
``utils/numerics.py`` gate (f32 accumulation either way; only the
reduction order over slots differs).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from arrow_matrix_tpu.ops.ell import align_up
from arrow_matrix_tpu.ops.kernel_contract import (
    CARRIAGE_ITEMSIZE,
    KernelContract,
)
from arrow_matrix_tpu.ops.pallas_blocks import VMEM_BUDGET, _interpret
from arrow_matrix_tpu.ops.sell import SellMatrix

GRANULE = 8          # rows per packed line at the f32 k=16 protocol point

#: Every packed feature line is 128 32-bit words (512 B): one full-lane
#: row, the unit a single-row DMA moves from HBM on TPU (Mosaic refuses
#: a one-row DMA out of a wider, (8, 128)-tiled table — measured on
#: v5e, PR 21).  ``line_geometry`` derives how many feature rows share
#: a line for each (k, carriage).
LINE_WORDS = 128

# Streaming lane constraint: a feature row must fill whole words and
# tile a 128-word line, and the output lines hold 128 // k rows.
STREAM_K_MULTIPLE = 16

#: SMEM bytes the double-buffered (slots, row_block) column block of
#: one grid step may take: half of v5e's 1 MiB SMEM (a 1 MiB block
#: overflows it — AOT compile for v5e, PR 21).
SMEM_BLOCK_BUDGET = 1 << 19

#: The contract-declared slab budget (the certified value —
#: ``KERNEL_CONTRACT`` and the committed kernel_manifest pin THIS one,
#: independent of the env override below): column-index bytes one
#: ``pallas_call`` streams.  SMEM only ever holds one row block's
#: columns, so this bounds call size, not on-chip memory.
DEFAULT_SMEM_COLS_BUDGET = 1 << 26

#: Column bytes per slab; tiers whose cols exceed it are streamed
#: through the kernel in row slabs, one ``pallas_call`` each.
#: ``AMT_PALLAS_SELL_SMEM`` is the *default only*, read once at import
#: (R9: no per-call env reads); callers — and graft-tune plans — pass
#: ``smem_cols_budget=`` explicitly to override.
SMEM_COLS_BUDGET = int(os.environ.get("AMT_PALLAS_SELL_SMEM",
                                      str(DEFAULT_SMEM_COLS_BUDGET)))

#: Carriage dtypes the fused kernel serves (graft-kcert KC4 contract:
#: the carriage may narrow, the accumulator stays f32).  The int8
#: carriage is the fused (q, scale) pair: the packed feature table
#: travels as int8 granule lines, the kernel decodes to f32 in the
#: accumulator, and the per-feature scale multiplies the f32 output
#: OUTSIDE the kernel (SpMM is separable per feature column, so the
#: factorization is exact given the quantized table).
CARRIAGE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                   "int8": jnp.int8}

DEFAULT_ROW_BLOCK = 256  # rows per grid program (multiple of GRANULE)
DEFAULT_WAVE = 16        # async copies per DMA wave (streaming path)
DEFAULT_RING = 2         # DMA waves in flight (VMEM ring depth)


def slab_rows(m_t: int, rb: int,
              smem_cols_budget: Optional[int] = None) -> int:
    """Rows per slot-major slab: as many ``rb``-row blocks as fit the
    slab budget (4 bytes of int32 cols per padded slot and row),
    never less than one row block — a tier whose per-row cols alone
    exceed the budget still streams, one block at a time."""
    budget = (SMEM_COLS_BUDGET if smem_cols_budget is None
              else smem_cols_budget)
    per_row = align_up(max(m_t, 1), _slot_rows(m_t)) * 4
    return max(rb, (budget // max(per_row, 1)) // rb * rb)


def line_geometry(k: int, carriage: str = "f32") -> tuple:
    """``(words_per_row, rows_per_line, planes, k_pad)`` of the packed
    feature table.

    A feature row of ``k`` carriage elements (zero-padded to ``k_pad``,
    a whole number of words) is ``words_per_row`` 32-bit words;
    ``planes = 4 / itemsize`` elements share one word (plane p of word
    q holds feature ``q + p * words_per_row``, so a decoded plane is a
    contiguous feature run).  When a row tiles a 128-word line, a line
    holds ``rows_per_line`` consecutive rows (the streaming layout);
    other widths — interpret-mode only — fall back to ``planes`` rows
    per line."""
    if k < 1:
        raise ValueError(f"pallas_sell needs k >= 1, got {k}")
    item = CARRIAGE_ITEMSIZE[carriage]
    planes = 4 // item
    k_pad = align_up(k, planes)
    wpr = k_pad // planes
    if LINE_WORDS % wpr == 0 and (LINE_WORDS // wpr) % planes == 0:
        return wpr, LINE_WORDS // wpr, planes, k_pad
    return wpr, planes, planes, k_pad


def pack_features_t(x_t: jax.Array, feature_dtype=None) -> jax.Array:
    """Pack feature-major ``(k, n)`` features into int32 word lines
    ``(n_pad // C, C * words_per_row)`` in the carriage dtype
    (``feature_dtype``, default: the input's): line g holds rows
    ``[g*C, (g+1)*C)`` of the row-major view — one single-row DMA per
    gathered row group.  Zero-pads n up to a ``C`` multiple.  An int8
    carriage expects an already-quantized table
    (:func:`quantize_features_t`)."""
    k, n = x_t.shape
    carriage, dt = resolve_carriage_dtype(feature_dtype,
                                          default=x_t.dtype)
    wpr, c, planes, k_pad = line_geometry(k, carriage)
    n_pad = align_up(max(n, 1), c)
    x = x_t.T                                     # (n, k) row-major view
    if n_pad != n or k_pad != k:
        x = jnp.pad(x, ((0, n_pad - n), (0, k_pad - k)))
    if planes == 1:
        words = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                             jnp.int32)
    else:
        bits = jax.lax.bitcast_convert_type(
            x.astype(dt), jnp.uint16 if planes == 2 else jnp.uint8
        ).astype(jnp.uint32)
        acc = bits[:, :wpr]
        for p in range(1, planes):
            acc = acc | (bits[:, p * wpr:(p + 1) * wpr]
                         << (32 // planes * p))
        words = jax.lax.bitcast_convert_type(acc, jnp.int32)
    return words.reshape(n_pad // c, c * wpr)

def quantize_features_t(x_t: jax.Array):
    """Symmetric per-feature-row int8 quantization of the feature-major
    ``(k, n)`` block: ``q = round(x / scale)`` with
    ``scale = max|x| / 127`` taken per feature row.  Returns
    ``(q int8 (k, n), scale f32 (k, 1))``.  Because SpMM is separable
    per feature column, ``scale * (A @ q)`` reconstructs ``A @ x``
    exactly up to the rounding of ``q`` itself — the scale never enters
    the kernel, so the int8 carriage keeps the certified f32
    accumulator (KC4)."""
    xf = x_t.astype(jnp.float32)
    q_max = jnp.float32(127.0)
    amax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)        # (k, 1)
    scale = jnp.where(amax > 0, amax / q_max, jnp.float32(1.0))
    q = jnp.clip(jnp.round(xf / scale), -q_max, q_max).astype(jnp.int8)
    return q, scale


def _schedule_overrides(schedule) -> dict:
    """Normalize a graft-synth per-tier schedule into
    ``tier index -> override dict``.  Accepts the TunePlan payload
    shape (a list of dicts each carrying a ``"tier"`` key) or a dict
    keyed by tier (string keys survive a JSON round trip)."""
    if not schedule:
        return {}

    def _coerce(ov: dict) -> dict:
        # Schedule knobs are JSON/TunePlan metadata (static Python
        # ints after a round trip as strings/floats), never traced.
        for key in ("row_block", "wave", "ring", "smem_cols_budget"):
            if ov.get(key) is not None:
                ov[key] = int(ov[key])  # graft-lint: disable=R1
        return ov

    if isinstance(schedule, dict):
        return {int(t): _coerce(dict(ov))  # graft-lint: disable=R1
                for t, ov in schedule.items()}
    out = {}
    for entry in schedule:
        ov = dict(entry)
        try:
            t = int(ov.pop("tier"))  # graft-lint: disable=R1
        except KeyError:
            raise ValueError(
                "per-tier schedule entries need a 'tier' key; got "
                f"{sorted(entry)}") from None
        out[t] = _coerce(ov)
    return out


def _decode_planes(lines, planes: int) -> list:
    """int32 word lines -> ``planes`` f32 arrays of the same shape
    (plane p of word q = feature ``q + p * words_per_row``)."""
    if planes == 1:
        return [jax.lax.bitcast_convert_type(lines, jnp.float32)]
    if planes == 2:
        return [jax.lax.bitcast_convert_type(lines << 16, jnp.float32),
                jax.lax.bitcast_convert_type(lines & jnp.int32(-65536),
                                             jnp.float32)]
    return [((lines << (24 - 8 * p)) >> 24).astype(jnp.float32)
            for p in range(planes)]


def _select_accumulate(lines, off, w, k: int, carriage: str,
                       interpret: bool):
    """Shared select/accumulate math of both kernel bodies.

    ``lines`` (r, L) int32 holds each row's gathered line; ``off``
    (r, 1) its row's position in the line; ``w`` (r, 1) f32 the slot
    weight.  Masks every plane to the row's word segment, folds the
    segments onto each other with lane rotations (after the fold every
    segment holds the row), merges the planes so lane l carries feature
    ``l % k``, and weights: (r, L) f32, each row's k features
    replicated ``L // k`` times."""
    wpr, c, planes, k = line_geometry(k, carriage)
    r, lanes = lines.shape
    roll = jnp.roll if interpret else pltpu.roll
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, lanes), 1)
    seg = lane // wpr
    total = None
    for p, plane in enumerate(_decode_planes(lines, planes)):
        v = jnp.where(seg == off, plane, 0.0)
        s = c // 2
        while s >= 1:
            v = v + roll(v, s * wpr, 1)
            s //= 2
        if planes > 1:
            v = jnp.where((lane % k) // wpr == p, v, 0.0)
        total = v if total is None else total + v
    return total * w


def out_rows_per_line(k: int, carriage: str = "f32") -> int:
    """Rows per kernel output line (``G``): the line width over the
    padded feature row."""
    wpr, c, _planes, k_pad = line_geometry(k, carriage)
    return c * wpr // k_pad


def _pack_rows(acc, k: int):
    """(r, L) row-replicated accumulator -> (r // G, L) output lines of
    ``G = L // k`` consecutive rows each (row-major (r, k) after a
    reshape): keep row i's copy in segment ``i % G``, sum row groups."""
    r, lanes = acc.shape
    g = lanes // k
    if g == 1:
        return acc
    rowseg = jax.lax.broadcasted_iota(jnp.int32, (r, lanes), 0) % g
    laneseg = jax.lax.broadcasted_iota(jnp.int32, (r, lanes), 1) // k
    return jnp.where(rowseg == laneseg, acc, 0.0).reshape(
        r // g, g, lanes).sum(axis=1)

def resolve_carriage_dtype(feature_dtype, default=jnp.float32):
    """Normalize a carriage-dtype request to ``(key, jnp dtype)``.

    ``feature_dtype`` may be a contract key ("f32"/"bf16"), a dtype
    name, or a dtype object; ``None`` means "carry whatever the input
    already is" (``default``), falling back to f32 for dtypes the
    contract does not serve — an *explicit* unsupported request raises
    instead of silently widening."""
    if feature_dtype is None:
        dt = jnp.dtype(default)
        for key, val in CARRIAGE_DTYPES.items():
            if dt == jnp.dtype(val):
                return key, val
        return "f32", jnp.float32
    try:
        if isinstance(feature_dtype, str):
            alias = {"f32": "float32", "bf16": "bfloat16",
                     "i8": "int8"}.get(feature_dtype, feature_dtype)
            dt = jnp.dtype(alias)
        else:
            dt = jnp.dtype(feature_dtype)
    except TypeError:
        raise ValueError(
            f"unsupported pallas_sell carriage dtype "
            f"{feature_dtype!r}; the kernel contract serves "
            f"{tuple(CARRIAGE_DTYPES)}") from None
    for key, val in CARRIAGE_DTYPES.items():
        if dt == jnp.dtype(val):
            return key, val
    raise ValueError(
        f"unsupported pallas_sell carriage dtype {feature_dtype!r}; "
        f"the kernel contract serves {tuple(CARRIAGE_DTYPES)}")


#: Rows per grid program are a multiple of this: rows are the lane
#: (minor) axis of the (slots, rows) column blocks, which Mosaic tiles
#: in whole 128-lane vregs (a 64-row block is refused — v5e AOT, PR 21).
ROW_ALIGN = 128

#: Slots per grid step: a wider tier (hub rows) walks its slots in
#: chunks along the inner grid axis, accumulating into the same output
#: block, so the (slots, row_block) column/weight blocks stay small in
#: SMEM and VMEM whatever the degree.
SLOT_CHUNK = 128


def _slot_rows(m_t: int) -> int:
    """Slot rows of one grid step's column/weight blocks (a whole
    number of sublanes, at most SLOT_CHUNK)."""
    return min(SLOT_CHUNK, align_up(max(m_t, 1), 8))


def slab_call_meta(m_t: int, slab: int, k: int, row_block: int,
                   binary: bool, stream: bool, wave: int, ring: int,
                   n_lines: Optional[int] = None,
                   carriage: str = "f32") -> dict:
    """The literal description of one concretized slab ``pallas_call``
    — grid, BlockSpecs, scratch, budgets — in the graft-kcert meta
    schema.  :func:`_make_slab_call` derives its real grid/block/
    scratch numbers FROM this dict, so the certified description and
    the executed call cannot drift apart."""
    if ring < 1:
        raise ValueError(f"ring depth must be >= 1, got {ring}")
    if m_t < 1:
        raise ValueError(f"meta needs m_t >= 1, got {m_t}")
    if k < 1:
        raise ValueError(f"meta needs k >= 1, got {k}")
    if carriage not in CARRIAGE_ITEMSIZE:
        raise ValueError(
            f"unknown carriage dtype key {carriage!r}; contract "
            f"serves {tuple(CARRIAGE_ITEMSIZE)}")
    g = out_rows_per_line(k, carriage)
    wpr, c, _planes, _k_pad = line_geometry(k, carriage)
    lanes = c * wpr
    if row_block < ROW_ALIGN or row_block % ROW_ALIGN or row_block % g:
        raise ValueError(
            f"row_block must be a positive ROW_ALIGN ({ROW_ALIGN}) "
            f"multiple, got {row_block}")
    if wave < 1 or row_block % wave:
        raise ValueError(
            f"wave must divide row_block ({row_block}), got {wave}")
    if slab < row_block or slab % row_block:
        raise ValueError(
            f"slab must be a positive row_block ({row_block}) "
            f"multiple, got {slab}")
    n_lines = (max(1, (1 << 12) // c) if n_lines is None
               # host-side meta builder: the argument is a static
               # shape, never a traced value
               else int(n_lines))  # graft-lint: disable=R1
    m_rows = _slot_rows(m_t)
    n_chunks = -(-m_t // m_rows)
    weights = ({"name": "weights", "shape": [1, slab],
                "block": [1, row_block], "index": [0, "i"],
                "space": "vmem", "itemsize": 4} if binary else
               {"name": "weights", "shape": [n_chunks * m_rows, slab],
                "block": [m_rows, row_block], "index": ["s", "i"],
                "space": "vmem", "itemsize": 4})
    meta = {
        "kernel": "sell_tier_spmm_packed",
        "kind": "sell_stream" if stream else "sell_vectorized",
        "grid": [["i", slab // row_block], ["s", n_chunks]],
        "out": {"shape": [slab // g, lanes],
                "block": [row_block // g, lanes],
                "index": ["i", 0], "itemsize": 4},
        "ins": [
            {"name": "cols_vmem", "shape": [n_chunks * m_rows, slab],
             "block": [m_rows, row_block], "index": ["s", "i"],
             "space": "vmem", "itemsize": 4},
            weights,
            {"name": "x_packed", "shape": [n_lines, lanes],
             "block": None, "index": None, "space": "any",
             "itemsize": 4},
        ],
        "smem": {"name": "cols_smem", "bytes": 2 * m_rows * 4 * row_block,
                 "budget": SMEM_BLOCK_BUDGET, "single_block": False},
        "scratch": ([{"name": "dma_scratch",
                      "shape": [row_block, lanes], "itemsize": 4}]
                    if stream else []),
        "sems": ({"shape": [ring, wave]} if stream else None),
        "vmem_budget": VMEM_BUDGET,
        "accum_dtype": "f32",
        "carriage_dtype": carriage,
        # Slot chunks of one row block accumulate into its output block.
        "revisit_axes": ["s"],
    }
    if stream:
        meta["stream"] = {
            "ring": ring, "wave": wave, "n_waves": row_block // wave,
            "row_block": row_block, "granule": c, "slab": slab,
            "m_t": m_t, "lines": n_lines, "table_rows": n_lines * c,
        }
    return meta


def _make_slab_call(m_t: int, slab: int, k: int, row_block: int,
                    binary: bool, stream: bool, wave: int,
                    interpret: bool, ring: int = DEFAULT_RING,
                    n_lines: Optional[int] = None,
                    carriage: str = "f32"):
    """One ``pallas_call`` over a (slots, slab) column slab -> packed
    (slab // G, L) f32 output (accumulation is f32 whatever the
    carriage dtype of ``x_packed`` — KC4).  Grid: row blocks outer,
    slot chunks inner."""
    import jax.experimental.pallas as pl

    meta = slab_call_meta(m_t, slab, k, row_block, binary, stream,
                          wave, ring, n_lines=n_lines,
                          carriage=carriage)
    c = line_geometry(k, carriage)[1]
    k_pad = line_geometry(k, carriage)[3]
    lanes = meta["out"]["shape"][1]
    m_rows = meta["ins"][0]["block"][0]
    grid = tuple(size for _axis, size in meta["grid"])
    n_waves = meta["stream"]["n_waves"] if stream else row_block // wave

    def _row_columns(cols_vmem, w_vmem):
        """This row block's slot columns as per-row (R, slots) arrays:
        line offsets ``col % C`` (f32, exact) and weights (binary: the
        (R, 1) degree).  Mosaic transposes the (slots, R) blocks; a
        value-level dynamic row index would not lower."""
        offs = jnp.transpose(cols_vmem[...] % c).astype(jnp.float32)
        if binary:
            deg = jnp.broadcast_to(w_vmem[...], (8, row_block))
            return offs, jnp.transpose(deg)[:, :1]
        return offs, jnp.transpose(w_vmem[...].astype(jnp.float32))

    def _slot(j, offs, w_rows, chunk):
        """Slot j (of slot chunk ``chunk``)'s (R, 1) line offset and
        weight."""
        pick = jax.lax.broadcasted_iota(jnp.int32, offs.shape, 1) == j
        off = jnp.sum(jnp.where(pick, offs, 0.0), axis=1,
                      keepdims=True).astype(jnp.int32)
        if binary:
            # Slot-validity mask (global slot < deg), generated in
            # registers — same addends as the golden's iota-vs-degree
            # compare.
            return off, (chunk * m_rows + j < w_rows).astype(jnp.float32)
        return off, jnp.sum(jnp.where(pick, w_rows, 0.0), axis=1,
                            keepdims=True)

    def _n_slots():
        """Real slots of this chunk (the last one may be short)."""
        return jnp.minimum(m_rows, m_t - pl.program_id(1) * m_rows)

    def _store(out_ref, acc):
        packed = _pack_rows(acc, k_pad)

        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = packed

        @pl.when(pl.program_id(1) > 0)
        def _():
            out_ref[...] += packed

    def kernel_vectorized(cols_smem, cols_vmem, w_vmem, x_any, out_ref):
        # interpret-only body: wholesale read + take stands in for the
        # DMA engine; grid, masking and accumulation order are shared
        # with the streaming body, so tier-1 pins both.
        del cols_smem
        chunk = pl.program_id(1)
        xg = x_any[...]
        lines_all = jnp.transpose(cols_vmem[...] // c)      # (R, slots)
        offs, w_rows = _row_columns(cols_vmem, w_vmem)

        def slot_body(j, acc):
            pick = jax.lax.broadcasted_iota(
                jnp.int32, lines_all.shape, 1) == j
            g_j = jnp.sum(jnp.where(pick, lines_all, 0), axis=1)
            lines = jnp.take(xg, g_j, axis=0)                # (R, L)
            off, w_j = _slot(j, offs, w_rows, chunk)
            return acc + _select_accumulate(lines, off, w_j, k,
                                            carriage, interpret)

        acc0 = jnp.zeros((row_block, lanes), dtype=jnp.float32)
        _store(out_ref, jax.lax.fori_loop(0, _n_slots(), slot_body, acc0))

    def kernel_stream(cols_smem, cols_vmem, w_vmem, x_any, out_ref,
                      scratch, sems):
        chunk = pl.program_id(1)
        offs, w_rows = _row_columns(cols_vmem, w_vmem)

        def copy(j, w, r):
            """The (slot j, wave w, lane r) line fetch: address from
            the SMEM column block, destination its own scratch row,
            semaphore by wave modulo the ring depth — up to ``ring``
            waves in flight."""
            rr = w * wave + r
            g = cols_smem[j, rr] // c
            return pltpu.make_async_copy(
                x_any.at[g], scratch.at[rr], sems.at[w % ring, r])

        def issue(j, w):
            jax.lax.fori_loop(
                0, wave, lambda r, _: (copy(j, w, r).start(), 0)[1], 0)

        def wait(j, w):
            jax.lax.fori_loop(
                0, wave, lambda r, _: (copy(j, w, r).wait(), 0)[1], 0)

        def slot_body(j, acc):
            # Prologue: fill the ring — waves 0..ring-2 in flight (the
            # steady state tops the ring up to ``ring`` deep; ring=1
            # degenerates to issue-then-wait, fully serial).
            for p in range(min(ring - 1, n_waves)):
                issue(j, p)

            def wave_body(w, carry):
                @pl.when(w + ring - 1 < n_waves)
                def _():
                    issue(j, w + ring - 1)  # top up: deepest wave whose
                wait(j, w)                  # sem slot is free of w's

                return carry

            jax.lax.fori_loop(0, n_waves, wave_body, 0)
            off, w_j = _slot(j, offs, w_rows, chunk)
            return acc + _select_accumulate(scratch[...], off, w_j, k,
                                            carriage, interpret)

        acc0 = jnp.zeros((row_block, lanes), dtype=jnp.float32)
        _store(out_ref, jax.lax.fori_loop(0, _n_slots(), slot_body, acc0))

    cols_block = tuple(meta["ins"][0]["block"])
    w_block = tuple(meta["ins"][1]["block"])
    w_index = ((lambda i, s: (0, i)) if binary
               else (lambda i, s: (s, i)))
    out_block = tuple(meta["out"]["block"])
    # Columns ride in twice, per block: an SMEM block for the DMA
    # addresses (scalar reads) and a VMEM block for the vector offsets.
    in_specs = [
        pl.BlockSpec(cols_block, lambda i, s: (s, i),
                     memory_space=pltpu.SMEM),   # cols, DMA addresses
        pl.BlockSpec(cols_block, lambda i, s: (s, i),
                     memory_space=pltpu.VMEM),   # cols, vector math
        pl.BlockSpec(w_block, w_index,
                     memory_space=pltpu.VMEM),   # data / deg
        pl.BlockSpec(memory_space=pl.ANY),       # packed x: HBM
    ]
    # DMA scratch holds the packed int32 words of any carriage; the
    # kernel body decodes to f32 before it accumulates.
    scratch = ([pltpu.VMEM(tuple(meta["scratch"][0]["shape"]), jnp.int32),
                pltpu.SemaphoreType.DMA(tuple(meta["sems"]["shape"]))]
               if stream else [])
    kernel = kernel_stream if stream else kernel_vectorized

    def call(cols_slab, w_slab, x_packed):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(tuple(meta["out"]["shape"]),
                                           jnp.float32),
            grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec(out_block, lambda i, s: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
            interpret=interpret,
        )(cols_slab, cols_slab, w_slab, x_packed)

    return call


def _tier_row_block(n_t: int, row_block: int, g: int = 1) -> int:
    """Rows per grid program: the requested block, shrunk to the tier
    so a tiny tier doesn't pad to a full block, and aligned to
    ROW_ALIGN and to the ``g`` rows of one output line."""
    align = max(ROW_ALIGN, g)
    rb = min(row_block, align_up(max(n_t, 1), align))
    return max(align, rb - rb % align)


def sell_tier_spmm_packed(cols: jax.Array, x_packed: jax.Array, k: int,
                          data: Optional[jax.Array] = None,
                          deg: Optional[jax.Array] = None,
                          row_block: int = DEFAULT_ROW_BLOCK,
                          wave: int = DEFAULT_WAVE,
                          stream: Optional[bool] = None,
                          interpret: Optional[bool] = None,
                          smem_cols_budget: Optional[int] = None,
                          ring: int = DEFAULT_RING,
                          feature_dtype=None) -> jax.Array:
    """One tier's fused SpMM against packed features.

    cols: (m_t, n_t) slot-major int32; x_packed: the int32 word lines
    of :func:`pack_features_t` for ``k`` features in the carriage
    ``feature_dtype`` ("f32", "bf16" or "int8"; default f32 — it must
    be the dtype the table was packed in); ``data`` (m_t, n_t)
    weighted or ``deg`` (n_t,) binary.  Returns (n_t, k) f32 —
    row-major (the caller re-majors per call, see
    :func:`sell_spmm_t_pallas`).

    ``smem_cols_budget`` bounds one slab's column bytes (default:
    module-level :data:`SMEM_COLS_BUDGET`); ``ring`` is the DMA ring
    depth of the streaming path (waves in flight).  Accumulation stays
    f32 for every carriage (the certified KC4 contract), so bf16
    carriage halves DMA bytes without narrowing the reduction.
    """
    if interpret is None:
        interpret = _interpret()
    if stream is None:
        stream = not interpret
    if ring < 1:
        raise ValueError(f"ring depth must be >= 1, got {ring}")
    m_t, n_t = cols.shape
    carriage, _dt = resolve_carriage_dtype(feature_dtype,
                                           default=jnp.float32)
    if data is None and deg is None and m_t > 0:
        raise ValueError("binary SELL tier (data=None) requires deg")
    if m_t == 0 or n_t == 0:
        return jnp.zeros((n_t, k), dtype=jnp.float32)
    if stream and not supported_feature_width(k):
        raise ValueError(
            f"streaming pallas_sell needs k % {STREAM_K_MULTIPLE} == 0 "
            f"and k | {LINE_WORDS} (a feature row must tile one "
            f"{LINE_WORDS}-word line), got k={k}; use the XLA fold "
            f"kernel for this feature width")
    if not stream and not interpret:
        raise ValueError(
            "the vectorized pallas_sell body is interpret-only (it "
            "reads the feature table wholesale); compiled TPU runs "
            "must use stream=True")

    binary = data is None
    k_pad = line_geometry(k, carriage)[3]
    rb = _tier_row_block(n_t, row_block, out_rows_per_line(k, carriage))
    w = min(wave, rb)
    while rb % w:
        w -= 1
    rows_pad = align_up(n_t, rb)
    m_rows = _slot_rows(m_t)
    slots_pad = align_up(m_t, m_rows)
    cols = jnp.pad(cols, ((0, slots_pad - m_t), (0, rows_pad - n_t)))
    if binary:
        weights = jnp.pad(deg.astype(jnp.int32),
                          (0, rows_pad - n_t)).reshape(1, rows_pad)
    else:
        weights = jnp.pad(data.astype(jnp.float32),
                          ((0, slots_pad - m_t), (0, rows_pad - n_t)))

    # Slot-major slab streaming: bound each call's column bytes; every
    # slab is a whole number of row blocks.
    slab = slab_rows(m_t, rb, smem_cols_budget)
    outs = []
    for lo in range(0, rows_pad, slab):
        hi = min(lo + slab, rows_pad)
        call = _make_slab_call(m_t, hi - lo, k, rb, binary, stream, w,
                               interpret, ring=ring,
                               n_lines=x_packed.shape[0],
                               carriage=carriage)
        outs.append(call(
            jax.lax.slice_in_dim(cols, lo, hi, axis=1),
            jax.lax.slice_in_dim(weights, lo, hi, axis=1),
            x_packed))
    packed = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 0)
    return packed.reshape(rows_pad, k_pad)[:n_t, :k]


def sell_spmm_t_pallas(m: SellMatrix, x_t: jax.Array,
                       row_block: int = DEFAULT_ROW_BLOCK,
                       wave: int = DEFAULT_WAVE,
                       stream: Optional[bool] = None,
                       interpret: Optional[bool] = None,
                       smem_cols_budget: Optional[int] = None,
                       ring: int = DEFAULT_RING,
                       feature_dtype=None,
                       schedule=None) -> jax.Array:
    """Drop-in fused twin of ``ops.sell.sell_spmm_t``: (k, n_rows)
    feature-major output, one kernel launch stream per tier, outputs
    concatenated along the sorted row axis (tiers are contiguous runs
    of the sorted order — no scatter).

    The ``gather_budget``/``chunk`` tiling knobs of the XLA kernel have
    no counterpart here: the fused kernel's footprint is its
    ``row_block`` VMEM tile, not a materialized gather intermediate.
    ``feature_dtype="bf16"`` narrows the packed-feature carriage only;
    accumulation stays f32 and the output dtype follows ``x_t``.
    ``feature_dtype="int8"`` is the fused (q, scale) carriage: the
    table is quantized per feature row (:func:`quantize_features_t`),
    the kernel streams int8 lines, and the f32 output is rescaled
    outside the kernel.

    ``schedule`` is the graft-synth per-tier override hook: a list of
    dicts (or tier-keyed dict) whose entries may set ``row_block``,
    ``wave``, ``ring``, ``smem_cols_budget`` and ``carriage`` for one
    tier, the uniform knobs covering the rest.  Per-tier ``carriage``
    is limited to f32/bf16 (casting from the shared f32 pack); the
    int8 pair quantizes the whole table, so it is whole-call only.
    """
    k = x_t.shape[0]
    sched = _schedule_overrides(schedule)
    carriage_key, _dt = resolve_carriage_dtype(feature_dtype,
                                               default=x_t.dtype)
    # An int8 table (pre-quantized q, scale applied by the caller)
    # still accumulates — and must return — f32 weighted sums.
    out_dtype = (jnp.float32 if x_t.dtype == jnp.int8 else x_t.dtype)
    scale = None
    if carriage_key == "int8" and x_t.dtype != jnp.int8:
        if any("carriage" in ov for ov in sched.values()):
            raise ValueError(
                "int8 (q, scale) carriage quantizes the whole feature "
                "table; per-tier schedule carriage overrides cannot "
                "apply on top of it")
        x_t, scale = quantize_features_t(x_t)
    packs = {}   # carriage -> packed table, built once per call

    def packed(carriage):
        if carriage not in packs:
            packs[carriage] = pack_features_t(x_t, carriage)
        return packs[carriage]

    outs = []
    for t, cols in enumerate(m.cols):
        ov = sched.get(t, {})
        if ov.get("carriage") == "int8":
            raise ValueError(
                "per-tier carriage 'int8' is not schedulable: the "
                "(q, scale) pair quantizes the whole feature table "
                "(pass feature_dtype='int8' instead)")
        fd_t = resolve_carriage_dtype(ov.get("carriage", carriage_key))[0]
        budget_t = ov.get("smem_cols_budget")
        out_t = sell_tier_spmm_packed(
            cols, packed(fd_t), k,
            data=None if m.data is None else m.data[t],
            deg=None if m.deg is None else m.deg[t],
            row_block=ov.get("row_block", row_block),
            wave=ov.get("wave", wave), stream=stream,
            interpret=interpret,
            smem_cols_budget=(smem_cols_budget if budget_t is None
                              else budget_t),
            ring=ov.get("ring", ring), feature_dtype=fd_t)
        if scale is not None:
            out_t = out_t * scale.reshape(1, k)
        outs.append(out_t.T.astype(out_dtype))               # (k, n_t)
    if not outs:
        return jnp.zeros((k, 0), dtype=out_dtype)
    return jnp.concatenate(outs, axis=1)


def supported_feature_width(k: int) -> bool:
    """Whether the streaming (compiled-TPU) path can carry width ``k``
    — callers racing formats use this to fall back to the XLA fold
    kernel instead of tripping the lane-alignment ValueError.

    Delegates to :meth:`KernelContract.supports_k` — the SAME predicate
    ``tune/space.py`` prunes with, so kernel validation and tuner
    feasibility can never disagree (graft-kcert satellite contract).
    """
    return KERNEL_CONTRACT.supports_k(k)


@functools.partial(jax.jit, static_argnames=("row_block", "wave",
                                             "stream", "interpret",
                                             "smem_cols_budget", "ring",
                                             "feature_dtype"))
def sell_spmm_t_pallas_jit(m: SellMatrix, x_t: jax.Array,
                           row_block: int = DEFAULT_ROW_BLOCK,
                           wave: int = DEFAULT_WAVE,
                           stream: Optional[bool] = None,
                           interpret: Optional[bool] = None,
                           smem_cols_budget: Optional[int] = None,
                           ring: int = DEFAULT_RING,
                           feature_dtype: Optional[str] = None
                           ) -> jax.Array:
    return sell_spmm_t_pallas(m, x_t, row_block=row_block, wave=wave,
                              stream=stream, interpret=interpret,
                              smem_cols_budget=smem_cols_budget,
                              ring=ring, feature_dtype=feature_dtype)


# --------------------------------------------------------------------
# graft-kcert: the declared contract + concretized metas + witness the
# KC1-KC5 certifier (analysis/kernels.py) reads.
# --------------------------------------------------------------------

KERNEL_CONTRACT = KernelContract(
    name="sell_tier_spmm_packed",
    module="arrow_matrix_tpu.ops.pallas_sell",
    kind="sell_stream",
    granule=GRANULE,
    stream_k_multiple=STREAM_K_MULTIPLE,
    line_k=LINE_WORDS,
    row_blocks=(128, 256),
    rings=(1, 2, 3, 4),
    waves=(8, 16),
    ks=(16, 128),
    carriage_dtypes=("f32", "bf16", "int8"),
    accum_dtype="f32",
    smem_cols_budget=DEFAULT_SMEM_COLS_BUDGET,
    vmem_budget_bytes=VMEM_BUDGET,
)


def kcert_metas():
    """Concretized slab-call metas at the contract's representative
    parameter points: every ring depth, all row-block tiers, both
    protocol feature widths, both carriage dtypes, plus the
    interpret-only vectorized twin.  Hermetic: budgets come from the
    CONTRACT, not the env-overridable module default, so the committed
    manifest cannot drift with ``AMT_PALLAS_SELL_SMEM``."""
    budget = KERNEL_CONTRACT.smem_cols_budget
    lines = (1 << 12) // GRANULE
    points = [
        # (row_block, ring, wave, k, m_t, binary, carriage)
        (256, 2, 16, 16, 16, True, "f32"),    # the defaults
        (256, 2, 16, 128, 8, False, "f32"),   # wide k, weighted
        (128, 1, 8, 16, 5, True, "f32"),      # serial ring, small tier
        (128, 3, 8, 128, 3, True, "bf16"),    # deep ring, bf16 carriage
        (256, 4, 16, 16, 16, False, "bf16"),  # deepest ring, weighted
        (128, 4, 8, 16, 4, False, "int8"),    # fused (q, scale) carriage
    ]
    metas = []
    for rb, ring, wave, k, m_t, binary, carriage in points:
        metas.append(slab_call_meta(
            m_t, slab_rows(m_t, rb, budget), k, rb, binary, True,
            wave, ring, n_lines=lines, carriage=carriage))
    # The interpret-only vectorized twin (tier-1 correctness path).
    metas.append(slab_call_meta(
        8, 256, 16, 256, True, False, 16, 1, n_lines=lines))
    return metas


def kcert_witness():
    """KC1 boundary witness -> (ok, detail): a tiny interpret-mode
    round trip in which EVERY slot points at the last feature row (the
    upper index bound), both carriage dtypes, streamed and vectorized
    bodies bit-identical and finite."""
    rows, m_t, k, n_table = 32, 3, 16, 64
    cols = jnp.full((m_t, rows), n_table - 1, dtype=jnp.int32)
    deg = jnp.full((rows,), m_t, dtype=jnp.int32)
    x_t = jnp.asarray(
        np.linspace(-1.0, 1.0, k * n_table, dtype=np.float32)
        .reshape(k, n_table))
    try:
        for fd in ("f32", "bf16"):
            x_packed = pack_features_t(x_t, fd)
            vec = sell_tier_spmm_packed(
                cols, x_packed, k, deg=deg, stream=False,
                interpret=True, row_block=64, wave=8, feature_dtype=fd)
            st = sell_tier_spmm_packed(
                cols, x_packed, k, deg=deg, stream=True, interpret=True,
                row_block=64, wave=8, ring=2, feature_dtype=fd)
            vec, st = np.asarray(vec), np.asarray(st)
            if not np.array_equal(vec, st):
                return False, (f"stream/vectorized mismatch at the "
                               f"boundary column ({fd})")
            if not np.isfinite(st).all():
                return False, f"non-finite boundary output ({fd})"
            # 32-element witness vector: provably tiny host fetch.
            want = m_t * np.asarray(x_t[:, -1], dtype=np.float32)  # graft-lint: disable=R6
            if fd == "f32" and not np.allclose(st[0], want, rtol=1e-6):
                return False, "boundary row value off the golden"
        # int8 carriage: an already-quantized table streams and decodes
        # exactly — both bodies bit-identical AND equal to the integer
        # golden (f32 holds +/-127*m_t without rounding).
        # Witness feature table: provably tiny host fetch.
        q = jnp.asarray(np.round(np.asarray(x_t) * 127.0)  # graft-lint: disable=R6
                        .astype(np.int8))
        q_packed = pack_features_t(q, "int8")
        vec = sell_tier_spmm_packed(
            cols, q_packed, k, deg=deg, stream=False, interpret=True,
            row_block=64, wave=8, feature_dtype="int8")
        st = sell_tier_spmm_packed(
            cols, q_packed, k, deg=deg, stream=True, interpret=True,
            row_block=64, wave=8, ring=2, feature_dtype="int8")
        vec, st = np.asarray(vec), np.asarray(st)
        if not np.array_equal(vec, st):
            return False, ("stream/vectorized mismatch at the "
                           "boundary column (int8)")
        want_q = m_t * np.asarray(q[:, -1], dtype=np.float32)  # graft-lint: disable=R6
        if not np.array_equal(st[0], want_q):
            return False, "int8 boundary row decode off the golden"
    except Exception as exc:  # a raise IS the out-of-bounds evidence
        return False, f"boundary interpret run raised: {exc!r}"
    return True, ("boundary-column interpret round trip ok "
                  "(f32+bf16+int8, stream==vectorized, finite)")
