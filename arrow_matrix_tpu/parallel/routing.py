"""Explicit table-driven permutation routing over ``all_to_all``.

Under GSPMD, a sharded gather-by-permutation ``x[table]`` may lower to
an **all-gather of the whole feature array** per exchange — O(n) volume
regardless of how many rows actually cross devices (measured by
``utils.commstats``; VERDICT r1 item 5).  This module is the explicit
alternative: the TPU-native equivalent of the reference's precomputed
Alltoallv routing tables (reference arrow/arrow_dec_mpi.py:210-281,
_all_to_all_tables :325-367) — all data-dependent routing is compiled
once into static index arrays at init, and the per-iteration path is a
fixed-shape ``lax.all_to_all`` plus local gathers/scatters inside
``shard_map``:

* rows that stay on their device are applied by a local gather;
* rows that cross devices ride one all_to_all with per-device-pair
  slot budgets padded to the max pair count (the reference pads its
  Alltoallv counts the same way, arrow_dec_mpi.py:703-749 — dummy
  slots point at a zero row and scatter into a dropped row here).

Volume per device becomes O(max-pair-count x n_dev) instead of
O(total rows) — the O(moved rows) ideal up to pair-count skew.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map



@struct.dataclass
class RouteTables:
    """Static routing tables for one permutation exchange
    ``out[j] = x[table[j]]`` on a row-sharded (total, k) array.

    Index arrays all carry a leading device axis (shard it over the
    mesh's row axis).  Padding slots gather from the per-device dummy
    row (local index R) and scatter into it (dropped on exit).
    """

    local_src: jax.Array   # (n_dev, L)        local gather sources
    local_dst: jax.Array   # (n_dev, L)        local gather destinations
    send_idx: jax.Array    # (n_dev, n_dev, S) rows device s sends to d
    recv_dst: jax.Array    # (n_dev, n_dev, S) where rows from s land on d

    rows_src: int = struct.field(pytree_node=False, default=0)
    rows_dst: int = struct.field(pytree_node=False, default=0)
    n_dev: int = struct.field(pytree_node=False, default=0)

    @property
    def rows_per_dev(self) -> int:   # permutation-exchange convenience
        assert self.rows_src == self.rows_dst
        return self.rows_src

    def device_bytes_per_exchange(self, k: int, itemsize: int = 4) -> int:
        """all_to_all payload bytes per device (the padded volume)."""
        return self.send_idx.shape[1] * self.send_idx.shape[2] * k * itemsize


# Streaming kicks in automatically above 2^24 rows (where the
# in-memory build's ~13 x 8 B x total scratch reaches ~1.7 GB) with
# 2^22-row chunks; AMT_ROUTE_STREAM_MIN overrides for tests.
_STREAM_MIN = int(os.environ.get("AMT_ROUTE_STREAM_MIN", 1 << 24))
_STREAM_CHUNK = 1 << 22


def _avail_bytes() -> Optional[int]:
    try:
        return os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return None


def _slots_within_groups(keys: np.ndarray) -> np.ndarray:
    """For sorted group keys, the running index of each element within
    its group (vectorized; O(len))."""
    if keys.size == 0:
        return keys.astype(np.int64)
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    group_of = np.cumsum(np.r_[False, keys[1:] != keys[:-1]])
    return np.arange(keys.size) - starts[group_of]


def _build_route_streamed(table: np.ndarray, n_dev: int, src_total: int,
                          pad_mask: Optional[np.ndarray], r_src: int,
                          r_dst: int, chunk: int) -> RouteTables:
    """Chunked two-pass table build: scratch bounded to O(chunk).

    Pass 1 counts per-device local rows and per-(src,dst) cross rows;
    pass 2 re-derives each chunk and scatters into the final tables
    with RUNNING per-group fill counters.  Chunks are processed in j
    order and entries enumerate ascending j within each chunk, so
    every group receives its entries in globally ascending j — the
    exact order of the in-memory build (local: j ascending per device;
    cross: the (pair, j) sort).  Tables are therefore elementwise
    identical for any chunk size."""
    total = table.size

    def derive(lo: int, hi: int, count_only: bool = False):
        t = table[lo:hi]
        j = np.arange(lo, hi, dtype=np.int64)
        dst_dev = j // r_dst
        if pad_mask is None:
            live = None
            src_dev = t // r_src
        else:
            live = ~np.asarray(pad_mask[lo:hi], dtype=bool)
            src_dev = np.where(live, t // r_src, dst_dev)
        checked = t if live is None else t[live]
        if not ((checked >= 0) & (checked < src_total)).all():
            raise ValueError("gather table entries outside [0, src_total)")
        if count_only:   # pass 1 discards the offsets — skip the work
            return dst_dev, src_dev, None, None
        if live is None:
            src_off = t % r_src
        else:
            src_off = np.where(live, t % r_src, r_src)
        return dst_dev, src_dev, src_off, j % r_dst

    loc_counts = np.zeros(n_dev, dtype=np.int64)
    pair_counts = np.zeros(n_dev * n_dev, dtype=np.int64)
    for lo in range(0, total, chunk):
        hi = min(total, lo + chunk)
        dst_dev, src_dev, _, _ = derive(lo, hi, count_only=True)
        is_local = dst_dev == src_dev
        loc_counts += np.bincount(dst_dev[is_local], minlength=n_dev)
        pair_counts += np.bincount(
            (src_dev * n_dev + dst_dev)[~is_local],
            minlength=n_dev * n_dev)

    l_max = int(loc_counts.max()) if loc_counts.size else 0
    s_max = int(pair_counts.max())
    out_bytes = 4 * (2 * n_dev * l_max + 2 * n_dev * n_dev * s_max)
    avail = _avail_bytes()
    if avail is not None and out_bytes > 0.8 * avail:
        import warnings

        warnings.warn(
            f"build_route (streamed) at {total} rows: the OUTPUT tables "
            f"need ~{out_bytes / 2**30:.0f} GB but only "
            f"{avail / 2**30:.0f} GB is free — shard the exchange "
            f"(feat_axis / per-level meshes) or use a fatter build host")
    local_src = np.full((n_dev, l_max), r_src, dtype=np.int32)
    local_dst = np.full((n_dev, l_max), r_dst, dtype=np.int32)
    send_idx = np.full((n_dev, n_dev, s_max), r_src, dtype=np.int32)
    recv_dst = np.full((n_dev, n_dev, s_max), r_dst, dtype=np.int32)

    fill_loc = np.zeros(n_dev, dtype=np.int64)
    fill_pair = np.zeros(n_dev * n_dev, dtype=np.int64)
    for lo in range(0, total, chunk):
        hi = min(total, lo + chunk)
        dst_dev, src_dev, src_off, dst_off = derive(lo, hi)
        is_local = dst_dev == src_dev
        loc = np.nonzero(is_local)[0]
        if loc.size:
            dev = dst_dev[loc]            # ascending (j-contiguous chunk)
            slot = fill_loc[dev] + _slots_within_groups(dev)
            local_src[dev, slot] = src_off[loc]
            local_dst[dev, slot] = dst_off[loc]
            fill_loc += np.bincount(dev, minlength=n_dev)
        cross = np.nonzero(~is_local)[0]
        if cross.size:
            pair = (src_dev[cross] * n_dev + dst_dev[cross])
            # In-chunk (pair, j) sort.  The packed key gives the
            # in-chunk index the low 32 bits; an explicit stream_chunk
            # above 2^32 would spill it into the pair field and
            # silently corrupt slot assignment — fall back to the real
            # lexsort there (same guard as the in-memory path).
            if hi - lo <= (1 << 32):
                order = np.argsort((pair << 32) | cross)
            else:
                order = np.lexsort((cross, pair))
            cross = cross[order]
            pair = pair[order]
            slot = fill_pair[pair] + _slots_within_groups(pair)
            s, d = src_dev[cross], dst_dev[cross]
            send_idx[s, d, slot] = src_off[cross]
            recv_dst[d, s, slot] = dst_off[cross]
            fill_pair += np.bincount(pair, minlength=n_dev * n_dev)

    return RouteTables(local_src=jnp.asarray(local_src),
                       local_dst=jnp.asarray(local_dst),
                       send_idx=jnp.asarray(send_idx),
                       recv_dst=jnp.asarray(recv_dst),
                       rows_src=r_src, rows_dst=r_dst, n_dev=n_dev)


def build_route(table: np.ndarray, n_dev: int,
                src_total: Optional[int] = None,
                pad_mask: Optional[np.ndarray] = None,
                stream_chunk: Optional[int] = None) -> RouteTables:
    """Compile a global gather table ``out[j] = x[table[j]]`` into
    RouteTables.

    For a permutation exchange (multi_level.compose_routing) source and
    destination sizes coincide; ``src_total`` supports rectangular
    exchanges between carried orderings of different padded lengths
    (SellMultiLevel).  Destination positions flagged by ``pad_mask``
    (tier padding — their values are never consumed) are routed from
    the LOCAL dummy row instead of their table entry, so they cost no
    cross-device slots and come out zero.

    Above ``_STREAM_MIN`` rows (or when ``stream_chunk`` is given) the
    build STREAMS in j-order chunks — two passes with running per-group
    counters replace the whole-table derived arrays and global sort,
    bounding scratch to O(chunk) + the output tables (VERDICT r4 item
    4).  The tables are elementwise IDENTICAL to the in-memory build:
    both enumerate j ascending within every device / device-pair
    group, so slot assignment never depends on how j is partitioned
    (pinned by tests/test_routing.py::test_streamed_build_identical;
    measured ~6x peak-RSS cut at 2^26 in
    tools/measure_routing_build.py).
    """
    from arrow_matrix_tpu.faults import inject as _fault_hook

    _fault_hook("routing.build_route")
    table = np.asarray(table, dtype=np.int64)
    total = table.size
    if src_total is None:
        src_total = total
    if total % n_dev != 0 or src_total % n_dev != 0:
        raise ValueError(f"{total}/{src_total} rows not divisible by "
                         f"{n_dev} devices")
    r_dst = total // n_dev
    r_src = src_total // n_dev
    if stream_chunk is None and total >= _STREAM_MIN:
        stream_chunk = _STREAM_CHUNK
    if stream_chunk is not None and total > stream_chunk:
        return _build_route_streamed(table, n_dev, src_total, pad_mask,
                                     r_src, r_dst, stream_chunk)
    # Host-global build guard (VERDICT r3 item 9): this composes ~13
    # full-length int64 vectors on one host — measured linear at
    # ~12 s / 2^26 rows and ~13 x 8 B x total peak incremental RSS
    # (tools/measure_routing_build.py; ~10 GB at 10^8 rows).  Warn
    # LOUDLY before an allocation that would swap/OOM rather than die
    # opaquely inside numpy.  (Reachable only when streaming is
    # explicitly disabled via a giant stream_chunk.)
    est_bytes = 13 * 8 * total
    avail = _avail_bytes()
    if avail is not None and est_bytes > 0.8 * avail:
        import warnings

        warnings.warn(
            f"build_route at {total} rows needs ~{est_bytes / 2**30:.0f}"
            f" GB of host scratch but only {avail / 2**30:.0f} GB is "
            f"free — the host-global table composition is the known "
            f"scale bound (PERFORMANCE.md routing-build row); shard "
            f"the exchange (feat_axis / per-level meshes) or use a "
            f"fatter build host")

    live = np.ones(total, dtype=bool) if pad_mask is None else ~np.asarray(
        pad_mask, dtype=bool)
    if not ((table[live] >= 0) & (table[live] < src_total)).all():
        # Fail loudly at build time: a clamped bad entry would deliver
        # a wrong row silently at runtime.
        raise ValueError("gather table entries outside [0, src_total)")
    # int32 derived arrays below 2^31 rows: the build is ~13
    # full-length passes (measured linear, tools/measure_routing_build
    # .py), so halving the element width halves its traffic.
    idx_dt = np.int32 if max(total, src_total) < np.iinfo(np.int32).max \
        else np.int64
    j = np.arange(total, dtype=idx_dt)
    dst_dev = (j // r_dst).astype(idx_dt, copy=False)
    src_dev = np.where(live, table // r_src, 0).astype(idx_dt,
                                                      copy=False)
    src_off = (table % r_src).astype(idx_dt, copy=False)
    dst_off = (j % r_dst).astype(idx_dt, copy=False)
    if pad_mask is not None:
        src_dev = np.where(live, src_dev, dst_dev).astype(idx_dt,
                                                         copy=False)
        src_off = np.where(live, src_off, r_src).astype(idx_dt,
                                                        copy=False)
    is_local = dst_dev == src_dev

    # Local part: per-device padded (L) gather lists (j ascending).
    loc = np.nonzero(is_local)[0]          # already ascending in j
    loc_counts = np.bincount(dst_dev[loc], minlength=n_dev)
    l_max = int(loc_counts.max()) if loc.size else 0
    local_src = np.full((n_dev, l_max), r_src, dtype=np.int32)
    local_dst = np.full((n_dev, l_max), r_dst, dtype=np.int32)
    if loc.size:
        slot = _slots_within_groups(dst_dev[loc])
        local_src[dst_dev[loc], slot] = src_off[loc]
        local_dst[dst_dev[loc], slot] = dst_off[loc]

    # Cross part: per-(src, dst) padded (S) slot lists.  Order within a
    # pair is arbitrary but must MATCH between send and recv sides (both
    # enumerate j in ascending order within the pair).
    cross = np.nonzero(~is_local)[0]
    s_max = 0
    send_idx = np.full((n_dev, n_dev, max(s_max, 0)), r_src, dtype=np.int32)
    recv_dst = np.full((n_dev, n_dev, max(s_max, 0)), r_dst, dtype=np.int32)
    if cross.size:
        # One combined-key sort replaces the 3-key lexsort (identical
        # order: src_dev major, dst_dev, then ascending j — pair ids
        # fit 32 bits, j fits 32 bits below 2^31 rows).
        pair = (src_dev[cross].astype(np.int64) * n_dev
                + dst_dev[cross])
        # keys are unique (j embedded), so the default sort is already
        # deterministic — no stable mergesort needed.  The packing
        # gives j the LOW 32 bits: past 2^32 entries j would spill
        # into the pair bits and silently break the claimed lexsort
        # equivalence, so fall back to the real lexsort there
        # (ADVICE r4; the int64 idx_dt switch above survives to 2^63).
        if cross[-1] < (1 << 32):
            order = np.argsort((pair << 32) | cross.astype(np.int64))
        else:
            order = np.lexsort((cross, pair))
        cross = cross[order]
        s, d = src_dev[cross], dst_dev[cross]
        slot = _slots_within_groups(s * n_dev + d)
        s_max = int(slot.max()) + 1
        send_idx = np.full((n_dev, n_dev, s_max), r_src, dtype=np.int32)
        recv_dst = np.full((n_dev, n_dev, s_max), r_dst, dtype=np.int32)
        send_idx[s, d, slot] = src_off[cross]
        recv_dst[d, s, slot] = dst_off[cross]

    return RouteTables(local_src=jnp.asarray(local_src),
                       local_dst=jnp.asarray(local_dst),
                       send_idx=jnp.asarray(send_idx),
                       recv_dst=jnp.asarray(recv_dst),
                       rows_src=r_src, rows_dst=r_dst, n_dev=n_dev)


def shard_route(route: RouteTables, mesh: Mesh,
                axis: str = "blocks") -> RouteTables:
    """Place every table leaf sharded on its leading device axis (one
    recipe for all callers)."""
    from jax.sharding import NamedSharding

    from arrow_matrix_tpu.parallel.mesh import put_global

    shard = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda a: put_global(np.asarray(a), shard), route)


def routed_take(x: jax.Array, route: RouteTables, mesh: Mesh,
                axis: str = "blocks",
                feat_axis: Optional[str] = None,
                init: Optional[jax.Array] = None) -> jax.Array:
    """``out[j] = x[table[j]]`` via the compiled route (jit-safe).

    ``x`` is (total, k) sharded on rows over ``axis`` (and optionally on
    columns over ``feat_axis``); the exchange is one fixed-shape
    all_to_all + local gather/scatter per device.

    ``init`` seeds the output carriage instead of zeros: a staged
    sub-exchange (graft-reshard) scatters its disjoint slice of rows
    straight into the running accumulator — no per-stage full-size
    zeros buffer and no add, so the staged path's peak temp stays one
    accumulator plus ONE stage's bounded payload.
    """
    r_src, r_dst = route.rows_src, route.rows_dst

    def local_fn(xl, accl, local_src, local_dst, send_idx, recv_dst):
        # Per-device operands (leading device axis stripped to size 1).
        xl = xl.reshape(r_src, -1)
        xe = jnp.concatenate(
            [xl, jnp.zeros((1, xl.shape[1]), xl.dtype)], axis=0)
        if accl is None:
            out = jnp.zeros((r_dst + 1, xl.shape[1]), xl.dtype)
        else:
            out = jnp.concatenate(
                [accl.reshape(r_dst, -1),
                 jnp.zeros((1, xl.shape[1]), xl.dtype)], axis=0)
        # Rows that stay local.
        out = out.at[local_dst[0]].set(xe[local_src[0]])
        # Rows that cross devices: device p sends payload[d] to d and
        # receives recv[s] from s, landing at recv_dst[p, s, slot].
        payload = xe[send_idx[0]]                       # (n_dev, S, k)
        if payload.shape[1] > 0:
            recv = jax.lax.all_to_all(payload, axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            out = out.at[recv_dst[0].reshape(-1)].set(
                recv.reshape(-1, xl.shape[1]))
        return out[:r_dst]

    spec = P(axis)
    x_spec = P(axis, feat_axis) if feat_axis else spec
    if init is None:
        fn = shard_map(
            lambda xl, a, b, c, d: local_fn(xl, None, a, b, c, d),
            mesh=mesh, in_specs=(x_spec, spec, spec, spec, spec),
            out_specs=x_spec, check_vma=False)
        return fn(x, route.local_src, route.local_dst, route.send_idx,
                  route.recv_dst)
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(x_spec, x_spec, spec, spec, spec, spec),
                   out_specs=x_spec,
                   check_vma=False)
    return fn(x, init, route.local_src, route.local_dst,
              route.send_idx, route.recv_dst)


@struct.dataclass
class StagedRoute:
    """A permutation exchange split into S bounded-scratch
    sub-exchanges (graft-reshard consumer b): each stage is a valid
    :class:`RouteTables` whose all_to_all payload (send + recv) fits
    ``scratch_budget_bytes`` at feature width ``budget_k``.  Stage 0
    carries the local gather; later stages have empty local tables and
    a disjoint slice of the cross-device slots.  Every destination row
    is written by exactly ONE stage (the exchange is a partial
    permutation and unwritten rows stay zero), so the staged result is
    the f32-exact SUM of the per-stage outputs — bit-identical to the
    one-shot exchange."""

    stages: tuple   # tuple[RouteTables, ...] (pytree)

    rows_src: int = struct.field(pytree_node=False, default=0)
    rows_dst: int = struct.field(pytree_node=False, default=0)
    n_dev: int = struct.field(pytree_node=False, default=0)
    scratch_budget_bytes: int = struct.field(pytree_node=False, default=0)
    budget_k: int = struct.field(pytree_node=False, default=0)

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    def device_bytes_per_exchange(self, k: int, itemsize: int = 4) -> int:
        """Peak per-stage all_to_all payload bytes per device."""
        return max((s.device_bytes_per_exchange(k, itemsize)
                    for s in self.stages), default=0)


def split_route_stages(route: RouteTables, k: int,
                       scratch_budget_bytes: int,
                       itemsize: int = 4) -> StagedRoute:
    """Split one compiled route into bounded-scratch stages.

    One stage's scratch is its send payload plus its received payload:
    ``2 x n_dev x S_stage x k x itemsize`` per device.  Raises loudly
    when the budget cannot carry even ONE slot per device pair — an
    over-budget stage is never emitted (the H7 contract,
    analysis/prove.py).  Slots are already padded per device pair, so
    slicing the slot axis keeps send/recv sides aligned; dummy slots
    stay dummy in whichever stage they land.
    """
    n_dev = route.n_dev
    S = int(route.send_idx.shape[-1])
    slot_bytes = 2 * n_dev * k * itemsize
    s_stage = int(scratch_budget_bytes) // slot_bytes
    if s_stage < 1:
        raise ValueError(
            f"scratch budget {scratch_budget_bytes} B cannot carry one "
            f"exchange slot per device pair at k={k} (needs "
            f"{slot_bytes} B: n_dev={n_dev} rows sent + received) — "
            f"raise the budget or narrow k; refusing to emit an "
            f"over-budget stage")

    def sub(lo: int, hi: int, with_local: bool) -> RouteTables:
        width = 0 if with_local else int(route.local_src.shape[-1])
        return RouteTables(
            local_src=route.local_src[:, width:],
            local_dst=route.local_dst[:, width:],
            send_idx=route.send_idx[:, :, lo:hi],
            recv_dst=route.recv_dst[:, :, lo:hi],
            rows_src=route.rows_src, rows_dst=route.rows_dst,
            n_dev=n_dev)

    bounds = list(range(0, max(S, 1), s_stage)) or [0]
    stages = tuple(
        sub(lo, min(lo + s_stage, S), with_local=(i == 0))
        for i, lo in enumerate(bounds))
    return StagedRoute(stages=stages, rows_src=route.rows_src,
                       rows_dst=route.rows_dst, n_dev=n_dev,
                       scratch_budget_bytes=int(scratch_budget_bytes),
                       budget_k=int(k))


def staged_routed_take(x: jax.Array, sroute: StagedRoute, mesh: Mesh,
                       axis: str = "blocks",
                       feat_axis: Optional[str] = None) -> jax.Array:
    """Run a :class:`StagedRoute` as S sequential sub-exchanges.

    Each destination row is written by exactly one stage, and later
    stages scatter their disjoint rows straight into the running
    accumulator (``init=``) — pure row copies, no arithmetic at all,
    so the staged result is bit-identical to the one-shot
    ``routed_take``.  ``optimization_barrier`` pins stage order so the
    compiler cannot hoist all payloads live at once: peak collective
    scratch stays one stage's send+recv (proven per stage by H7)."""
    acc = routed_take(x, sroute.stages[0], mesh, axis,
                      feat_axis=feat_axis)
    for st in sroute.stages[1:]:
        acc, x = jax.lax.optimization_barrier((acc, x))
        acc = routed_take(x, st, mesh, axis, feat_axis=feat_axis,
                          init=acc)
    return acc


def overlap_slices(k: int, overlap_slabs: int) -> list:
    """Static sub-slab bounds of the feature axis for the chunked
    overlap schedule (graft-stream): split ``k`` feature rows into
    ``overlap_slabs`` equal contiguous slabs so each slab's exchange is
    a separate collective — slab i+1's dispatch is dataflow-independent
    of slab i's compute, which is what lets XLA's latency-hiding
    scheduler run them concurrently.  Everything here is trace-time
    static (``k`` is a shape), so sweeping S never recompiles within
    one S.
    """
    s = int(overlap_slabs)
    if s <= 1:
        return [(0, k)]
    if s > k or k % s:
        raise ValueError(
            f"overlap_slabs={s} must divide the feature width k={k} "
            f"(equal static sub-slabs; pick S from the divisors of k)")
    step = k // s
    return [(i * step, (i + 1) * step) for i in range(s)]


def repl_slab_width(k: int, repl: int) -> int:
    """Per-replica feature-slab width for the 2.5D replicated
    executors (graft-repl): replica group j owns the static column
    slab ``[j*k/c, (j+1)*k/c)``.  SpMM is column-separable, so the
    slab split never regroups any f32 accumulation — the replicated
    run is bit-identical to c=1.  Mirrors ``overlap_slices``
    validation: c must divide k."""
    c = int(repl)
    if c <= 1:
        return int(k)
    if c > k or k % c:
        raise ValueError(
            f"repl={c} must divide the feature width k={k} "
            f"(each replica group owns an equal static column slab)")
    return k // c


def repl_slab_take_t(xt: jax.Array, mesh: Mesh, axis: str,
                     repl_axis: str) -> jax.Array:
    """(k, total) -> (k/c, total): keep only the feature slab this
    replica group owns.  The result is intentionally DIVERGENT across
    ``repl_axis`` (each group holds different rows under the same
    shape/spec — legal under check=False shard_map); every downstream
    exchange over ``axis`` then moves a 1/c-width payload within its
    own replica group."""
    c = mesh.shape[repl_axis]
    kc = repl_slab_width(xt.shape[0], c)

    def local_fn(xl):
        j = jax.lax.axis_index(repl_axis)
        return jax.lax.dynamic_slice_in_dim(xl, j * kc, kc, axis=0)

    return shard_map(local_fn, mesh=mesh, in_specs=(P(None, axis),),
                     out_specs=P(None, axis),
                     check_vma=False)(xt)


def repl_slab_scatter_t(slab: jax.Array, k: int, mesh: Mesh, axis: str,
                        repl_axis: str) -> jax.Array:
    """(k/c, total) per-replica slabs -> (k, total): replica group j's
    slab lands back at feature rows ``[j*k/c, (j+1)*k/c)``, zeros
    elsewhere.  The output stays divergent across ``repl_axis`` (each
    group carries its own slab + zeros) — exactly the partial-carry
    form ``repl_merge_t``'s masked psum merges."""
    c = mesh.shape[repl_axis]
    kc = slab.shape[0]
    if kc * c != k:
        raise ValueError(f"slab width {kc} x repl={c} != k={k}")

    def local_fn(sl):
        j = jax.lax.axis_index(repl_axis)
        out = jnp.zeros((k, sl.shape[1]), sl.dtype)
        return jax.lax.dynamic_update_slice_in_dim(out, sl, j * kc,
                                                   axis=0)

    return shard_map(local_fn, mesh=mesh, in_specs=(P(None, axis),),
                     out_specs=P(None, axis),
                     check_vma=False)(slab)


def repl_merge_t(ct: jax.Array, mesh: Mesh, axis: str,
                 repl_axis: str) -> jax.Array:
    """Final masked ``psum`` over the replica axis merging the
    per-replica partial carries into one truly replicated (k, total)
    array: replica group j contributes only its owned feature slab
    (everything else is masked to zero), so every output element has
    exactly ONE real addend and c-1 zeros — the merge is f32-exact.
    This is the 2.5D scheme's final reduction; its cost is reported as
    ``reduce_bytes`` in the comm accounts, separate from the per-step
    exchange bytes it buys down."""
    c = mesh.shape[repl_axis]
    kc = repl_slab_width(ct.shape[0], c)

    def local_fn(cl):
        j = jax.lax.axis_index(repl_axis)
        owner = jnp.arange(cl.shape[0]) // kc
        masked = jnp.where((owner == j)[:, None], cl,
                           jnp.zeros_like(cl))
        return jax.lax.psum(masked, repl_axis)

    return shard_map(local_fn, mesh=mesh, in_specs=(P(None, axis),),
                     out_specs=P(None, axis),
                     check_vma=False)(ct)


def routed_take_t(xt: jax.Array, route: RouteTables, mesh: Mesh,
                  axis: str = "blocks",
                  feat_axis: Optional[str] = None,
                  overlap_slabs: int = 1) -> jax.Array:
    """Feature-major twin of ``routed_take``: ``out[:, j] =
    xt[:, table[j]]`` on a (k, total) array sharded on axis 1 — the
    exchange for the padding-free carried layouts
    (parallel/sell_slim.py).

    ``feat_axis`` additionally shards the feature rows (axis 0): the
    tables are per-device along ``axis`` and independent of feature
    rows, so each feature slice runs its own identical exchange — the
    k-tiling axis composes with the explicit routing for free.

    ``overlap_slabs`` splits the exchange into S independent
    sub-exchanges along the feature axis (``overlap_slices``): a caller
    interleaving its own compute between them gets slab i+1's
    all_to_all in flight while slab i is consumed."""
    if overlap_slabs > 1:
        if feat_axis is not None:
            raise ValueError(
                "overlap_slabs composes with the unsharded feature "
                "axis (feat_axis=None): a feat-sharded slab would "
                "re-split an already-distributed dimension")
        outs = [routed_take_t(xt[lo:hi], route, mesh, axis)
                for lo, hi in overlap_slices(xt.shape[0], overlap_slabs)]
        return jnp.concatenate(outs, axis=0)
    r_src, r_dst = route.rows_src, route.rows_dst

    def local_fn(xl, local_src, local_dst, send_idx, recv_dst):
        k = xl.shape[0]
        xe = jnp.concatenate(
            [xl, jnp.zeros((k, 1), xl.dtype)], axis=1)  # (k, r_src+1)
        out = jnp.zeros((k, r_dst + 1), xl.dtype)
        out = out.at[:, local_dst[0]].set(xe[:, local_src[0]])
        payload = xe[:, send_idx[0].reshape(-1)]        # (k, n_dev*S)
        S = send_idx.shape[-1]
        if S > 0:
            payload = payload.reshape(k, route.n_dev, S)
            recv = jax.lax.all_to_all(payload, axis, split_axis=1,
                                      concat_axis=1, tiled=False)
            out = out.at[:, recv_dst[0].reshape(-1)].set(
                recv.reshape(k, -1))
        return out[:, :r_dst]

    spec = P(axis)
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(P(feat_axis, axis), spec, spec, spec, spec),
                   out_specs=P(feat_axis, axis),
                   check_vma=False)
    return fn(xt, route.local_src, route.local_dst, route.send_idx,
              route.recv_dst)


def take(x: jax.Array, table_or_route, mesh: Optional[Mesh] = None,
         axis: str = "blocks") -> jax.Array:
    """Dispatch: RouteTables -> routed all_to_all exchange; StagedRoute
    -> bounded-scratch staged exchange (graft-reshard); plain index
    array -> jnp.take (GSPMD decides — may all-gather)."""
    if isinstance(table_or_route, StagedRoute):
        return staged_routed_take(x, table_or_route, mesh, axis)
    if isinstance(table_or_route, RouteTables):
        return routed_take(x, table_or_route, mesh, axis)
    out = jnp.take(x, table_or_route, axis=0)
    if mesh is not None and x.ndim == 2 and len(mesh.axis_names) > 1:
        # On a multi-axis mesh, jax 0.4.37's partitioner miscompiles the
        # fused gather chain unless the output's spec pins *every* dim
        # (row-only or UNCONSTRAINED specs still produce wrong rows).
        feat = tuple(a for a in mesh.axis_names if a != axis)
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, P(axis, feat)))
    return out
