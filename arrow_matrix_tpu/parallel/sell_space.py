"""Space-shared feature-major execution: K levels on disjoint device
groups in the padding-free SELL layouts.

Completes the execution-mode matrix: ``MultiLevelArrow`` /
``SellMultiLevel`` time-share all devices over the levels sequentially;
``SpaceSharedArrow`` runs the levels concurrently on disjoint groups in
the stacked row-major layouts; this module is the concurrent mode on
the slot-major/feature-major layouts the measured layout-padding law
demands (PERFORMANCE.md).  Reference counterpart: the K arrow matrices
of one decomposition running simultaneously on disjoint MPI rank
groups with permutation-routed feature/result exchanges
(arrow/arrow_dec_mpi.py:106-177, 210-281, 404-550).

Mapping to SPMD:

* mesh ``("lvl", "blocks")`` — one ``lvl`` slice per level (the
  reference's per-matrix ``Comm.Create`` groups), ``blocks`` the
  feature-major slim layout axis within each group;
* every level's body/head SELL operators stack on ONE leading
  (level x device) axis sharded over both mesh axes jointly
  (``P(("lvl", "blocks"))``), so the whole decomposition is a single
  SPMD program: tier ladders and tier row counts are unified across
  levels AND devices by one ``_pack_shard_tiers`` call over the
  flattened share list (the degree-ladder trick of sell_slim.py, one
  dimension higher), and every group runs the max halo reach over
  levels — converged levels pay the unified exchange, the structural
  cost of space-sharing (SpaceSharedArrow pays the analogous uniform
  banded width);
* the reference's K-1 sequential backward/forward exchange chains
  collapse to composed static tables exactly as in SpaceSharedArrow:
  ``bwd0[g]`` maps level-0 carried positions to level-g partial
  positions (one gather + a sum over groups = the cross-group
  reduction), ``fwd0[g]`` re-distributes the aggregate into every
  group's carried ordering.  Both compose the level permutations AND
  the per-shard tier orderings, so the tier sorts stay free.

Carried state is feature-major ``(k, K * total_out)`` — all K carried
orderings materialized, level g's slice in level-g order (the
reference forward-propagates X to every matrix before the first
compute; each group materializes its own ordering up front).
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from arrow_matrix_tpu.io.graphio import num_rows
from arrow_matrix_tpu.ops.ell import align_up
from arrow_matrix_tpu.parallel.mesh import (fetch_replicated, make_mesh,
                                             put_global)
from arrow_matrix_tpu.parallel.multi_level import resolve_feature_dtype
from arrow_matrix_tpu.parallel.sell_slim import (
    _banded_reach,
    _hops_rem,
    global_max_reach,
    local_shard_coords,
    _carried_maps,
    _gather_carried,
    _live,
    _pack_shard_tiers,
    mesh_gather_budget,
    _positions_inv,
    _remap_body_cols,
    _remap_head_cols,
    _scatter_carried,
    _SliceSource,
    _slim_local_step,
    _slim_shares,
    degree_ladder,
    resolve_ladder,
    shard_map,
)


class SellSpaceShared:
    """K decomposition levels concurrent on disjoint device groups of a
    ("lvl", "blocks") mesh, in the padding-free SELL layouts.

    Same feature API as the other orchestrations: ``set_features`` /
    ``step`` / ``run`` / ``gather_result``; carried state is
    feature-major (k, K * total_out).
    """

    def __init__(self, levels, width: int, mesh: Optional[Mesh] = None,
                 lvl_axis: str = "lvl", axis: str = "blocks",
                 dtype=np.float32, binary="auto",
                 feat_axis: Optional[str] = None, feature_dtype=None,
                 ladder=None):
        """``feat_axis`` additionally shards the feature rows (the
        k-dimension tiling axis, reference GPU feature blocking) — with
        ``lvl`` and ``blocks`` that makes a 3-axis sharding: levels x
        block-rows x feature columns.  Neither the per-group compute
        nor the cross-group exchanges mix feature rows, so the axis
        composes transparently."""
        from arrow_matrix_tpu.parallel.multi_level import pad_permutation

        self.feature_dtype = resolve_feature_dtype(feature_dtype)

        if not levels:
            raise ValueError("empty decomposition")
        self.feat_axis = feat_axis
        if feat_axis is not None and (mesh is None
                                      or feat_axis not in mesh.shape):
            raise ValueError(
                f"feat_axis={feat_axis!r} requires an explicit mesh "
                f"containing that axis (e.g. make_mesh((K, b, f), "
                f"('lvl', 'blocks', {feat_axis!r})))")
        k_levels = len(levels)
        if mesh is None:
            n_all = len(jax.devices())
            if n_all % k_levels != 0:
                raise ValueError(
                    f"{n_all} devices not divisible by {k_levels} levels; "
                    f"pass an explicit mesh")
            mesh = make_mesh((k_levels, n_all // k_levels),
                             (lvl_axis, axis))
        if mesh.shape[lvl_axis] != k_levels:
            raise ValueError(
                f"mesh axis {lvl_axis!r} has size {mesh.shape[lvl_axis]}, "
                f"need one slice per level ({k_levels})")
        self.mesh = mesh
        self.lvl_axis = lvl_axis
        self.axis = axis
        self.k_levels = k_levels
        self.gather_budget = mesh_gather_budget(mesh)
        n_dev = mesh.shape[axis]
        w = width

        self.n = num_rows(levels[0].matrix)
        L = max(align_up(-(-self.n // n_dev), w), w)
        total = L * n_dev
        # Streaming sources (sell_slim._SliceSource): memmapped-triplet
        # levels build device share by device share, never
        # materializing a level on the host.
        srcs = [_SliceSource(lvl.matrix, n_dev, w, shard_len=L)
                for lvl in levels]
        if binary is False:
            self.binary = False
        else:
            self.binary = all(s.resolve_binary(binary) for s in srcs)

        # Per-host build (see sell_slim.build_slim_level): when the
        # mesh spans processes, each process scans/constructs/validates
        # only the (level, device) shards its devices own; the
        # flattened share index is g * n_dev + d, matching the
        # P((lvl, blocks)) placement below.
        local_pairs = local_shard_coords(mesh, lvl_axis, axis)

        def level_mat(g):
            return (None if local_pairs is None
                    else {d for gg, d in local_pairs if gg == g})

        # One SPMD program runs every group, so all levels share the
        # max halo reach (see module docstring).
        reach = max(_banded_reach(s, w, shard_ids=level_mat(g))
                    for g, s in enumerate(srcs))
        if local_pairs is not None:
            reach = global_max_reach(reach)
        hops, rem = _hops_rem(reach, L, n_dev)
        shares = [_slim_shares(s, w, hops, materialize=level_mat(g))
                  for g, s in enumerate(srcs)]
        body_flat = [s for body, _ in shares for s in body]
        head_flat = [s for _, head in shares for s in head]
        flat_mat = (None if local_pairs is None
                    else {g * n_dev + d for g, d in local_pairs})

        growth, align = resolve_ladder(ladder)
        ladder_body = degree_ladder(max(
            (int(np.diff(s.indptr).max()) if s.nnz else 0)
            for s in body_flat), growth, align)
        # Per-level global head degrees from the shares (columns
        # partition [0, total)) — no second head-block read.
        head_degs = [sum(np.diff(h.indptr) for h in heads)
                     for _, heads in shares]
        ladder_head = degree_ladder(max(
            (int(d.max()) if d.size else 0) for d in head_degs),
            growth, align)

        # ONE packing call over the flattened (level, device) share
        # list unifies tier shapes across everything; each level group
        # keys its head ordering on its own global head degrees
        # (device-independent within the group — its psum needs that).
        body, body_order, rows_out = _pack_shard_tiers(
            body_flat, ladder_body, self.binary, dtype)
        head, head_order, _ = _pack_shard_tiers(
            head_flat, ladder_head, self.binary, dtype,
            shared_degrees=[head_degs[g]
                            for g in range(k_levels)
                            for _ in range(n_dev)])
        for g in range(k_levels):
            grp = head_order[g * n_dev:(g + 1) * n_dev]
            if not np.array_equal(body_order[g * n_dev, :w],
                                  np.arange(w)):
                raise AssertionError(
                    f"level {g}: device 0's head rows must lead its "
                    f"tiered ordering")
            if not np.all(grp[0] == grp):
                raise AssertionError(
                    f"level {g}: head tier ordering must be "
                    f"device-independent within the group")

        inv = _positions_inv(body_order, L)
        body = _remap_body_cols(body, inv, L, rows_out, w, hops,
                                materialize=flat_mat)
        head = _remap_head_cols(head, inv, L, rows_out,
                                materialize=flat_mat)
        # head_unsort[g][j] = tiered head position of head row j.  The
        # cross-group tier unification maxes tier counts over ALL
        # groups, so a group whose bucket is smaller gets -1 padding
        # slots INTERLEAVED in its head tiers — sell_slim's
        # argsort-of-prefix shortcut (valid there: within one level the
        # shared-degree buckets are identical across devices, so no
        # padding exists) would scramble here.
        head_unsort = np.zeros((k_levels, w), dtype=np.int32)
        for g in range(k_levels):
            ho = head_order[g * n_dev]
            live = ho >= 0
            head_unsort[g, ho[live]] = np.flatnonzero(live).astype(
                np.int32)

        self.width = w
        self.rows_out = rows_out
        self.shard_len = L
        self.n_dev = n_dev
        self.hops = hops
        self.total_out = rows_out * n_dev          # per level
        T = self.total_out

        # Carried-position <-> original-row maps per level
        # (_carried_maps on each level's slice of the flattened share
        # axis, s = g*n_dev + d).
        orig_of_pos, pos_of_orig = [], []
        for g, lvl in enumerate(levels):
            perm = pad_permutation(np.asarray(lvl.permutation), total)
            oop, poo = _carried_maps(
                perm, body_order[g * n_dev:(g + 1) * n_dev], L, total)
            orig_of_pos.append(oop)
            pos_of_orig.append(poo)
        self._orig_of_pos = orig_of_pos

        # Composed cross-group tables with WITHIN-LEVEL indices (each
        # group reorders its own partial into level-0 order before the
        # cross-group sum — a group-local all-to-all, not a cross-slice
        # gather; the stacked SpaceSharedArrow lowers the same way).
        # Tier padding routes from position 0 — never consumed by any
        # live slot (SellMultiLevel's established convention).
        bwd0 = np.zeros((k_levels, T), dtype=np.int64)
        fwd0 = np.zeros((k_levels, T), dtype=np.int64)
        oop0, poo0 = orig_of_pos[0], pos_of_orig[0]
        for g in range(k_levels):
            idx = np.where(oop0 >= 0,
                           pos_of_orig[g][np.minimum(oop0, total - 1)], 0)
            bwd0[g] = np.maximum(idx, 0)
            idxf = np.where(
                orig_of_pos[g] >= 0,
                poo0[np.minimum(orig_of_pos[g], total - 1)], 0)
            fwd0[g] = np.maximum(idxf, 0)

        both = NamedSharding(mesh, P((lvl_axis, axis)))
        lvl_only = NamedSharding(mesh, P(lvl_axis))
        self._feat_sharding = NamedSharding(
            mesh, P(feat_axis, (lvl_axis, axis)))
        self.body = jax.tree_util.tree_map(
            lambda a_: put_global(a_, both), body)
        self.head = jax.tree_util.tree_map(
            lambda a_: put_global(a_, both), head)
        self.head_unsort = put_global(head_unsort, lvl_only)
        self.orig_pos = put_global(inv.astype(np.int32), both)
        self.bwd0 = put_global(bwd0.astype(np.int32), lvl_only)
        self.fwd0 = put_global(fwd0.astype(np.int32), lvl_only)

        # Paper cost model of the cross-group routing in row-units
        # (k=1, itemsize=1): the exchanges are star-shaped (every group
        # reorders against level 0), so sum the pairwise moved-row
        # counts (commstats.ideal_routing_bytes already counts both
        # directions).  obs/comm scales by feature width.
        from arrow_matrix_tpu.utils import commstats

        padded = [pad_permutation(np.asarray(lvl.permutation), total)
                  for lvl in levels]
        self._ideal_route_units = sum(
            commstats.ideal_routing_bytes([padded[0], padded[g]],
                                          n_dev, 1, itemsize=1)
            for g in range(1, k_levels))

        # Concurrent slim step over BOTH mesh axes: the per-group body
        # IS sell_slim's shared step body — its collectives name only
        # the "blocks" axis, so psum/ppermute stay within each level
        # group by construction (the reference's per-matrix
        # communicators, for free).  head_unsort arrives (1, w) here
        # (its lvl slice); the shared body wants the resolved (w,).
        def local_step(body, head, head_unsort, orig_pos, xt):
            return _slim_local_step(axis, w, rows_out, hops, rem,
                                    n_dev, self.gather_budget,
                                    body, head, head_unsort[0],
                                    orig_pos, xt)

        spec = lambda tree: jax.tree_util.tree_map(
            lambda _: P((lvl_axis, axis)), tree)
        x_spec = P(feat_axis, (lvl_axis, axis))

        def sharded_compute(body, head, head_unsort, orig_pos, xt):
            return shard_map(
                local_step, mesh=mesh,
                in_specs=(spec(body), spec(head), P(lvl_axis),
                          P((lvl_axis, axis)), x_spec),
                out_specs=x_spec,
                check_vma=False,
            )(body, head, head_unsort, orig_pos, xt)

        def space_step(xt, body, head, head_unsort, orig_pos,
                       bwd0, fwd0):
            with jax.named_scope("level_spmm"):
                ct = sharded_compute(body, head, head_unsort, orig_pos,
                                     xt)
            # Collapsed backward chain: per-level composed gather into
            # level-0 order + sum over groups (cross-group reduce);
            # forward chain: the aggregate gathered into every group's
            # ordering.  Left to the GSPMD partitioner, like
            # SpaceSharedArrow (lowers to all-to-all + all-reduce).
            k = ct.shape[0]
            ctk = ct.reshape(k, k_levels, T)
            # Each group reorders its own partial into level-0 order
            # (within-level indices -> group-local movement), the sum
            # over the lvl axis is the one cross-group reduce, and the
            # forward redistribution reads each group's copy of the
            # reduced aggregate in its own ordering (group-local
            # again).
            with jax.named_scope("aggregate_backward"):
                c0 = jnp.take_along_axis(ctk, bwd0[None], axis=2)
                agg = c0.sum(axis=1)
            with jax.named_scope("redistribute_forward"):
                nxt = jnp.take_along_axis(
                    jnp.broadcast_to(agg[:, None, :], (k, k_levels, T)),
                    fwd0[None], axis=2)
                return lax.with_sharding_constraint(
                    nxt.reshape(k, k_levels * T), self._feat_sharding)

        self._step = jax.jit(space_step)

        def scan_steps(xt, body, head, head_unsort, orig_pos,
                       bwd0, fwd0, n):
            def step_body(xc, _):
                return space_step(xc, body, head, head_unsort, orig_pos,
                                  bwd0, fwd0), None

            out, _ = lax.scan(step_body, xt, None, length=n)
            return out

        self._scan = jax.jit(scan_steps, static_argnames=("n",))
        self._scan_donated = jax.jit(scan_steps, static_argnames=("n",),
                                     donate_argnums=(0,))

    def _args(self):
        return (self.body, self.head, self.head_unsort, self.orig_pos,
                self.bwd0, self.fwd0)

    carries_feature_major = True

    @property
    def step_fn(self):
        """Jitted step callable (see MultiLevelArrow.step_fn)."""
        return self._step

    def step_operands(self):
        """Device operands of one step (see MultiLevelArrow
        .step_operands)."""
        return self._args()

    def device_nbytes(self) -> int:
        return (self.body.device_nbytes() + self.head.device_nbytes()
                + self.orig_pos.size * self.orig_pos.dtype.itemsize)

    def ideal_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Paper cost model for one space-shared step at feature width
        ``k``: the star-shaped cross-group routing (rows changing
        device against level-0 order, both directions) plus each level
        group's O(width) head exchange."""
        per_level_head = max(self.n_dev - 1, 0) * self.width
        return (self._ideal_route_units
                + self.k_levels * per_level_head) * k * itemsize

    def predicted_hbm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Static per-shard HBM model for one space-shared step at
        feature width ``k``: this device's slice of the flattened
        (level, device) tier stacks and route tables, plus the carried
        feature input and output (rows_out positions each)."""
        from arrow_matrix_tpu.obs.memview import tree_device_bytes

        total_dev = self.k_levels * self.n_dev
        ops_bytes = (self.device_nbytes()
                     + tree_device_bytes((self.bwd0, self.fwd0)))
        return (ops_bytes // total_dev
                + 2 * self.rows_out * k * itemsize)

    def shard_report(self) -> dict:
        """Per-(level, device) load report from the flattened tier
        stacks (obs/imbalance.py schema) — each entry is one level
        group's device shard, the unit the concurrent step computes."""
        from arrow_matrix_tpu.obs.imbalance import summarize_units

        b_nnz, b_slots = self.body.shard_stats()
        h_nnz, h_slots = self.head.shard_stats()
        rows = np.full(b_nnz.shape[0], self.rows_out, dtype=np.int64)
        return summarize_units(rows, b_nnz + h_nnz, b_slots + h_slots,
                               units="level-shard")

    def set_features(self, x: np.ndarray) -> jax.Array:
        """Host (n, k) original order -> (k, K * total_out), level g's
        slice in level-g carried order."""
        n, k = x.shape
        if n != self.n:
            raise ValueError(f"expected {self.n} rows, got {n}")
        feat = np.concatenate(
            [_scatter_carried(x, self._orig_of_pos[g], n)
             for g in range(self.k_levels)])
        if self.feature_dtype is not None:
            feat = feat.astype(self.feature_dtype)
        return put_global(np.ascontiguousarray(feat.T),
                          self._feat_sharding)

    def step(self, xt: jax.Array) -> jax.Array:
        return self._step(xt, *self._args())

    def run(self, xt: jax.Array, iterations: int,
            donate: bool = False) -> jax.Array:
        """``donate=True`` donates ``xt`` to the scan carry (see
        MultiLevelArrow.run; the donated input is invalid afterwards)."""
        fn = self._scan_donated if donate else self._scan
        return fn(xt, *self._args(), n=iterations)

    def gather_result(self, ct: jax.Array) -> np.ndarray:
        """Device (k, K * total_out) -> host (n, k) original order
        (level 0's slice IS the canonical aggregate)."""
        return _gather_carried(
            fetch_replicated(ct[:, :self.total_out])
            .astype(np.float32, copy=False).T,
            self._orig_of_pos[0], self.n)

    def carried_mask(self) -> jax.Array:
        """(1, K * total_out) f32 validity mask: live positions of the
        CANONICAL (level-0) slice only — the other slices carry copies
        of the same vector, so whole-state reductions must count each
        row once (and skip tier padding, which holds routed filler
        after a step)."""
        T = self.total_out
        m = np.zeros((1, self.k_levels * T), dtype=np.float32)
        m[0, :T] = _live(self._orig_of_pos[0], self.n).astype(np.float32)
        # Size-1 feature dim: replicate over feat_axis (it cannot
        # shard), positions follow the carriage.
        return put_global(
            m, NamedSharding(self.mesh,
                             P(None, (self.lvl_axis, self.axis))))
