"""Iterated-propagation models on the multi-level arrow SpMM.

Everything here consumes the *pure* jitted SpMM
:func:`arrow_matrix_tpu.parallel.multi_level.multi_level_spmm` — the
same function the distributed runtime runs — so a model trained on one
chip runs unchanged over a mesh (operands carry the shardings; GSPMD
inserts the collectives).

All feature arrays are flat ``(total_rows, k)`` in level-0 order (see
``MultiLevelArrow``); ``SGCModel.predict`` / ``set_features`` handle
padding and permutation from original row order, and
``MultiLevelArrow.real_row_mask`` marks the non-padding rows for
losses and per-row reductions.

The flagship model is SGC (simplified graph convolution): ``K`` hops of
``X := A @ X`` followed by one dense layer — exactly the reference's
benchmark workload (reference arrow/arrow_bench.py:111-134: iterated
``arrow.step()``) with a trainable MXU head on top.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from arrow_matrix_tpu.ops.arrow_blocks import ArrowBlocks
from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow, multi_level_spmm


@struct.dataclass
class SGCParams:
    """Dense readout head: logits = X_prop @ w + b."""

    w: jax.Array
    b: jax.Array


def _check_not_folded(multi: MultiLevelArrow, what: str) -> None:
    """The propagation drivers compose per-level SpMMs with masks and
    head matmuls on flat (total_rows, k) features; the folded
    single-chip mode carries feature-major arrays and a SellMatrix
    operator instead — reject it up front rather than mis-broadcasting
    downstream (fold is a ``step``/``run``-only execution mode)."""
    if getattr(multi, "folded", False):
        raise ValueError(
            f"{what} does not support fmt='fold' (feature-major "
            f"step/run-only execution); build the MultiLevelArrow with "
            f"fmt='auto'/'ell'/'dense' instead")


def sgc_init(rng: jax.Array, k_in: int, k_out: int,
             dtype=jnp.float32) -> SGCParams:
    """LeCun-normal head init."""
    w = jax.random.normal(rng, (k_in, k_out), dtype) / jnp.sqrt(
        jnp.asarray(k_in, dtype))
    return SGCParams(w=w, b=jnp.zeros((k_out,), dtype))


def sgc_forward(params: SGCParams, x: jax.Array, fwd: jax.Array,
                bwd: jax.Array, blocks: Sequence[ArrowBlocks],
                widths: tuple, hops: int,
                chunk: Optional[int] = None) -> jax.Array:
    """K propagation hops through the decomposition, then the dense head.

    Pure and jittable; ``blocks`` is a pytree argument, so the one trace
    serves any decomposition with the same shapes, and shardings
    propagate from the operands under a mesh.
    """
    for _ in range(hops):
        x = multi_level_spmm(x, fwd, bwd, blocks, widths, chunk=chunk)
    return x @ params.w + params.b[None, :]


class SGCModel:
    """Simplified graph convolution over an arrow decomposition.

    Construction wires a :class:`MultiLevelArrow` (which owns the
    device-resident blocks, routing tables and mesh placement) to a
    jitted forward/loss/train-step.  The adjacency is fixed (it is the
    decomposed graph); only the head parameters train — the defining
    property of SGC.
    """

    def __init__(self, multi: MultiLevelArrow, k_in: int, k_out: int,
                 hops: int = 2, seed: int = 0,
                 chunk: Optional[int] = None):
        _check_not_folded(multi, "SGCModel")
        self.multi = multi
        self.hops = hops
        self.params = sgc_init(jax.random.key(seed), k_in, k_out)
        self._forward = jax.jit(functools.partial(
            sgc_forward, widths=tuple(multi.widths), hops=hops, chunk=chunk))

    def forward(self, x: jax.Array) -> jax.Array:
        """x: flat (total_rows, k_in) in level-0 order -> logits
        (total_rows, k_out)."""
        m = self.multi
        return self._forward(self.params, x, m.fwd, m.bwd, m.blocks)

    def predict(self, x_original: np.ndarray) -> np.ndarray:
        """Host (n, k_in) features in original row order -> host logits."""
        m = self.multi
        out = self.forward(m.set_features(x_original))
        return m.gather_result(out)


def make_train_step(widths: tuple, hops: int,
                    optimizer: optax.GradientTransformation,
                    chunk: Optional[int] = None) -> Callable:
    """Jitted SGD/Adam training step for the SGC head.

    Returns ``train_step(params, opt_state, x, y, mask, fwd, bwd, blocks)
    -> (params, opt_state, loss)``.  ``mask`` is a per-row weight (zero
    for padding rows — the blocked layout pads to the mesh-uniform row
    count, and those rows must not contribute to the loss).
    """

    def loss_fn(params, x, y, mask, fwd, bwd, blocks):
        logits = sgc_forward(params, x, fwd, bwd, blocks, widths, hops,
                             chunk=chunk)
        per_row = jnp.sum((logits - y) ** 2, axis=-1)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(per_row * mask) / denom

    @jax.jit
    def train_step(params, opt_state, x, y, mask, fwd, bwd, blocks):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, mask,
                                                  fwd, bwd, blocks)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# ---------------------------------------------------------------------------
# GCN: per-layer weights with a nonlinearity between propagation hops
# (SGC collapses to one head exactly because it drops these).  Same
# pure-function shape as SGC: blocks/routing are pytree arguments, so
# the layers shard under a mesh unchanged.


def gcn_init(rng: jax.Array, dims: Sequence[int],
             dtype=jnp.float32) -> list[SGCParams]:
    """Per-layer LeCun-normal init; ``dims`` = [k_in, h1, ..., k_out]."""
    keys = jax.random.split(rng, len(dims) - 1)
    return [sgc_init(k, d_in, d_out, dtype)
            for k, d_in, d_out in zip(keys, dims[:-1], dims[1:])]


def gcn_forward(params: Sequence[SGCParams], x: jax.Array, fwd: jax.Array,
                bwd: jax.Array, blocks: Sequence[ArrowBlocks],
                widths: tuple,
                chunk: Optional[int] = None) -> jax.Array:
    """Each layer: propagate through the decomposition, then a dense
    layer; ReLU between layers, raw logits out of the last."""
    for i, p in enumerate(params):
        x = multi_level_spmm(x, fwd, bwd, blocks, widths, chunk=chunk)
        x = x @ p.w + p.b
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def make_gcn_train_step(widths: tuple,
                        optimizer: optax.GradientTransformation,
                        chunk: Optional[int] = None) -> Callable:
    """Jitted masked-MSE training step over the per-layer GCN weights
    (same contract as ``make_train_step``)."""

    def loss_fn(params, x, y, mask, fwd, bwd, blocks):
        logits = gcn_forward(params, x, fwd, bwd, blocks, widths,
                             chunk=chunk)
        per_row = jnp.sum((logits - y) ** 2, axis=-1)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(per_row * mask) / denom

    @jax.jit
    def train_step(params, opt_state, x, y, mask, fwd, bwd, blocks):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, mask,
                                                  fwd, bwd, blocks)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


class GCNModel:
    """Multi-layer GCN over a fixed decomposed adjacency: the deep
    counterpart of :class:`SGCModel` (which is its 1-head collapse)."""

    def __init__(self, multi: MultiLevelArrow, dims: Sequence[int],
                 seed: int = 0, chunk: Optional[int] = None):
        _check_not_folded(multi, "GCNModel")
        self.multi = multi
        self.params = gcn_init(jax.random.key(seed), list(dims))
        self._forward = jax.jit(functools.partial(
            gcn_forward, widths=tuple(multi.widths), chunk=chunk))

    def forward(self, x: jax.Array) -> jax.Array:
        m = self.multi
        return self._forward(self.params, x, m.fwd, m.bwd, m.blocks)

    def predict(self, x_original: np.ndarray) -> np.ndarray:
        m = self.multi
        return m.gather_result(self.forward(m.set_features(x_original)))


# ---------------------------------------------------------------------------
# Solver-style model families on the same operator.  Bodies are
# module-level jitted functions (widths/chunk static) so repeated solver
# calls on the same decomposition shapes hit the jit cache instead of
# re-tracing the K-level SpMM.
# ---------------------------------------------------------------------------


# One stateless default optimizer shared by every carried model: a
# fresh optax transform per fit() would defeat the per-optimizer
# train-step caches and recompile the backprop program each call.
_DEFAULT_CARRIED_OPT = optax.adam(1e-2)


def _check_carried(multi, what: str) -> None:
    """Mirror of _check_not_folded for the opposite mistake: a flat
    row-major executor would feed (rows, k) into the feature-major
    head and die deep inside jit."""
    if not getattr(multi, "carries_feature_major", False):
        raise ValueError(
            f"{what} needs a feature-major executor (fmt='fold' "
            f"MultiLevelArrow, SellMultiLevel, or SellSpaceShared); "
            f"for the flat layouts use the non-Carried sibling class")


def _carried_mask_or_ones(multi, total: int) -> jax.Array:
    if getattr(multi, "carries_feature_major", False):
        return multi.carried_mask()
    return jnp.ones((1, total), jnp.float32)


class SGCCarried:
    """SGC on the feature-major (carried) executors — `SellMultiLevel`,
    `SellSpaceShared`, and the folded single-chip `MultiLevelArrow` —
    i.e. anything with ``set_features``/``run``/``gather_result`` over
    a ``(k, positions)`` carriage.

    SGC's defining property (only the dense head trains) makes the
    propagation a fixed preprocessing: ``X_prop = A^hops X`` runs once
    on the executor, then the head fits on carried positions.  The
    executor's ``carried_mask`` weights the loss — tier pads hold
    routed filler, the space-shared carriage holds K copies of each
    row (count once), and even the zero-padded fold carriage needs it
    so pad positions don't dilute the denominator and drag the output
    bias toward zero.
    """

    def __init__(self, multi, k_in: int, k_out: int, hops: int = 2,
                 seed: int = 0):
        _check_carried(multi, "SGCCarried")
        self.multi = multi
        self.hops = hops
        self.params = sgc_init(jax.random.key(seed), k_in, k_out)

    def propagate(self, x_host: np.ndarray) -> jax.Array:
        """Host (n, k_in) -> carried ``(k_in, positions)`` after
        ``hops`` applications of the decomposed operator."""
        xt = self.multi.set_features(x_host.astype(np.float32))
        return self.multi.run(xt, self.hops) if self.hops else xt

    def predict(self, x_original: np.ndarray) -> np.ndarray:
        """Host (n, k_in) original order -> host (n, k_out) logits."""
        logits_t = _sgc_head(self.params, self.propagate(x_original))
        return self.multi.gather_result(logits_t)

    def fit(self, x_host: np.ndarray, y_host: np.ndarray, *,
            steps: int = 100,
            optimizer: Optional[optax.GradientTransformation] = None
            ) -> list[float]:
        """Masked-MSE fit of the head on carried positions; returns the
        per-step losses."""
        xp = self.propagate(x_host)
        yt = self.multi.set_features(y_host.astype(np.float32))
        mask = _carried_mask_or_ones(self.multi, yt.shape[1])
        # Adaptive default: propagated features carry degree^hops
        # magnitudes, which blow fixed-step SGD up on power-law graphs.
        opt = optimizer or _DEFAULT_CARRIED_OPT
        opt_state = opt.init(self.params)
        # Carried operands are ARGUMENTS of the jitted step (the
        # make_train_step pattern): baking them in as closure constants
        # would duplicate them in the executable and retrace per fit.
        train_step = _make_carried_train_step(opt)

        losses = []
        for _ in range(steps):
            self.params, opt_state, loss = train_step(
                self.params, opt_state, xp, yt, mask)
            losses.append(float(loss))
        return losses


@jax.jit
def _sgc_head(params: SGCParams, xp: jax.Array) -> jax.Array:
    """Feature-major head: (k_out, positions) logits."""
    return params.w.T @ xp + params.b[:, None]


class GCNCarried:
    """GCN on the feature-major executors — per-layer weights with ReLU
    between propagation steps, gradients flowing THROUGH the executor's
    step (the shard_map collectives — psum, ppermute, the routed
    gathers — differentiate natively), so the same distributed program
    that serves inference backpropagates.

    Works on any carried-layout executor exposing ``step_operands``
    (fold ``MultiLevelArrow``, ``SellMultiLevel``, ``SellSpaceShared``);
    loss is masked by ``carried_mask`` like :class:`SGCCarried`.
    """

    def __init__(self, multi, dims: Sequence[int], seed: int = 0):
        _check_carried(multi, "GCNCarried")
        self.multi = multi
        self.params = gcn_init(jax.random.key(seed), dims)
        # Per-instance jits (NOT a module cache: every executor's
        # step_fn is a per-instance object, so a global cache could
        # never hit across instances and would pin dropped executors'
        # device blocks alive).
        self._forward = _make_carried_gcn_forward(multi.step_fn)
        self._train_steps: dict = {}

    def predict(self, x_original: np.ndarray) -> np.ndarray:
        xt = self.multi.set_features(x_original.astype(np.float32))
        logits = self._forward(self.params, xt,
                               self.multi.step_operands())
        return self.multi.gather_result(logits)

    def fit(self, x_host: np.ndarray, y_host: np.ndarray, *,
            steps: int = 100,
            optimizer: Optional[optax.GradientTransformation] = None
            ) -> list[float]:
        """Masked-MSE fit of every layer; propagation recomputes inside
        each step (the weights sit between hops — GCN's defining
        difference from SGC)."""
        xt = self.multi.set_features(x_host.astype(np.float32))
        yt = self.multi.set_features(y_host.astype(np.float32))
        mask = _carried_mask_or_ones(self.multi, yt.shape[1])
        opt = optimizer or _DEFAULT_CARRIED_OPT
        opt_state = opt.init(self.params)
        train_step = self._train_steps.get(opt)
        if train_step is None:
            train_step = _make_carried_gcn_train_step(self._forward, opt)
            self._train_steps[opt] = train_step

        operands = self.multi.step_operands()
        losses = []
        for _ in range(steps):
            self.params, opt_state, loss = train_step(
                self.params, opt_state, xt, yt, mask, operands)
            losses.append(float(loss))
        return losses


def _make_carried_gcn_forward(step_fn):
    """Jitted carried-layout GCN forward for one executor step
    callable; operands thread through as arguments (no baked
    constants)."""

    @jax.jit
    def forward(params, xt, operands):
        for i, p in enumerate(params):
            xt = step_fn(xt, *operands)
            xt = p.w.T @ xt + p.b[:, None]
            if i < len(params) - 1:
                xt = jax.nn.relu(xt)
        return xt

    return forward


def _make_carried_gcn_train_step(forward,
                                 optimizer: optax.GradientTransformation):
    @jax.jit
    def train_step(params, opt_state, xt, yt, mask, operands):
        def loss_fn(ps):
            per = ((forward(ps, xt, operands) - yt) ** 2).sum(
                axis=0, keepdims=True)
            return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


@functools.lru_cache(maxsize=8)
def _make_carried_train_step(optimizer: optax.GradientTransformation):
    """Jitted masked-MSE head step over carried operands (cached per
    optimizer so repeated fit() calls reuse the compilation)."""

    @jax.jit
    def train_step(params, opt_state, xp, yt, mask):
        def loss_fn(p):
            per = ((_sgc_head(p, xp) - yt) ** 2).sum(
                axis=0, keepdims=True)
            return (per * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


def pagerank_carried(multi, damping: float = 0.85,
                     iterations: int = 50) -> np.ndarray:
    """PageRank on a feature-major executor (fold / SellMultiLevel /
    SellSpaceShared): ``r := d * A_norm r + (1-d)/n`` like
    :func:`pagerank`, with the teleport vector scattered through
    ``set_features`` — that places it at every live carried position
    (including each slice of the space-shared K-copy carriage), so the
    iteration needs no executor-specific masking at all."""
    _check_carried(multi, "pagerank_carried")
    n = multi.n
    r = multi.set_features(np.full((n, 1), 1.0 / n, np.float32))
    tele = multi.set_features(
        np.full((n, 1), (1.0 - damping) / n, np.float32))
    operands = multi.step_operands()
    d = jnp.float32(damping)
    for _ in range(iterations):
        r = _pagerank_carried_body(multi.step_fn, r, d, tele, operands)
    return multi.gather_result(r)


def label_propagation_carried(multi, labels: np.ndarray,
                              seed_mask: np.ndarray,
                              iterations: int = 20) -> np.ndarray:
    """Label propagation on a feature-major executor: ``Y := A_norm Y``
    then clamp seed rows, like :func:`label_propagation` (same default
    iteration count); the seed values and the seed indicator travel
    through ``set_features`` so clamping is pure positionwise
    arithmetic on the carriage."""
    _check_carried(multi, "label_propagation_carried")
    labels = labels.astype(np.float32)
    y = multi.set_features(labels)
    seeds = multi.set_features(labels * seed_mask[:, None])
    m = multi.set_features(seed_mask[:, None].astype(np.float32))
    operands = multi.step_operands()
    for _ in range(iterations):
        y = _label_prop_carried_body(multi.step_fn, y, seeds, m,
                                     operands)
    return multi.gather_result(y)


# Module-level jits with the executor step as a STATIC argument: like
# the flat _pagerank_body/_label_prop_body, repeated calls hit the jit
# cache (keyed per step callable) instead of recompiling the whole
# distributed step program.
@functools.partial(jax.jit, static_argnums=(0,))
def _pagerank_carried_body(step_fn, r, d, tele, operands):
    return d * step_fn(r, *operands) + tele


@functools.partial(jax.jit, static_argnums=(0,))
def _label_prop_carried_body(step_fn, y, seeds, m, operands):
    y = step_fn(y, *operands)
    return jnp.where(m > 0, seeds, y)


@jax.jit
def _normalize(y, m):
    """y / ||y * m||.  ``m`` is scalar 1.0 for layouts whose pads are
    zero, or a carried-validity mask (sell orchestrations) — one jitted
    fused call either way."""
    return y / jnp.maximum(jnp.linalg.norm(y * m), 1e-30)


@jax.jit
def _rayleigh(x, y, m):
    xm, ym = x * m, y * m
    return jnp.vdot(xm, ym) / jnp.maximum(jnp.vdot(xm, xm), 1e-30)


def power_iteration(multi: MultiLevelArrow, x0: np.ndarray,
                    iterations: int = 50) -> tuple[np.ndarray, float]:
    """Dominant eigenpair by normalized iterated SpMM.

    Returns (eigenvector in original row order, Rayleigh-quotient
    eigenvalue estimate).  ``x0``: host (n, 1) start vector.

    Uses only ``multi.step`` plus whole-array reductions, both of which
    are layout-agnostic — so this driver works on every execution mode:
    the flat layouts and the folded single-chip one (whose pads stay
    zero), and the sell orchestrations, whose ``carried_mask`` weights
    the reductions — their tier pads hold routed filler after a step,
    and the space-shared carriage holds K copies of the vector that
    must count once.
    """
    if getattr(multi, "carries_feature_major", False):
        m = multi.carried_mask()
    else:
        m = jnp.float32(1.0)   # flat layouts: pads are zeros

    x = multi.set_features(x0.astype(np.float32))
    for _ in range(iterations):
        x = _normalize(multi.step(x), m)
    # One more multiply for the Rayleigh quotient x^T A x / x^T x.
    y = multi.step(x)
    lam = float(_rayleigh(x, y, m))
    return multi.gather_result(x), lam


@functools.partial(jax.jit, static_argnames=("widths", "chunk"))
def _pagerank_body(r, mask, damping, teleport, fwd, bwd, blocks, widths,
                   chunk):
    y = multi_level_spmm(r, fwd, bwd, blocks, widths, chunk=chunk)
    return damping * y + teleport * mask


def pagerank(multi: MultiLevelArrow, damping: float = 0.85,
             iterations: int = 50) -> np.ndarray:
    """PageRank by damped iterated SpMM: r := d * A_norm r + (1-d)/n.

    ``multi`` must hold the *column-normalized* adjacency (build the
    decomposition from ``A @ D^{-1}``); this function runs the iteration,
    it does not normalize.
    """
    _check_not_folded(multi, "pagerank")
    n = multi.n
    r = multi.set_features(np.full((n, 1), 1.0 / n, dtype=np.float32))
    # Padding rows stay zero: the teleport mass is masked to real rows.
    mask = multi.real_row_mask()
    damping_arr = jnp.float32(damping)
    teleport = jnp.float32((1.0 - damping) / n)
    for _ in range(iterations):
        r = _pagerank_body(r, mask, damping_arr, teleport, multi.fwd,
                           multi.bwd, multi.blocks, tuple(multi.widths),
                           multi.chunk)
    return multi.gather_result(r)


@functools.partial(jax.jit, static_argnames=("widths", "chunk"))
def _label_prop_body(y, seeds, clamp, fwd, bwd, blocks, widths, chunk):
    prop = multi_level_spmm(y, fwd, bwd, blocks, widths, chunk=chunk)
    # Typed scalar: a bare float literal would ride weak-type promotion
    # (graft-lint R5) and silently widen a narrow feature dtype.
    one = clamp.dtype.type(1)
    return clamp * seeds + (one - clamp) * prop


def label_propagation(multi: MultiLevelArrow, labels: np.ndarray,
                      seed_mask: np.ndarray,
                      iterations: int = 20) -> np.ndarray:
    """Semi-supervised label propagation with clamped seeds.

    labels: host (n, c) one-hot (or soft) labels; seed_mask: (n,) bool —
    True rows are clamped to their labels every iteration.
    ``multi`` should hold a row-normalized adjacency for convergence.
    """
    _check_not_folded(multi, "label_propagation")
    y = multi.set_features(labels.astype(np.float32))
    seeds = multi.set_features(
        (labels * seed_mask[:, None]).astype(np.float32))
    clamp = multi.set_features(seed_mask.astype(np.float32)[:, None])

    for _ in range(iterations):
        y = _label_prop_body(y, seeds, clamp, multi.fwd, multi.bwd,
                             multi.blocks, tuple(multi.widths), multi.chunk)
    return multi.gather_result(y)


# ---------------------------------------------------------------------------
# APPNP (Gasteiger et al., "Predict then Propagate", ICLR 2019): one
# trainable prediction head, then personalized-PageRank propagation
#   Z := (1 - alpha) * A_hat Z + alpha * H,   Z_0 = H = head(X)
# which decouples model depth from propagation range — the propagation
# IS the reference's iterated-step() workload with a teleport mix-in,
# so it runs on every executor unchanged.


def appnp_forward(params: SGCParams, x: jax.Array, fwd: jax.Array,
                  bwd: jax.Array, blocks: Sequence[ArrowBlocks],
                  widths: tuple, hops: int, alpha: float,
                  chunk: Optional[int] = None) -> jax.Array:
    """Flat (total_rows, k) APPNP forward: head first, then ``hops``
    personalized-PageRank steps.  Pure and jittable like sgc_forward."""
    h = x @ params.w + params.b[None, :]
    z = h
    # Typed mix weights (graft-lint R5): alpha is a static python
    # float; fold it into scalars of the activation dtype once.
    keep = h.dtype.type(1 - alpha)
    tele = h.dtype.type(alpha)
    for _ in range(hops):
        z = keep * multi_level_spmm(z, fwd, bwd, blocks,
                                    widths, chunk=chunk)
        z = z + tele * h
    return z


class APPNPModel:
    """APPNP over the flat executors (mirrors :class:`SGCModel`)."""

    def __init__(self, multi: MultiLevelArrow, k_in: int, k_out: int,
                 hops: int = 10, alpha: float = 0.1, seed: int = 0,
                 chunk: Optional[int] = None):
        _check_not_folded(multi, "APPNPModel")
        self.multi = multi
        self.hops = hops
        self.alpha = alpha
        self.params = sgc_init(jax.random.key(seed), k_in, k_out)
        self._forward = jax.jit(functools.partial(
            appnp_forward, widths=tuple(multi.widths), hops=hops,
            alpha=alpha, chunk=chunk))

    def forward(self, x: jax.Array) -> jax.Array:
        m = self.multi
        return self._forward(self.params, x, m.fwd, m.bwd, m.blocks)

    def predict(self, x_original: np.ndarray) -> np.ndarray:
        m = self.multi
        return m.gather_result(self.forward(m.set_features(x_original)))


def make_appnp_train_step(widths: tuple, hops: int, alpha: float,
                          optimizer: optax.GradientTransformation,
                          chunk: Optional[int] = None) -> Callable:
    """Jitted masked-MSE train step for the APPNP head; gradients flow
    through the whole propagation (unlike SGC, the head sits UNDER the
    hops, so dL/dW crosses every SpMM)."""

    def loss_fn(params, x, y, mask, fwd, bwd, blocks):
        z = appnp_forward(params, x, fwd, bwd, blocks, widths, hops,
                          alpha, chunk=chunk)
        per_row = jnp.sum((z - y) ** 2, axis=-1)
        return jnp.sum(per_row * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    @jax.jit
    def train_step(params, opt_state, x, y, mask, fwd, bwd, blocks):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, mask,
                                                  fwd, bwd, blocks)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


class APPNPCarried:
    """APPNP on the feature-major executors (fold ``MultiLevelArrow``,
    ``SellMultiLevel``, ``SellSpaceShared``): the head applies
    feature-major, the propagation runs through the executor's jitted
    step with gradients crossing the distributed collectives (the
    :class:`GCNCarried` property), and ``carried_mask`` weights the
    loss so tier pads / K-copy carriages count correctly."""

    def __init__(self, multi, k_in: int, k_out: int, hops: int = 10,
                 alpha: float = 0.1, seed: int = 0):
        _check_carried(multi, "APPNPCarried")
        self.multi = multi
        self.params = sgc_init(jax.random.key(seed), k_in, k_out)
        self._forward = _make_carried_appnp_forward(multi.step_fn, hops,
                                                    alpha)
        self._train_steps: dict = {}

    def predict(self, x_original: np.ndarray) -> np.ndarray:
        xt = self.multi.set_features(x_original.astype(np.float32))
        return self.multi.gather_result(
            self._forward(self.params, xt, self.multi.step_operands()))

    def fit(self, x_host: np.ndarray, y_host: np.ndarray, *,
            steps: int = 100,
            optimizer: Optional[optax.GradientTransformation] = None
            ) -> list[float]:
        xt = self.multi.set_features(x_host.astype(np.float32))
        yt = self.multi.set_features(y_host.astype(np.float32))
        mask = _carried_mask_or_ones(self.multi, yt.shape[1])
        opt = optimizer or _DEFAULT_CARRIED_OPT
        opt_state = opt.init(self.params)
        train_step = self._train_steps.get(opt)
        if train_step is None:
            train_step = _make_carried_gcn_train_step(self._forward, opt)
            self._train_steps[opt] = train_step

        operands = self.multi.step_operands()
        losses = []
        for _ in range(steps):
            self.params, opt_state, loss = train_step(
                self.params, opt_state, xt, yt, mask, operands)
            losses.append(float(loss))
        return losses


def _make_carried_appnp_forward(step_fn, hops: int, alpha: float):
    """Jitted carried-layout APPNP forward (same operand-threading rule
    as the GCN forward: no baked-in device constants)."""

    @jax.jit
    def forward(params, xt, operands):
        h = params.w.T @ xt + params.b[:, None]
        z = h
        keep = h.dtype.type(1 - alpha)   # typed scalars, graft-lint R5
        tele = h.dtype.type(alpha)
        for _ in range(hops):
            z = keep * step_fn(z, *operands) + tele * h
        return z

    return forward


# ---------------------------------------------------------------------
# Conjugate gradient on the distributed SpMM operator.  The classic
# iterated-SpMM consumer the reference's workload class feeds
# (reference README.md:3: iterated X := A @ X for graph analytics):
# solving (shift*I + A) x = b exercises exactly one distributed SpMM
# plus axpy/dot per iteration.


@functools.partial(jax.jit, static_argnums=(0,))
def _cg_carried_iter(step_fn, x, r, p, rz, shift, mask, operands):
    """One CG iteration in carried layout.  All reductions are masked
    by ``carried_mask`` (pads hold routed filler after a step; the
    space-shared carriage holds K copies of each row — the mask counts
    every original row exactly once, so the dots equal their host
    values)."""
    ap = shift * p + step_fn(p, *operands)
    denom = jnp.sum(p * ap * mask, dtype=jnp.float32)
    alpha = rz / jnp.where(denom == 0, 1.0, denom)
    x = x + alpha * p
    r = r - alpha * ap
    rz_new = jnp.sum(r * r * mask, dtype=jnp.float32)
    beta = rz_new / jnp.where(rz == 0, 1.0, rz)
    p = r + beta * p
    return x, r, p, rz_new


def conjugate_gradient(multi, b: np.ndarray, *, shift: float,
                       iterations: int = 50,
                       tol: float = 0.0) -> tuple[np.ndarray, float]:
    """Solve ``(shift*I + A) x = b`` by CG on a feature-major executor
    (fold / SellMultiLevel / SellSpaceShared).

    ``A`` is the executor's (symmetric) operator; ``shift`` must make
    ``shift*I + A`` positive definite — for a symmetric adjacency any
    ``shift > max degree`` suffices (strict diagonal dominance).
    ``b`` is (n, k); each feature column is an independent system (the
    dots reduce over carried positions per column and sum — standard
    block-CG-free multi-RHS treatment: one shared step, per-column
    convergence not separated, matching the framework's feature-major
    batching).  Returns ``(x, final_residual_norm)`` with ``x``
    gathered to host order.

    ``tol`` > 0 stops early when ||r|| / ||b|| drops below it (checked
    on host once per iteration — one scalar fetch against a chained
    device step; pass 0 to run a fixed count with no host syncs).
    """
    _check_carried(multi, "conjugate_gradient")
    b = np.asarray(b, dtype=np.float32)
    squeeze = b.ndim == 1
    if squeeze:
        b = b[:, None]
    bt = multi.set_features(b)
    mask = _carried_mask_or_ones(multi, bt.shape[1])
    operands = multi.step_operands()
    x = jnp.zeros_like(bt)
    r = bt
    p = bt
    rz = jnp.sum(r * r * mask, dtype=jnp.float32)
    # Host syncs only in tol mode: the fixed-count path stays fully
    # async until the final gather.
    b_norm = float(jnp.sqrt(rz)) if tol > 0.0 else None
    sh = jnp.float32(shift)
    for _ in range(iterations):
        x, r, p, rz = _cg_carried_iter(multi.step_fn, x, r, p, rz, sh,
                                       mask, operands)
        if tol > 0.0 and float(jnp.sqrt(rz)) <= tol * max(b_norm, 1e-30):
            break
    out = multi.gather_result(x)
    if squeeze:
        out = out[:, 0]
    return out, float(jnp.sqrt(rz))
