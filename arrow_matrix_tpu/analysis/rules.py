"""The shipped graft-lint rules (R1-R9).

* R1 host-sync-in-jit — float()/.item()/np.asarray on traced values
* R2 recompile-hazard — jit-in-loop, jit-then-call, unhashable statics
* R3 missing-donation — scan-carry entry points jitted undonated
* R4 spec-axis-consistency — PartitionSpec axes the mesh never declares
* R5 dtype-promotion — bare float literals in traced arithmetic
* R6 unguarded-device-get — unbounded device->host fetches
* R7 unsynced-timing — perf_counter regions with no block_until_ready
* R8 swallowed-exception — broad except handlers that only discard
* R9 env-read-in-step — AMT_* environment reads inside the hot loop

Each rule encodes a hazard this codebase has actually met (or defends
against by convention), grounded at the call sites named in its
docstring.  Rules are registered with ``core.register`` and receive a
``ModuleContext``; they yield ``(line, message)`` pairs.  Suppress a
deliberate violation inline with ``# graft-lint: disable=Rn``.

The R rules are one quarter of the package's static-rule family:
H1-H7 (analysis/prove.py) prove HLO collective contracts, RC1-RC5
(analysis/sync.py, graft-sync) prove the serving stack's lock
discipline, and KC1-KC5 (analysis/kernels.py, graft-kcert) certify
the Pallas kernel layer's bounds, budgets, DMA ring discipline,
accumulator widths, and output coverage.  Ids are unique across all
four engines so one finding line always names one rule.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator, Optional, Tuple

from arrow_matrix_tpu.analysis.core import (
    JIT_WRAPPERS,
    ModuleContext,
    register,
)

# ---------------------------------------------------------------------------
# Shared predicates
# ---------------------------------------------------------------------------

#: Attribute reads that are static (python values) under tracing.
_STATIC_ATTRS = frozenset({"shape", "ndim", "size", "itemsize", "dtype",
                           "nbytes", "n_blocks", "width", "banded", "fmt"})

#: Calls whose results are static python values under tracing.
_STATIC_CALLS = frozenset({"len", "min", "max", "abs", "round", "isinstance",
                           "numpy.prod", "math.prod", "numpy.dtype",
                           "math.ceil", "math.floor", "math.log2"})


def _is_static_expr(ctx: ModuleContext, node) -> bool:
    """Conservative: True only for expressions that trace to python
    values (shape arithmetic, dtype metadata, literals)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return True
    if isinstance(node, ast.Subscript):
        return _is_static_expr(ctx, node.value)
    if isinstance(node, ast.UnaryOp):
        return _is_static_expr(ctx, node.operand)
    if isinstance(node, ast.BinOp):
        return (_is_static_expr(ctx, node.left)
                and _is_static_expr(ctx, node.right))
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_static_expr(ctx, e) for e in node.elts)
    if isinstance(node, ast.Call):
        full = ctx.resolve(node.func)
        if full in _STATIC_CALLS:
            return True
    return False


def _traced_calls(ctx: ModuleContext) -> Iterator[ast.Call]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and ctx.in_traced_scope(node):
            yield node


def _jit_calls(ctx: ModuleContext) -> Iterator[ast.Call]:
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and ctx.resolve(node.func) in JIT_WRAPPERS):
            yield node


def _keyword(call: ast.Call, name: str):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _wrapped_function(ctx: ModuleContext, call: ast.Call):
    """The function object a jit call wraps, unwrapping
    functools.partial: (node-or-None, display-name)."""
    if not call.args:
        return None, ""
    arg = call.args[0]
    if (isinstance(arg, ast.Call)
            and ctx.resolve(arg.func) == "functools.partial" and arg.args):
        arg = arg.args[0]
    if isinstance(arg, ast.Lambda):
        return arg, "<lambda>"
    if isinstance(arg, ast.Name):
        fns = ctx.funcs_by_name.get(arg.id, ())
        return (fns[0] if fns else None), arg.id
    return None, ctx.dotted(arg) or "<expr>"


# ---------------------------------------------------------------------------
# R1 — host-sync-in-jit
# ---------------------------------------------------------------------------


@register("R1", "host-sync-in-jit",
          "float()/int()/.item()/np.asarray on a traced value forces a "
          "blocking device->host transfer inside a jitted function")
def check_host_sync(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """Host-sync in a traced scope.

    ``float()``, ``.item()``, ``int(np.asarray(...))`` and friends are
    fine at build time (the ops/arrow_blocks.py packers run on the
    host), but inside a function passed to ``jax.jit``/``shard_map``
    they either fail on tracers or — worse, via ``io_callback``-style
    escapes — serialize the step on a device round-trip.  Shape/dtype
    reads (``x.shape``, ``len(x)``) are static and exempt.
    """
    for call in _traced_calls(ctx):
        line = call.lineno
        func = call.func
        if (isinstance(func, ast.Name) and func.id in ("float", "int", "bool")
                and len(call.args) == 1
                and not _is_static_expr(ctx, call.args[0])):
            yield line, (f"{func.id}() on a traced value is a host sync "
                         f"inside a jitted scope; keep it an array (or "
                         f"compute it from static shape/dtype metadata)")
        elif (isinstance(func, ast.Attribute) and func.attr == "item"
              and not call.args):
            yield line, (".item() blocks on device->host transfer inside "
                         "a traced scope")
        elif ctx.is_numpy_call(call, "asarray") or ctx.is_numpy_call(
                call, "array"):
            yield line, ("np.asarray/np.array inside a traced scope pulls "
                         "the value to the host every step; use jnp, or "
                         "hoist the conversion out of the jitted function")
        elif ctx.resolve(func) == "jax.device_get":
            yield line, "jax.device_get inside a traced scope is a host sync"


# ---------------------------------------------------------------------------
# R2 — recompile-hazard
# ---------------------------------------------------------------------------


def _lru_cached(ctx: ModuleContext, fn) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    for deco in fn.decorator_list:
        target = deco.func if isinstance(deco, ast.Call) else deco
        if ctx.resolve(target) in ("functools.lru_cache", "functools.cache"):
            return True
    return False


_UNHASHABLE_ANNOS = frozenset({"list", "dict", "set", "List", "Dict", "Set",
                               "numpy.ndarray", "jax.Array"})


@register("R2", "recompile-hazard",
          "jit call sites that defeat the compilation cache: jit inside "
          "a loop, jit-then-call in a function body, unhashable static "
          "arguments")
def check_recompile(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """Jit-cache misses.

    A ``jax.jit(...)`` call creates a NEW cache; doing it per loop
    iteration or per function call recompiles every time (the hazard
    the cached ``_replicator`` in parallel/mesh.py exists to avoid).
    Static arguments must be hashable — a list/dict/ndarray-typed
    static arg raises or, with drifting values, recompiles per call.
    """
    for call in _jit_calls(ctx):
        line = call.lineno
        if ctx.in_loop(call):
            yield line, ("jax.jit inside a loop builds a fresh compilation "
                         "cache every iteration; hoist the jit out of the "
                         "loop (or functools.lru_cache the factory)")
        parent = ctx.parents.get(call)
        encl = ctx.enclosing_function(call)
        if (isinstance(parent, ast.Call) and parent.func is call
                and encl is not None and not _lru_cached(ctx, encl)):
            yield line, ("jit-then-call in a function body drops the "
                         "compiled cache on return (recompiles every "
                         "call); cache the jitted callable, e.g. via "
                         "functools.lru_cache keyed on the static config")

        fn, name = _wrapped_function(ctx, call)
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = fn.args.args
        defaults = fn.args.defaults
        default_of = {}
        for p, d in zip(params[len(params) - len(defaults):], defaults):
            default_of[p.arg] = d
        static_params = []
        nums = _keyword(call, "static_argnums")
        names = _keyword(call, "static_argnames")
        for v in ([nums] if nums is not None else []):
            for c in ([v] if isinstance(v, ast.Constant) else
                      getattr(v, "elts", [])):
                if isinstance(c, ast.Constant) and isinstance(c.value, int):
                    if 0 <= c.value < len(params):
                        static_params.append(params[c.value].arg)
        for v in ([names] if names is not None else []):
            for c in ([v] if isinstance(v, ast.Constant) else
                      getattr(v, "elts", [])):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    static_params.append(c.value)
        for pname in static_params:
            d = default_of.get(pname)
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                yield line, (f"static argument {pname!r} of {name!r} "
                             f"defaults to an unhashable "
                             f"{type(d).__name__.lower()}; jit static "
                             f"args must be hashable (use a tuple)")
            ann = next((p.annotation for p in params if p.arg == pname
                        and p.annotation is not None), None)
            if ann is not None:
                a = ctx.resolve(ann) or ""
                if a.split("[")[0] in _UNHASHABLE_ANNOS:
                    yield line, (f"static argument {pname!r} of {name!r} "
                                 f"is annotated {a}; unhashable static "
                                 f"args raise (or recompile per call)")


# ---------------------------------------------------------------------------
# R3 — missing-donation
# ---------------------------------------------------------------------------

#: loop primitive -> positional index of its carry-init argument.
_CARRY_INIT_POS = {"jax.lax.scan": 1, "jax.lax.fori_loop": 3,
                   "jax.lax.while_loop": 2}
_CARRY_INIT_KW = {"jax.lax.scan": "init", "jax.lax.fori_loop": "init_val",
                  "jax.lax.while_loop": "init_val"}


def _first_param(fn) -> Optional[str]:
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        args = fn.args.args
        if args:
            first = args[0].arg
            return args[1].arg if first == "self" and len(args) > 1 else first
    return None


def _is_scan_carry_fn(ctx: ModuleContext, fn) -> bool:
    """Does ``fn`` thread its first parameter as the carry of a lax
    loop primitive (the iterated-update X := A @ X shape)?"""
    first = _first_param(fn)
    if first is None:
        return False
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        full = ctx.resolve(node.func)
        if full not in _CARRY_INIT_POS:
            continue
        pos = _CARRY_INIT_POS[full]
        init = (node.args[pos] if len(node.args) > pos
                else _keyword(node, _CARRY_INIT_KW[full]))
        if isinstance(init, ast.Name) and init.id == first:
            return True
    return False


@register("R3", "missing-donation",
          "an iterated-update function (lax.scan over its first array "
          "argument) jitted without donate_argnums doubles its carry's "
          "memory footprint")
def check_donation(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """Missing buffer donation on the iterated SpMM scan.

    The ``X := A @ X`` scan rebinds its carry every call; without
    ``donate_argnums`` the old X stays live across the step and the
    footprint doubles (at protocol scale that is the difference between
    fitting in HBM and not).  A sibling jit of the SAME function WITH
    donation (the parallel/multi_level.py donated/undonated pair, where
    the undonated variant deliberately preserves its input) waives the
    site.
    """
    donated_names = set()
    candidates = []
    for call in _jit_calls(ctx):
        fn, name = _wrapped_function(ctx, call)
        if fn is None or not _is_scan_carry_fn(ctx, fn):
            continue
        has_donate = (_keyword(call, "donate_argnums") is not None
                      or _keyword(call, "donate_argnames") is not None)
        if has_donate:
            donated_names.add(name)
        else:
            candidates.append((call.lineno, name, fn))
    for line, name, fn in candidates:
        if name != "<lambda>" and name in donated_names:
            continue
        carry = _first_param(fn)
        yield line, (f"{name!r} scans its first argument {carry!r} as an "
                     f"iterated carry but is jitted without "
                     f"donate_argnums; donate the carry (or add a donated "
                     f"sibling jit) so the old buffer is reused")


# ---------------------------------------------------------------------------
# R4 — spec-axis-consistency
# ---------------------------------------------------------------------------

#: The package-default mesh axis, declared by parallel/mesh.py
#: ``make_mesh(axis_names=("blocks",))`` — in scope for any module that
#: imports the mesh helpers.
DEFAULT_MESH_AXES = frozenset({"blocks"})

_MESH_CTORS = frozenset({"Mesh", "make_mesh", "make_hybrid_mesh",
                         "AbstractMesh"})


def _declared_axes(ctx: ModuleContext) -> set:
    axes: set = set()

    def add_strings(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            axes.add(node.value)
        elif isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                add_strings(e)

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            full = ctx.resolve(node.func) or ""
            if full.rsplit(".", 1)[-1] in _MESH_CTORS:
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    add_strings(arg)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            pos_with_default = args.args[len(args.args)
                                         - len(args.defaults):]
            for p, d in list(zip(pos_with_default, args.defaults)) + list(
                    zip(args.kwonlyargs, args.kw_defaults)):
                if d is None:
                    continue
                if p.arg == "axis" or p.arg.endswith("_axis") \
                        or p.arg == "axis_names":
                    add_strings(d)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and "axis" in t.id.lower():
                    add_strings(node.value)
    if any(v.startswith("arrow_matrix_tpu.parallel")
           for v in ctx.aliases.values()):
        axes |= DEFAULT_MESH_AXES
    return axes


@register("R4", "spec-axis-consistency",
          "every PartitionSpec axis-name literal must be declared by a "
          "Mesh/make_mesh axis-names literal reachable in the module "
          "(or be the package default 'blocks' from parallel/mesh.py)")
def check_spec_axes(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """PartitionSpec axis names the mesh does not declare.

    ``P("rowz")`` against a mesh with axes ``("rows", "repl")`` fails
    only at dispatch — deep inside shard_map, with an error naming
    neither the spec nor the site.  The rule checks every string
    literal passed to ``PartitionSpec`` against the axis names declared
    in the module (Mesh/make_mesh literals, ``*_axis`` parameter
    defaults) plus the package default axis.  Skipped when the module
    declares no axes at all (no mesh context to check against).
    """
    declared = _declared_axes(ctx)
    if not declared:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        full = ctx.resolve(node.func) or ""
        if full.rsplit(".", 1)[-1] != "PartitionSpec":
            continue
        for arg in node.args:
            elts = arg.elts if isinstance(arg, (ast.Tuple, ast.List)) \
                else [arg]
            for e in elts:
                if (isinstance(e, ast.Constant)
                        and isinstance(e.value, str)
                        and e.value not in declared):
                    yield node.lineno, (
                        f"PartitionSpec axis {e.value!r} is not declared "
                        f"by any mesh in scope (known axes: "
                        f"{sorted(declared)}); a mismatched spec fails "
                        f"only at dispatch time")


# ---------------------------------------------------------------------------
# R5 — dtype-promotion
# ---------------------------------------------------------------------------

_ARITH_OPS = (ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv, ast.Mod,
              ast.Pow)


@register("R5", "dtype-promotion",
          "bare python float literals in traced arithmetic promote "
          "narrow dtypes (bf16 -> f32) silently")
def check_dtype_promotion(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """Python float literals in jitted arithmetic.

    Under jit, ``x * 0.5`` with a bf16 ``x`` stays bf16 only through
    weak-type promotion; the moment the literal is wrapped (e.g.
    ``np.float64(0.5)`` from a config) or promotion rules change, the
    whole hot-loop array silently widens and the layout-padding law
    (PERFORMANCE.md) is paying double bytes.  State the dtype:
    ``x * x.dtype.type(0.5)`` or ``jnp.asarray(0.5, x.dtype)``.
    Integer literals (shape arithmetic, indexing) are exempt.
    """
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, _ARITH_OPS)
                and ctx.in_traced_scope(node)):
            continue
        for lit, other in ((node.left, node.right),
                           (node.right, node.left)):
            if (isinstance(lit, ast.Constant)
                    and isinstance(lit.value, float)
                    and not _is_static_expr(ctx, other)):
                yield node.lineno, (
                    f"bare float literal {lit.value!r} in traced "
                    f"arithmetic relies on weak-type promotion; spell "
                    f"the dtype (x.dtype.type({lit.value!r}) or "
                    f"jnp.asarray({lit.value!r}, x.dtype))")
                break


# ---------------------------------------------------------------------------
# R6 — unguarded-device-get
# ---------------------------------------------------------------------------

#: Call roots that produce device arrays.
_DEVICE_PRODUCERS = ("jax.numpy.", "jax.lax.")
_DEVICE_CALLS = frozenset({
    "jax.device_put", "jax.make_array_from_callback",
    "jax.make_array_from_single_device_arrays", "jax.block_until_ready",
})


def _scope_nodes(ctx: ModuleContext):
    """(scope, nodes-in-scope) for the module and every function, where
    a node belongs to the innermost enclosing function only."""
    scopes: dict = {None: []}
    for fn in ast.walk(ctx.tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
            scopes[fn] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Call)):
            scopes.setdefault(ctx.enclosing_function(node), []).append(node)
    for scope, nodes in scopes.items():
        nodes.sort(key=lambda n: (n.lineno, n.col_offset))
        yield scope, nodes


def _produces_device_value(ctx: ModuleContext, expr, device_names) -> bool:
    if isinstance(expr, ast.Name):
        return expr.id in device_names
    if isinstance(expr, ast.Call):
        full = ctx.resolve(expr.func) or ""
        if full in _DEVICE_CALLS or full.startswith(_DEVICE_PRODUCERS):
            return True
        # Method chain rooted at a known device value: y = x.sum() etc.
        root = expr.func
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name) and root.id in device_names:
            return True
        if isinstance(root, ast.Call):
            return _produces_device_value(ctx, root, device_names)
    if isinstance(expr, (ast.Subscript, ast.Attribute)):
        return _produces_device_value(ctx, expr.value, device_names)
    if isinstance(expr, ast.BinOp):
        return (_produces_device_value(ctx, expr.left, device_names)
                or _produces_device_value(ctx, expr.right, device_names))
    return False


@register("R6", "unguarded-device-get",
          "np.asarray/np.array on a jax.Array is an unbounded "
          "device->host fetch")
def check_device_get(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """Unbounded device fetches.

    A whole-array fetch blocks the host on the device and copies every
    byte; on a mesh that spans processes it raises.  Large results go
    through ``fetch_replicated``.  The rule tracks names assigned from
    jnp/lax/device_put expressions within each function and flags
    ``np.asarray``/``np.array`` applied to them.
    """
    for scope, nodes in _scope_nodes(ctx):
        device_names: set = set()
        for node in nodes:
            if isinstance(node, ast.Assign):
                if _produces_device_value(ctx, node.value, device_names):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            device_names.add(t.id)
                else:
                    # Rebinding to a host value clears the mark.
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            device_names.discard(t.id)
            elif isinstance(node, ast.Call):
                if not (ctx.is_numpy_call(node, "asarray")
                        or ctx.is_numpy_call(node, "array")):
                    continue
                if not node.args:
                    continue
                arg = node.args[0]
                if _produces_device_value(ctx, arg, device_names):
                    name = (arg.id if isinstance(arg, ast.Name)
                            else ast.unparse(arg)[:40])
                    yield node.lineno, (
                        f"np.asarray({name}) fetches a device array "
                        f"whole; route it through fetch_replicated or "
                        f"waive if provably tiny")


# ---------------------------------------------------------------------------
# R7 — unsynced-timing
# ---------------------------------------------------------------------------

#: Host clocks used to time wall intervals.
_TIMER_CALLS = frozenset({"time.perf_counter", "time.monotonic",
                          "time.time"})


def _is_timer_call(ctx: ModuleContext, node) -> bool:
    return (isinstance(node, ast.Call)
            and ctx.resolve(node.func) in _TIMER_CALLS)


def _is_block_call(ctx: ModuleContext, node) -> bool:
    """Any spelling of a dispatch barrier: ``jax.block_until_ready(x)``,
    ``x.block_until_ready()``, or the tolerant helper from
    utils/logging.py imported as a bare name."""
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    if isinstance(func, ast.Name) and func.id == "block_until_ready":
        return True
    if isinstance(func, ast.Attribute) and func.attr == "block_until_ready":
        return True
    full = ctx.resolve(func) or ""
    return full.endswith("block_until_ready")


@register("R7", "unsynced-timing",
          "a perf_counter region that times a jitted callable without "
          "block_until_ready measures async dispatch, not device "
          "execution")
def check_unsynced_timing(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """Timing a jitted call without synchronising.

    JAX dispatch is asynchronous: ``t0 = time.perf_counter(); y = f(x);
    dt = time.perf_counter() - t0`` with a jitted ``f`` measures launch
    overhead (microseconds) while the device is still computing — the
    hazard the block-until-ready harness in obs/tracer.py exists to
    close.  The rule tracks names assigned from ``jax.jit(...)``, finds
    ``start = perf_counter()`` / ``... perf_counter() - start`` pairs in
    the same function, and flags jitted-name calls inside the region
    when no ``block_until_ready`` (any spelling) appears between start
    and stop.
    """
    jit_names: set = set()
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and ctx.resolve(node.value.func) in JIT_WRAPPERS):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    jit_names.add(t.id)
    if not jit_names:
        return
    for scope, nodes in _scope_nodes(ctx):
        starts = {}
        for node in nodes:
            if (isinstance(node, ast.Assign)
                    and _is_timer_call(ctx, node.value)):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        starts[t.id] = node.lineno
        if not starts:
            continue
        body = ctx.tree if scope is None else scope
        regions = []
        for node in ast.walk(body):
            if (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)
                    and _is_timer_call(ctx, node.left)
                    and isinstance(node.right, ast.Name)
                    and node.right.id in starts
                    and node.lineno > starts[node.right.id]
                    and ctx.enclosing_function(node) is scope):
                regions.append((starts[node.right.id], node.lineno))
        for lo, hi in regions:
            in_region = [c for c in nodes
                         if isinstance(c, ast.Call)
                         and lo < c.lineno <= hi]
            if any(_is_block_call(ctx, c) for c in in_region):
                continue
            for call in in_region:
                if (isinstance(call.func, ast.Name)
                        and call.func.id in jit_names):
                    yield call.lineno, (
                        f"{call.func.id!r} (a jitted callable) is timed "
                        f"by a perf_counter region with no "
                        f"block_until_ready; dispatch is asynchronous, "
                        f"so this measures launch overhead, not device "
                        f"time — block on the result inside the region")


@register("R8", "swallowed-exception",
          "a broad `except Exception: pass` in runtime code silently "
          "swallows device errors, injected faults, and watchdog "
          "escapes — recovery must see them")
def check_swallowed_exception(ctx: ModuleContext
                              ) -> Iterable[Tuple[int, str]]:
    """Broad exception handlers whose only action is to discard.

    ``except Exception: pass`` (or bare ``except:``, or a tuple
    containing ``Exception``/``BaseException``, with a body of only
    ``pass``/``continue``/``...``) turns every failure — device OOM,
    injected chaos-gate faults, a supervisor's watchdog escape riding a
    worker thread — into silent success.  The graft-heal contract is
    that every fault is *seen* (flight-recorder event, metrics counter,
    retry) before any decision to continue; a swallow-and-go handler
    around a narrow, documented hazard should name the narrow exception
    type, and a deliberate broad swallow takes an inline waiver
    (``# graft-lint: disable=R8``) stating why.
    """
    broad = {"Exception", "BaseException"}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not node.body or not all(
                isinstance(s, ast.Pass) or isinstance(s, ast.Continue)
                or (isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant)
                    and s.value.value is Ellipsis)
                for s in node.body):
            continue
        t = node.type
        types = ([] if t is None
                 else list(t.elts) if isinstance(t, ast.Tuple)
                 else [t])
        names = [(ctx.resolve(nd) or "").rsplit(".", 1)[-1]
                 for nd in types]
        if t is not None and not any(nm in broad for nm in names):
            continue
        caught = ("bare except" if t is None
                  else "except " + "/".join(n for n in names if n))
        yield node.lineno, (
            f"{caught} whose body only discards swallows every "
            f"failure silently — catch the narrow exception this site "
            f"expects, or record the fault (obs.flight / metrics) "
            f"before continuing; a deliberate broad swallow takes an "
            f"inline `# graft-lint: disable=R8` waiver")


# ---------------------------------------------------------------------------
# R9 — env-read-in-step
# ---------------------------------------------------------------------------

#: Spellings of an environment read, post alias resolution.
_ENV_GETTERS = frozenset({"os.getenv", "os.environ.get"})


def _env_read_name(ctx: ModuleContext, node) -> Optional[str]:
    """The constant variable name an expression reads from the
    environment, or None when it is not an env read / not constant."""
    if isinstance(node, ast.Call):
        if ctx.resolve(node.func) in _ENV_GETTERS and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                return arg.value
    elif isinstance(node, ast.Subscript):
        if ctx.resolve(node.value) == "os.environ":
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                return sl.value
    return None


@register("R9", "env-read-in-step",
          "os.environ/os.getenv reads of AMT_* knobs inside a jitted "
          "step function or a per-iteration loop re-read host state "
          "every step; resolve the knob once at build time")
def check_env_read_in_step(ctx: ModuleContext) -> Iterable[Tuple[int, str]]:
    """AMT_* environment reads on the per-step path.

    The AMT_* knobs are build-time configuration (the pallas_sell.py
    fuse gate, decompose worker counts, the comm chunk sizes): every
    shipped read happens once at module import or object construction.
    An ``os.environ.get("AMT_...")`` inside a function handed to
    jax.jit/shard_map is worse than slow — the value is baked at TRACE
    time, so flipping the knob later silently does nothing while the
    code reads as if it were live.  Inside a per-iteration loop it is a
    dict probe plus getenv lock on the hot path and drifts the bench
    timings the obs layer records.  Hoist the read to build time and
    thread the value in as an argument or closure constant; a
    deliberate per-step read (e.g. a chaos-gate probe) takes an inline
    ``# graft-lint: disable=R9`` waiver stating why.
    """
    for node in ast.walk(ctx.tree):
        name = _env_read_name(ctx, node)
        if name is None or not name.startswith("AMT_"):
            continue
        if ctx.in_traced_scope(node):
            yield node.lineno, (
                f"environment read of {name!r} inside a jitted scope is "
                f"baked at trace time (silently stale after the first "
                f"compile); hoist it to build time and pass the value in")
        elif (ctx.in_loop(node)
              and ctx.enclosing_function(node) is not None):
            yield node.lineno, (
                f"environment read of {name!r} inside a per-iteration "
                f"loop probes host state every step; resolve the knob "
                f"once before the loop")
