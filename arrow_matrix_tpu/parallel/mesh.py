"""Mesh construction and sharding helpers.

The mesh replaces the reference's MPI communicators and rank groups
(reference arrow/arrow_mpi.py:74-81,501-525, arrow/arrow_dec_mpi.py:140-165):
rank arithmetic becomes named mesh axes, and sub-communicators become
collectives over a subset of axes.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = ("blocks",),
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a mesh over the available devices.

    Default: a 1-D mesh named ``blocks`` over all devices — the slim
    arrow layout's block-row axis (the TPU analog of the reference's
    one-rank-per-block-row slim communicator,
    reference arrow/arrow_slim_mpi.py:298-326).
    """
    explicit = devices is not None
    devs = list(devices if explicit else jax.devices())
    if shape is None:
        shape = (len(devs),)
    if len(axis_names) != len(shape):
        raise ValueError(
            f"mesh shape {tuple(shape)} has {len(shape)} dimension(s) "
            f"but axis_names {tuple(axis_names)} names "
            f"{len(axis_names)} — one name per mesh dimension required")
    n = int(np.prod(shape))
    if n > len(devs):
        raise ValueError(f"mesh shape {tuple(shape)} needs {n} devices, "
                         f"only {len(devs)} available")
    # A smaller shape takes the first n devices: sub-meshes of any size
    # (including non-power-of-two) from one device pool — the analog of
    # the reference's many-rank test matrix on an oversubscribed host
    # (reference tests/test_arrowmpi.py:11-17 runs at up to 30 ranks).
    # Warn when the subset was not asked for explicitly: a stale shape
    # silently idling part of the machine is a perf bug, not a choice.
    if n < len(devs) and not explicit:
        import warnings

        warnings.warn(f"mesh shape {tuple(shape)} uses {n} of "
                      f"{len(devs)} available devices; pass devices= to "
                      f"silence", stacklevel=2)
    arr = np.asarray(devs[:n], dtype=object).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names))


def largest_replication(n_dev: int) -> int:
    """Largest power-of-two c with c**2 <= n_dev that yields a valid
    grid, i.e. n_dev divisible by c**2 (reference auto-replication rule
    plus its runtime divisibility requirement,
    scripts/spmm_15d_main.py:87-96, spmm_15d.py:34-40)."""
    c = 1
    while (2 * c) ** 2 <= n_dev and n_dev % ((2 * c) ** 2) == 0:
        c *= 2
    return c


def make_repl_mesh(n_dev: int, repl: int,
                   axis_names: Sequence[str] = ("blocks", "repl"),
                   devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-D ``(blocks, repl)``-style mesh for the replicated (2.5D)
    arrow/SELL executors: ``n_dev // repl`` block shards x ``repl``
    replica groups.  Each replica group (a column of the mesh) holds a
    complete copy of the operator — that is the c-fold memory the 2.5D
    scheme (arxiv 1705.10218) trades for cheaper exchanges — and runs
    its exchanges among its own ``n_dev // repl`` devices only.

    ``repl=1`` degenerates to the 1-D layout (a trailing axis of
    extent 1), so callers can thread one mesh shape through both the
    replicated and the baseline paths."""
    repl = int(repl)
    if repl < 1:
        raise ValueError(f"repl={repl} must be >= 1")
    if n_dev % repl != 0:
        raise ValueError(
            f"repl={repl} must divide the device count n_dev={n_dev} "
            f"(each replica group needs an equal share of the mesh)")
    return make_mesh((n_dev // repl, repl), tuple(axis_names),
                     devices=devices)


def blocks_sharding(mesh: Mesh, axis: str = "blocks") -> NamedSharding:
    """Sharding for a (nb, w, k) blocked array: block axis over ``axis``."""
    return NamedSharding(mesh, P(axis))


def put_global(x, sharding: NamedSharding) -> jax.Array:
    """Place a host array onto a (possibly multi-process) sharding.

    Single-process (every device of the sharding is local): plain
    ``jax.device_put`` — the fast path, unchanged.  Multi-process: each
    process materializes ONLY its addressable shards via
    ``jax.make_array_from_callback`` (``device_put`` of a host array
    onto non-addressable devices is an error).  With a memmapped ``x``
    the callback slicing means each host reads only its own shards from
    disk — the IO-parallel loading of the reference's per-rank slice
    files (reference arrow/baseline/spmm_petsc.py:421-440), for free.
    """
    from arrow_matrix_tpu.faults import inject as _fault_hook

    _fault_hook("mesh.put_global")
    if all(d.process_index == jax.process_index()
           for d in sharding.device_set):
        return jax.device_put(x, sharding)
    x = np.asarray(x)
    # dtype explicitly: a process holding NO shard of this array (e.g.
    # a replicated table on a sub-mesh owned by other processes) cannot
    # infer it from its (empty) shard list.
    return jax.make_array_from_callback(
        x.shape, sharding, lambda idx: np.ascontiguousarray(x[idx]),
        dtype=x.dtype)


def build_global(global_shape, sharding: NamedSharding, builder,
                 dtype) -> jax.Array:
    """Construct a sharded array whose shards are BUILT on demand.

    ``builder(index)`` receives the shard's global index (a tuple of
    slices) and returns that shard's numpy block — called only for the
    shards THIS process addresses.  This is how layouts whose blocks
    are *derived* (packed ELL tables, exchange indices) get per-host
    parallel construction: no process ever materializes the global
    array, the per-host counterpart of the reference's per-rank slice
    loading (reference arrow/baseline/spmm_petsc.py:421-440).  Peak
    host memory is O(one shard) beyond the builder's own inputs.
    """
    dtype = np.dtype(dtype)
    return jax.make_array_from_callback(
        tuple(global_shape), sharding,
        lambda idx: np.ascontiguousarray(
            np.asarray(builder(idx), dtype=dtype)),
        dtype=dtype)


def build_global_parts(global_shape, sharding: NamedSharding, builder,
                       dtypes) -> list:
    """``build_global`` for several same-shaped arrays built together.

    ``builder(index)`` returns one numpy block PER PART (e.g. an ELL
    pack's cols and data) — called exactly once per addressable shard,
    with each part uploaded to its device before the next shard is
    built.  This keeps host memory at O(one shard) AND builds each
    shard once, where two independent ``build_global`` passes would
    re-derive every shard per part (packing produces all parts at
    once).
    """
    gshape = tuple(global_shape)
    dtypes = [np.dtype(d) for d in dtypes]
    addr = sharding.addressable_devices_indices_map(gshape)
    if not addr:
        # A process can legitimately address no shard of a sub-mesh /
        # replicated sharding; make_array_from_single_device_arrays
        # would crash on the empty buffer list with an opaque error
        # (ADVICE r3).  build_global handles the case via the dtype
        # kwarg — build each part through it (the builder is never
        # called here, so the one-build-per-shard economy is moot).
        return [build_global(gshape, sharding,
                             lambda idx, p=p: builder(idx)[p], dt)
                for p, dt in enumerate(dtypes)]
    part_bufs: list = [[] for _ in dtypes]
    for dev, idx in addr.items():
        blocks = builder(idx)
        if len(blocks) != len(dtypes):
            raise ValueError(f"builder returned {len(blocks)} parts, "
                             f"expected {len(dtypes)}")
        for p, (blk, dt) in enumerate(zip(blocks, dtypes)):
            part_bufs[p].append(jax.device_put(
                np.ascontiguousarray(np.asarray(blk, dtype=dt)), dev))
    return [jax.make_array_from_single_device_arrays(gshape, sharding,
                                                     bufs)
            for bufs in part_bufs]


def fetch_replicated(arr) -> np.ndarray:
    """Global (possibly multi-process) array -> host numpy, identical on
    every process.

    Fully-addressable arrays convert directly.  Otherwise the array is
    resharded to fully-replicated — one XLA all-gather across hosts
    (riding ICI/DCN; the counterpart of the reference's result
    ``Gather`` to rank 0, reference arrow/arrow_slim_mpi.py:423) — and
    every process reads its now-local copy.
    """
    from arrow_matrix_tpu.faults import inject as _fault_hook

    _fault_hook("mesh.fetch_replicated")
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    repl = NamedSharding(arr.sharding.mesh, P())
    arr = _replicator(repl)(arr)
    return np.asarray(arr.addressable_data(0))


@functools.lru_cache(maxsize=32)
def _replicator(repl: NamedSharding):
    # One jitted identity per target sharding: a fresh lambda per fetch
    # would miss the jit cache and recompile the all-gather every call.
    return jax.jit(lambda a: a, out_shardings=repl)


def shard_blocked(x, mesh: Mesh, axis: str = "blocks") -> jax.Array:
    """Place a blocked (nb, ...) array with its leading axis sharded.

    The load-time equivalent of the reference's rank-by-rank tagged
    Send/Recv block distribution (reference arrow_dec_mpi.py:894-924) —
    on TPU a single `device_put` with a NamedSharding.
    """
    nb = x.shape[0]
    n_dev = mesh.shape[axis]
    if nb % n_dev != 0:
        raise ValueError(f"{nb} blocks not divisible by {n_dev} devices "
                         f"on axis {axis!r}; pad with pad_blocks_to")
    return put_global(x, blocks_sharding(mesh, axis))


def shard_arrow_blocks(blocks, mesh: Mesh, axis: str = "blocks"):
    """Shard every array leaf of an ArrowBlocks pytree on its leading
    (block) axis."""
    return jax.tree_util.tree_map(lambda a: shard_blocked(a, mesh, axis),
                                  blocks)


def pad_to_multiple(nb: int, n_dev: int) -> int:
    """Smallest block count >= nb divisible by the device count."""
    return -(-nb // n_dev) * n_dev


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         cpu_devices: Optional[int] = None,
                         heartbeat_timeout_seconds: int = 100) -> int:
    """Join a multi-host JAX runtime (the framework's scale-out story;
    the counterpart of the reference's MPI launch across nodes,
    reference README.md:10 Cray-MPICH).

    After this, `jax.devices()` spans every host's chips and the same
    single-SPMD-program code runs unchanged — collectives ride ICI
    within a slice and DCN across slices.  On TPU pods the arguments
    are auto-detected from the environment; pass them explicitly for
    CPU clusters.  Returns this process's index.

    ``cpu_devices``: pin this process to the host CPU with that many
    virtual devices and gloo cross-process collectives BEFORE joining —
    the multi-process testing fixture (the reference's ``mpiexec -n``
    analog with real process boundaries, reference
    scripts/run_tests.sh), and the CPU-cluster path.  Must be the
    process's first backend touch.

    ``heartbeat_timeout_seconds`` bounds failure-detection latency: a
    crashed peer aborts EVERY process within roughly this window (the
    coordination service's missed-heartbeat fatal, measured ~110 s at
    the default — the whole-job abort of the reference's collective
    failure flag, arrow_bench.py:128-134, detected by the runtime
    instead of a per-iteration allreduce).  Lower it for faster abort
    on flaky fleets; raise it to ride out long GC/compile pauses.
    """
    import jax

    if cpu_devices is not None:
        from arrow_matrix_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(cpu_devices)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        heartbeat_timeout_seconds=heartbeat_timeout_seconds)
    return jax.process_index()


def make_hybrid_mesh(ici_shape: Sequence[int], dcn_shape: Sequence[int],
                     axis_names: Sequence[str]) -> Mesh:
    """Mesh whose leading axes span slices over DCN and trailing axes
    span chips over ICI (via `mesh_utils.create_hybrid_device_mesh`).

    Lay out shardings so the high-volume exchanges (block axis psum /
    ppermute) map to ICI axes and only the low-volume ones cross DCN —
    the mesh-axis analog of the reference's node-local vs inter-node
    communicator split.  Falls back to a plain mesh when there is a
    single granule (e.g. single-host testing).
    """
    from jax.experimental import mesh_utils

    if int(np.prod(dcn_shape)) == 1:
        return make_mesh(tuple(ici_shape), tuple(axis_names))
    devs = mesh_utils.create_hybrid_device_mesh(
        tuple(ici_shape), tuple(dcn_shape))
    return Mesh(devs, tuple(axis_names))
