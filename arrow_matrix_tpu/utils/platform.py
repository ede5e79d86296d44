"""JAX platform pinning, device discovery and the compile cache, shared
by the CLIs, tests, and entry points.

- The device-count flag must be set before the first backend
  initialization (``force_cpu_devices``).
- A chip belongs to one process: a parent that spawns device children
  learns the platform from a child (``child_platform``), never by
  initializing a backend itself.
- One persistent compile cache location for every entry point
  (``enable_compile_cache``).
"""

from __future__ import annotations

import os
import re
import warnings

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def backend_initialized() -> bool:
    """True once any JAX backend has been created (after which platform
    pinning is a no-op and device counts are fixed)."""
    try:
        from jax._src import xla_bridge as _xb

        return bool(_xb._backends)
    except Exception:  # pragma: no cover - jax internals moved
        return False


def force_cpu_devices(n_devices: int | None = None) -> None:
    """Pin JAX to the host CPU platform, optionally with ``n_devices``
    virtual devices (the multi-chip-without-hardware fixture; the analog
    of the reference's ``mpiexec --oversubscribe`` many-rank testing,
    reference scripts/run_tests.sh).

    Must run before anything initializes a JAX backend.  Safe to call
    when jax is already imported, as long as no backend exists yet.
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + f" {_COUNT_FLAG}={n_devices}").strip()
        elif int(m.group(1)) != n_devices:
            # An inherited flag must not silently override the requested
            # count (a CLI asked for N devices and should get N).
            warnings.warn(
                f"XLA_FLAGS already pins {m.group(1)} host devices; "
                f"replacing with the requested {n_devices}")
            os.environ["XLA_FLAGS"] = re.sub(
                rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")


def host_load(max_pids: int = 8) -> dict:
    """Snapshot of competing host activity, attached to every committed
    measurement (VERDICT r5 item 6: a number without the load context
    of the host that produced it cannot be compared across rounds).

    Returns ``{"loadavg_1m": float, "competing": [process names...]}``
    where ``competing`` lists up to ``max_pids`` OTHER processes in the
    runnable/uninterruptible states (R/D) — the ones actually eating
    the cores while the measurement ran.  Linux-only fields degrade to
    empty on other platforms; never raises.
    """
    try:
        load1 = os.getloadavg()[0]
    except (OSError, AttributeError):  # pragma: no cover - non-unix
        load1 = -1.0
    names: list[str] = []
    me = os.getpid()
    try:
        for pid in os.listdir("/proc"):
            if not pid.isdigit() or int(pid) == me:
                continue
            try:
                with open(f"/proc/{pid}/stat") as f:
                    stat = f.read()
            except OSError:
                continue
            # comm may contain spaces/parens: field 2 ends at the LAST
            # ')'; the state letter is the first field after it.
            close = stat.rfind(")")
            if close < 0:
                continue
            comm = stat[stat.find("(") + 1:close]
            rest = stat[close + 1:].split()
            if rest and rest[0] in ("R", "D"):
                names.append(comm)
                if len(names) >= max_pids:
                    break
    except OSError:  # pragma: no cover - /proc absent
        pass
    return {"loadavg_1m": round(float(load1), 2), "competing": names}


def device_memory_budget(device=None, fraction: float = 0.5) -> int:
    """Bytes available for resident block storage on ``device``, derived
    from the live chip instead of a constant (a v5e has 16G HBM, a v5p
    95G — one hardcoded budget misformats on both).

    Uses PJRT ``memory_stats`` (free = limit − in_use); the CPU backend
    reports none and gets available host RAM.  An accelerator that
    reports no limit is an error, not a guess.  ``fraction`` leaves
    headroom for features, collectives buffers, and XLA scratch.
    """
    import jax

    dev = device if device is not None else jax.devices()[0]
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if limit:
        free = int(limit) - int(stats.get("bytes_in_use", 0))
        return max(int(free * fraction), 0)
    if dev.platform == "cpu":
        free = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        return max(int(free * fraction), 0)
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no memory "
        f"limit (memory_stats: {sorted(stats)}); pass an explicit "
        f"budget")


def repo_root() -> str:
    """The checkout this package lives in (anchors repo-relative state;
    never the working directory)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The persistent XLA compile cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<repo>/bench_cache/xla_cache``.  The path is part
    of the cache key, so it must not move between runs."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(repo_root(), "bench_cache", "xla_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at :func:`compile_cache_dir`
    and return the path.  When ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    reads it itself and nothing else is set; child processes inherit
    the cache through :func:`compile_cache_env`."""
    path = compile_cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax

        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_cache_env(env: dict) -> dict:
    """``env`` for a child process that shares this process's compile
    cache (set only when the caller has not set it)."""
    env = dict(env)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    return env


def child_platform(timeout_s: float = 300.0) -> dict:
    """``{"platform", "kind", "count", "hbm_budget"}`` of the default
    JAX backend (``hbm_budget``: :func:`device_memory_budget` of device
    0), read in a child process so the caller never initializes a
    backend (a chip belongs to one process: a parent that held it
    would starve the device children it spawns).  Raises RuntimeError
    when the child fails; there is no fallback platform."""
    import json
    import subprocess
    import sys

    code = (f"import json, sys; sys.path.insert(0, {repo_root()!r}); "
            "import jax; "
            "from arrow_matrix_tpu.utils.platform import "
            "device_memory_budget; d = jax.devices(); "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d), "
            "'hbm_budget': device_memory_budget(d[0])}))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RuntimeError(f"device discovery timed out after "
                           f"{timeout_s:.0f}s") from None
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise RuntimeError(f"device discovery failed (rc="
                           f"{proc.returncode}): "
                           f"{proc.stderr.strip()[-400:]}")
    return json.loads(lines[-1])
