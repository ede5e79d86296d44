"""Test configuration: force a 16-device virtual CPU platform so the
multi-chip sharding paths are exercised without TPU hardware (the TPU
analog of the reference's ``mpiexec --oversubscribe`` many-rank fixture,
reference scripts/run_tests.sh runs at up to 30 ranks).  Most tests use
an 8-device sub-mesh; tests/test_mesh_sizes.py sweeps sub-meshes of
2..16 devices including non-power-of-two sizes."""

import os
import tempfile

# Force CPU even when the environment selects a TPU platform: the test
# suite must be hermetic and must exercise the virtual multi-device mesh.
os.environ["JAX_PLATFORMS"] = "cpu"

# Redirect the default graft-ledger store to a throwaway directory so
# no test (or code under test that emits telemetry) ever appends to the
# committed bench_results/ledger history.
os.environ.setdefault("AMT_LEDGER_DIR",
                      tempfile.mkdtemp(prefix="amt_test_ledger_"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=16").strip()

# The shared helper repins the platform before the first backend init
# (JAX would otherwise take a TPU where one is attached).  The tests
# compile a lot of tiny programs: keep them out of the persistent
# compile cache, which belongs to the chip runs.
from arrow_matrix_tpu.utils.platform import force_cpu_devices

force_cpu_devices()
import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


def ensure_ba_256_3(repo_root):
    """Regenerate the loose ba_256_3 decomposition artifact if absent.

    tests/test_memview.py and tests/test_reshard.py load it as a real
    npy-triplet artifact from the repo root; the files are deliberately
    gitignored (ba_*.npy), so a fresh checkout — or anything that
    sweeps loose files — must not take those tests down with it.  The
    tests only depend on the artifact's shape (BA n=256 m=3, width 32,
    block-diagonal), not its bytes, so a deterministic rebuild is a
    faithful replacement.
    """
    base = os.path.join(repo_root, "ba_256_3")
    from arrow_matrix_tpu.io.graphio import FileKind, format_path
    marker = format_path(base, 32, 0, True, FileKind.widths)
    if os.path.exists(marker):
        return base
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.io import save_decomposition
    from arrow_matrix_tpu.utils import barabasi_albert
    a = barabasi_albert(256, 3, seed=0)
    levels = arrow_decomposition(a, 32, max_levels=10,
                                 block_diagonal=True, seed=0)
    save_decomposition(levels, base, block_diagonal=True)
    return base


import pytest


@pytest.fixture(scope="session")
def ba_256_3_base():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return ensure_ba_256_3(repo_root)
