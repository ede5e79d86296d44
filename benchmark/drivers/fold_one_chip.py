"""Driver: the program's default one-chip executor.

``MultiLevelArrow`` built as ``spmm_arrow`` builds it on one chip with
no format flags: the whole decomposition folded into one SELL operator,
run by the XLA kernel.  A job is ``run(x, J)``: J steps as one device
program on the resident features.
"""

from __future__ import annotations

# Carriage named in a workload file -> the executor's feature_dtype.
CARRIAGES = {"float32": None, "bfloat16": "bf16"}


def build(levels, width: int, carriage: str):
    import jax

    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow

    # f32 products at full precision, as the program's own chip drives
    # run them.
    jax.config.update("jax_default_matmul_precision", "highest")
    return MultiLevelArrow(levels, width, fmt="fold",
                           feature_dtype=CARRIAGES[carriage])


def upload(ex, x):
    """Host (n, k) features in original row order -> device carriage."""
    return ex.set_features(x)


def dispatch(ex, xd, iterations: int):
    """Start one job; the caller blocks on the result."""
    return ex.run(xd, iterations)


def fetch(ex, y):
    """Device result -> host (n, k) float32 in original row order."""
    return ex.gather_result(y)
