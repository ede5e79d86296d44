from arrow_matrix_tpu.decomposition.decompose import (
    ArrowLevel,
    achieved_width,
    arrow_decomposition,
    decomposition_matrix,
    decomposition_spmm,
    reconstruct,
)
from arrow_matrix_tpu.decomposition.linearize import bfs_order, random_forest_order
from arrow_matrix_tpu.decomposition import native

__all__ = [
    "ArrowLevel",
    "achieved_width",
    "arrow_decomposition",
    "decomposition_matrix",
    "decomposition_spmm",
    "reconstruct",
    "bfs_order",
    "random_forest_order",
]
