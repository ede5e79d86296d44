from arrow_matrix_tpu.ops.ell import (
    csr_flat_pack,
    csr_flat_spmm,
    ell_pack,
    ell_pack_stack,
    ell_spmm,
    ell_spmm_batched,
    ell_spmm_t,
)
from arrow_matrix_tpu.ops.arrow_blocks import (
    ArrowBlocks,
    arrow_blocks_from_csr,
    arrow_spmm,
    block_features,
    unblock_features,
)
from arrow_matrix_tpu.ops.sell import (
    SellMatrix,
    sell_from_csr,
    sell_spmm_t,
)
from arrow_matrix_tpu.ops.pallas_blocks import (
    arrow_spmm_pallas,
    column_spmm_pallas,
    head_spmm_pallas,
)

__all__ = [
    "csr_flat_pack",
    "csr_flat_spmm",
    "ell_pack",
    "ell_pack_stack",
    "ell_spmm",
    "ell_spmm_batched",
    "ell_spmm_t",
    "ArrowBlocks",
    "arrow_blocks_from_csr",
    "SellMatrix",
    "sell_from_csr",
    "sell_spmm_t",
    "arrow_spmm",
    "arrow_spmm_pallas",
    "column_spmm_pallas",
    "head_spmm_pallas",
    "block_features",
    "unblock_features",
]
