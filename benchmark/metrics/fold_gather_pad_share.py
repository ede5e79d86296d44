"""fold_gather_pad_share: share of the slot-rows the fold step gathers
that are padding, from the program's ``sell.gathered_slots`` (recorded
when ``ops/sell.sell_spmm_t`` is traced: each tier's slots as its
gathers walk them, chunk padding included) and ``sell.nnz`` gauges."""

from benchmark import program_obs


def read(run):
    gathered = program_obs.gauge("sell.gathered_slots")
    nnz = program_obs.gauge("sell.nnz")
    if not gathered or nnz is None:
        return None
    return 100.0 * (gathered - nnz) / gathered
