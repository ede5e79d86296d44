"""fold_pad_share: share of the fold operator's gathered slot-rows that
are padding, from the program's ``sell.slots`` and ``sell.nnz`` gauges
(recorded when ``ops/sell`` packs the tiers)."""

from benchmark import program_obs


def read(run):
    slots = program_obs.gauge("sell.slots")
    nnz = program_obs.gauge("sell.nnz")
    if not slots or nnz is None:
        return None
    return 100.0 * (slots - nnz) / slots
