"""fold_packed_share: share of the fold operator's gathered slot-rows
that take the lane-packed form (whole 128-lane rows, ``ops/ell``), from
the program's ``sell.packed_slots`` and ``sell.slots`` gauges."""

from benchmark import program_obs


def read(run):
    slots = program_obs.gauge("sell.slots")
    packed = program_obs.gauge("sell.packed_slots")
    if not slots or packed is None:
        return None
    return 100.0 * packed / slots
