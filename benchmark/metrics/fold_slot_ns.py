"""fold_slot_ns: device-busy time per gathered slot-row, ``step_busy_ms``
(the same union of device-op intervals per iteration) over the program's
``sell.slots`` gauge."""

from benchmark import program_obs


def read(run):
    slots = program_obs.gauge("sell.slots")
    if run.trace is None or not run.iterations or not slots:
        return None
    return 1e9 * run.trace["busy_s"] / run.iterations / slots
