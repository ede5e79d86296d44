"""step_busy_ms: device-busy time per iteration in the traced window
(union of the device's op intervals over the iterations run)."""


def read(run):
    if run.trace is None or not run.iterations:
        return None
    return 1e3 * run.trace["busy_s"] / run.iterations
