"""fold_step_roofline: the least time one iteration can take on this chip
(compulsory bytes over peak HBM bandwidth, or FLOPs over peak FLOP/s if
larger; ``benchmark/work.py``) over the measured device-busy time of an
iteration."""

from benchmark import work


def read(run):
    if run.trace is None or not run.iterations:
        return None
    w = run.work
    least, _bound = work.roofline_seconds(
        w["nnz"], w["n"], w["k"], w["itemsize"], work.peaks(run.device_kind))
    return 100.0 * least / (run.trace["busy_s"] / run.iterations)
