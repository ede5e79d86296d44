"""iter_ms: the whole measured window over every iteration completed in
it, job ends included."""


def read(run):
    if not run.iterations:
        return None
    return 1e3 * run.window_s / run.iterations
