"""setup_s: process start to the first timed job (load or generate and
decompose, build, compile, upload, warm-up job)."""


def read(run):
    return run.setup_s
