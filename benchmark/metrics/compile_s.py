"""compile_s: JAX's own trace, lowering and backend-compile durations
(a persistent-cache load counts as compile) summed over set-up."""


def read(run):
    return run.compile_s
