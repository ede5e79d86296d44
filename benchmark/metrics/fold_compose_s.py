"""fold_compose_s: the program's ``fold.compose`` span, the fold's
composition of every level's triplets into one CSR in level-0 order
(``parallel/multi_level``), within ``build_s``."""

from benchmark import program_obs


def read(run):
    return program_obs.span_seconds("fold.compose")
