"""load_s: the harness span around the program's artifact load
(``io.load_decomposition``, ``load_level_widths``, ``as_levels``)."""


def read(run):
    return run.spans.get("load")
