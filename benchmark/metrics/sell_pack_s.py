"""sell_pack_s: the program's ``sell.pack`` span, the host packing of
the folded CSR into degree-sorted SELL tiers (``ops/sell``), within
``build_s``."""

from benchmark import program_obs


def read(run):
    return program_obs.span_seconds("sell.pack")
