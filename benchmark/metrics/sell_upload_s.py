"""sell_upload_s: the program's ``sell.upload`` span, the copy of the
packed SELL tiers to the device, blocked until resident
(``ops/sell.upload_sell``), within ``build_s``."""

from benchmark import program_obs


def read(run):
    return program_obs.span_seconds("sell.upload")
