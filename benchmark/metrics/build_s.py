"""build_s: the harness span around the executor's constructor (fold
composition, ``ops/sell.sell_from_csr``, operator upload)."""


def read(run):
    return run.spans.get("build")
