"""Collective time during which nothing else ran on the chip, over device
busy time, mean over the chips: what an overlap of the exchange with compute
could still hide. Read only where the cell spans chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or ctx["chips"] < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["busy_s"]
