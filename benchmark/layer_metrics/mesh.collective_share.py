"""Device time of collective operations over device busy time, on the chip
where that share is largest. Read only where the cell spans chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or ctx["chips"] < 2:
        return None
    return 100.0 * t["collective_share"]
