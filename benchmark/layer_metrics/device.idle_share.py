"""1 - (union of device-operation intervals over the traced window), mean
over the chips used."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
