"""Peak bytes in use on the fullest chip after the window, in GB (1e9)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9 if ctx["memory_peak_bytes"] else None
