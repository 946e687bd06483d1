"""Compile or cache-load events inside the measured window. Expected 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])
