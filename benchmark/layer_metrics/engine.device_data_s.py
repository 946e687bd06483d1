"""Seconds the dataset's way to the device took on the host (the per-client
rows or the flat cast, then the puts), by the program's span
``fed.setup.first_dispatch.device_data`` (counter
``fedtpu_setup_seconds{phase="first_dispatch.device_data"}``)."""

from benchmark import program_counters


def read(ctx):
    return program_counters.setup_seconds("first_dispatch.device_data")
