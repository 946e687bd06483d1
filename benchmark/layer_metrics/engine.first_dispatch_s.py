"""Seconds the round program's first call took on the host, until the
enqueue returned (the dataset's upload, the trace, lowering, compile or
cache load; not the first execution, which is the device's), by the
program's span ``fed.setup.first_dispatch`` (counter
``fedtpu_setup_seconds{phase="first_dispatch"}``)."""

from benchmark import program_counters


def read(ctx):
    return program_counters.setup_seconds("first_dispatch")
