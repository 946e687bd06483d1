"""Seconds the host spent placing state leaves on the devices before the
first dispatch, the constructor's placement and the ``state`` setter's (the
seeded weights go through the setter), by the program's span
``fed.setup.place_state`` (counter
``fedtpu_setup_seconds{phase="place_state"}``)."""

from benchmark import program_counters


def read(ctx):
    return program_counters.setup_seconds("place_state")
