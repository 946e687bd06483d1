"""Seconds the engine's constructor took on the host, by the program's own
span ``fed.setup.build`` (counter ``fedtpu_setup_seconds{phase="build"}``):
the assignment, the model's init traced, compiled or loaded and run, the
jit wrappers, and on a mesh the state's first placement."""

from benchmark import program_counters


def read(ctx):
    return program_counters.setup_seconds("build")
