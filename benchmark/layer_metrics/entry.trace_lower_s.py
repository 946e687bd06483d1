"""Seconds jax reported for tracing and for lowering the engine's own
programs during its set-up (the model's init and the round program's first
call): the ``.trace`` and ``.lower`` series of
``fedtpu_setup_seconds{phase}`` under every phase that can compile. A warm
compile cache saves the compile, not these."""

from benchmark import program_counters


def read(ctx):
    return program_counters.setup_seconds_ending(".trace", ".lower")
