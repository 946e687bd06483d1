"""Programs of the engine's set-up that compiled and were written to the
persistent compile cache instead of being loaded from it (counter
``fedtpu_setup_cache_misses``, from jax's own cache event inside the
engine's compiling phases). 0 in a warm run."""

from benchmark import program_counters


def read(ctx):
    return program_counters.value("fedtpu_setup_cache_misses")
